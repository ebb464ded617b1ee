"""Weighted-fair tenant admission: WFQ ordering, rate caps with burst
credits, and per-tenant starvation SLOs (ISSUE 17).

The fairness gap this module closes (the round-12 tenant-starvation
soak, ``run_soak.py --tenant``, showed it): admission was FIFO, so a
within-capacity ×8 burst from one tenant pushed its queueing delay onto
every other tenant.  The policy here is Gavel's FAIRNESS objective
(arxiv 2008.09213) — weighted accelerator-time shares — applied at the
queue's admission point, with Tesserae-style per-tenant substrate
(arxiv 2508.04953): the tenant is the unit of admission, not just of
attribution.

Three mechanisms, one deterministic state machine:

- **Weighted fair queueing** over the admission order.  Virtual time is
  the classic start-time tag: admitting one pod of tenant ``t`` sets
  ``start = max(vtime, vfinish[t])``, ``vfinish[t] = start +
  cost/weight[t]``, ``vtime = start``.  A tenant with twice the weight
  advances its finish tag half as fast, so it is selected twice as
  often.  Weights are accelerator-time shares derived from the active
  throughput/measured matrix (:func:`weights_from_matrix`) — a tenant
  whose workload class runs slower on the available pools earns a
  proportionally larger weight, equalizing accelerator TIME, not pod
  count — with a uniform fallback when no matrix/class mapping is
  armed.

- **Rate caps with burst credits.**  A token bucket per tenant: the
  balance refills at ``rate_pods_per_s`` on the LOGICAL clock, capped
  at ``burst`` credits.  Admission debits one credit; an empty bucket
  defers the tenant (its pods stay queued — the queue reports the stall
  as throttled, never drops).  ``rate_pods_per_s=0`` disarms the cap.

- **Starvation SLOs with a guaranteed-admission aging escape.**  A
  capped tenant must be throttled, never starved: once its oldest
  queued pod has waited ``aging_max_wait_s`` on the logical clock, the
  tenant becomes eligible regardless of credits (the escape is counted,
  and the debit floors at zero).  Admission waits feed the
  ``scheduler_tenant_slo_*`` families; a wait beyond
  ``slo_wait_budget_s`` is a starvation-SLO violation (structurally
  impossible while ``aging_max_wait_s < slo_wait_budget_s`` and the
  scheduler drains — the r17 soak's "0 violations" acceptance).

Determinism and durability contracts (the kill matrix's terms):

- Every decision is a pure function of (ledger state, logical clock,
  candidate tenant set).  The clock is injected — the fleet router
  forwards its logical clock, soaks their scenario clock — and NEVER a
  wall read; ties break on the sorted tenant name.  Metrics observe,
  they never steer: the policy runs identically with no registry.
- TWO ledgers.  The EFFECTIVE ledger advances at pop time (selection
  must see in-flight debits — at pipeline depth ≥ 2 a batch pops before
  the previous batch's group fsync has returned).  The DURABLE ledger
  advances only in :meth:`apply_admission`, called by the commit
  drain AFTER the batch's ``admission`` journal record is inside the
  group barrier — journal-before-apply at group scope, exactly the
  binds' discipline (tpulint's WAL family checks the drain).  Snapshots
  serialize the durable ledger; recovery replays ``admission`` records
  on top and re-derives the effective ledger, so a SIGKILL anywhere
  recovers the identical admission sequence.
"""

from __future__ import annotations

from .metrics import TENANT_FALLBACK, pod_tenant

DEFAULT_ADMISSION_COST = 1.0
DEFAULT_BURST_CREDITS = 8.0
DEFAULT_AGING_MAX_WAIT_S = 30.0
DEFAULT_SLO_WAIT_BUDGET_S = 60.0


def tenant_of(pod) -> str:
    """The admission key of a pod: its tenant label, fallback ``"-"``.
    Raw (ledger key, journal field) — never a metric label value; the
    bounded labeler owns that mapping."""
    return pod_tenant(pod) or TENANT_FALLBACK


def weights_from_matrix(matrix, tenant_classes, pools=None) -> dict:
    """Accelerator-time share weights from a throughput matrix.

    ``matrix`` is the row-tuple shape both sources share — the synthetic
    ``ops/throughput.DEFAULT_THROUGHPUT_MATRIX`` and the measured
    ``framework/measured.matrix_rows(...)`` artifact: ``((workload_class,
    ((accel_class, milli_throughput), ...)), ...)``.  ``tenant_classes``
    maps tenant → workload class; ``pools`` optionally weights each
    accelerator class by its node count (hetero pools — a class absent
    from ``pools`` contributes nothing).

    A tenant's weight is the accelerator time one of its pods costs on
    the pool mix (the reciprocal of its pool-weighted throughput),
    normalized so the mean weight over the mapped tenants is 1.0 —
    Gavel's FAIRNESS share: equal weights equalize accelerator TIME,
    so slower-class tenants are not starved of time by fast-class pod
    counts.  Tenants without a class, classes without a matrix row, and
    an empty matrix all fall back to weight 1.0 (the uniform arm)."""
    rows = {w: dict(r) for w, r in (matrix or ())}
    shares: dict[str, float] = {}
    for tenant in sorted(tenant_classes or {}):
        row = rows.get(tenant_classes[tenant])
        if not row:
            continue
        if pools:
            num = sum(float(pools.get(a, 0)) for a in row)
            den = sum(
                float(pools.get(a, 0)) * float(tp) for a, tp in row.items()
            )
        else:
            num = float(len(row))
            den = float(sum(row.values()))
        if den > 0.0:
            shares[tenant] = num / den
    out = {t: 1.0 for t in (tenant_classes or {})}
    if shares:
        mean = sum(shares.values()) / len(shares)
        if mean > 0.0:
            out.update({t: s / mean for t, s in shares.items()})
    return out


class _TenantLedger:
    """Per-tenant durable fairness state (one WFQ flow)."""

    __slots__ = ("vfinish", "credits", "last_refill", "attempts")

    def __init__(self, credits: float, now: float = 0.0):
        self.vfinish = 0.0
        self.credits = credits
        self.last_refill = now
        self.attempts = 0


class _Ledger:
    """One full fairness ledger: the global virtual clock plus every
    tenant flow.  The policy holds two — effective and durable — and
    mutates both through the same arithmetic so they cannot drift."""

    def __init__(self):
        self.vtime = 0.0
        self.tenants: dict[str, _TenantLedger] = {}


class FairAdmission:
    """The admission policy object ``SchedulingQueue`` consults when
    armed (``admission_policy=``).  Off by default everywhere — an
    unarmed queue's pop path is byte-identical to pre-PR behavior."""

    def __init__(
        self,
        weights: dict | None = None,
        rate_pods_per_s: float = 0.0,
        burst: float = DEFAULT_BURST_CREDITS,
        aging_max_wait_s: float = DEFAULT_AGING_MAX_WAIT_S,
        slo_wait_budget_s: float = DEFAULT_SLO_WAIT_BUDGET_S,
        cost: float = DEFAULT_ADMISSION_COST,
        clock=None,
        registry=None,
        labeler=None,
    ):
        self.weights = dict(weights or {})
        self.rate = float(rate_pods_per_s)
        self.burst = float(burst)
        self.aging_max_wait_s = float(aging_max_wait_s)
        self.slo_wait_budget_s = float(slo_wait_budget_s)
        self.cost = float(cost)
        # The LOGICAL clock: a callable (router.lc, a soak's scenario
        # clock) or the note_time high-water mark.  Never wall time —
        # credits and aging are decisions, and decisions replay.
        self.clock = clock
        self._now = 0.0
        # Effective ledger (selection truth, runs ahead by the in-flight
        # batches) and durable ledger (journal/snapshot truth).
        self._led = _Ledger()
        self._dur = _Ledger()
        # Queue-content state shared by both ledgers: first-enqueue
        # stamp per pending uid (aging + the starvation SLO measure) and
        # the per-tenant pending order (dict = insertion order; stamps
        # are monotone, so the first entry is the oldest).
        self._pending: dict[str, tuple[str, float]] = {}  # uid → (tenant, t)
        self._by_tenant: dict[str, dict[str, None]] = {}
        # Debit intents: popped but not yet drained into the durable
        # ledger — the commit drain takes its batch's slice by uid.
        self._intents: dict[str, dict] = {}
        # Recovery carry-over: uids whose ``admission`` record survived a
        # crash but whose bind did not (the debit is durable, the pod is
        # re-fed unbound).  The armed pop path re-admits these FIRST, in
        # durable admission order, without a second debit or log entry.
        self.preadmitted: dict[str, None] = {}
        # Durable admission order (uids, apply/replay order): the kill
        # matrix's admission-order artifact reads this after recovery.
        self.admitted_log: list[str] = []
        self._escapes = 0
        self._throttle_hits = 0
        # Starvation-SLO violations (admission wait > budget), total and
        # per tenant — tracked on the policy itself (not just the metric
        # families) so the soak artifact's "0 violations for the capped
        # tenant" claim reads the same number with observability off.
        self.starved = 0
        self._starved_by_tenant: dict[str, int] = {}
        self._wait_hist = None
        self._starved_counter = None
        self._escape_counter = None
        self._throttled_counter = None
        self._labeler = labeler
        if registry is not None and labeler is not None:
            self._wait_hist = registry.histogram(
                "scheduler_tenant_slo_admission_wait_seconds",
                "Logical-clock wait from a pod's first queue entry to its "
                "WFQ admission, by tenant (the starvation-SLO measure).",
            )
            self._starved_counter = registry.counter(
                "scheduler_tenant_slo_starvation_total",
                "Admissions whose logical queue wait exceeded the "
                "per-tenant starvation-SLO budget, by tenant.",
            )
            self._escape_counter = registry.counter(
                "scheduler_tenant_slo_aging_escapes_total",
                "Admissions granted through the guaranteed-admission "
                "aging escape (credits empty, oldest wait past the aging "
                "threshold), by tenant.",
            )
            self._throttled_counter = registry.counter(
                "scheduler_tenant_slo_throttled_total",
                "Selection rounds in which a tenant with queued pods was "
                "passed over for lack of burst credits, by tenant.",
            )

    # -- clock ---------------------------------------------------------------

    def note_time(self, t: float) -> None:
        """Advance the logical clock high-water mark (monotone — stale
        events never rewind refills)."""
        if t > self._now:
            self._now = t

    def now(self) -> float:
        return float(self.clock()) if self.clock is not None else self._now

    # -- weights -------------------------------------------------------------

    def set_weights(self, weights: dict) -> None:
        self.weights = {str(k): float(v) for k, v in (weights or {}).items()}

    def weight(self, tenant: str) -> float:
        w = self.weights.get(tenant, 1.0)
        return w if w > 0.0 else 1.0

    # -- queue-content bookkeeping --------------------------------------------

    def note_enqueue(self, tenant: str, uid: str) -> None:
        """Stamp a pod's first queue entry (aging/SLO clock starts).
        Re-activations (backoff flush, snapshot restore) keep the
        ORIGINAL stamp: starvation is measured from first entry, so a
        retried pod's accumulated wait still counts."""
        if uid not in self._pending:
            self._pending[uid] = (tenant, self.now())
        self._by_tenant.setdefault(tenant, {})[uid] = None

    def forget(self, uid: str) -> None:
        """Drop a pod deleted while pending (its ghost stamp must not
        hold the aging escape open forever)."""
        ent = self._pending.pop(uid, None)
        if ent is not None:
            pool = self._by_tenant.get(ent[0])
            if pool is not None:
                pool.pop(uid, None)
                if not pool:
                    self._by_tenant.pop(ent[0], None)

    def oldest_wait(self, tenant: str, now: float) -> float:
        pool = self._by_tenant.get(tenant)
        if not pool:
            return 0.0
        uid = next(iter(pool))
        return max(0.0, now - self._pending[uid][1])

    # -- the shared ledger arithmetic ----------------------------------------

    def _refill(self, st: _TenantLedger, now: float) -> None:
        if self.rate > 0.0 and now > st.last_refill:
            st.credits = min(
                self.burst, st.credits + self.rate * (now - st.last_refill)
            )
        if now > st.last_refill:
            st.last_refill = now

    def _flow(self, led: _Ledger, tenant: str) -> _TenantLedger:
        st = led.tenants.get(tenant)
        if st is None:
            st = led.tenants[tenant] = _TenantLedger(self.burst)
        return st

    def _admit_one(
        self, led: _Ledger, tenant: str, now: float, escape: bool
    ) -> None:
        """One debit, identical on either ledger: refill → credit debit
        (floored on an aging escape) → WFQ tag advance.  The refill is
        composable (min-clamped linear accumulation), so replaying the
        durable ledger through the journaled debit stream lands on
        exactly the effective ledger's state."""
        st = self._flow(led, tenant)
        self._refill(st, now)
        if self.rate > 0.0:
            st.credits = max(0.0, st.credits - self.cost)
        start = max(led.vtime, st.vfinish)
        st.vfinish = start + self.cost / self.weight(tenant)
        led.vtime = start
        st.attempts += 1
        del escape  # recorded on the intent; the ledger math is uniform

    # -- selection (the queue's armed pop path) -------------------------------

    def select(self, tenants, now: float):
        """Pick the next tenant to admit from among those with a queued
        head: the minimum WFQ start tag over the eligible set (credits
        available, cap disarmed, or the aging escape), ties on the
        sorted tenant name.  Returns ``(tenant, escape)`` or ``None``
        when every candidate is credit-blocked — the queue surfaces
        that as throttled (callers stop polling; aging re-arms it)."""
        best = None
        for tenant in sorted(tenants):
            st = self._flow(self._led, tenant)
            self._refill(st, now)
            escape = False
            if self.rate > 0.0 and st.credits < self.cost:
                if self.oldest_wait(tenant, now) < self.aging_max_wait_s:
                    self._throttle_hits += 1
                    if self._throttled_counter is not None:
                        self._throttled_counter.inc(
                            tenant=self._labeler.label_for(tenant)
                        )
                    continue
                escape = True
            key = (max(self._led.vtime, st.vfinish), tenant)
            if best is None or key < best[0]:
                best = (key, tenant, escape)
        if best is None:
            return None
        return best[1], best[2]

    def admit(self, tenant: str, uid: str, now: float, escape: bool) -> None:
        """Debit the EFFECTIVE ledger for one admitted pod and record
        the intent the commit drain will journal + apply durably."""
        ent = self._pending.pop(uid, None)
        wait = max(0.0, now - ent[1]) if ent is not None else 0.0
        pool = self._by_tenant.get(tenant)
        if pool is not None:
            pool.pop(uid, None)
            if not pool:
                self._by_tenant.pop(tenant, None)
        self._admit_one(self._led, tenant, now, escape)
        if escape:
            self._escapes += 1
        self._intents[uid] = {
            "uid": uid,
            "tenant": tenant,
            "now": now,
            "escape": bool(escape),
        }
        if wait > self.slo_wait_budget_s:
            self.starved += 1
            self._starved_by_tenant[tenant] = (
                self._starved_by_tenant.get(tenant, 0) + 1
            )
        if self._wait_hist is not None:
            tlabel = self._labeler.label_for(tenant)
            self._wait_hist.observe(wait, tenant=tlabel)
            if escape and self._escape_counter is not None:
                self._escape_counter.inc(tenant=tlabel)
            if (
                wait > self.slo_wait_budget_s
                and self._starved_counter is not None
            ):
                self._starved_counter.inc(tenant=tlabel)

    # -- the durable half (commit drain + recovery) ---------------------------

    def pending_intents(self) -> list[str]:
        """UIDs popped under admission whose debits are not yet group-
        committed, in POP order — the queue snapshot re-emits them as
        front-of-queue active entries so a crash that loses their group
        restores them at their pre-pop positions (presumed abort)."""
        return list(self._intents)

    def take_intents(self, uids) -> list[dict]:
        """Remove and return the debit intents of one batch — the
        payload of the batch's ``admission`` journal record.  Order is
        POP order (the intent dict's insertion order), NOT the caller's
        uid order: the packer may permute a batch, but replaying debits
        out of pop order would evolve the durable WFQ tags differently
        from the effective ledger."""
        want = frozenset(uids)
        out = [d for uid, d in self._intents.items() if uid in want]
        for d in out:
            del self._intents[d["uid"]]
        return out

    def apply_admission(self, debits) -> None:
        """Make a journaled debit batch durable: replay it onto the
        durable ledger (the snapshot/recovery truth).  Called by the
        commit drain strictly AFTER the batch's ``admission`` record is
        inside the group barrier — journal-before-apply."""
        for d in debits:
            self._admit_one(
                self._dur, d["tenant"], float(d["now"]), bool(d["escape"])
            )
            self.admitted_log.append(d["uid"])

    def replay_admission(self, debits) -> None:
        """Recovery replay (journal.recover): the debits are already
        durable, so they advance BOTH ledgers — after replay the
        effective ledger equals the durable one and the next pop
        selects exactly what the uninterrupted run selected."""
        for d in debits:
            now = float(d["now"])
            self.note_time(now)
            self._admit_one(self._led, d["tenant"], now, bool(d["escape"]))
            self._admit_one(self._dur, d["tenant"], now, bool(d["escape"]))
            self.admitted_log.append(d["uid"])
            self.forget(d["uid"])
            # If the pod's bind record did NOT survive, reconcile will
            # re-feed it unbound — already admitted, never re-debited.
            self.preadmitted[d["uid"]] = None

    def take_preadmitted(self, live) -> str | None:
        """Next durably-admitted-but-unbound uid still queued (``live`` =
        the queue's active uid set), consuming entries as it scans: a uid
        no longer live had its bind survive the crash (or was deleted) —
        its carry-over is spent either way.  The consumed pod's pending
        stamp is dropped here (re-feeding re-stamped it after the replay
        already forgot it); a later scheduling FAILURE re-enqueues it
        through the normal WFQ path, debited like any retry — exactly the
        uninterrupted run's behavior."""
        while self.preadmitted:
            uid = next(iter(self.preadmitted))
            del self.preadmitted[uid]
            if uid in live:
                self.forget(uid)
                return uid
        return None

    # -- durability (queue.durable_state surface) ------------------------------

    def durable_state(self) -> dict:
        """Serialize the DURABLE ledger for a journal snapshot.  Clocks
        are relative ages like every queue clock (refill stamps and
        enqueue stamps rebase on the restoring process's logical clock);
        WFQ tags are dimensionless and carry verbatim.  Values are NOT
        rounded — recovery must land on bit-identical selection state."""
        now = self.now()
        return {
            # The absolute clock reading the ages below are relative TO:
            # a restoring process that resumes the SAME logical clock
            # (the journaled deployment — note_time-driven) note_times it
            # and lands on absolute original stamps; one whose clock
            # restarts (an injected clock, e.g. a rebuilt fleet router)
            # ignores it and rebases the ages onto its own clock.
            "now": now,
            "vtime": self._dur.vtime,
            "tenants": {
                t: {
                    "vfinish": st.vfinish,
                    "credits": st.credits,
                    "refill_age": max(0.0, now - st.last_refill),
                    "attempts": st.attempts,
                }
                for t, st in sorted(self._dur.tenants.items())
            },
            "pending": [
                {
                    "uid": uid,
                    "tenant": tenant,
                    "age": max(0.0, now - t0),
                }
                for uid, (tenant, t0) in self._pending.items()
            ],
            # The durable admission order up to this checkpoint: replayed
            # post-snapshot "admission" records append to it, so recovery
            # reconstructs the FULL audit order, not just the suffix (the
            # tenant kill cells compare it end to end).  Long-running
            # deployments that must bound snapshot growth harvest-and-
            # re-arm instead (the soak driver's rebuild path).
            "admitted_log": list(self.admitted_log),
        }

    def restore_state(self, state: dict) -> None:
        """Rebuild both ledgers from a snapshot document.  The queue
        restores admission BEFORE its pod entries, so the re-enqueued
        pods find their original (rebased) stamps already present and
        keep them — accumulated starvation wait survives the crash."""
        self.note_time(float(state.get("now", 0.0)))
        now = self.now()
        dur = _Ledger()
        dur.vtime = float(state.get("vtime", 0.0))
        for t, d in (state.get("tenants") or {}).items():
            st = _TenantLedger(self.burst)
            st.vfinish = float(d.get("vfinish", 0.0))
            st.credits = float(d.get("credits", self.burst))
            st.last_refill = now - float(d.get("refill_age", 0.0))
            st.attempts = int(d.get("attempts", 0))
            dur.tenants[t] = st
        self._dur = dur
        led = _Ledger()
        led.vtime = dur.vtime
        for t, st in dur.tenants.items():
            cp = _TenantLedger(self.burst)
            cp.vfinish = st.vfinish
            cp.credits = st.credits
            cp.last_refill = st.last_refill
            cp.attempts = st.attempts
            led.tenants[t] = cp
        self._led = led
        self._pending = {}
        self._by_tenant = {}
        self._intents = {}
        self.preadmitted = {}
        self.admitted_log = [str(u) for u in state.get("admitted_log", ())]
        for e in state.get("pending", ()):
            tenant = str(e.get("tenant", TENANT_FALLBACK))
            uid = str(e["uid"])
            self._pending[uid] = (tenant, now - float(e.get("age", 0.0)))
            self._by_tenant.setdefault(tenant, {})[uid] = None

    # -- operator view (fleet status --sockets fairness block) -----------------

    def status(self) -> dict:
        """Per-tenant fairness view from the EFFECTIVE state mirror:
        weight, credit balance, virtual-time lag (how far the tenant's
        finish tag runs ahead of the global virtual clock — a large lag
        means it has been admitted ahead of its share), pending depth,
        oldest wait, and the starvation-SLO verdict."""
        now = self.now()
        tenants: dict[str, dict] = {}
        names = set(self._led.tenants) | set(self._by_tenant)
        for t in sorted(names):
            st = self._flow(self._led, t)
            wait = self.oldest_wait(t, now)
            tenants[t] = {
                "weight": round(self.weight(t), 6),
                "credits": round(st.credits, 6),
                "vfinish": round(st.vfinish, 6),
                "vtime_lag": round(st.vfinish - self._led.vtime, 6),
                "attempts": st.attempts,
                "pending": len(self._by_tenant.get(t, ())),
                "oldest_wait_s": round(wait, 3),
                "starved": self._starved_by_tenant.get(t, 0),
                "slo": (
                    "starved" if wait > self.slo_wait_budget_s else "ok"
                ),
            }
        return {
            "armed": True,
            "vtime": round(self._led.vtime, 6),
            "rate_pods_per_s": self.rate,
            "burst": self.burst,
            "aging_max_wait_s": self.aging_max_wait_s,
            "slo_wait_budget_s": self.slo_wait_budget_s,
            "aging_escapes": self._escapes,
            "throttle_hits": self._throttle_hits,
            "starvation_violations": self.starved,
            "admitted": len(self.admitted_log),
            "tenants": tenants,
        }
