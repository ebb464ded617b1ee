"""Software-pipelined batch commit: staged binds, group-commit drain,
and the predispatch double buffer (ISSUE 15).

The serial batch loop interleaves three kinds of work that have no data
dependence on each other once the device pass has been dispatched:

- **featurize(k+1)** — host CPU building the next batch's feature rows
  (already overlapped by the scheduler's prefetch since PR 6);
- **device(k)** — the compiled pass, running asynchronously on the
  accelerator from dispatch until the completion fetch;
- **commit/journal(k-1)** — host bookkeeping plus the write-ahead
  journal's durability barrier (one fsync per append, before group
  commit, was about half the serial loop's wall on the CPU box).

This module supplies the two pieces that turn the loop into a real
pipeline (the generalization of PR 6's ``post_dispatch_hook``
amortization into a stage engine):

- :class:`CommitTicket` / :func:`drain_commit` — the commit stage is
  SPLIT.  ``_complete_batch`` stages every bind (reserve plugins run,
  cache assumed, outcome built) into a ticket; ``drain_commit`` then
  journals the whole ticket inside ONE ``journal.group()`` barrier and
  applies the binds only after the group's single fsync has returned —
  journal-before-apply preserved strictly, at group scope (tpulint's
  WAL family checks this file).  At pipeline depth 1 the drain runs at
  exactly the point the serial loop applied binds inline; at depth >= 2
  the scheduler dispatches batch k+1 FIRST, so the fsync and the apply
  loop execute under the in-flight device pass.

- :class:`Predispatch` / :func:`predispatch_valid` — the double buffer
  for the dispatch stage: batch k+1 (already featurized by the
  prefetch) is dispatched at the END of batch k's cycle, before the
  drain, so the device is never idle while the host commits.  The
  predispatched pass ran against the host state visible at dispatch
  time; ``predispatch_valid`` re-checks every token that state could
  have changed under (feature version, mutation epoch, schema, dirty
  rows, live nominations) when the next cycle picks the pass up — a
  mismatch discards the pass, rolls the tie-break cycle counter back,
  and re-dispatches exactly as the serial loop would have, so bindings
  stay bit-identical to pipeline depth 1 (the parity oracle).

Determinism: this module decides nothing — staging order is the
serial loop's entry order, the drain applies in that order, and every
validity token is a pure function of scheduler state (the determinism
lint family covers this file like the rest of ``engine/``).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ..framework.events import NORMAL
from ..journal import _crash


@dataclass
class StagedBind:
    """One bind that passed Permit + Reserve and awaits its group's
    durability barrier.  ``outcome`` is the ScheduleOutcome already in
    the batch's outcome list (node set optimistically at stage time; a
    same-batch race rollback clears it and unstages the bind)."""

    qp: object  # QueuedPodInfo
    node_name: str
    outcome: object  # ScheduleOutcome
    # Once-only accounting ran (gang quorum credit, counters, events):
    # a resumed drain may replay a partially applied bind's idempotent
    # state steps, but must never credit it twice.
    counted: bool = False


@dataclass
class CommitTicket:
    """The staged commit group of one batch: binds whose journal records
    and applies drain together under one group fsync."""

    staged: list = field(default_factory=list)
    # Batch commit clock (time.monotonic at phase 1) — latency samples
    # and first/last-scheduled stamps use it so a deferred drain reports
    # the same numbers the inline apply would have.
    now: float = 0.0
    drained: bool = False
    # Drain progress: staged[:journaled] have records WRITTEN to the
    # log (bytes in the file: the group is written whole or not at all,
    # so this is 0 or len(staged)), barriered means the group's fsync
    # RETURNED (written is not durable), staged[:applied] are live.  A
    # drain interrupted by an exception (deposed-writer fence, write or
    # fsync OSError) leaves drained False with these markers saying
    # what is in the file, so the recovery drain resumes exactly what
    # remains — never re-journaling, never silently abandoning the
    # group, and never applying ahead of a barrier that has not
    # actually returned.
    journaled: int = 0
    barriered: bool = False
    applied: int = 0
    # Weighted-fair admission debits of THIS batch's pops (framework/
    # fairness intent records, pop order).  Captured at ticket creation
    # so a depth-2 prefetch pop for batch k+1 can never smuggle its
    # debits into batch k's group.  Journaled as one "admission" record
    # FIRST inside the group (a bind is only durable together with the
    # debit that admitted it), applied to the durable ledger after the
    # barrier; the two flags make an interrupted drain resume without
    # re-journaling or double-debiting.
    admission: list | None = None
    admission_journaled: bool = False
    admission_applied: bool = False
    # Membership index (never iterated): rollback paths and the
    # scheduler's metrics loop ask "is this uid staged?".
    _uids: set = field(default_factory=set)

    def stage(self, qp, node_name: str, outcome) -> None:
        self.staged.append(StagedBind(qp, node_name, outcome))
        self._uids.add(qp.pod.uid)

    def unstage(self, uid: str) -> None:
        """Remove a bind a same-batch race rolled back (its record was
        never journaled; nothing to undo on the log)."""
        self._uids.discard(uid)
        self.staged = [sb for sb in self.staged if sb.qp.pod.uid != uid]

    def holds(self, uid: str) -> bool:
        return uid in self._uids

    def __len__(self) -> int:
        return len(self.staged)


def drain_commit(sched, ticket: CommitTicket) -> None:
    """Journal + apply one staged commit group.  The caller's
    `pipeline/drain` span is the flight recorder's ``drain`` stage
    segment; inside it three spans say where the drain goes:
    `drain/journal_append` (serialise and encode every record into the
    group's buffer, with the serialisation's share as ``serialize_us``),
    `drain/journal_fsync` (the group's one barrier) and `drain/apply`;
    the group's one write + flush runs between the first two and is the
    flight record's ``journal.append_s``.

    Ordering contract (the WAL family's apply sites live here):

    1. every staged bind's record is appended inside ONE
       ``journal.group()`` — encoded and buffered, no syscall a record;
    2. the group exits — one fence check, one write, one flush, one
       fsync: all records in the file and durable together;
    3. only then does any bind apply (spec mutation, finish_binding,
       queue bookkeeping, events/metrics), in stage order.

    A crash before or inside the barrier applied nothing; recovery
    replays the durable prefix and reschedules the rest — the
    pipeline cells of scripts/run_fault_matrix.py probe exactly these
    windows (stage-boundary / mid-group-fsync / post-group-fsync /
    torn-group-tail).

    The group is all-or-nothing in the file, and the ticket's counters
    say so.  After any in-process EXCEPTION out of the group (a record
    that will not serialise, the epoch fence, a write or fsync error)
    ``ticket.journaled`` and ``ticket.admission_journaled`` count only
    records whose bytes are in the file, no ``seq`` appears twice in
    the file, and no bind is applied whose record is not durable: an
    exception before the write discards the buffer and rewinds ``seq``
    (the counters stay where they were), and the one case that leaves
    the whole group written — its fsync raised — advances them, so the
    retry (the recovery path's ``_drain_pending``) re-runs the barrier
    instead of the group.  ``drained`` stays False throughout.
    """
    if ticket.drained:
        return
    if not ticket.staged and not ticket.admission:
        ticket.drained = True
        return
    # The commit stage is fully staged, nothing journaled yet — the
    # stage-boundary crash window (at depth >= 2 a device pass for the
    # NEXT batch is typically in flight right now).
    _crash("stage-boundary")
    journal = sched.journal
    if journal is not None and not ticket.barriered:
        need_admission = bool(ticket.admission) and not ticket.admission_journaled
        if ticket.journaled < len(ticket.staged) or need_admission:
            # group() writes and fsyncs at its exit, where the journal
            # times the barrier as `drain/journal_fsync`.
            written = journal.appends
            try:
                with journal.group():
                    with sched.span("drain/journal_append") as sp:
                        sched._serialize_s = 0.0
                        if need_admission:
                            # The batch's fairness debits ride the SAME
                            # barrier as its binds, ahead of them: a crash
                            # either loses the whole group (restored pods
                            # re-pop through the identical ledger) or
                            # recovers debits + binds together — admission
                            # order replays bit-identical.
                            sched._journal_append(
                                "admission", debits=ticket.admission
                            )
                        for sb in ticket.staged[ticket.journaled :]:
                            sched._journal_bind(sb.qp.pod, sb.node_name)
                        sp.set("serialize_us", int(sched._serialize_s * 1e6))
            finally:
                # `appends` moves only when the group's one write has
                # returned: count the group then, whole, even if the
                # fsync after it raised.
                if journal.appends != written:
                    ticket.admission_journaled |= need_admission
                    ticket.journaled = len(ticket.staged)
        else:
            # Every record is already written; only the group's fsync
            # raised on the last attempt.  Re-entering group() would see
            # nothing buffered and skip the fsync — re-run the barrier
            # explicitly instead.
            journal.barrier()
        ticket.barriered = True
    # Group fsync returned: every record in the group is durable.
    if ticket.admission and not ticket.admission_applied:
        # Debits are durable (journaled above, inside the barrier) —
        # advance the DURABLE fairness ledger to match the effective
        # ledger's pop-time debits.  Flag-guarded so an in-process
        # resume of an interrupted drain never double-debits.
        sched.queue.admission.apply_admission(ticket.admission)
        ticket.admission_applied = True
    # Apply in stage order — identical to the serial loop's inline
    # order, just batched behind the single barrier.
    with sched.span("drain/apply"):
        _apply_staged(sched, ticket)
    ticket.drained = True
    # Stage flight fields: deterministic drain counts on the current
    # batch's flight record — the trace exporter sizes/labels the drain
    # slice from these, never from wall seconds (which differ run to
    # run).  A recovery drain outside a batch has no accumulator; the
    # guard inside _flight_add keeps this a no-op there.
    sched._flight_add("drained", ticket.applied)
    if journal is not None:
        sched._flight_add("group_fsyncs", 1)


def _apply_staged(sched, ticket: CommitTicket) -> None:
    """The apply loop of drain_commit: stage order, after the barrier."""
    m = sched.metrics
    now = ticket.now
    for sb in ticket.staged[ticket.applied :]:
        qp, node_name = sb.qp, sb.node_name
        # State steps — each idempotent, so a resume may replay a
        # partially applied bind from the top.
        qp.pod.spec.node_name = node_name
        sched.cache.finish_binding(qp.pod.uid)
        # Self-placed pods get their NoExecute judgment at bind (the
        # reference's handlePodUpdate fires on the binding update).
        sched.taint_eviction.handle_pod_assigned(qp.pod, node_name)
        sched.queue.done(qp.pod.uid)
        if not sb.counted:
            sb.counted = True
            # Gang quorum credit first (state-critical), observational
            # accounting after — a fault below loses at most one bind's
            # metrics, never credit, and a resume never double-counts.
            if qp.pod.spec.pod_group:
                sched.gang_bound[qp.pod.spec.pod_group] = (
                    sched.gang_bound.get(qp.pod.spec.pod_group, 0) + 1
                )
            if m.scheduled == 0:
                m.first_scheduled_ts = now
            m.scheduled += 1
            m.last_scheduled_ts = now
            sched._note_bound(qp.pod, node_name)
            sched.recorder.event(
                qp.pod.uid, NORMAL, "Scheduled",
                f"Successfully assigned {qp.pod.uid} to {node_name}",
            )
            lat = now - qp.initial_attempt_timestamp
            m.e2e_latency_samples.append(lat)
            m.registry.scheduling_sli.observe(lat)
        ticket.applied += 1


@dataclass
class Predispatch:
    """A device pass dispatched one cycle early (the double buffer).

    ``infos`` is the batch in its ORIGINAL pop order (the packer may
    have permuted ``ctx['infos']``; an invalidated predispatch must
    re-dispatch from the unpermuted order or the re-pack would see
    pre-permuted input and diverge from the serial loop)."""

    infos: list
    ctx: dict
    profile: object
    # Validity tokens, captured at dispatch:
    version: tuple  # builder.feature_version()
    mutation_epoch: int
    schema: object
    nominator_token: tuple
    cycle0: int  # _cycle before the dispatch (rollback target)


def nominator_token(sched) -> tuple:
    """Stable fingerprint of the live nominations a dispatch read
    (_full_inv's nom_* arrays and _inject_nomrows both depend on them):
    any change between predispatch and pickup must invalidate."""
    return tuple(
        sorted(
            (uid, node, prio)
            for uid, (node, _delta, prio) in sched.nominator.items()
        )
    )


def predispatch_valid(sched, pd: Predispatch) -> bool:
    """True when nothing the predispatched pass read has changed since
    dispatch — the pass's decisions are exactly what a fresh dispatch
    would compute, so the pipeline may complete it as-is."""
    b = sched.builder
    return (
        pd.version == b.feature_version()
        and pd.mutation_epoch == b.mutation_epoch
        and pd.schema == b.schema
        and not b._dirty_all
        and not b._dirty_rows
        and pd.nominator_token == nominator_token(sched)
    )
