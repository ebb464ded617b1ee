"""Three-stage scheduling queue: activeQ / backoffQ / unschedulable pool.

Mirrors the reference's PriorityQueue (pkg/scheduler/backend/queue/
scheduling_queue.go:152): activeQ is a heap ordered by the QueueSort plugin
(priority desc, then enqueue time — queuesort/priority_sort.go), backoffQ
holds pods whose backoff hasn't expired (1s initial, ×2 per attempt, 10s cap —
scheduling_queue.go:73–81), and the unschedulable pool holds pods waiting for
a cluster event that might make them schedulable again
(flushUnschedulablePodsLeftover re-activates them after 5min, :807).

Requeue-on-event hints are simplified to event bitmasks per rejection source
(the analog of isPodWorthRequeuing's per-plugin QueueingHintFn, :406)."""

from __future__ import annotations

import heapq
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from enum import IntFlag, auto

import numpy as np

from .api import types as t
from .framework import fairness


class Event(IntFlag):
    """Cluster event kinds driving requeue (framework/events.go:40)."""

    NODE_ADD = auto()
    NODE_UPDATE = auto()
    NODE_TAINT = auto()
    NODE_LABEL = auto()
    POD_ADD = auto()
    POD_UPDATE = auto()
    POD_DELETE = auto()
    PV_ADD = auto()
    PVC_ADD = auto()
    CLAIM_ADD = auto()  # ResourceClaim/ResourceSlice events (DRA)
    ANY = (
        NODE_ADD | NODE_UPDATE | NODE_TAINT | NODE_LABEL | POD_ADD | POD_UPDATE
        | POD_DELETE | PV_ADD | PVC_ADD | CLAIM_ADD
    )


# Which events can unblock a pod rejected by a given plugin — the static core
# of the reference's per-plugin EventsToRegister (e.g. fit.go:253 queueing hints).
PLUGIN_REQUEUE_EVENTS: dict[str, Event] = {
    "NodeResourcesFit": Event.NODE_ADD | Event.NODE_UPDATE | Event.POD_DELETE | Event.POD_UPDATE,
    "NodeAffinity": Event.NODE_ADD | Event.NODE_LABEL,
    "NodeName": Event.NODE_ADD,
    "NodeUnschedulable": Event.NODE_ADD | Event.NODE_UPDATE,
    "TaintToleration": Event.NODE_ADD | Event.NODE_TAINT,
    "NodePorts": Event.NODE_ADD | Event.POD_DELETE,
    "PodTopologySpread": Event.NODE_ADD | Event.NODE_LABEL | Event.POD_ADD | Event.POD_DELETE | Event.POD_UPDATE,
    "InterPodAffinity": Event.NODE_ADD | Event.NODE_LABEL | Event.POD_ADD | Event.POD_DELETE | Event.POD_UPDATE,
    "VolumeBinding": Event.NODE_ADD | Event.PV_ADD | Event.PVC_ADD | Event.POD_DELETE,
    "VolumeZone": Event.NODE_ADD | Event.NODE_LABEL | Event.PV_ADD | Event.PVC_ADD,
    "VolumeRestrictions": Event.POD_DELETE | Event.PV_ADD | Event.PVC_ADD | Event.NODE_ADD,
    "NodeVolumeLimits": Event.NODE_ADD | Event.NODE_UPDATE | Event.POD_DELETE | Event.PVC_ADD,
    # Gang members wait for more members (pod adds) or capacity.
    "GangScheduling": Event.POD_ADD | Event.POD_DELETE | Event.NODE_ADD,
    "DynamicResources": Event.CLAIM_ADD | Event.POD_DELETE | Event.NODE_ADD
    | Event.NODE_UPDATE,
}

DEFAULT_POD_INITIAL_BACKOFF_S = 1.0
DEFAULT_POD_MAX_BACKOFF_S = 10.0
DEFAULT_MAX_UNSCHEDULABLE_DURATION_S = 300.0
# Quarantine-release history window (SchedulingQueue.release_history):
# bounded so an unbounded release stream cannot grow the queue's durable
# state — compaction trims snapshots to this trailing window.
RELEASE_HISTORY_MAX = 256


@dataclass
class EventCtx:
    """Event-object payload for object-aware queueing hints — the batch
    analog of the oldObj/newObj arguments the reference passes to each
    plugin's QueueingHintFn (scheduling_queue.go:406 isPodWorthRequeuing;
    e.g. fit.go:253 isSchedulableAfterPodChange checks whether the deleted
    pod actually frees enough for the waiting pod).

    ``max_free``/``max_slots`` summarize capacity freed or added by the
    event, elementwise-maxed over every affected node (nominated pods'
    claims already subtracted).  The max is an upper bound on any single
    node's free vector, so hints stay conservative: a pod that fits some
    affected node always fits the max and is woken; a pod that cannot fit
    the max cannot fit anywhere and is skipped."""

    max_free: np.ndarray | None = None  # (R,) free allocatable upper bound
    max_slots: int = 0  # free pod slots upper bound


def _pack_reqs(reqs: list[np.ndarray]) -> np.ndarray:
    """Stack request vectors into one (K, maxR) int64 matrix (zero-padded;
    a missing column means the pod does not request that resource)."""
    mx = max((q.shape[0] for q in reqs), default=0)
    reqm = np.zeros((len(reqs), max(mx, 1)), np.int64)
    for i, q in enumerate(reqs):
        reqm[i, : q.shape[0]] = q
    return reqm


def _fits_packed(reqm: np.ndarray, ctx: EventCtx) -> np.ndarray:
    """(K,) bool over a prepacked request matrix: which pods the event's
    freed capacity could seat — THE fit predicate, shared by the scalar
    hint and the queue's batched wake path so the two cannot drift.  A pod
    needing a resource column the affected nodes don't expose never
    wakes."""
    k = reqm.shape[0]
    if ctx.max_slots < 1:
        return np.zeros(k, np.bool_)
    r = ctx.max_free.shape[0]
    head = reqm[:, :r]
    free = ctx.max_free[: head.shape[1]]
    # The fit filter's per-resource escape: a resource the pod does not
    # request never blocks it (negative free in an unrequested column —
    # nominated-claim subtraction — must not pin the pod asleep).
    fits = ((head == 0) | (head <= free[None, :])).all(axis=1)
    if reqm.shape[1] > r:
        fits &= ~(reqm[:, r:] != 0).any(axis=1)
    return fits


def _fits_free(reqs: list[np.ndarray], ctx: EventCtx) -> np.ndarray:
    return _fits_packed(_pack_reqs(reqs), ctx)


def _fit_hint(qp: "QueuedPodInfo", event: "Event", ctx: EventCtx) -> bool:
    """NodeResourcesFit QueueingHint (fit.go:253 isSchedulableAfterPodChange
    / :300 isSchedulableAfterNodeChange): requeue only when the event's
    freed/added capacity could actually seat this pod."""
    if ctx.max_free is None or qp.delta is None:
        return True  # no object info — conservative requeue
    return bool(_fits_free([qp.delta["req"]], ctx)[0])


# Object-aware per-plugin hints; plugins absent here fall back to the static
# event-mask behavior (PLUGIN_REQUEUE_EVENTS alone).
PLUGIN_HINTS = {
    "NodeResourcesFit": _fit_hint,
}

@dataclass(order=False)
class QueuedPodInfo:
    """Mirror of framework.QueuedPodInfo (types.go:362)."""

    pod: t.Pod
    timestamp: float = 0.0  # time added to activeQ this round
    initial_attempt_timestamp: float = 0.0
    attempts: int = 0
    unschedulable_plugins: set[str] = field(default_factory=set)
    gated: bool = False
    # The pod's featurized commit delta from its last attempt (request
    # vector etc.) — the object-aware hints read it; None before the first
    # attempt or after a spec update invalidated it.
    delta: dict | None = None
    # A nominated-pin evaluation failed for this pod: its next attempt
    # takes the full pass (the scheduler's _pin_rows skips it).  Reset when
    # a fresh nomination is recorded.
    nom_pin_failed: bool = False
    # Requeue-verdict class this pod was filed under when it entered the
    # unschedulable pool (set by _unsched_insert, read by _unsched_remove).
    unsched_class: tuple | None = None
    # When the server first held the pod, where that was before the queue
    # did (a hint frame's arrival, on the queue's clock; 0 = the queue
    # add): what a flight record's queue_wait counts from.
    held_at: float = 0.0


class SchedulingQueue:
    def __init__(
        self,
        initial_backoff_s: float = DEFAULT_POD_INITIAL_BACKOFF_S,
        max_backoff_s: float = DEFAULT_POD_MAX_BACKOFF_S,
        max_unschedulable_s: float = DEFAULT_MAX_UNSCHEDULABLE_DURATION_S,
        clock=time.monotonic,
        admission_policy=None,
    ):
        self._clock = clock
        # Weighted-fair admission (framework/fairness.FairAdmission), OFF
        # by default: unarmed, pop_batch is the byte-identical pre-fairness
        # QueueSort path.  Armed, active pods pool into per-tenant heaps
        # and the policy's WFQ/credit state picks which tenant's head pops
        # next.  Arm at construction or via arm_admission().
        self.admission = admission_policy
        self._tenant_active: dict[str, list] = {}
        # True after a pop_batch that returned short NOT because the
        # active pool drained but because every queued tenant is credit-
        # blocked — drain loops must stop polling on this instead of
        # spinning on len(queue) (aging re-arms eligibility later).
        self.last_pop_throttled = False
        self._seq = itertools.count()
        self._active: list = []  # heap of (-priority, timestamp, seq, uid)
        self._backoff: list = []  # heap of (expiry, seq, uid)
        self._unschedulable: dict[str, QueuedPodInfo] = {}
        # Verdict-class index over the unschedulable pool: pods whose
        # requeue verdict is identical for every event share a class
        # ((rejecting plugins, delta presence) — valid while every
        # registered hint is the batched fit hint), so on_event computes
        # ONE verdict per class and one vectorized fit check over a cached
        # request matrix instead of a Python walk of a 15k-pod pool.
        self._unsched_classes: dict[tuple, dict[str, QueuedPodInfo]] = {}
        self._unsched_req_cache: dict[tuple, tuple[list, np.ndarray]] = {}
        self._info: dict[str, QueuedPodInfo] = {}
        self._in_active: set[str] = set()
        # Quarantine pool: pods whose presence in a batch made the ENGINE
        # raise (poison pods, isolated by the scheduler's batch bisect).
        # Unlike the unschedulable pool, no cluster event re-admits them —
        # the failure is a property of the pod, not of capacity — so they
        # sit here until an operator (or a spec update, which invalidates
        # the poison featurization) releases them back through the backoff
        # machinery.  Surfaced as scheduler_pending_pods{queue="quarantine"}.
        self._quarantine: dict[str, QueuedPodInfo] = {}
        # Release history: the trailing window of quarantine releases
        # (operator actions worth triaging after the fact).  BOUNDED —
        # over an unbounded soak the release stream never ends, so the
        # ring trims itself and snapshots carry only this window; the
        # journal's release_quarantine records beyond it are reclaimed
        # by the next snapshot+truncate compaction cycle.
        self.release_history: deque = deque(maxlen=RELEASE_HISTORY_MAX)
        self.initial_backoff_s = initial_backoff_s
        self.max_backoff_s = max_backoff_s
        self.max_unschedulable_s = max_unschedulable_s
        self._gated: dict[str, QueuedPodInfo] = {}
        # Gang admission (the coscheduling plugin's PreEnqueue/Permit pair):
        # members of a registered PodGroup park here until the gang can meet
        # quorum — parked + already-bound credit ≥ minMember — then release
        # TOGETHER so they land in one batch (all-or-nothing co-scheduling;
        # without this, members scatter across pools and quorum never forms).
        self._gang_pool: dict[str, dict[str, QueuedPodInfo]] = {}
        self.gang_min: dict[str, int] = {}
        # Credit per gang beyond the parked members (bound members + members
        # waiting on Permit); the scheduler injects this so PreEnqueue
        # admission and the Permit gate agree.
        self.gang_credit = lambda g: 0
        # Members currently queued anywhere (active/backoff/unschedulable/
        # gated/pool), per gang — the Permit gate asks "are enough members
        # still coming?" before deciding wait-vs-rollback (WaitOnPermit).
        self._gang_members: dict[str, set[str]] = {}
        # SchedulerQueueingHints feature gate: when False, requeue decisions
        # use the static per-plugin event masks alone (the reference's
        # pre-hint behavior); object-aware PLUGIN_HINTS are skipped.
        self.use_queueing_hints = True
        # PodSchedulingReadiness gate: off ⇒ the SchedulingGates plugin is
        # not registered (plugins/registry.go), so .spec.schedulingGates is
        # ignored and gated pods enter the queue like any other.
        self.respect_scheduling_gates = True
        # Per-profile PreEnqueue (profile.pre_enqueue): the scheduler
        # installs a pod → bool predicate saying whether the pod's profile
        # runs SchedulingGates; None = every profile does.
        self.gates_apply_to = None
        # Write-ahead binding journal (journal.Journal), attached by
        # TPUScheduler.attach_journal.  The queue journals the one durable
        # decision IT owns — releasing a quarantined pod — before applying
        # it; everything else is journaled at the scheduler's commit sites.
        self.journal = None
        # Tenant attribution hook (framework/metrics.py TenantMetrics
        # .note_pod), installed by the scheduler/router when tenant
        # attribution is armed: called with ("admitted", pod) on a pod's
        # FIRST queue entry and ("deferred", pod) on every backoff /
        # unschedulable parking — the queue-admission leg of the
        # per-tenant fairness counters.  None = attribution off.
        self.tenant_note = None

    def __len__(self) -> int:
        return len(self._in_active)

    def pending_count(self) -> int:
        return (
            len(self._in_active)
            + len(self._backoff)
            + len(self._unschedulable)
            + len(self._gated)
            + len(self._quarantine)
            + sum(len(p) for p in self._gang_pool.values())
        )

    # -- quarantine ------------------------------------------------------------

    def quarantine(self, qp: QueuedPodInfo) -> None:
        """Isolate a poison pod (its batch made the engine raise).  Re-owns
        the info entry pop_batch dropped; the pod leaves every other pool
        and stays out of scheduling until released."""
        uid = qp.pod.uid
        self._info[uid] = qp
        self._in_active.discard(uid)
        self._unsched_remove(uid)
        qp.unschedulable_plugins = {"EngineFault"}
        qp.timestamp = self._clock()
        qp.delta = None  # featurization is suspect — never trust it again
        self._quarantine[uid] = qp

    def quarantined(self) -> list[str]:
        return list(self._quarantine)

    def release_quarantine(self, uid: str | None = None) -> int:
        """Hand quarantined pod(s) back through the backoff machinery (an
        operator action after a fix, or the update path after a spec
        change).  Backoff grows with the pod's accumulated attempts, so a
        still-poisonous pod re-quarantines at a bounded retry rate instead
        of wedging batches back-to-back."""
        uids = [uid] if uid is not None else list(self._quarantine)
        n = 0
        for u in uids:
            if u in self._quarantine and self.journal is not None:
                # Write-ahead: the release is a durable decision — a
                # restart must not resurrect the pod into quarantine.
                self.journal.append("release_quarantine", {"uid": u})
            qp = self._quarantine.pop(u, None)
            if qp is not None:
                self.add_backoff(qp)
                # Triage trail: what was released and after how many
                # attempts.  The deque bounds itself (RELEASE_HISTORY_MAX)
                # — the clock is the queue's own (monotonic by default,
                # rebased across restarts like every other queue clock).
                self.release_history.append(
                    {
                        "uid": u,
                        "attempts": qp.attempts,
                        "ts": round(self._clock(), 3),
                    }
                )
                n += 1
        return n

    def restore_quarantine(self, pod: t.Pod, attempts: int = 1) -> None:
        """Recovery path (journal.recover): re-isolate a pod a journal
        record says was quarantined, preserving its accumulated attempt
        count so the post-release backoff damping survives the restart.
        The pod may also exist as a snapshot-restored PENDING entry (the
        quarantine decision postdates the snapshot) — quarantine() pulls
        it out of whatever pool it sits in."""
        qp = self._info.get(pod.uid)
        if qp is None:
            now = self._clock()
            qp = QueuedPodInfo(
                pod=pod, timestamp=now, initial_attempt_timestamp=now
            )
        qp.attempts = max(qp.attempts, attempts)
        # Replay applies a decision the journal already holds; appends are
        # muted during recovery, so re-journaling here is wrong by design.
        self.quarantine(qp)  # tpulint: disable=wal-unjournaled-apply

    # -- gang admission --------------------------------------------------------

    def register_gang(self, name: str, min_member: int) -> None:
        self.gang_min[name] = min_member
        self._try_admit_gang(name)

    def gang_pending(self, g: str) -> int:
        """Members of gang g currently queued anywhere (not in-flight)."""
        return len(self._gang_members.get(g, ()))

    def _track_gang_member(self, qp: QueuedPodInfo) -> None:
        self._gang_members.setdefault(qp.pod.spec.pod_group, set()).add(qp.pod.uid)

    def _untrack_gang_member(self, pod: t.Pod) -> None:
        g = pod.spec.pod_group
        if g:
            members = self._gang_members.get(g)
            if members is not None:
                members.discard(pod.uid)
                if not members:
                    self._gang_members.pop(g, None)

    def _park_gang_member(self, qp: QueuedPodInfo) -> None:
        self._gang_pool.setdefault(qp.pod.spec.pod_group, {})[qp.pod.uid] = qp
        self._track_gang_member(qp)

    def _gang_admissible(self, g: str) -> bool:
        pool = self._gang_pool.get(g)
        return pool is not None and len(pool) + self.gang_credit(g) >= self.gang_min.get(g, 1)

    def _try_admit_gang(self, g: str, via_backoff: bool = False) -> bool:
        """Release every parked member of gang ``g`` if quorum is reachable.
        ``via_backoff`` damps event-driven re-admission after a rollback (the
        gang failed with these exact members, so retry behind backoff)."""
        if not self._gang_admissible(g):
            return False
        for qp in self._gang_pool.pop(g).values():
            if via_backoff:
                self.add_backoff(qp)
            else:
                self._push_active(qp)
        return True

    # -- add / pop -----------------------------------------------------------

    def now(self) -> float:
        """The queue's clock: what ``held_at`` is stamped on."""
        return self._clock()

    def held_for(self, infos: list[QueuedPodInfo]) -> tuple[float, float]:
        """(sum, max) over just-popped ``infos`` of the seconds since the
        server first held each pod: one subtraction a pod."""
        now = self._clock()
        waits = [now - (qp.held_at or qp.initial_attempt_timestamp) for qp in infos]
        return sum(waits), max(waits)

    def add(self, pod: t.Pod, held_at: float = 0.0) -> None:
        now = self._clock()
        if pod.uid in self._quarantine:
            # Informer re-deliveries must not resurrect a poison pod into
            # the active queue; spec CHANGES go through update(), which
            # does release it (new featurization, new chance).
            self._quarantine[pod.uid].pod = pod
            return
        qp = self._info.get(pod.uid)
        if qp is None:
            qp = QueuedPodInfo(pod=pod, timestamp=now, initial_attempt_timestamp=now)
            self._info[pod.uid] = qp
            if self.tenant_note is not None:
                self.tenant_note("admitted", pod)
        qp.pod = pod
        if held_at and not qp.held_at:
            qp.held_at = held_at
        # PreEnqueue: SchedulingGates holds gated pods out of every queue
        # (plugins/schedulinggates/scheduling_gates.go).
        if (
            self.respect_scheduling_gates
            and pod.spec.scheduling_gates
            and (self.gates_apply_to is None or self.gates_apply_to(pod))
        ):
            qp.gated = True
            self._gated[pod.uid] = qp
            return
        qp.gated = False
        g = pod.spec.pod_group
        if g:
            self._track_gang_member(qp)
            if g in self.gang_min:
                # New member arrival: park, then admit the whole gang at
                # once if quorum is now reachable.
                self._park_gang_member(qp)
                self._try_admit_gang(g)
                return
        self._push_active(qp)

    def reactivate(self, qp: QueuedPodInfo) -> None:
        """Return an in-flight pod to the ACTIVE queue for a next-batch
        retry (prefetch dissolution, schema-grown-batch fallbacks).
        Restores the bookkeeping pop_batch dropped — the info entry and
        gang membership; registered-gang members re-park so the
        all-or-nothing release is preserved, with an instant re-admission
        attempt (this retry is not a quorum failure)."""
        self._info[qp.pod.uid] = qp
        g = qp.pod.spec.pod_group
        if g:
            self._track_gang_member(qp)
            if g in self.gang_min:
                self._park_gang_member(qp)
                self._try_admit_gang(g)
                return
        self._push_active(qp)

    def requeue_gang_member(self, qp: QueuedPodInfo) -> None:
        """Park a rolled-back gang member WITHOUT instant re-admission — the
        gang just failed with exactly these members, so re-admission waits
        for a cluster event (damped through backoff in on_event) or an
        explicit readmit_gang from the scheduler.  Takes the original
        QueuedPodInfo so attempts/first-enqueue survive the rollback
        (backoff damping and e2e latency stay honest)."""
        self._info[qp.pod.uid] = qp
        self._park_gang_member(qp)

    def readmit_gang(self, g: str) -> bool:
        """Retry a parked gang behind backoff (transient failures — e.g. a
        same-batch PV race — must not strand a quorum-complete gang in a
        quiet cluster where no event would ever re-admit it)."""
        return self._try_admit_gang(g, via_backoff=True)

    def _push_active(self, qp: QueuedPodInfo) -> None:
        if qp.pod.uid in self._in_active:
            return
        qp.timestamp = self._clock()
        item = (
            -qp.pod.spec.priority,
            qp.timestamp,
            next(self._seq),
            qp.pod.uid,
        )
        if self.admission is not None:
            # Armed: active pods pool per tenant (QueueSort order WITHIN
            # a tenant; the policy orders ACROSS tenants) and the policy
            # stamps first-enqueue for aging/starvation accounting.
            tenant = fairness.tenant_of(qp.pod)
            heapq.heappush(self._tenant_active.setdefault(tenant, []), item)
            self.admission.note_enqueue(tenant, qp.pod.uid)
        else:
            heapq.heappush(self._active, item)
        self._in_active.add(qp.pod.uid)
        self._unsched_remove(qp.pod.uid)

    def arm_admission(self, policy) -> None:
        """Arm weighted-fair admission on a live queue: migrate the
        active heap into per-tenant heaps (heap tuples carry over — the
        within-tenant QueueSort order is preserved) and stamp every
        migrated pod's enqueue with the policy so aging starts now."""
        self.admission = policy
        self._tenant_active = {}
        drained, self._active = self._active, []
        for item in drained:
            uid = item[3]
            if uid not in self._in_active:
                continue  # stale heap entry — drop, like pop_batch would
            tenant = fairness.tenant_of(self._info[uid].pod)
            heapq.heappush(self._tenant_active.setdefault(tenant, []), item)
            policy.note_enqueue(tenant, uid)

    def pop_batch(self, k: int) -> list[QueuedPodInfo]:
        """Pop up to k pods in QueueSort order — the batch analog of
        activeQueue.pop (active_queue.go:186).  With admission armed the
        order is the fairness policy's WFQ admission order instead."""
        if self.admission is not None:
            return self._pop_batch_admission(k)
        self.flush_backoff()
        out: list[QueuedPodInfo] = []
        while self._active and len(out) < k:
            _, _, _, uid = heapq.heappop(self._active)
            if uid not in self._in_active:
                continue
            self._in_active.discard(uid)
            qp = self._info[uid]
            qp.attempts += 1
            self._untrack_gang_member(qp.pod)  # in-flight, no longer pending
            out.append(qp)
        return out

    def _pop_batch_admission(self, k: int) -> list[QueuedPodInfo]:
        """The armed pop path: each slot asks the policy which queued
        tenant admits next (WFQ tags + credits + aging escape), then pops
        that tenant's QueueSort head.  Deterministic: candidates are the
        sorted tenant names with a live head, the clock is the policy's
        logical clock, and every debit lands in the policy's intent set
        for the commit drain to journal."""
        self.flush_backoff()
        self.last_pop_throttled = False
        out: list[QueuedPodInfo] = []
        while len(out) < k:
            # Recovery carry-over first: a pod whose admission record
            # survived the crash but whose bind did not is ALREADY
            # admitted (durable debit + admitted_log entry) — it re-enters
            # the batch in durable admission order, ahead of and without
            # re-debiting new WFQ selections.  Its heap entry goes stale
            # and is pruned lazily below, like a delete's.
            pre = self.admission.take_preadmitted(self._in_active)
            if pre is not None:
                self._in_active.discard(pre)
                qp = self._info[pre]
                qp.attempts += 1
                self._untrack_gang_member(qp.pod)
                out.append(qp)
                continue
            tenants = []
            for t in sorted(self._tenant_active):
                heap = self._tenant_active[t]
                while heap and heap[0][3] not in self._in_active:
                    heapq.heappop(heap)  # stale entry (deleted/updated)
                if heap:
                    tenants.append(t)
                else:
                    del self._tenant_active[t]
            if not tenants:
                break
            now = self.admission.now()
            picked = self.admission.select(tenants, now)
            if picked is None:
                # Pods are queued but every tenant is credit-blocked:
                # throttled, not starved — aging re-arms eligibility.
                self.last_pop_throttled = True
                break
            tenant, escape = picked
            _, _, _, uid = heapq.heappop(self._tenant_active[tenant])
            self._in_active.discard(uid)
            qp = self._info[uid]
            qp.attempts += 1
            self._untrack_gang_member(qp.pod)  # in-flight, no longer pending
            self.admission.admit(tenant, uid, now, escape)
            out.append(qp)
        return out

    # -- failure / backoff -----------------------------------------------------

    def backoff_duration(self, attempts: int) -> float:
        d = self.initial_backoff_s
        for _ in range(1, attempts):
            d *= 2
            if d >= self.max_backoff_s:
                return self.max_backoff_s
        return d

    def _unsched_insert(self, qp: QueuedPodInfo) -> None:
        # Idempotent under re-classification: a uid already pooled under a
        # different rejecting-plugin set must leave its old class index.
        if qp.pod.uid in self._unschedulable:
            self._unsched_remove(qp.pod.uid)
        self._unschedulable[qp.pod.uid] = qp
        ck = (
            frozenset(qp.unschedulable_plugins)
            if qp.unschedulable_plugins
            else None,
            qp.delta is None,
        )
        qp.unsched_class = ck
        self._unsched_classes.setdefault(ck, {})[qp.pod.uid] = qp
        self._unsched_req_cache.pop(ck, None)

    def _unsched_remove(self, uid: str) -> QueuedPodInfo | None:
        qp = self._unschedulable.pop(uid, None)
        if qp is None:
            return None
        pool = self._unsched_classes.get(qp.unsched_class)
        if pool is not None:
            pool.pop(uid, None)
            if not pool:
                self._unsched_classes.pop(qp.unsched_class, None)
        self._unsched_req_cache.pop(qp.unsched_class, None)
        return qp

    def add_unschedulable(self, qp: QueuedPodInfo, plugins: set[str]) -> None:
        """AddUnschedulableIfNotPresent (scheduling_queue.go:728): pods that
        failed go to the unschedulable pool keyed by what rejected them.
        Members of a registered gang park in the gang pool instead."""
        qp.unschedulable_plugins = plugins
        if self.tenant_note is not None:
            self.tenant_note("deferred", qp.pod)
        g = qp.pod.spec.pod_group
        if g:
            self._track_gang_member(qp)
            if g in self.gang_min:
                self._park_gang_member(qp)
                return
        self._unsched_insert(qp)

    def add_backoff(self, qp: QueuedPodInfo) -> None:
        if self.tenant_note is not None:
            self.tenant_note("deferred", qp.pod)
        expiry = self._clock() + self.backoff_duration(qp.attempts)
        heapq.heappush(self._backoff, (expiry, next(self._seq), qp.pod.uid))

    def restore_backoff(self, qp: QueuedPodInfo) -> None:
        """Re-own a pod released with done() (e.g. from an off-queue wait
        room) and park it behind backoff — restores the info entry
        done() dropped, like reactivate does for the active queue."""
        self._info[qp.pod.uid] = qp
        self.add_backoff(qp)

    def next_backoff_expiry(self) -> float | None:
        """Earliest backoff expiry, or None when the backoffQ is empty."""
        return self._backoff[0][0] if self._backoff else None

    def sleep_until_backoff(self) -> bool:
        """Sleep until the earliest backoff expires.  Returns False when
        there is nothing to wait for — including under an injected test
        clock, which wall-clock sleeping can never advance."""
        expiry = self.next_backoff_expiry()
        if expiry is None or self._clock is not time.monotonic:
            return False
        time.sleep(max(0.0, expiry - self._clock()) + 1e-3)
        return True

    def flush_backoff(self) -> int:
        """Move expired backoff pods to activeQ (flushBackoffQCompleted :777)."""
        now = self._clock()
        n = 0
        while self._backoff and self._backoff[0][0] <= now:
            _, _, uid = heapq.heappop(self._backoff)
            qp = self._info.get(uid)
            # A stale heap entry must not spring a quarantined pod (a
            # restored snapshot can hold a pod in backoff that a later
            # journal record moved to quarantine).
            if qp is not None and uid not in self._quarantine:
                self._push_active(qp)
                n += 1
        return n

    def flush_unschedulable_leftover(self) -> int:
        """Re-activate pods stuck unschedulable > max duration (:807).
        Stale parked gangs get a re-admission attempt too."""
        now = self._clock()
        stale = [
            uid
            for uid, qp in self._unschedulable.items()
            if now - qp.timestamp > self.max_unschedulable_s
        ]
        for uid in stale:
            self._push_active(self._unsched_remove(uid))
        n = len(stale)
        for g in list(self._gang_pool):
            if any(
                now - qp.timestamp > self.max_unschedulable_s
                for qp in self._gang_pool[g].values()
            ) and self._try_admit_gang(g):
                n += 1
        return n

    # -- events ----------------------------------------------------------------

    def _requeue_verdict(self, qp: QueuedPodInfo, event: Event, ctx: EventCtx | None):
        """isPodWorthRequeuing (scheduling_queue.go:406), three-valued: the
        pod requeues when ANY plugin that rejected it (a) registered for
        this event kind and (b) — when an object-aware hint and event
        payload exist — says the event object could actually unblock it.
        Returns True/False, or 'fit' when the only deciding hint is the
        fit hint with a usable payload — the caller batches those into one
        vectorized check (a preemption burst scans a 15k-pod pool per
        POD_DELETE; per-pod Python was ~20% of the measured window)."""
        defer_fit = False
        for pl in qp.unschedulable_plugins or {"NodeResourcesFit"}:
            if not (PLUGIN_REQUEUE_EVENTS.get(pl, Event.ANY) & event):
                continue
            hint = PLUGIN_HINTS.get(pl) if self.use_queueing_hints else None
            if hint is None or ctx is None:
                return True
            if hint is _fit_hint and qp.delta is not None and ctx.max_free is not None:
                defer_fit = True
                continue
            if hint(qp, event, ctx):
                return True
        return "fit" if defer_fit else False

    def _worth_requeuing(self, qp: QueuedPodInfo, event: Event, ctx: EventCtx | None) -> bool:
        v = self._requeue_verdict(qp, event, ctx)
        if v == "fit":
            return _fit_hint(qp, event, ctx)
        return v

    def _class_reqs(self, ck: tuple) -> tuple[list, np.ndarray]:
        """(uids, packed request matrix) for one verdict class, cached
        until the class's membership changes (insert/remove invalidate)."""
        cached = self._unsched_req_cache.get(ck)
        if cached is None:
            pool = self._unsched_classes.get(ck, {})
            uids = list(pool)
            cached = (uids, _pack_reqs([pool[u].delta["req"] for u in uids]))
            self._unsched_req_cache[ck] = cached
        return cached

    def on_event(self, event: Event, ctx: EventCtx | None = None) -> int:
        """MoveAllToActiveOrBackoffQueue (scheduling_queue.go:1029): wake
        unschedulable pods whose rejecting plugins care about this event
        (filtered through the object-aware hints when ``ctx`` is given)."""
        woken: list[str] = []
        # The verdict depends only on (rejecting plugins, delta presence)
        # as long as every registered hint is the BATCHED fit hint — one
        # verdict per CLASS over the maintained index instead of a Python
        # walk of the pool (a preemption burst scans a 15k-pod pool per
        # POD_DELETE; the per-pod verdict walk was ~15% of the
        # preemption-async measured window), and the fit classes check one
        # cached request matrix per class in a single vectorized compare.
        if all(h is _fit_hint for h in PLUGIN_HINTS.values()):
            for ck in list(self._unsched_classes):
                pool = self._unsched_classes.get(ck)
                if not pool:
                    continue
                rep = next(iter(pool.values()))
                verdict = self._requeue_verdict(rep, event, ctx)
                if verdict is True:
                    woken.extend(pool)
                elif verdict == "fit":
                    uids, reqm = self._class_reqs(ck)
                    fits = _fits_packed(reqm, ctx)
                    woken.extend(u for u, ok in zip(uids, fits) if ok)
        else:
            # Custom hints registered: per-pod verdicts, but the fit checks
            # still batch into one vectorized compare (the per-pod numpy
            # path costs ~0.5s per event over a 15k-pod pool).
            fit_uids: list[str] = []
            fit_reqs: list[np.ndarray] = []
            for uid, qp in self._unschedulable.items():
                verdict = self._requeue_verdict(qp, event, ctx)
                if verdict is True:
                    woken.append(uid)
                elif verdict == "fit":
                    fit_uids.append(uid)
                    fit_reqs.append(qp.delta["req"])
            if fit_uids:
                fits = _fits_free(fit_reqs, ctx)
                woken.extend(u for u, ok in zip(fit_uids, fits) if ok)
        for uid in woken:
            qp = self._unsched_remove(uid)
            if qp is not None:
                self.add_backoff(qp)
        # Parked gangs re-try when an event the gang cares about fires —
        # membership changes (the GangScheduling mask) OR anything the
        # members' own rejecting plugins wait on (a gang blocked by taints
        # wakes on the taint removal, like a solo pod would).  Re-admission
        # goes through backoff (the gang already failed once as-is).
        for g in list(self._gang_pool):
            interested = PLUGIN_REQUEUE_EVENTS["GangScheduling"]
            for qp in self._gang_pool[g].values():
                for pl in qp.unschedulable_plugins:
                    interested |= PLUGIN_REQUEUE_EVENTS.get(pl, Event.ANY)
            if interested & event and self._try_admit_gang(g, via_backoff=True):
                woken.append(g)
        return len(woken)

    def update(self, pod: t.Pod) -> None:
        """updatePodInSchedulingQueue (eventhandlers.go:136): refresh the
        queued object; a scheduling-relevant change (labels, spec) to an
        unschedulable pod may have made it schedulable — move it straight to
        activeQ (the reference's isPodUpdated → queue.Update path).  Pods in
        activeQ/backoffQ just get the fresher object."""
        qp = self._info.get(pod.uid)
        if qp is None:
            self.add(pod)
            return
        changed = (
            qp.pod.metadata.labels != pod.metadata.labels
            or qp.pod.spec != pod.spec
        )
        qp.pod = pod
        if pod.uid in self._quarantine:
            # A spec/label change invalidates the poison featurization:
            # give the pod another chance, behind backoff (its attempt
            # count keeps the retry rate bounded if it is still poison).
            if changed:
                self.release_quarantine(pod.uid)
            return
        if qp.gated and not pod.spec.scheduling_gates:
            self.remove_gate(pod.uid)
            return
        if changed:
            qp.delta = None  # featurization delta is stale
            if pod.uid in self._unschedulable:
                self._push_active(qp)

    def remove_gate(self, uid: str) -> None:
        """A pod's scheduling gates were cleared; admit it."""
        qp = self._gated.pop(uid, None)
        if qp is not None:
            qp.gated = False
            self._push_active(qp)

    def delete(self, uid: str) -> None:
        self._in_active.discard(uid)
        if self.admission is not None:
            # A deleted pod's enqueue stamp must not keep holding the
            # tenant's aging escape open (its heap entry goes stale and
            # drops lazily at the next pop).
            self.admission.forget(uid)
        self._unsched_remove(uid)
        self._gated.pop(uid, None)
        self._quarantine.pop(uid, None)
        qp = self._info.pop(uid, None)
        if qp is not None and qp.pod.spec.pod_group:
            self._untrack_gang_member(qp.pod)
            pool = self._gang_pool.get(qp.pod.spec.pod_group)
            if pool is not None:
                pool.pop(uid, None)
                if not pool:
                    self._gang_pool.pop(qp.pod.spec.pod_group, None)

    def done(self, uid: str) -> None:
        """Pod scheduled successfully; drop bookkeeping."""
        self._info.pop(uid, None)

    # -- durability (journal.py snapshot surface) ------------------------------

    def durable_state(self) -> dict:
        """Serialize every queued pod for a journal snapshot.  Clocks are
        RELATIVE (backoff remaining, age since first enqueue): monotonic
        timestamps don't survive a process, so restore_state rebases them
        on the restoring process's clock — a pod 3s into a 10s backoff
        resumes with ~7s left, not a reset."""
        from .api import serialize

        now = self._clock()
        backoff_left: dict[str, float] = {}
        for exp, _seq, uid in self._backoff:
            left = max(0.0, exp - now)
            # Duplicate heap entries: keep the earliest expiry (the one
            # flush_backoff would honor first).
            if uid not in backoff_left or left < backoff_left[uid]:
                backoff_left[uid] = left
        entries: list[dict] = []
        seen: set[str] = set()

        def ent(qp: QueuedPodInfo, pool: str, **extra) -> None:
            if qp.pod.uid in seen:
                return
            seen.add(qp.pod.uid)
            entries.append(
                {
                    "pod": serialize.to_dict(qp.pod),
                    "pool": pool,
                    "attempts": qp.attempts,
                    "age": max(0.0, now - qp.initial_attempt_timestamp),
                    "plugins": sorted(qp.unschedulable_plugins),
                    **extra,
                }
            )

        for uid, qp in self._quarantine.items():
            ent(qp, "quarantine")
        for uid, qp in self._gated.items():
            ent(qp, "gated")
        for uid, qp in self._unschedulable.items():
            ent(qp, "unschedulable")
        for pool in self._gang_pool.values():
            for qp in pool.values():
                ent(qp, "gang")
        if self.admission is not None:
            # In-flight pops whose debits are not yet group-committed:
            # presumed-aborted on recovery, so they re-enter ACTIVE at
            # the FRONT in pop order — the restored WFQ ledger predates
            # their debits and re-selects them exactly as the
            # interrupted run did.  If the crash DID leave their group
            # durable, replay supersedes this entry: a bind record's
            # bound upsert deletes the queue entry (scheduler.add_pod),
            # and a surviving admission record consumes it through the
            # preadmitted drain ahead of any fresh selection.
            for uid in self.admission.pending_intents():
                qp = self._info.get(uid)
                if qp is not None and uid not in self._in_active:
                    ent(qp, "active")
        # Active pods emit in QueueSort heap order, NOT set order: the
        # restorer re-pushes entries in document order with fresh seqs and
        # one shared timestamp, so the stored order IS the recovered pop
        # order — iterating the _in_active set here would bake one
        # process's hash order into the snapshot and scramble the armed
        # per-tenant heads (the tenant kill cells catch this).
        live = (
            [it for h in self._tenant_active.values() for it in h]
            if self.admission is not None
            else list(self._active)
        )
        for item in sorted(live):
            if item[3] in self._in_active:
                ent(self._info[item[3]], "active")
        for uid in sorted(self._in_active):  # heap-orphan backstop
            ent(self._info[uid], "active")
        for uid, left in backoff_left.items():
            qp = self._info.get(uid)
            if qp is not None:
                ent(qp, "backoff", backoff_remaining_s=round(left, 6))
        out = {
            "entries": entries,
            # Already trimmed to the trailing window (bounded deque):
            # the snapshot can never grow with the release stream.
            # Clocks rebase as ages (like backoff remaining-seconds) —
            # raw monotonic stamps are meaningless in the next process.
            "release_history": [
                {
                    "uid": e["uid"],
                    "attempts": e["attempts"],
                    "age_s": round(max(0.0, now - e["ts"]), 3),
                }
                for e in self.release_history
            ],
        }
        if self.admission is not None:
            # The DURABLE fairness ledger (WFQ tags, credit balances,
            # per-tenant attempts, rebased enqueue stamps): snapshot +
            # journaled "admission" records replay the exact selection
            # state, so recovery admits in the identical order.
            out["admission"] = self.admission.durable_state()
        return out

    def restore_state(self, state: dict) -> int:
        """Rebuild the pools from a durable_state() document (recovery).
        Pods already present — bound pods the snapshot's store section
        restored first, say — are skipped; gang members re-park through
        the normal admission machinery so quorum logic stays live."""
        from .api import serialize

        now = self._clock()
        # Admission restores FIRST: the pod entries below re-enter through
        # _push_active → note_enqueue, which keeps an already-present
        # (rebased) stamp — accumulated starvation wait survives the crash.
        adm = state.get("admission")
        if adm is not None and self.admission is not None:
            self.admission.restore_state(adm)
        n = 0
        for e in state.get("entries", ()):
            pod = serialize.pod_from_data(e["pod"])
            uid = pod.uid
            if uid in self._info or uid in self._quarantine:
                continue
            qp = QueuedPodInfo(
                pod=pod,
                timestamp=now,
                initial_attempt_timestamp=now - float(e.get("age", 0.0)),
                attempts=int(e.get("attempts", 0)),
                unschedulable_plugins=set(e.get("plugins", ())),
            )
            self._info[uid] = qp
            pool = e.get("pool", "active")
            if pool == "quarantine":
                qp.unschedulable_plugins = qp.unschedulable_plugins or {
                    "EngineFault"
                }
                self._quarantine[uid] = qp
            elif pool == "gated":
                qp.gated = True
                self._gated[uid] = qp
            elif pool == "unschedulable":
                if pod.spec.pod_group:
                    self._track_gang_member(qp)
                self._unsched_insert(qp)
            elif pool == "gang":
                self._park_gang_member(qp)
            elif pool == "backoff":
                if pod.spec.pod_group:
                    self._track_gang_member(qp)
                heapq.heappush(
                    self._backoff,
                    (
                        now + float(e.get("backoff_remaining_s", 0.0)),
                        next(self._seq),
                        uid,
                    ),
                )
            else:
                if pod.spec.pod_group:
                    self._track_gang_member(qp)
                self._push_active(qp)
            n += 1
        # The release-history window survives restarts (its ring bound
        # applies on restore too — an over-long stored list trims).
        # Stored ages rebase onto this process's clock; a raw "ts" from
        # an in-process ring copy passes through unchanged.
        for rec in state.get("release_history", ()):
            e = dict(rec)
            if "age_s" in e:
                e["ts"] = round(now - e.pop("age_s"), 3)
            self.release_history.append(e)
        # Parked gangs whose quorum is already reachable release now (a
        # restart must not strand a quorum-complete gang).
        for g in list(self._gang_pool):
            self._try_admit_gang(g)
        return n

    def depths(self) -> dict[str, int]:
        """Per-class queue depths — the scheduler_pending_pods{queue=…}
        gauge payload (metrics.go:121 PendingPods) and the dump's counts.
        Label values match the reference's queue names where one exists."""
        return {
            "active": len(self._in_active),
            "backoff": len(self._backoff),
            "unschedulable": len(self._unschedulable),
            "gated": len(self._gated),
            "gang-parked": sum(len(p) for p in self._gang_pool.values()),
            "quarantine": len(self._quarantine),
        }

    def dump(self) -> dict:
        """Queue state for the debugger dump (keeps the privates here)."""
        d = self.depths()
        return {
            "active": d["active"],
            "backoff": d["backoff"],
            "pending": self.pending_count(),
            "unschedulable": d["unschedulable"],
            "gated": d["gated"],
            "gang_pool": {g: sorted(p) for g, p in self._gang_pool.items()},
            "quarantine": sorted(self._quarantine),
        }
