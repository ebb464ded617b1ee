"""The scheduler: the driving loop over queue → device pass → bind.

The batched equivalent of ScheduleOne (pkg/scheduler/schedule_one.go:65):
instead of popping one pod, running the framework's extension points over a
goroutine pool, and binding asynchronously, we pop a batch in QueueSort order,
run the compiled device pass (filter+score+select+commit for every pod in the
batch in one dispatch), then apply the resulting assignments to the host cache
(the assume step — the device already committed them to its state) and hand
unschedulable pods back to the queue."""

from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import numpy as np

from .api import types as t
from .cache import Cache
from .engine.features import build_pod_batch
from .engine.packing import pack_batch
from .faults import EngineFault
from .engine.pass_ import PassCache, filter_op_names
from .framework.config import DEFAULT_PROFILE, Profile
from .framework.events import NORMAL, WARNING, EventBroadcaster
from .framework.flight import FlightRecorder
from .framework.metrics import MetricsRegistry, TenantMetrics, pod_tenant
from .framework.status import Diagnosis
from .framework.tracing import PROCESS, SpanSink, Trace
from .intern import InternTable
from .ops.common import registered_subset
from .preemption import PreemptionEvaluator
from .queue import Event, EventCtx, QueuedPodInfo, SchedulingQueue
from .utils import device_fetch
from .snapshot import SnapshotBuilder

from functools import partial  # noqa: E402

import jax.numpy as jnp  # noqa: E402


# Keys of a featurized batch that are not feature rows of a pod's template.
_PER_BATCH_KEYS = frozenset(
    {"valid", "nominated_row", "uniform_all", "step_offset"}
)


@partial(jax.jit, static_argnums=3)
def _expand_uniform(small, valid, nomrow, k):
    """Broadcast a uniform batch's single representative feature row to
    the full batch axis on device (see _dispatch_batch: identical rows
    need not be transferred k times)."""
    out = {
        kk: jnp.broadcast_to(v[0], (k,) + v.shape[1:])
        for kk, v in small.items()
    }
    out["valid"] = valid
    out["nominated_row"] = nomrow
    return out


@dataclass
class ScheduleOutcome:
    pod: t.Pod
    node_name: str | None  # None → unschedulable this round
    score: int = 0
    feasible_nodes: int = 0
    nominated_node: str | None = None  # set when preemption picked victims
    victims: int = 0
    # Victim identities for an out-of-process host's async DELETE calls
    # (prepareCandidate, preemption.go:342): uids for sidecar-cache
    # addressing, namespace/name refs for the API DELETE.
    victim_uids: tuple[str, ...] = ()
    victim_names: tuple[str, ...] = ()
    # Why the pod failed (framework/types.go Diagnosis): which plugins
    # rejected nodes, from the device pass's per-op fail bitmask.
    diagnosis: Diagnosis | None = None


@dataclass
class SchedulerMetrics:
    """Counters mirroring the reference's core series
    (pkg/scheduler/metrics/metrics.go:138 schedule_attempts_total etc.)."""

    schedule_attempts: int = 0
    scheduled: int = 0
    unschedulable: int = 0
    preemptions: int = 0
    deferred: int = 0  # chunk-conflict deferrals resolved by the strict tail
    pinned_batches: int = 0  # batches served by the pinned fast path
    # Conflict-aware chunk packing (engine/packing.py): batches reordered,
    # residual same-chunk collisions the plans accepted, and the last
    # batch's plan shape (width / class count) for the gauges.
    packed_batches: int = 0
    pack_collisions: int = 0
    pack_width: int = 0
    pack_classes: int = 0
    # Carried DomTables (ISSUE 13): main-pass dispatches that reused last
    # batch's domain aggregates vs. ones that rebuilt from cluster state.
    dom_carry_hits: int = 0
    dom_carry_rebuilds: int = 0
    batches: int = 0
    device_time_s: float = 0.0
    featurize_time_s: float = 0.0
    first_scheduled_ts: float = 0.0
    last_scheduled_ts: float = 0.0
    throughput_samples: list = field(default_factory=list)
    # Per-pod e2e scheduling latency (enqueue → bind), the analog of
    # pod_scheduling_sli_duration_seconds (metrics/metrics.go:225).
    e2e_latency_samples: list = field(default_factory=list)
    # Histograms: per-extension-point durations + SLI
    # (framework_extension_point_duration_seconds, metrics.go:245).
    registry: MetricsRegistry = field(default_factory=MetricsRegistry)


# Hard filters that read MUTABLE per-node state (pods' labels/ports/volumes
# on the node — anything a strict-tail placement can change).  Node-static
# filters (taints, labels, capacity, unschedulable) are NOT here: tail
# commits cannot invalidate them, and resources re-check via _fits_now.
DYNAMIC_HARD_OPS = frozenset(
    {
        "InterPodAffinity", "PodTopologySpread", "NodePorts",
        "VolumeRestrictions", "NodeVolumeLimits", "VolumeBinding",
        "VolumeZone", "DynamicResources",
    }
)


class TPUScheduler:
    def __init__(
        self,
        profile: Profile = DEFAULT_PROFILE,
        batch_size: int = 256,
        queue: SchedulingQueue | None = None,
        enable_preemption: bool = True,
        mesh=None,
        chunk_size: int = 1,
        profiles: list[Profile] | None = None,
        extenders: list | None = None,
        consistency_check_every: int = 0,
        feature_gates=None,
        inline_preempt_commit: bool | None = None,
        flight_capacity: int = 4096,
        tenant_attribution: bool = True,
        pipeline_depth: int = 1,
    ):
        from .framework.features import DEFAULT_GATES

        # Feature gates (pkg/features/kube_features.go): runtime behavior
        # switches; see framework/features.py for the wired subset.
        self.feature_gates = feature_gates or DEFAULT_GATES
        # Restrict to plugins whose vectorized ops are registered (a no-op
        # once the op inventory is complete; prevents KeyError mid-build-out).
        self.profile = registered_subset(profile)
        # Multi-profile map (profile/profile.go:47): schedulerName →
        # compiled program variant.  `profile` stays the default; extra
        # profiles get their own XLA programs via PassCache and pods select
        # by .spec.scheduler_name.  Pods naming an unknown scheduler are not
        # ours (eventhandlers.go responsibleForPod) and are ignored.
        self.profiles: dict[str, Profile] = {self.profile.name: self.profile}
        for p in profiles or ():
            self.profiles[p.name] = registered_subset(p)
        if not self.feature_gates.enabled("DynamicResourceAllocation"):
            # plugins/registry.go:49: the DRA plugin is only registered when
            # the gate is on; with it off the plugin simply doesn't exist.
            import dataclasses as _dc

            self.profiles = {
                name: _dc.replace(
                    p,
                    **{
                        fld: tuple(
                            f for f in getattr(p, fld) if f != "DynamicResources"
                        )
                        for fld in (
                            "filters", "pre_enqueue", "pre_filter",
                            "post_filter", "reserve", "pre_bind",
                        )
                    },
                )
                for name, p in self.profiles.items()
            }
            self.profile = self.profiles[self.profile.name]
        # Gate off ⇒ the plugin exists at NO extension point: claims are
        # never allocated at Reserve/PreBind either (the reference scheduler
        # simply has no DRA code registered).
        self._dra_enabled = self.feature_gates.enabled("DynamicResourceAllocation")
        # Out-of-process extenders (pkg/scheduler/extender.go); a non-empty
        # chain routes scheduling through the per-pod eval-only path.
        self.extenders = list(extenders or ())
        self.batch_size = batch_size
        # chunk_size=1 → strictly sequential-equivalent scan (parity mode);
        # >1 → C pods per device step with conflict-deferral + a strict tail
        # pass for the deferred readers (engine/pass_.py module docstring).
        assert batch_size % chunk_size == 0, "batch_size must be a chunk multiple"
        self.chunk_size = chunk_size
        # Strict tail batches are padded to this fixed shape (one compile).
        # Small on purpose: the chunk=1 tail pass costs one scan step per
        # SLOT whether occupied or not, so a 64-slot tail is 4× cheaper
        # than 256 for the common few-dozen-deferral case; large deferral
        # bursts are first drained by a chunked replay (see _complete_batch).
        self.tail_size = min(batch_size, 64)
        self.interns = InternTable()
        self.builder = SnapshotBuilder(self.interns)
        self.cache = Cache(self.builder)
        self.queue = queue or SchedulingQueue()
        self.queue.use_queueing_hints = self.feature_gates.enabled(
            "SchedulerQueueingHints"
        )
        self.queue.respect_scheduling_gates = self.feature_gates.enabled(
            "PodSchedulingReadiness"
        )
        self.queue.gates_apply_to = lambda pod: "SchedulingGates" in (
            (self._profile_for(pod) or self.profile).pre_enqueue
        )
        # Featurizers read gates via FeaturizeContext.gates (the
        # plfeature.Features snapshot, plugins/registry.go:49).
        self.builder.feature_gates = self.feature_gates
        self.passes = PassCache()
        # Carried DomTables (ISSUE 13): the previous main pass's final
        # (group_dom, et_dom) device arrays plus the (schema,
        # mutation_epoch) token they are valid under.  Derivable state — a
        # restart/recovery rebuilds from the journaled store and the carry
        # starts cold; any host-side mutation (node churn, deletes,
        # preemption evictions, recovery reconcile) bumps the builder's
        # mutation_epoch and forces the next pass to rebuild on device.
        self._dom_carry: tuple | None = None
        self._dom_token: tuple | None = None
        self._dom_zeros: dict[tuple, tuple] = {}
        self.metrics = SchedulerMetrics()
        # Event recorder (client-go record.EventBroadcaster analog): the
        # structured Scheduled/FailedScheduling/Preempted/GangWaiting
        # narration, counted into scheduler_events_total{reason} and
        # readable via the sidecar `events` frame.
        self.events = EventBroadcaster(registry=self.metrics.registry)
        self.recorder = self.events.new_recorder()
        # Flight recorder (framework/flight.py): one per-phase attribution
        # record per scheduled batch + state-transition markers, in a
        # bounded ring.  Always on; auto-dumps on engine fault/quarantine
        # (and SIGTERM via the CLI), readable via the `flight` frame,
        # GET /debug/flight, and the `flight` subcommand.
        self.flight = FlightRecorder(capacity=flight_capacity)
        # Per-schedule_batch phase accumulator (set by schedule_batch,
        # filled by _dispatch_batch/_complete_batch; None outside a batch
        # so direct _schedule_infos callers skip recording).
        self._flight_acc: dict | None = None
        # True while inside the batch-recovery bisect: nested recoveries
        # record markers but only the OUTERMOST failure writes the
        # auto-dump (a 256-pod bisect must not shed a file per halving).
        self._recovering = False
        # Cross-boundary tracing: (trace_id, parent_span_id) of the REMOTE
        # caller's span — the sidecar server sets it from the envelope so
        # the next batch's root span joins the client's trace.
        self.trace_parent: tuple[str, str | None] | None = None
        # The most recent batch's root span (the server echoes its span_id
        # in the schedule response) and a ring of slow span trees for the
        # debugger dump.
        self.last_batch_span: Trace | None = None
        self.slow_spans: deque = deque(maxlen=16)
        self._install_metric_collectors()
        # Per-tenant SLO attribution (ISSUE 12): pods carry a tenant id
        # (framework/metrics.py TENANT_LABEL_KEY); admission / bind /
        # preemption / deferral count into the bounded-cardinality
        # scheduler_tenant_*_total families.  Observational only — a
        # scheduler with attribution off binds bit-identically.
        self.tenant_metrics = (
            TenantMetrics(self.metrics.registry) if tenant_attribution else None
        )
        if self.tenant_metrics is not None:
            self.queue.tenant_note = self.tenant_metrics.note_pod
        self.preemption = PreemptionEvaluator(self) if enable_preemption else None
        # Inline preemptor commit (perf mode): a successful dry-run commits
        # the preemptor immediately instead of nominate + requeue — sound
        # IN-PROCESS because victim deletion is synchronous here, so the
        # retry's nominated fast path would take exactly the freed node the
        # what-if verified.  Stays OFF in parity mode (chunk_size=1) and
        # for wire deployments (the HOST owns the victims' API deletes —
        # the sidecar must hand the nomination back, not act on it).
        # Pods with Permit groups or relevant Reserve plugins always take
        # the nominate path (their Reserve/Permit chains run on the retry).
        if inline_preempt_commit is None:
            inline_preempt_commit = chunk_size > 1
        self.inline_preempt_commit = inline_preempt_commit
        # Gang scheduling (the out-of-tree coscheduling plugin's PodGroup):
        # group name → PodGroup; bound-member counts for quorum checks.
        # The queue shares gang_bound as its admission credit so PreEnqueue
        # parking and the Permit gate agree.
        self.pod_groups: dict[str, t.PodGroup] = {}
        self.gang_bound: dict[str, int] = {}
        # PodDisruptionBudgets (preemption criterion 1, the disruption
        # controller's state in-process).
        self.pdbs: dict[str, t.PodDisruptionBudget] = {}
        from .controllers import (
            DisruptionController,
            NodeLifecycleController,
            PodGCController,
            TaintEvictionController,
        )

        # Controller clock override (tests / deterministic harnesses):
        # None = the default domain (wall monotonic, or the node-lifecycle
        # controller's logical clock once armed) — see _now().
        self.clock = None
        self.disruption_controller = DisruptionController(self)
        self.taint_eviction = TaintEvictionController(self)
        # The failure-response WRITER half (ISSUE 9): heartbeat-staleness
        # taint writer + pod GC.  Disarmed by default — nodes that never
        # renew a Lease are exempt, so embedders keep the consumer-only
        # behavior until they arm the loop (serve --node-grace-s).
        self.node_lifecycle = NodeLifecycleController(self)
        self.pod_gc = PodGCController(self)
        # Called with the node name after a journaled taint write applies
        # (the speculative frontend registers an invalidation here —
        # taints flip feasibility globally, exactly like a wire-fed taint
        # change through its note_add path).
        self.taints_changed_hook = None
        # Uids ever evicted through the requeue path (taint eviction /
        # pod GC) — the dump's loop-closure evidence: an evicted uid
        # bound again means eviction → requeue → reschedule completed
        # for that pod.  Membership-only (no iteration-order dependence);
        # journal replay repopulates it, so the count survives a crash.
        self._evicted_uids: set[str] = set()
        # Nominator (backend/queue/nominator.go): preemptors' claims on
        # their freed nodes — uid → (node name, row delta, priority).  The
        # fit filter counts these on their nodes so a same/next-batch pod
        # cannot steal a freed node (framework.go:973), and the retrying
        # preemptor takes its nominated node via the engine's fast path.
        self.nominator: dict[str, tuple[str, dict, int]] = {}
        # WaitOnPermit room (framework.go:1503): gang → [(qp, node, score,
        # feasible)] of members assumed-but-not-bound until quorum forms.
        self.permit_waiting: dict[str, list] = {}
        self.permit_wait_since: dict[str, float] = {}
        self.permit_timeout_s = 60.0  # coscheduling PermitWaitingTimeSeconds
        # Host-side extension points (framework/hostplugins.py): the loop
        # runs whatever is registered here and special-cases nothing —
        # coscheduling is one PermitPlugin, volume/DRA reservation are
        # ReservePlugins (runtime/framework.go:1359,1443).
        from .framework.coscheduling import CoschedulingPermit
        from .framework.hostplugins import DEFAULT_RESERVE_PLUGINS

        self.permit_plugins = [CoschedulingPermit()]
        self.reserve_plugins = list(DEFAULT_RESERVE_PLUGINS)
        # Waiting-room group → owning PermitPlugin (for timeout/rollback).
        self.permit_wait_owner: dict[str, object] = {}
        # PreBind wait room (the blocking tail of volume_binding.go:521
        # BindPodVolumes, made non-blocking): pod uid → entry while an
        # external provisioner works; see notify_prebind /
        # expire_waiting_prebinds.  Timeout = the reference's bindTimeout
        # default (volumebinding DefaultBindTimeoutSeconds, 600s).
        self.prebind_waiting: dict[str, dict] = {}
        self.prebind_timeout_s = 600.0
        # Gang members whose PreBind completed while group-mates still wait:
        # group → [{qp, undos, node}].  A later timeout in the group rolls
        # these back too (all-or-nothing); the group's last completion
        # clears its list.
        self.prebind_done_pending: dict[str, list[dict]] = {}
        # Binds completed by informer-driven notify_prebind between batches;
        # the next schedule_batch returns them so outcome-consuming drivers
        # (the benchmark harness) observe wait-mode binds.
        self._prebind_outcomes: list[ScheduleOutcome] = []
        # Assumed-pod TTL (cache.go:42 ticks cleanupAssumedPods at 1s; the
        # 30s expiry mirrors durationToExpireAssumedPod's safety-net role).
        self.assume_ttl_s = 30.0
        # LogIfLong threshold for the per-batch cycle span (the reference
        # logs any >100ms CYCLE; a batch amortizes hundreds of cycles, so
        # the default only surfaces genuinely slow batches).
        self.trace_threshold_s = 2.0
        self._next_assumed_sweep = 0.0
        self.queue.gang_credit = lambda g: (
            self.gang_bound.get(g, 0)
            + len(self.permit_waiting.get(g, ()))
            + self.fleet_gang_credit(g)
        )
        if mesh is not None:
            # Multi-chip: node axis sharded over the mesh (parallel/mesh.py);
            # XLA inserts the ICI collectives for the cross-shard reductions.
            self.builder.set_mesh(mesh)
        self._cycle = 0
        # Truncated (parity) mode: percentage_of_nodes_to_score != 100
        # reproduces the reference's adaptive search truncation + rotating
        # start + zone-interleaved order; needs the sequential scan.
        self._truncated = any(
            p.percentage_of_nodes_to_score != 100 for p in self.profiles.values()
        )
        if self._truncated:
            assert chunk_size == 1, (
                "percentage_of_nodes_to_score != 100 (parity mode) requires "
                "chunk_size=1 (sequential-equivalent scan)"
            )
        self._eval_passes: dict = {}  # extender path: per-profile eval pass
        # Decision provenance (framework/provenance.py): OFF by default —
        # a ProvenanceRing only once arm_provenance() is called, so the
        # unarmed hot path pays a single `is not None` test per bind and
        # stays byte-identical.  The attribution passes compile lazily on
        # the first explain, never from the scheduling loop.
        self.provenance = None
        self._attr_passes: dict = {}
        # Placed-but-not-yet-journaled tie-break steps (uid → device
        # step), staged at phase-1 and drained into the bind WAL record
        # so journal-mode explain reproduces selectHost exactly even
        # when the ring was never armed.  Only populated while a journal
        # or the ring is attached; entries for pods whose bind rolls
        # back are overwritten at their next placement.
        self._tie_pending: dict = {}
        # Periodic host↔device comparer (the cache debugger's SIGUSR2 check
        # run on a schedule): 0 = disabled.
        self.consistency_check_every = consistency_check_every
        # Prefetched next batch: (infos, featurize work) — schedule_batch
        # featurizes batch k+1 while the device crunches batch k.  The
        # speculative sidecar frontend counts these uids among its
        # in-flight set (speculate._prefetched_uids) so hint admission
        # never double-commits a prefetched pod.
        self._prefetched: tuple | None = None
        self._prefetch_enabled = True
        # Software pipeline (ISSUE 15, engine/pipeline.py): depth 1 is
        # the serial loop (the parity oracle) — commits stage + drain at
        # exactly the inline-apply point, one group fsync per batch.
        # Depth >= 2 additionally dispatches batch k+1 BEFORE draining
        # batch k's staged commit group, so the fsync and the apply loop
        # run under the in-flight device pass (featurize(k+1) already
        # overlaps device(k) via the prefetch).  Bindings stay
        # bit-identical across depths: the predispatched pass is
        # discarded and re-dispatched whenever any state it read changed
        # (engine/pipeline.predispatch_valid).
        self.pipeline_depth = max(1, int(pipeline_depth))
        # The current batch's staged commit group (engine/pipeline.py
        # CommitTicket) — never outlives its schedule_batch call.
        self._pending_ticket = None
        # A device pass dispatched one cycle early (Predispatch), picked
        # up by the next schedule_batch.
        self._predispatched = None
        # Adaptive predispatch gate: every invalidated predispatch threw
        # away a full device pass and re-dispatched (churn workloads
        # mutate host state between EVERY batch, so the double buffer
        # only doubles device cost there).  Consecutive invalidations
        # back the gate off — skip-and-decay halves the retry rate under
        # sustained churn while recovering immediately once hits return.
        self._pd_consec_invalid = 0
        # Called between the async device dispatch and the blocking fetch
        # of each batch — host work done here (the speculative frontend's
        # hint parse/build) hides under the in-flight pass.
        self.post_dispatch_hook = None
        # Uids of the batch currently in flight (popped, not yet
        # committed).  The post-dispatch hook's admission path must not
        # re-add one of these to the active queue: the commit's
        # queue.done() would strand a stale active entry and a later
        # pop_batch would find a uid with no info record.
        self._inflight_uids: frozenset = frozenset()
        # Fault injection hook (faults.FaultPlan.install_engine): called
        # with the batch's pods at the top of every device dispatch.  None
        # in production; the batch-recovery path it exercises (bisect +
        # quarantine) is always armed — a REAL engine exception takes the
        # same road.
        self.fault_injector = None
        # Write-ahead binding journal (journal.py): None in the default
        # in-memory configuration; attach_journal() arms the commit-path
        # hooks, snapshot cadence and scheduler_journal_* metrics.
        self.journal = None
        self.snapshot_every_records = 0
        # Speculative frontend (sidecar/speculate.py), when one wraps this
        # scheduler: registered so snapshots can persist its decision-cache
        # epoch.  _recovered_spec_epoch carries the journaled epoch across
        # recovery, so a restarted frontend resumes the monotonic sequence
        # instead of cold-starting at 0 (subscribers hold epoch-stamped
        # decisions; a reset would violate the Push ordering contract).
        self._spec_frontend = None
        self._recovered_spec_epoch = 0
        # Journal bind records whose node was unknown at recovery time —
        # informers.reconcile_after_recovery re-applies them once the
        # LIST delivers the node (or drops them when it never does).
        self._recovered_bindings: dict[str, dict] = {}
        # Fleet recovery surfaces (journal.recover): crash-orphaned 2PC
        # reservations (presumed abort — the router re-admits the gang)
        # and journaled shard-map handoffs (takeover redoes a lost map
        # write idempotently).
        self._recovered_gang_intents: dict[str, dict] = {}
        self._recovered_handoffs: list[dict] = []
        # Shard scope (fleet/owner.py): a fleet owner's store holds ONLY
        # its shard's nodes.  When set, add_node consults the predicate
        # and drops foreign nodes (counted — a misconfigured feed should
        # be visible, not silently absorbed into the wrong shard).
        self.shard_guard = None
        self.shard_rejected_nodes = 0
        # In-flight fleet 2PC reservations: pod uid → {pod, node, undos,
        # gang} between reserve_proposed and commit/abort_reserved.
        self._fleet_reserved: dict[str, dict] = {}
        # Gang quorum credit earned on OTHER shards (fleet/router.py
        # installs a counter over its fleet-wide gang_bound): the queue's
        # PreEnqueue admission must count members a different owner
        # already bound, or a gang split across shards never reaches
        # quorum anywhere.
        self.fleet_gang_credit = lambda g: 0
        # Eviction requeue sink (fleet/owner.py): a shard owner's local
        # queue is never drained by the router, so an armed lifecycle
        # controller's evict-as-requeue must hand the unbound pod BACK to
        # the router (which can rebind it on a different shard) instead
        # of parking it locally.  None (the default) keeps the single-
        # scheduler behavior: the evicted pod re-enters this queue.
        self.eviction_requeue_hook = None
        # Rotating scan start (schedule_one.go nextStartNodeIndex).
        self._next_start = 0
        # Shapes of the last scheduled batch (for warm_tail precompilation).
        self._last_batch_meta: tuple | None = None
        # Pre-intern the hot topology keys so node rows materialize them.
        for key in ("kubernetes.io/hostname", "topology.kubernetes.io/zone",
                    "topology.kubernetes.io/region"):
            self.builder.ensure_topo_key(key)

    def _install_metric_collectors(self) -> None:
        """Register the scrape-time gauge/counter sync on the registry:
        point-in-time series (queue depths, cache sizes, compiled-program
        and device-memory stats) are sampled when `/metrics` or the
        sidecar `metrics` frame renders, so the hot loop pays nothing."""
        reg = self.metrics.registry
        # Hot-path counter cached as an attribute (registry.reset() clears
        # values in place, so the handle stays valid across bench resets).
        self._dispatch_counter = reg.counter(
            "scheduler_device_dispatch_total",
            "Device pass dispatches by kind (batch/pinned/tail/eval).",
        )
        self._scan_steps_counter = reg.counter(
            "scheduler_pass_scan_steps_total",
            "Steps of the batch pass, by kind: run, and padded_skipped "
            "(steps of the batch shape the pass did not run).",
        )
        self._pass_inputs_counter = reg.counter(
            "scheduler_pass_inputs_total",
            "Input leaves of the batch pass per dispatch, by kind: shipped "
            "(crossed from the host) and resident (the device's own from "
            "an earlier dispatch).",
        )
        self._filter_rejecting_counter = reg.counter(
            "scheduler_pass_filter_rejecting_pods_total",
            "Pods for which a filter op of the compiled pass ruled out at "
            "least one node that every earlier filter had let through, by "
            "plugin, counted once a pod.",
        )
        # Flight-recorder phase attribution (the tiled per-batch segments;
        # journal_append/journal_fsync nest inside featurize+commit and
        # are exported for the durability-tax view, not the tiling sum).
        self._phase_hist = reg.histogram(
            "scheduler_phase_duration_seconds",
            "Per-batch scheduling phase duration, by phase.",
        )
        # The one span source of the served path (framework/tracing.py):
        # flight record, this histogram, the ScheduleBatch step log and
        # the profiler's trace all read the same interval.
        self.spans = SpanSink(self._phase_hist)
        self.span = self.spans.span
        self._serialize_s = 0.0  # serialize.pod_to_data seconds of the open drain
        # The tpulint-clean companion of the upstream-parity
        # plugin_execution_duration_seconds exposition: same sampled
        # observations, scheduler_-prefixed family.
        self._plugin_hist = reg.histogram(
            "scheduler_plugin_duration_seconds",
            "Sampled per-plugin duration, by plugin and extension point.",
        )
        attempts = reg.counter(
            "scheduler_schedule_attempts_total",
            "Scheduling attempts by result (metrics.go:138 analog).",
        )
        preempt = reg.counter(
            "scheduler_preemption_attempts_total",
            "Successful preemption candidates.",
        )
        batches = reg.counter(
            "scheduler_batches_total",
            "Device batches run; kinds partition (full + pinned = all).",
        )
        deferred = reg.counter(
            "scheduler_deferred_pods_total",
            "Pods deferred to the strict tail by chunk conflicts.",
        )
        # Conflict-aware chunk packing + carried DomTables (ISSUE 13).
        packed = reg.counter(
            "scheduler_chunk_packed_batches_total",
            "Batches reordered by the conflict-aware chunk packer.",
        )
        pack_coll = reg.counter(
            "scheduler_chunk_pack_collisions_total",
            "Residual same-chunk same-class pods accepted by pack plans "
            "(each is an expected strict-tail deferral).",
        )
        pack_width = reg.gauge(
            "scheduler_chunk_pack_width",
            "Chunk width the last pack plan chose.",
        )
        pack_classes = reg.gauge(
            "scheduler_chunk_pack_classes",
            "Conflict classes in the last packed batch.",
        )
        dom_carry = reg.counter(
            "scheduler_chunk_dom_carry_total",
            "Main-pass dispatches by domain-table source (carried vs "
            "rebuilt from cluster state).",
        )
        csi_claims = reg.gauge(
            "scheduler_csi_claims",
            "Known CSI claims by how the attach budget holds them: counted "
            "(one known pod: a per-node count, no row) or shared (several: "
            "a row of per-claim, per-node counts).",
        )
        # Heterogeneity attribution (ISSUE 14): armed only when a
        # registered profile ships a throughput matrix — homogeneous
        # deployments pay nothing and export no empty families.
        # _hetero_classes caches the bounded label vocabularies
        # (accelerator classes × workload classes from the matrix
        # config; off-config values fold to "other").
        matrix_accels: set = set()
        matrix_classes: set = set()
        for p in self.profiles.values():
            for wclass, row in p.throughput_matrix:
                matrix_classes.add(wclass)
                matrix_accels.update(a for a, _tp in row)
        self._hetero_classes = (
            (frozenset(matrix_accels), frozenset(matrix_classes))
            if matrix_classes
            else None
        )
        self._hetero_bound = reg.counter(
            "scheduler_hetero_bound_total",
            "Pods bound, by the chosen node's accelerator class and the "
            "pod's workload class (heterogeneity profiles).",
        )
        self._profile_bound = reg.counter(
            "scheduler_profile_bound_total",
            "Pods bound per scheduler profile (the multi-profile map's "
            "serving split).",
        )
        self._measured_tput = reg.gauge(
            "scheduler_measured_throughput_millis",
            "Flight-derived measured milli-throughput per (workload "
            "class, accelerator class) — published when a measured "
            "matrix artifact is armed (framework/measured.py).",
        )
        # Software pipeline (ISSUE 15): predispatch double-buffer hits vs
        # invalidations (a miss re-dispatches serially — correctness is
        # free, overlap is not), drain placement (overlapped under an
        # in-flight pass vs inline at the serial point), and the wall
        # seconds the overlap actually saved (per-batch stage sum minus
        # batch wall, the flight recorder's overlap-coverage numerator).
        self._pipeline_predispatch_counter = reg.counter(
            "scheduler_pipeline_predispatch_total",
            "Predispatched device passes by pickup result "
            "(hit/invalidated).",
        )
        self._pipeline_drain_counter = reg.counter(
            "scheduler_pipeline_drains_total",
            "Staged commit-group drains by placement (overlapped/inline).",
        )
        self._pipeline_overlap_counter = reg.counter(
            "scheduler_pipeline_overlap_saved_seconds_total",
            "Wall seconds saved by stage overlap (serial stage sum minus "
            "batch wall, clamped at zero).",
        )
        # Poison-batch recovery observability: how often the engine raised
        # mid-batch and how many pods ended up isolated.  The quarantine
        # DEPTH rides scheduler_pending_pods{queue="quarantine"} below.
        self._engine_fault_counter = reg.counter(
            "scheduler_engine_faults_total",
            "Engine exceptions caught by the batch-recovery path.",
        )
        self._quarantine_counter = reg.counter(
            "scheduler_quarantined_pods_total",
            "Pods isolated into the quarantine pool after engine faults.",
        )
        # Rejection attribution (NodeToStatusMap analog): which plugin
        # made a pod unschedulable.  Incremented once per rejecting
        # plugin at the filter-reject diagnosis site, and as
        # plugin="EngineFault" at quarantine parks — label cardinality
        # is bounded by the profiles' filter-op registry.
        self._unsched_reasons = reg.counter(
            "scheduler_unschedulable_reasons_total",
            "Unschedulable verdicts attributed to the rejecting plugin.",
        )
        # Failure-response loop (controllers.py): lifecycle transitions
        # are counted at the write site; the per-state gauge, the GC
        # reasons and the eviction total are scraped below.
        self._lifecycle_transitions = reg.counter(
            "scheduler_node_lifecycle_transitions_total",
            "Node lifecycle state transitions written as taints, by "
            "target state.",
        )
        self._pod_gc_counter = reg.counter(
            "scheduler_pod_gc_total",
            "Pods collected by the GC sweeps, by reason.",
        )
        lifecycle_state = reg.gauge(
            "scheduler_node_lifecycle_state",
            "Lease-tracked nodes by lifecycle state.",
        )
        taint_evictions = reg.counter(
            "scheduler_taint_evictions_total",
            "Pods evicted by the NoExecute taint-eviction controller.",
        )
        pending = reg.gauge(
            "scheduler_pending_pods", "Pending pods by queue class."
        )
        cache_g = reg.gauge(
            "scheduler_cache_size", "Cached cluster objects by kind."
        )
        snap = reg.gauge(
            "scheduler_snapshot_node_rows", "Device snapshot node-row capacity."
        )
        programs = reg.gauge(
            "scheduler_jax_compiled_programs",
            "Compiled XLA program variants held.",
        )
        devmem = reg.gauge(
            "scheduler_device_memory_bytes",
            "Device allocator stats when the backend reports them.",
        )
        # Process counters (framework/tracing.PROCESS): every XLA program
        # of the process, the collector's pauses once `serve` hooked
        # gc.callbacks, and the frozen heap once `serve` armed it.
        PROCESS.hook_compiles()
        jax_compiles = reg.counter(
            "scheduler_jax_compiles_total",
            "XLA programs handed to the backend by this process, built "
            "or loaded from the persistent cache.",
        )
        jax_compile_s = reg.counter(
            "scheduler_jax_compile_seconds_total",
            "Seconds spent building or loading those programs.",
        )
        gc_collections = reg.counter(
            "scheduler_gc_collections_total",
            "Python collector runs in the serving process, by generation.",
        )
        gc_pause = reg.counter(
            "scheduler_gc_pause_seconds_total",
            "Seconds the serving process spent inside collector runs.",
        )

        gc_freezes = reg.counter(
            "scheduler_gc_freezes_total",
            "Times the serving process froze its heap: batch boundaries "
            "that bound a pod, and checkpoints.",
        )
        gc_frozen = reg.gauge(
            "scheduler_gc_frozen_objects",
            "Objects outside the collector's walk (gc.get_freeze_count()).",
        )
        gc_reclaimed = reg.counter(
            "scheduler_gc_sweep_reclaimed_total",
            "Unreachable objects found by the checkpoints' full collections.",
        )

        def collect(_reg) -> None:
            m = self.metrics
            jax_compiles.set(PROCESS.compiles)
            jax_compile_s.set(PROCESS.compile_s)
            if PROCESS.gc_hooked:
                for gen, n in enumerate(PROCESS.gc_collections):
                    gc_collections.set(n, generation=str(gen))
                gc_pause.set(PROCESS.gc_pause_s)
            if PROCESS.heap_armed:
                gc_freezes.set(PROCESS.gc_freezes)
                gc_frozen.set(gc.get_freeze_count())
                gc_reclaimed.set(PROCESS.gc_sweep_reclaimed)
            # The reference's partitioning label set {scheduled,
            # unschedulable, error} (metrics.go:138): the cells sum to the
            # attempt total, so sum(rate(...)) dashboards stay honest.
            # "error" is the residual — attempts whose pods are neither
            # bound nor pooled (in-flight waits, rollbacks).
            attempts.set(m.scheduled, result="scheduled")
            attempts.set(m.unschedulable, result="unschedulable")
            attempts.set(
                max(m.schedule_attempts - m.scheduled - m.unschedulable, 0),
                result="error",
            )
            preempt.set(m.preemptions)
            # Disjoint cells (m.batches counts every batch, pinned ones
            # included): sum() over the label reproduces the true total.
            batches.set(max(m.batches - m.pinned_batches, 0), kind="full")
            batches.set(m.pinned_batches, kind="pinned")
            deferred.set(m.deferred)
            packed.set(m.packed_batches)
            pack_coll.set(m.pack_collisions)
            pack_width.set(m.pack_width)
            pack_classes.set(m.pack_classes)
            dom_carry.set(m.dom_carry_hits, result="hit")
            dom_carry.set(m.dom_carry_rebuilds, result="rebuild")
            counted, shared = self.builder.csi_claim_counts()
            csi_claims.set(counted, kind="counted")
            csi_claims.set(shared, kind="shared")
            for q, depth in self.queue.depths().items():
                pending.set(depth, queue=q)
            for state, count in self.node_lifecycle.stats()["states"].items():
                lifecycle_state.set(count, state=state)
            taint_evictions.set(self.taint_eviction.evictions)
            cache_g.set(len(self.cache.nodes), kind="nodes")
            cache_g.set(len(self.cache.pods), kind="pods")
            cache_g.set(
                sum(1 for p in self.cache.pods.values() if p.assumed),
                kind="assumed",
            )
            snap.set(getattr(self.builder.schema, "N", 0) or 0)
            programs.set(len(self.passes) + len(self._eval_passes))
            try:
                stats = jax.local_devices()[0].memory_stats() or {}
            except Exception:  # CPU backends return None / lack the call
                stats = {}
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                if k in stats:
                    devmem.set(stats[k], kind=k)

        reg.add_collector(collect)

    # -- durability (journal.py) ---------------------------------------------

    def attach_journal(self, journal, snapshot_every_batches: int = 0) -> None:
        """Arm the write-ahead binding journal: every bind/preempt/
        quarantine/delete decision is appended (and fsync'd, per the
        journal's policy) BEFORE it is applied, snapshots checkpoint the
        store+queue once the log holds as many records past the last
        barrier as ``snapshot_every_batches`` full batches (0 = only on
        explicit snapshot), and the journal's counters export as
        scheduler_journal_* at scrape time.  Recovery (journal.recover)
        must run BEFORE attaching — its replay drives this scheduler's
        mutation surface, which would otherwise re-journal every record."""
        self.journal = journal
        self.queue.journal = journal
        journal.spans = self.spans
        if snapshot_every_batches:
            self.snapshot_every_records = snapshot_every_batches * self.batch_size
        reg = self.metrics.registry
        appends = reg.counter(
            "scheduler_journal_appends_total",
            "Decisions durably appended to the write-ahead journal.",
        )
        writes = reg.counter(
            "scheduler_journal_writes_total",
            "write calls on the journal file: one per commit group, so "
            "appends / writes is the group size.",
        )
        fence_checks = reg.counter(
            "scheduler_journal_fence_checks_total",
            "Lease-epoch fence checks: at most two per commit group.",
        )
        fsyncs = reg.counter(
            "scheduler_journal_fsync_total", "Journal fsync calls."
        )
        fenced = reg.counter(
            "scheduler_journal_fenced_total",
            "Appends rejected by the lease-epoch fence (deposed writer).",
        )
        group_commits = reg.counter(
            "scheduler_journal_group_commits_total",
            "Group-commit fsync barriers (one durability fsync per "
            "staged commit group).",
        )
        group_size = reg.gauge(
            "scheduler_journal_last_group_size",
            "Records covered by the last group-commit fsync barrier.",
        )
        snaps = reg.counter(
            "scheduler_journal_snapshots_total",
            "Checkpoints written (log truncated at each barrier).",
        )
        replayed = reg.counter(
            "scheduler_journal_replayed_records_total",
            "Records applied by the last recovery replay.",
        )
        seq_g = reg.gauge(
            "scheduler_journal_last_seq", "Sequence number of the last record."
        )
        wal_g = reg.gauge(
            "scheduler_journal_wal_bytes", "Current journal file size."
        )

        def collect(_reg) -> None:
            j = self.journal
            if j is None:
                return
            appends.set(j.appends)
            writes.set(j.writes)
            fence_checks.set(j.fence_checks)
            fsyncs.set(j.fsyncs)
            fenced.set(j.fenced)
            group_commits.set(j.group_commits)
            group_size.set(j.last_group_size)
            snaps.set(j.snapshots)
            replayed.set(j.replayed)
            seq_g.set(j.seq)
            try:
                import os as _os

                wal_g.set(_os.path.getsize(j.wal_path))
            except OSError:
                wal_g.set(0)

        reg.add_collector(collect)

    def _journal_append(self, rtype: str, **data) -> None:
        """Write-ahead one decision.  StaleEpochError propagates — a
        deposed leader must stop committing, not commit unjournaled."""
        if self.journal is not None:
            self.journal.append(rtype, data)

    def _journal_bind(self, pod: t.Pod, node_name: str) -> None:
        if self.journal is not None:
            from .api import serialize

            t0 = time.perf_counter()
            pod_d = serialize.pod_to_data(pod)
            self._serialize_s += time.perf_counter() - t0
            data = {"uid": pod.uid, "node": node_name, "pod": pod_d}
            # Decision provenance rides the WAL: the device tie-break
            # step makes a journal-mode explain's selectHost trace exact
            # without the in-memory ring (replay ignores the field).
            tie = self._tie_pending.pop(pod.uid, None)
            if tie is not None and tie >= 0:
                data["tie"] = tie
            seq = self.journal.append("bind", data)
            if self.provenance is not None and seq is not None:
                self.provenance.note_seq(pod.uid, seq)

    def maybe_snapshot(self) -> bool:
        """Checkpoint once the journal holds ``snapshot_every_records``
        records past the last barrier: as many as attach_journal's
        ``snapshot_every_batches`` full batches write.  The cadence bounds
        the log a recovery replays, so it counts records: the bound is the
        same whatever the batches hold, and an idle scheduler never
        rewrites its snapshot."""
        j = self.journal
        if j is None or not self.snapshot_every_records:
            return False
        if j.seq - j.snapshot_seq < self.snapshot_every_records:
            return False
        from . import journal as journal_mod

        with self.span("pipeline/snapshot", phase="snapshot"):
            with self.span("snapshot/collect"):
                state = journal_mod.scheduler_state(self)
            j.snapshot(state)
            if PROCESS.heap_armed:
                # The server stands still to walk the whole store anyway:
                # the one place a full collection runs (tracing.py).
                with self.span("snapshot/heap_sweep"):
                    PROCESS.sweep_heap()
        return True

    def _note_slow_span(self, tr: Trace) -> None:
        """on_slow hook: keep the logged span TREE for the debugger dump
        (the `dump` frame surfaces the joined host↔sidecar trace)."""
        self.slow_spans.append(tr.as_dict())

    # -- flight recorder (framework/flight.py) -------------------------------

    def _trace_id(self) -> str | None:
        """The current batch's trace id (joins events and flight records
        to the span tree — and, over the wire, to the HOST's trace)."""
        span = self.last_batch_span
        return span.trace_id if span is not None else None

    def _trace_extra(self) -> dict:
        """Event extra carrying the originating trace id, so an event can
        be joined to its batch's flight record and span tree."""
        tid = self._trace_id()
        return {"trace_id": tid} if tid else {}

    def _note_tenant(self, event: str, pod: t.Pod) -> None:
        """Count one tenant event (bound/preempted; admission/deferral
        ride the queue's tenant_note hook) — a no-op with attribution
        off."""
        if self.tenant_metrics is not None:
            self.tenant_metrics.note(event, pod_tenant(pod))

    def _note_bound(self, pod: t.Pod, node_name: str) -> None:
        """Per-bind attribution, every bind path: the tenant counter
        plus — when any registered profile carries a throughput matrix —
        the heterogeneity split (scheduler_hetero_bound_total by the
        chosen node's accelerator class × the pod's workload class;
        label values bounded by the matrix config, everything else
        folds to "-"/"other") and the per-profile serving split
        (scheduler_profile_bound_total, bounded by the profile map)."""
        self._note_tenant("bound", pod)
        key = self.hetero_bind_key(pod, node_name)
        if key is None:
            return
        wl, al = key.split("|", 1)
        self._hetero_bound.inc(accel=al, workload_class=wl)
        # The per-batch heterogeneity split on the flight record — the
        # deterministic input framework/measured.py folds into measured
        # throughput rows (counts, never wall time).
        acc = self._flight_acc
        if acc is not None:
            h = acc.setdefault("hetero", {})
            h[key] = h.get(key, 0) + 1
        profile = self._profile_for(pod) or self.profile
        self._profile_bound.inc(profile=profile.name)

    def hetero_bind_key(self, pod: t.Pod, node_name: str) -> str | None:
        """The bounded ``"workload_class|accel"`` key for one bind — None
        when no registered profile carries a throughput matrix.  Label
        values are bounded by the matrix config (everything else folds to
        "-"/"other"), shared by the hetero counter, the per-batch flight
        ``hetero`` field, and the fleet owners' per-op commit records, so
        measured-matrix derivation sees one vocabulary everywhere."""
        if self._hetero_classes is None:
            return None
        accels, wclasses = self._hetero_classes
        from .ops.throughput import ACCEL_LABEL_KEY, WORKLOAD_CLASS_LABEL_KEY

        rec = self.cache.nodes.get(node_name)
        accel = (
            rec.node.metadata.labels.get(ACCEL_LABEL_KEY, "")
            if rec is not None
            else ""
        )
        wclass = pod.metadata.labels.get(WORKLOAD_CLASS_LABEL_KEY, "")
        al = (accel if accel in accels else "other") if accel else "-"
        wl = (wclass if wclass in wclasses else "other") if wclass else "-"
        return f"{wl}|{al}"

    def note_measured_matrix(self, matrix) -> None:
        """Publish a measured throughput matrix into the
        scheduler_measured_throughput_millis gauge family — called when
        serve arms a measured artifact (``--measured-matrix``) so a
        scrape shows exactly which rows the profile scores against.
        Accepts the profile's tuple-of-rows form, a measured artifact
        document, or its ``{wclass: {accel: milli}}`` mapping."""
        rows = matrix.get("matrix", matrix) if isinstance(matrix, dict) else matrix
        if isinstance(rows, dict):
            rows = tuple(
                (w, tuple(sorted(r.items()))) for w, r in sorted(rows.items())
            )
        for wclass, row in rows:
            for accel, milli in row:
                self._measured_tput.set(
                    float(milli),
                    workload_class=str(wclass),
                    accel=str(accel),
                )

    def _flight_add(self, key: str, n) -> None:
        acc = self._flight_acc
        if acc is not None:
            acc[key] = acc.get(key, 0) + n

    def _count_scan_steps(self, steps_run, shape_steps: int) -> None:
        """One fetched pass's steps into scheduler_pass_scan_steps_total and
        the open flight record.  ``steps_run`` is the program's own count
        (PassResult.scan_steps, fetched with the result) of the
        ``shape_steps`` its padded shape holds."""
        ran = int(steps_run)
        self._scan_steps_counter.inc(ran, kind="run")
        self._scan_steps_counter.inc(shape_steps - ran, kind="padded_skipped")
        self._flight_add("scan_steps", ran)

    def _count_pass_inputs(self, total: int, shipped: int, nbytes: int) -> None:
        """One dispatch's input leaves into scheduler_pass_inputs_total
        {kind} and the open flight record: of the ``total`` leaves the pass
        takes besides the state and the domain tables, ``shipped`` crossed
        from the host (``nbytes`` in all) and the rest were the device's
        own from an earlier dispatch."""
        self._pass_inputs_counter.inc(shipped, kind="shipped")
        self._pass_inputs_counter.inc(max(total - shipped, 0), kind="resident")
        self._flight_add("inputs_shipped", shipped)
        self._flight_add("inputs_shipped_bytes", nbytes)

    def _count_filter_rejections(self, fails, picks, n: int, bit_names) -> None:
        """One batch's settled fail masks into
        scheduler_pass_filter_rejecting_pods_total{plugin} and the open
        flight record's ``filter_rejecting``: for every filter op of the
        compiled pass (``bit_names`` = filter_op_names) the valid rows whose
        bit is set.  Once a pod: a tail overwrote its rows in place before
        this runs, and a row sent back to the queue (pick -3) is counted by
        the batch that decides it.  Host arithmetic on the array the batch
        fetched anyway; no program changes for it."""
        live = fails[:n][picks[:n] != -3]
        acc = self._flight_acc
        for b, name in enumerate(bit_names):
            hit = int(np.count_nonzero(live & np.uint32(1 << b)))
            self._filter_rejecting_counter.inc(hit, plugin=name)
            if hit and acc is not None:
                rec = acc.setdefault("filter_rejecting", {})
                rec[name] = rec.get(name, 0) + hit

    # -- software pipeline (ISSUE 15, engine/pipeline.py) ---------------------

    def _pipeline_active(self) -> bool:
        """Deferred drain + predispatch apply only on the single-profile
        batch path: multi-profile groups, extender chains, and truncated
        (parity) mode keep the serial order — depth 1 everywhere."""
        return (
            self.pipeline_depth >= 2
            and not self._truncated
            and len(self.profiles) == 1
            and not self.extenders
        )

    @property
    def has_inflight_work(self) -> bool:
        """Work popped from the queue but not yet completed: a prefetched
        (featurized) batch or a predispatched device pass.  Drivers that
        loop on queue length must also drain these."""
        return self._prefetched is not None or self._predispatched is not None

    def _drain_pending(self, overlapped: bool) -> None:
        """Drain the current staged commit group (group fsync + applies,
        engine/pipeline.drain_commit) under the `pipeline/drain` span (the
        `drain` flight phase), and count where it ran."""
        ticket = self._pending_ticket
        if ticket is None or ticket.drained:
            return
        from .engine.pipeline import drain_commit

        if ticket.staged:
            with self.span("pipeline/drain", phase="drain"):
                drain_commit(self, ticket)
        else:
            drain_commit(self, ticket)
        # Fully drained: release the scheduler's reference so an idle
        # process does not pin the last batch's pods/outcomes until the
        # next batch overwrites the slot.  (A mid-drain exception leaves
        # the ticket in place with its progress counters — the recovery
        # drain resumes it.)
        self._pending_ticket = None
        if ticket.staged:
            self._pipeline_drain_counter.inc(
                kind="overlapped" if overlapped else "inline"
            )

    def _predispatch_next(self) -> bool:
        """Dispatch the prefetched batch k+1 NOW (before batch k's drain)
        so the drain's fsync + applies run under the in-flight device
        pass.  The pass is picked up — or invalidated and re-dispatched —
        by the next schedule_batch (engine/pipeline.predispatch_valid).
        Returns whether a pass was dispatched."""
        pre = self._prefetched
        if pre is None:
            return False
        if self._pd_consec_invalid > 0:
            # Churn regime: a recent predispatch was thrown away at
            # pickup — it cost a whole wasted device pass.  Sit this
            # batch out and decay, so sustained churn converges to ~one
            # probe per penalty window instead of doubling device time
            # every batch, while a single transient mutation costs only
            # a few skipped overlaps.
            self._pd_consec_invalid -= 1
            return False
        infos, work = pre
        if work["version"] != self.builder.feature_version():
            return False  # stale featurization: let the serial path redo it
        self._prefetched = None
        cycle0 = self._cycle
        with self.span("pipeline/predispatch", phase="predispatch"):
            return self._predispatch(infos, work, cycle0)

    def _predispatch(self, infos, work: dict, cycle0: int) -> bool:
        from .engine.pipeline import Predispatch, nominator_token

        try:
            # _dispatch_batch may permute its local infos (the packer);
            # keep OUR list in original pop order for re-dispatch.  The
            # packer also rebinds work["batch"]/work["deltas"] on the
            # dict it is handed — dispatch a shallow COPY so a failure
            # below cannot restore a work dict whose rows were permuted
            # while infos kept pop order (the serial retry would read
            # each pod against another pod's feature row).
            ctx = self._dispatch_batch(list(infos), self.profile, dict(work))
        except Exception:
            # A dispatch failure (engine fault) must surface inside the
            # VICTIM batch's own cycle for recovery attribution: restore
            # the pop and let the next cycle dispatch serially.
            self._cycle = cycle0
            self._prefetched = (infos, work)
            return False
        self._predispatched = Predispatch(
            infos=list(infos),
            ctx=ctx,
            profile=self.profile,
            version=self.builder.feature_version(),
            mutation_epoch=self.builder.mutation_epoch,
            schema=self.builder.schema,
            nominator_token=nominator_token(self),
            cycle0=cycle0,
        )
        return True

    def _observe_plugin(self, plugin: str, point: str, secs: float) -> None:
        """One sampled per-plugin duration, fanned to the upstream-parity
        exposition, the scheduler_plugin_duration_seconds family, and the
        current flight record."""
        self.metrics.registry.observe_plugin(plugin, point, secs)
        self._plugin_hist.observe(secs, plugin=plugin, extension_point=point)
        acc = self._flight_acc
        if acc is not None:
            key = f"{plugin}/{point}"
            acc["plugins"][key] = acc["plugins"].get(key, 0.0) + secs

    def _record_flight(self, acc: dict, wall: float, jbase) -> None:
        """Finalize one per-batch flight record: close the phase tiling
        (featurize/device/commit/snapshot + the explicit `other` residual
        — pop, expiry sweeps, loop overhead), attach the spans, the
        queue wait and the journal's append/fsync slice deltas, and
        observe every phase's batch sum into
        scheduler_phase_duration_seconds (a span that feeds no phase
        observed its own name as it ended)."""
        phases = acc["phases"]
        # Per-stage serial sum BEFORE the residual: with the pipeline on,
        # a predispatched batch's device window started in the PREVIOUS
        # call, so the stage sum can exceed this call's wall — the excess
        # is exactly the wall time stage overlap saved vs running the
        # stages serially.
        serial_s = sum(phases.values())
        saved_s = max(serial_s - wall, 0.0)
        phases["other"] = max(wall - serial_s, 0.0)
        rec = {
            "pods": acc["pods"],
            "scheduled": acc["scheduled"],
            "unschedulable": acc["unschedulable"],
            "deferred": acc.get("deferred", 0),
            "scan_steps": acc.get("scan_steps", 0),
            "inputs_shipped": acc.get("inputs_shipped", 0),
            "inputs_shipped_bytes": acc.get("inputs_shipped_bytes", 0),
            "filter_rejecting": acc.get("filter_rejecting", {}),
            "dispatch": acc["dispatches"],
            "wall_s": round(wall, 6),
            "phases": {k: round(v, 6) for k, v in phases.items()},
            # [name, start_us from t0_ns, dur_us, parent index] in start
            # order (a fifth element holds accumulated sub-times)
            "spans": acc["spans"],
            "t0_ns": acc["t0_ns"],
            "bid": acc["bid"],
        }
        qw = acc.get("queue_wait")
        if qw is not None:
            rec["queue_wait"] = {
                "pods": qw[0],
                "sum_ms": round(qw[1] * 1e3, 3),
                "max_ms": round(qw[2] * 1e3, 3),
            }
        if self.pipeline_depth >= 2:
            serial_total = serial_s + phases["other"]
            rec["overlap"] = {
                "serial_s": round(serial_total, 6),
                "saved_s": round(saved_s, 6),
                # wall saved vs the serial stage sum — 0.0 with nothing
                # overlapped, approaching the device share as the commit
                # stage fully hides under the next in-flight pass.
                "coverage": round(saved_s / serial_total, 4)
                if serial_total > 0
                else 0.0,
            }
            if saved_s > 0:
                self._pipeline_overlap_counter.inc(saved_s)
        if acc.get("hetero"):
            rec["hetero"] = {
                k: acc["hetero"][k] for k in sorted(acc["hetero"])
            }
        if acc.get("drained"):
            rec["drained"] = acc["drained"]
        if acc.get("group_fsyncs"):
            rec["group_fsyncs"] = acc["group_fsyncs"]
        if acc["plugins"]:
            rec["plugins"] = {
                k: round(v, 6) for k, v in sorted(acc["plugins"].items())
            }
        j = self.journal
        if j is not None and jbase is not None:
            append_s = j.append_latency.total - jbase[2]
            fsync_s = j.fsync_s - jbase[3]
            rec["journal"] = {
                "appends": j.appends - jbase[0],
                # One write a commit group: appends / writes is its size.
                "writes": j.writes - jbase[4],
                "fence_checks": j.fence_checks - jbase[5],
                "fsyncs": j.fsyncs - jbase[1],
                "append_s": round(append_s, 6),
                "fsync_s": round(fsync_s, 6),
            }
            # Sub-slices of featurize/commit (journaled deletes can land
            # pre-dispatch), exported for the durability-tax view — they
            # deliberately stay OUT of the tiling sum above.
            self._phase_hist.observe(append_s, phase="journal_append")
            self._phase_hist.observe(fsync_s, phase="journal_fsync")
        span = self.last_batch_span
        if span is not None:
            rec["trace_id"] = span.trace_id
            rec["span_id"] = span.span_id
        for k, v in phases.items():
            self._phase_hist.observe(v, phase=k)
        self.flight.record_batch(rec)

    def warm_tail(self) -> None:
        """Pre-compile the programs a measured window would otherwise
        compile lazily: the dirty-row scatter flush (always) and the strict
        tail pass (chunked mode, once a batch has established shapes)."""
        # Warmup binds are device-side commits (never dirty), so without
        # this the first host-side mutation (node churn, a delete) pays the
        # scatter's XLA compile inside the measured window.  The device
        # mirror must exist first — a flush against no mirror takes the
        # full-rebuild branch and compiles nothing — and flushing a clean
        # row is idempotent (host == device values).
        if self.cache.nodes:
            self.builder.state()  # ensure the mirror exists
            rec = next(iter(self.cache.nodes.values()))
            self.builder._dirty_rows.add(rec.row)
            self.builder.state()
        if self.chunk_size == 1 or self._last_batch_meta is None:
            return
        shapes, active = self._last_batch_meta
        ts = self.tail_size
        sub = {
            k: np.zeros((ts,) + shape[1:], dtype) for k, (shape, dtype) in shapes.items()
        }
        sub["valid"] = np.zeros(ts, np.bool_)
        sub.setdefault("step_offset", np.zeros(ts, np.int32))
        inv = self._full_inv()
        state = self.builder.state()
        strict = self.passes.get(
            self.profile, self.builder.schema, self.builder.res_col, active, 1,
            carry_dom=True,
        )
        # All-invalid batch: commits nothing; discard the (identical) state.
        ph = self._dom_placeholder()
        strict(state, sub, inv, np.uint32(0), ph[0], ph[1], np.bool_(False))
        # Uniform-batch broadcast program (_expand_uniform): template
        # workloads' first uniform batch would otherwise pay this XLA
        # compile mid-window (warmup batches with per-pod labels never
        # take the uniform path).
        kfull = next(iter(shapes.values()))[0][0]
        small = {
            k: np.zeros((1,) + shape[1:], dtype)
            for k, (shape, dtype) in shapes.items()
            if k not in ("valid", "nominated_row", "pin_row")
        }
        _expand_uniform(
            small, np.zeros(kfull, np.bool_), np.full(kfull, -1, np.int32),
            kfull,
        )

    # -- controller clock / the failure-response loop (ISSUE 9) --------------

    def _note_lifecycle_transition(self, target: str) -> None:
        self._lifecycle_transitions.inc(to=target)

    def _note_pod_gc(self, reason: str) -> None:
        self._pod_gc_counter.inc(reason=reason)

    def _now(self) -> float:
        """The controllers' shared clock: an explicit override wins
        (tests); an ARMED node-lifecycle controller supplies its logical
        clock (the Lease high-water mark — liveness, taint grace, GC
        horizons and eviction deadlines all become a pure function of the
        fed operation stream, which is what makes the chaos harness's
        bit-identical-reschedule oracle and the soak's same-seed
        determinism hold); otherwise wall monotonic (the pre-lifecycle
        behavior every existing caller sees)."""
        if self.clock is not None:
            return self.clock()
        if self.node_lifecycle.armed:
            return self.node_lifecycle.now()
        return time.monotonic()

    def renew_node_lease(self, lease: t.Lease) -> None:
        """Lease informer (coordination.k8s.io): one node-heartbeat
        renewal.  Feeds the node-lifecycle controller's staleness clock;
        armed, a renewal also drives the transition/eviction/GC tick."""
        self.node_lifecycle.renew(lease.node_name, lease.renew_time)

    def remove_node_lease(self, node_name: str) -> None:
        """Lease DELETED (or absent from a relist): the node drops out of
        heartbeat tracking — unleased nodes are lifecycle-exempt, the
        documented pre-ISSUE-9 behavior.  The Lease Reflector's
        LIST-as-replace delivers this (informers.KIND_HANDLERS), so a
        takeover that relists Leases converges on exactly the host-truth
        tracked set."""
        self.node_lifecycle.forget_node(node_name)

    def write_node_taints(
        self, name: str, taints: tuple, reason: str = ""
    ) -> bool:
        """Write a node's full taint set through the journaled update
        path (the node-lifecycle controller's API PATCH analog).  The
        decision is write-ahead journaled BEFORE it applies, so a crash
        mid-transition replays it deterministically; an identical taint
        set is a no-op and journals nothing.  Returns whether a write
        happened."""
        rec = self.cache.nodes.get(name)
        if rec is None:
            return False
        taints = tuple(taints)
        if rec.node.spec.taints == taints:
            return False
        from .api import serialize

        self._journal_append(
            "taint",
            node=name,
            taints=[serialize.to_dict(taint) for taint in taints],
            reason=reason,
            # The logical time of the write: replay advances the
            # lifecycle clock here, so a recovered process re-arms
            # eviction deadlines against the incident's clock instead of
            # a rewound zero (a feed whose clock kept running would
            # otherwise fire every restored grace instantly).
            ts=self._now(),
        )
        self._apply_node_taints(name, taints)
        return True

    def _apply_node_taints(self, name: str, taints: tuple) -> None:
        """Apply a (journaled) taint set: route through update_node so
        the precise NODE_TAINT requeue event fires and the NoExecute
        eviction re-judges the node's pods — exactly what a wire-fed
        taint update would do.  Also the journal-replay apply site."""
        rec = self.cache.nodes.get(name)
        if rec is None:
            return
        import copy

        node = copy.deepcopy(rec.node)
        node.spec.taints = tuple(taints)
        self.update_node(node)
        if self.taints_changed_hook is not None:
            # The speculative frontend's decision cache reads taints as
            # global feasibility: invalidate like a wire-fed taint change.
            self.taints_changed_hook(name)

    def evict_pod(
        self, uid: str, reason: str = "eviction", pod: t.Pod | None = None
    ) -> bool:
        """Journaled evict-with-requeue: the binding is dropped and the
        pod re-enters the queue UNBOUND, to reschedule on a surviving
        node — the eviction half of upstream's sequence fused with the
        workload controller's recreate half (this repo has none).  The
        ``evict`` record is write-ahead journaled so a crash between the
        eviction and the re-bind replays the requeue instead of losing
        the pod.  ``pod`` supplies the object when the uid is not cached
        (a recovered orphan binding whose node never relisted)."""
        pr = self.cache.pods.get(uid)
        source = pr.pod if pr is not None else pod
        if source is None:
            return False
        import copy

        from .api import serialize

        requeued = copy.deepcopy(source)
        requeued.spec.node_name = ""
        requeued.status.nominated_node_name = ""
        requeued.__dict__.pop("_uid", None)
        self._journal_append(
            "evict",
            uid=uid,
            pod=serialize.pod_to_data(requeued),
            reason=reason,
            ts=self._now(),
        )
        self._apply_eviction(uid, requeued, reason=reason)
        return True

    def _apply_eviction(
        self, uid: str, requeued: t.Pod, reason: str = "eviction"
    ) -> None:
        """Apply a (journaled) eviction: unwind the binding's state, then
        requeue the unbound copy.  Also the journal-replay apply site —
        replaying an evict for a pod the snapshot never bound still
        requeues it (the delete half no-ops)."""
        self._unwind_pod(uid, notify=False)
        self._evicted_uids.add(uid)
        self.recorder.event(
            uid,
            NORMAL,
            "Evicted",
            f"Evicted {uid} ({reason}); requeued for rescheduling",
            **self._trace_extra(),
        )
        if self.eviction_requeue_hook is not None:
            # Fleet owner: the router requeues (and may rebind the pod on
            # a DIFFERENT shard); journal replay routes here too, so a
            # takeover surfaces crash-interrupted evictions to the router
            # instead of stranding them in a queue nothing drains.
            self.eviction_requeue_hook(uid, requeued, reason)
        else:
            self.add_pod(requeued)

    # -- cluster events (the informer surface, eventhandlers.go:341) ---------

    def add_node(self, node: t.Node) -> None:
        if self.shard_guard is not None and not self.shard_guard(node.name):
            # Not this shard's node (fleet partitioning): the shard map,
            # not the feed, decides ownership.
            self.shard_rejected_nodes += 1
            return
        self.cache.add_node(node)
        # Replay CSINode/ResourceSlices that arrived before their Node
        # (informer races).
        csinode = self.builder.volumes.csinodes.get(node.name)
        if csinode is not None:
            self.builder.set_csinode_limits(self.cache.row_of(node.name), csinode)
        for (nname, cls) in self.builder.dra.slices:
            if nname == node.name:
                self.builder.set_dra_cap(self.cache.row_of(node.name), nname, cls)
        cat = self.builder.dra
        for uid, charges in list(cat.pending_external.items()):
            if charges and charges[0][0] == node.name:
                del cat.pending_external[uid]
                self.builder.apply_external_claim(
                    self.cache.row_of(node.name), uid,
                    [(sig, cnt) for _n, sig, cnt in charges], +1,
                )
                cat.row_charged[uid] = charges
        # Replay parked pool-overlap corrections whose base charges just
        # replayed (external claims of this node).
        for uid in list(cat.pending_corr):
            claim = cat.claims.get(uid)
            if (
                claim is not None
                and claim.allocated_node == node.name
                and uid in cat.row_charged
            ):
                corr = cat.pending_corr.pop(uid)
                cat.corrections[uid] = corr
                self.builder.apply_dra_correction(
                    self.cache.row_of(node.name), corr, +1
                )
        # Lifecycle state rides the node's taints (recovery replay and
        # wire-fed taints both land here); heartbeats ride Leases.
        self.node_lifecycle.observe_node(node)
        self.queue.on_event(
            Event.NODE_ADD, self._free_ctx({self.cache.row_of(node.name)})
        )

    def update_node(self, node: t.Node) -> None:
        """Diff the node against its cached record to emit the precise event
        kinds (the reference computes ActionType the same way,
        eventhandlers.go:341 nodeSchedulingPropertiesChange) — so a pod
        rejected only by TaintToleration wakes on the taint removal, not on
        every capacity change (VERDICT r1 weak-5)."""
        old = self.cache.nodes.get(node.name)
        if old is None:  # unknown node: an informer add delivered as update
            self.add_node(node)
            return
        old_node = old.node
        self.cache.update_node(node)
        ev = Event(0)
        if old_node.spec.taints != node.spec.taints:
            ev |= Event.NODE_TAINT
            # NoExecute eviction judges the node's pods on a taint change
            # (tainteviction handleNodeUpdate); the lifecycle controller
            # adopts whatever state the new taint set encodes.
            self.node_lifecycle.observe_node(node)
            self.taint_eviction.handle_node(node)
        if old_node.metadata.labels != node.metadata.labels:
            ev |= Event.NODE_LABEL
        if (
            old_node.spec.unschedulable != node.spec.unschedulable
            or old_node.status.allocatable != node.status.allocatable
            or old_node.status.images != node.status.images
        ):
            ev |= Event.NODE_UPDATE
        if ev:
            # The free-capacity payload lets the fit hint skip pods this
            # node still can't seat; taint/label-only updates carry it too
            # (only fit consults it, and its mask gates on NODE_UPDATE).
            self.queue.on_event(ev, self._free_ctx({old.row}))

    def remove_node(self, name: str) -> None:
        # Externally-charged claims on the vanishing node: the row is
        # cleared wholesale, so re-park their charges as pending (a
        # returning node replays them, like slices/CSINode).
        cat = self.builder.dra
        for uid, charges in list(cat.row_charged.items()):
            if charges and charges[0][0] == name:
                del cat.row_charged[uid]
                cat.pending_external[uid] = charges
        # Applied pool-overlap corrections died with the row too: park them
        # for replay alongside the base charges.
        for uid in list(cat.corrections):
            claim = cat.claims.get(uid)
            if claim is not None and claim.allocated_node == name:
                cat.pending_corr[uid] = cat.corrections.pop(uid)
        # Bound gang members vanish with the node; their quorum credit must
        # go with them (same invariant as delete_pod).
        rec = self.cache.nodes.get(name)
        if rec is not None:
            for uid in rec.pods:
                pr = self.cache.pods.get(uid)
                if pr is not None and pr.bound and pr.pod.spec.pod_group:
                    self._debit_gang(pr.pod.spec.pod_group)
                if pr is not None:
                    self._note_claims(pr.pod, -1)
        self.cache.remove_node(name)
        # Waiting gang members assumed on the removed node lost their
        # assumption (cache.remove_node vaporized their records): send them
        # back to the gang pool to retry with their gang.
        if rec is not None and self.permit_waiting:
            for qp, _n, _s, _f in self._drop_permit_waiters(set(rec.pods)):
                self.queue.requeue_gang_member(qp)
                self._note_claims(qp.pod, +1)  # pending again, so known again
        # A deleted node leaves the lifecycle/GC tracking maps — its
        # pods vanished with it, so there is nothing left to collect.
        self.node_lifecycle.forget_node(name)
        self.pod_gc.forget_node(name)

    def add_pod(self, pod: t.Pod, held_at: float = 0.0) -> None:
        """Unassigned pods enter the queue; assigned pods enter the cache
        (eventhandlers.go:126 addPodToSchedulingQueue / :203 addPodToCache).
        ``held_at``: when the server first held the pod, where that was
        before this call (a hint frame's arrival, on the queue's clock)."""
        if not pod.spec.node_name and self._profile_for(pod) is None:
            return  # another scheduler's pod (responsibleForPod)
        self._note_claims(pod, +1)
        if pod.spec.node_name:
            if pod.uid in self.cache.pods:
                # Upsert of a known bound pod (watch re-delivery): route
                # through the diffing update path — re-running add would
                # double-apply the resource delta and gang credit (ADVICE r2).
                self.update_pod(pod)
                return
            # A pod we knew as PENDING arriving bound (another scheduler —
            # or this host's degraded mode — bound it; the replay after a
            # resync re-ships it with its node) must leave the queue: a
            # later drain re-scheduling an already-bound pod would
            # double-apply its resource delta.
            self.queue.delete(pod.uid)
            self.cache.add_pod(pod)
            # Informer-delivered bound gang members count toward quorum —
            # delete_pod debits symmetrically.
            if pod.spec.pod_group:
                self.gang_bound[pod.spec.pod_group] = (
                    self.gang_bound.get(pod.spec.pod_group, 0) + 1
                )
            # A pod arriving bound to a NoExecute-tainted node is judged
            # immediately (tainteviction handlePodUpdate).
            self.taint_eviction.handle_pod_assigned(pod, pod.spec.node_name)
            self.queue.on_event(Event.POD_ADD)
        else:
            if pod.uid in self.cache.pods:
                # At-least-once re-delivery: a pod we already hold bound/
                # assumed arriving WITHOUT its node (a host's resync replay
                # recorded it before the binding response landed).  The
                # commit already happened — re-queueing would double-apply
                # its resource delta on the next drain.
                return
            self.queue.add(pod, held_at)

    def update_pod(self, pod: t.Pod) -> None:
        """Pod informer update (eventhandlers.go:136 updatePodInScheduling-
        Queue / :235 updatePodInCache), diffed so routine status-only
        updates are no-ops.  A cached (bound/assumed) pod's label or spec
        change rewrites its node's row delta — including the group/term
        domain tensors on device — and fires POD_UPDATE so e.g. a waiting
        anti-affinity pod wakes when the blocking pod's label changes."""
        pr = self.cache.pods.get(pod.uid)
        was = pr.pod if pr is not None else None
        if was is None:
            qp = self.queue._info.get(pod.uid)
            was = qp.pod if qp is not None else None
        if was is not None and was.spec.volumes != pod.spec.volumes:
            # The claims it names changed: the known users follow.
            self._note_claims(was, -1)
            if self._profile_for(pod) is not None or pod.spec.node_name:
                self._note_claims(pod, +1)
        if pr is not None:
            if pod.spec.node_name and pod.spec.node_name != pr.node_name:
                # The upsert carries a DIFFERENT node: host truth rebound
                # the pod (a resync replay overriding a stale local
                # placement — the host store is the apiserver analog).
                # Relocate via remove+add (cache.go updatePod's
                # removePod+addPod contract); cache.update_pod alone only
                # rewrites the delta on the pod's current node.
                self.delete_pod(pod.uid, notify=False)
                self.add_pod(pod)
                return
            old = pr.pod
            if (
                old.metadata.labels == pod.metadata.labels
                and old.spec == pod.spec
            ):
                # Status/metadata-only: keep the fresher object in BOTH
                # mirrors (the node record feeds preemption's victim
                # ordering — a stale start_time there would change the
                # eviction order).
                pr.pod = pod
                node_rec = self.cache.nodes.get(pr.node_name)
                if node_rec is not None:
                    node_rec.pods[pod.uid] = pod
                    # start_time feeds victim ordering: the staged victim
                    # tensors for this node are stale.
                    self.cache._bump_pods_gen(node_rec)
                return
            self.cache.update_pod(pod)
            self.queue.on_event(
                Event.POD_UPDATE, self._free_ctx({self.cache.nodes[pr.node_name].row})
            )
            return
        if pod.spec.node_name:
            self.add_pod(pod)  # informer add delivered as update
            return
        if self._profile_for(pod) is None:
            return
        self.queue.update(pod)

    def _free_ctx(self, rows) -> EventCtx:
        """EventCtx summarizing free capacity on the given node rows AFTER
        the current host-state change, with nominated pods' claims
        subtracted (a freed node a preemptor nominated is not actually free
        to a waiting pod — the fit overlay would reject it anyway)."""
        host = self.builder.host
        nom_req: dict[int, np.ndarray] = {}
        nom_cnt: dict[int, int] = {}
        if self.nominator:
            for _uid, (node_name, delta, _p) in self.nominator.items():
                rec = self.cache.nodes.get(node_name)
                if rec is None or rec.row not in rows:
                    continue
                d = delta["req"]
                acc = nom_req.get(rec.row)
                if acc is None:
                    acc = np.zeros(host["alloc"].shape[1], np.int64)
                    nom_req[rec.row] = acc
                acc[: d.shape[0]] += d
                nom_cnt[rec.row] = nom_cnt.get(rec.row, 0) + 1
        max_free = None
        max_slots = 0
        for r in rows:
            free = host["alloc"][r] - host["req"][r]
            if r in nom_req:
                free = free - nom_req[r]
            slots = int(host["allowed_pods"][r] - host["num_pods"][r]) - nom_cnt.get(r, 0)
            max_free = free if max_free is None else np.maximum(max_free, free)
            max_slots = max(max_slots, slots)
        return EventCtx(max_free=max_free, max_slots=max_slots)

    def _drop_permit_waiters(self, uids) -> list:
        """Remove the given pods from the WaitOnPermit room (deleted pods,
        pods vaporized by node removal) so gang quorum credit and later
        finalize/expiry don't see ghosts.  Returns the dropped entries."""
        dropped: list = []
        for g in list(self.permit_waiting):
            entries = self.permit_waiting[g]
            kept = [e for e in entries if e[0].pod.uid not in uids]
            if len(kept) != len(entries):
                dropped.extend(e for e in entries if e[0].pod.uid in uids)
                if kept:
                    self.permit_waiting[g] = kept
                else:
                    self.permit_waiting.pop(g)
                    self.permit_wait_since.pop(g, None)
                    self.permit_wait_owner.pop(g, None)
        return dropped

    def delete_pod(self, uid: str, notify: bool = True) -> None:
        """``notify=False`` batches the requeue wake-up: preemption deletes
        victims in bulk and fires ONE POD_DELETE for the batch (a per-victim
        scan of the unschedulable pool is O(victims × pool))."""
        # Write-ahead: the deletion (a preemption victim's eviction, an
        # informer delete) is durable before any state unwinds — recovery
        # must not resurrect a deleted pod's binding.
        self._journal_append("delete", uid=uid)
        self._unwind_pod(uid, notify)

    def _mark_inflight(self, infos: list) -> None:
        """A prefetched or predispatched batch is now in flight for real:
        gang members leave the queue's pending-quorum tracking (the pop
        re-tracked them so a dissolved batch could reactivate cleanly)."""
        for qp in infos:
            if qp.pod.spec.pod_group:
                self.queue._untrack_gang_member(qp.pod)

    def _dissolve_inflight(self, infos: list, uid: str) -> None:
        """Hand an in-flight batch (prefetched or predispatched) back to
        the queue minus the departing pod: the dead member is dropped —
        the pop re-tracked it in _gang_members (gang_pending quorum
        credit), so untrack or the dead uid overcounts quorum forever
        and Permit waits on a ghost — and every survivor reactivates."""
        for qp in infos:
            if qp.pod.uid == uid:
                self.queue._info.pop(uid, None)
                self.queue._untrack_gang_member(qp.pod)
                continue
            self.queue.reactivate(qp)

    def _unwind_pod(self, uid: str, notify: bool = True) -> None:
        """The state unwind a pod's departure requires — shared by
        delete_pod (journaled ``delete``) and _apply_eviction (journaled
        ``evict``): prefetch dissolution, wait-room exits, nomination and
        eviction-timer cleanup, DRA release, cache/queue removal."""
        # A pod held in the prefetched batch would otherwise be scheduled
        # after its deletion: dissolve the prefetch back into the queue.
        if self._prefetched is not None and any(
            qp.pod.uid == uid for qp in self._prefetched[0]
        ):
            infos_p, _work = self._prefetched
            self._prefetched = None
            self._dissolve_inflight(infos_p, uid)
        # Same for a PREDISPATCHED batch (ISSUE 15): the early device
        # pass included the pod, and an unbound pod's deletion moves no
        # validity token (no cache entry → no dirty row), so pickup
        # would complete the pass and bind a deleted pod.  Discard the
        # pass outright — rewind the tie-break cycle counter and hand
        # the surviving members back to the queue, exactly like the
        # prefetch dissolution above.
        pd = self._predispatched
        if pd is not None and any(qp.pod.uid == uid for qp in pd.infos):
            self._predispatched = None
            self._cycle = pd.cycle0
            self._dissolve_inflight(pd.infos, uid)
        self._drop_permit_waiters({uid})
        # A deleted pod leaves the PreBind wait room: revert its Reserve
        # chain now (the cache entry goes below with the delete); scrub it
        # from gang-rollback records so a later group timeout cannot unwind
        # a pod that no longer exists.
        entry = self.prebind_waiting.pop(uid, None)
        if entry is not None:
            for rp, u in reversed(entry["undos"]):
                rp.unreserve(u, self)
        for e in self.prebind_waiting.values():
            e["mates"] = [m for m in e["mates"] if m[0].pod.uid != uid]
        for g in list(self.prebind_done_pending):
            self.prebind_done_pending[g] = [
                d for d in self.prebind_done_pending[g]
                if d["qp"].pod.uid != uid
            ]
        self.nominator.pop(uid, None)
        # A deleted pod's pending NoExecute eviction dies with it — a
        # re-created pod with the same namespace/name must not inherit
        # the old deadline (or its per-taint clocks).
        self.taint_eviction.cancel(uid)
        # DRA: drop the pod's claim reservations; claims nobody reserves
        # deallocate (the resourceclaim controller's cleanup).  Externally-
        # charged claims discharge their phantom row reservation here.
        by_claim: dict[tuple[str, str], list[tuple[str, int]]] = {}
        for cuid, node_name, sig, cnt in self.builder.dra.release_pod(uid):
            by_claim.setdefault((cuid, node_name), []).append((sig, cnt))
        for (cuid, node_name), charges in by_claim.items():
            nrec = self.cache.nodes.get(node_name)
            if nrec is not None:
                self.builder.apply_external_claim(nrec.row, cuid, charges, -1)
        self._drain_dra_corrections()
        rec = self.cache.pods.get(uid)
        gone = rec.pod if rec is not None else None
        if gone is None:
            qp_gone = self.queue._info.get(uid)
            gone = qp_gone.pod if qp_gone is not None else None
        if gone is not None:
            self._note_claims(gone, -1)
        if rec is not None:
            # A bound gang member leaving drops its gang below quorum for
            # future Permit checks (ADVICE r1: gang_bound never decremented).
            g = rec.pod.spec.pod_group
            if g and rec.bound:
                self._debit_gang(g)
            node_rec = self.cache.nodes.get(rec.node_name)
            self.cache.remove_pod(uid)
            if notify:
                ctx = (
                    self._free_ctx({node_rec.row}) if node_rec is not None else None
                )
                self.queue.on_event(Event.POD_DELETE, ctx)
        else:
            self.queue.delete(uid)

    def add_pdb(self, pdb: t.PodDisruptionBudget) -> None:
        """PodDisruptionBudget informer: preemption counts victims against
        these budgets (pickOneNodeForPreemption criterion 1).  Budgets
        carrying SPEC fields get their status recomputed from live pod
        state by the disruption controller (controllers.py)."""
        self.pdbs[pdb.name] = pdb
        self.disruption_controller.sync_one(pdb)

    def _debit_gang(self, group: str) -> None:
        left = self.gang_bound.get(group, 0) - 1
        if left > 0:
            self.gang_bound[group] = left
        else:
            self.gang_bound.pop(group, None)

    def add_pod_group(self, group: t.PodGroup) -> None:
        """Register a gang (coscheduling-style PodGroup: all-or-nothing
        below minMember).  Members park in the queue's gang pool until the
        gang can reach quorum, then release together into one batch."""
        self.pod_groups[group.name] = group
        self.queue.register_gang(group.name, group.min_member)
        self.queue.on_event(Event.POD_ADD)

    # -- volume objects (PV/PVC/StorageClass/CSINode informers) --------------

    def add_pv(self, pv: t.PersistentVolume) -> None:
        fulfilled = self.builder.volumes.add_pv(pv)
        if fulfilled:
            # The provisioner delivered a claimRef'd PV for an open intent:
            # complete the waiting PreBinds.
            self.notify_prebind({f"pvc:{u}" for u in fulfilled})
        self.queue.on_event(Event.PV_ADD)

    def add_pvc(self, pvc: t.PersistentVolumeClaim) -> None:
        self.builder.volumes.add_pvc(pvc)
        self.queue.on_event(Event.PVC_ADD)

    def add_storage_class(self, sc: t.StorageClass) -> None:
        self.builder.volumes.add_class(sc)
        self.queue.on_event(Event.PVC_ADD)

    def add_resource_claim(self, claim: t.ResourceClaim) -> None:
        """ResourceClaim informer (DRA).  Externally-allocated claims
        charge their node's device row immediately as phantom reservations
        (the claim assume-cache sees status.allocation; without this an
        informer-delivered allocated claim would leave the node's devices
        looking free).  Charges for nodes not yet cached park in
        pending_external — add_node replays them, like CSINode/slices."""
        cat = self.builder.dra
        uid = claim.uid
        deltas = cat.add_claim(claim)
        neg = [(n, sig, cnt) for n, sig, cnt, s in deltas if s < 0]
        pos = [(n, sig, cnt) for n, sig, cnt, s in deltas if s > 0]
        if neg:
            if cat.pending_external.pop(uid, None) is None:
                charged = cat.row_charged.pop(uid, None)
                if charged is not None:
                    rec = self.cache.nodes.get(charged[0][0])
                    if rec is not None:
                        self.builder.apply_external_claim(
                            rec.row, uid,
                            [(sig, cnt) for _n, sig, cnt in charged], -1,
                        )
        if pos:
            rec = self.cache.nodes.get(pos[0][0])  # one node per allocation
            if rec is None:
                cat.pending_external[uid] = pos
            else:
                self.builder.apply_external_claim(
                    rec.row, uid, [(sig, cnt) for _n, sig, cnt in pos], +1
                )
                cat.row_charged[uid] = pos
        self._drain_new_pools()
        self._drain_dra_corrections()
        self.queue.on_event(Event.CLAIM_ADD)

    def _drain_new_pools(self) -> None:
        """Backfill cap AND alloc columns for selector pools registered
        since the last drain (a claim introduced a new (class, selector)
        pool; every cached node publishing that class gets its
        matching-device count, and devices already owned under other pools
        charge the new one)."""
        cat = self.builder.dra
        if not cat.new_pools:
            return
        sigs, cat.new_pools = list(cat.new_pools), []
        for sig in sigs:
            cls, _reqs = cat.pools[sig]
            for (nname, c) in list(cat.slices):
                if c != cls:
                    continue
                rec = self.cache.nodes.get(nname)
                if rec is not None:
                    self.builder.set_pool_cap(rec.row, nname, sig)
                    alloc = cat.new_pool_alloc(nname, sig)
                    if alloc:
                        self.builder.set_pool_alloc(rec.row, sig, alloc)

    def _drain_dra_corrections(self) -> None:
        """Apply queued pool-overlap corrections (ClaimCatalog.corr_events)
        to node rows — allocation named devices that overlap pools beyond
        the claim's request pools (or a deallocation reversed them)."""
        cat = self.builder.dra
        if not cat.corr_events:
            return
        events, cat.corr_events = cat.corr_events, []
        for node_name, charges, sign in events:
            rec = self.cache.nodes.get(node_name)
            if rec is not None:
                self.builder.apply_dra_correction(rec.row, charges, sign)

    def add_resource_slice(self, s: t.ResourceSlice) -> None:
        """ResourceSlice informer (DRA): per-node published device counts."""
        self.builder.dra.add_slice(s)
        rec = self.cache.nodes.get(s.node_name)
        if rec is not None:
            self.builder.set_dra_cap(rec.row, s.node_name, s.device_class)
        self.queue.on_event(Event.CLAIM_ADD)

    def add_csinode(self, csinode: t.CSINode) -> None:
        self.builder.volumes.add_csinode(csinode)
        rec = self.cache.nodes.get(csinode.name)
        if rec is not None:
            self.builder.set_csinode_limits(rec.row, csinode)
        self.queue.on_event(Event.NODE_UPDATE)

    # -- object deletions (the generalized Reflector's DELETED surface) ------
    # A watch DELETED (or a LIST-replace repairing a missed delete) must
    # land for every kind the plugins consume, not just Pod/Node — these
    # are the removal halves of the add_* informer handlers above.

    def remove_pv(self, name: str) -> None:
        vols = self.builder.volumes
        pv = vols.pvs.pop(name, None)
        if pv is None:
            return
        vols.unbound.get(pv.storage_class, {}).pop(name, None)
        vols.epoch += 1

    def remove_pvc(self, uid: str) -> None:
        vols = self.builder.volumes
        pvc = vols.pvcs.pop(uid, None)
        if pvc is None:
            return
        # An open provisioning intent dies with its claim.
        vols.provisioning.pop(uid, None)
        vols.pvc_users.pop(uid, None)
        vols.epoch += 1

    def remove_storage_class(self, name: str) -> None:
        if self.builder.volumes.classes.pop(name, None) is not None:
            self.builder.volumes.epoch += 1

    def remove_csinode(self, name: str) -> None:
        vols = self.builder.volumes
        old = vols.csinodes.pop(name, None)
        if old is None:
            return
        vols.epoch += 1
        rec = self.cache.nodes.get(name)
        if rec is not None:
            # Restore the removed drivers to the no-CSINode default
            # (unlimited — the snapshot's 2^31-1 fill).
            self.builder.set_csinode_limits(
                rec.row,
                t.CSINode(
                    name, {d: 2**31 - 1 for d in old.driver_limits}
                ),
            )
        self.queue.on_event(Event.NODE_UPDATE)

    def remove_pdb(self, name: str) -> None:
        self.pdbs.pop(name, None)

    def remove_resource_claim(self, uid: str) -> None:
        """A deleted claim discharges whatever it held: route a
        deallocated copy through the diffing add path (which reverses
        external row charges and corrections), then drop the object."""
        cat = self.builder.dra
        claim = cat.claims.get(uid)
        if claim is None:
            return
        if claim.allocated_node:
            import dataclasses

            self.add_resource_claim(
                dataclasses.replace(
                    claim,
                    allocated_node="",
                    reserved_for=(),
                    allocated_devices=(),
                )
            )
        cat.claims.pop(uid, None)
        self.queue.on_event(Event.CLAIM_ADD)

    def remove_resource_slice(self, uid: str) -> None:
        """``uid`` is the Reflector's composite "node/device_class" key;
        the node's published capacity for that class drops to zero."""
        node_name, device_class = uid.split("/", 1)
        cat = self.builder.dra
        key = (node_name, device_class)
        if cat.slices.pop(key, None) is None:
            return
        cat.devices.pop(key, None)
        cat.device_owner.pop(key, None)
        cat.epoch += 1
        rec = self.cache.nodes.get(node_name)
        if rec is not None:
            # Caps recompute to 0 over the emptied device set; allocated
            # charges stay until their claims release (upstream drains a
            # slice before deleting it — a dangling allocation is the
            # claim's problem, not the slice informer's).
            self.builder.set_dra_cap(rec.row, node_name, device_class)
        self.queue.on_event(Event.CLAIM_ADD)

    # -- scheduling ------------------------------------------------------------

    def dump_state(self) -> dict:
        """Debugger dump (backend/cache/debugger CacheDumper.DumpAll): per-
        node pod counts, queue depths, gang/nominator state, and the
        host↔device mirror comparison.  The journal key appears only when
        durability is armed — the golden dump fixtures pin the journal-less
        shape."""
        if self.journal is not None:
            base = {"journal": self.journal.stats()}
        else:
            base = {}
        if self.node_lifecycle.armed:
            # Only when the failure-response loop is armed — the golden
            # dump fixtures pin the disarmed shape (like the journal key).
            base["node_lifecycle"] = self.node_lifecycle.stats()
            base["pod_gc"] = self.pod_gc.stats()
            rebound = sum(
                1
                for uid in self._evicted_uids
                if (pr := self.cache.pods.get(uid)) is not None and pr.bound
            )
            base["evictions"] = {
                "total": self.taint_eviction.evictions,
                "evicted_uids": len(self._evicted_uids),
                # Loop closure per pod: evicted uids bound again.
                "rebound": rebound,
            }
        return {
            **base,
            "nodes": {
                name: {
                    "row": rec.row,
                    "pods": sorted(rec.pods),
                    "zone": rec.zone,
                }
                for name, rec in self.cache.nodes.items()
            },
            "pods": {
                uid: {"node": pr.node_name, "assumed": pr.assumed, "bound": pr.bound}
                for uid, pr in self.cache.pods.items()
            },
            "queue": self.queue.dump(),
            "gang_bound": dict(self.gang_bound),
            "nominated": {u: n for u, (n, _d, _p) in self.nominator.items()},
            "permit_waiting": {
                g: [e[0].pod.uid for e in lst]
                for g, lst in self.permit_waiting.items()
            },
            "mirror_equal": self.builder.host_mirror_equal(),
            "metrics": self.metrics.registry.summary(),
            # Slow-cycle span trees (cross-boundary: server-side spans
            # carry the client's trace id) and the recent event ring.
            "slow_spans": list(self.slow_spans),
            "events": self.events.list(limit=50),
        }

    def check_consistency(self) -> None:
        """The cache comparer (debugger/comparer.go): verify the host
        staging arrays and the device mirror agree.  Called every
        ``consistency_check_every`` batches when configured.  Raises (not
        assert — the configured comparer must survive ``python -O``)."""
        if not self.builder.host_mirror_equal():
            raise RuntimeError(
                "host/device mirror divergence — dump_state() for details"
            )

    def rebuild_device_state(self) -> None:
        """Recovery: drop the device mirror and rebuild everything from host
        truth on the next pass (the builder's _dirty_all path).  The restart
        analog of the reference's informer resync (app/server.go:249–271) for
        a live process whose device state is suspect — host staging is the
        authoritative cache, the device tensors are a pure mirror of it."""
        self.builder.invalidate_device()

    def _record_preemption(self, qp: QueuedPodInfo, outcome, res, delta) -> None:
        """Shared PostFilter bookkeeping for a successful preemption
        (prepareCandidate, preemption.go:342): outcome fields, the
        nominator's claim on the freed node, and the immediate retry (the
        reference waits on the victims' graceful deletion; in-process
        deletion is synchronous)."""
        # Write-ahead: the victims' deletions were journaled by delete_pod;
        # this record preserves the NOMINATION so a restart routes the
        # still-pending preemptor back onto its freed node.
        self._journal_append(
            "preempt",
            uid=qp.pod.uid,
            node=res.node_name,
            priority=qp.pod.spec.priority,
            victims=[v.uid for v in res.victims],
        )
        self.metrics.preemptions += 1
        outcome.nominated_node = res.node_name
        outcome.victims = len(res.victims)
        outcome.victim_uids = tuple(v.uid for v in res.victims)
        outcome.victim_names = tuple(
            f"{v.namespace}/{v.name}" for v in res.victims
        )
        self._emit_preempted(qp.pod, res)
        self.nominator[qp.pod.uid] = (
            res.node_name, delta, qp.pod.spec.priority
        )
        qp.nom_pin_failed = False  # fresh nomination: the pin may try again
        self.queue.add(qp.pod)

    def _emit_preempted(self, preemptor: t.Pod, res) -> None:
        """Preempted events on the victims (preemption.go:362 emits on
        each victim pod; the reference's reason is "Preempted")."""
        for v in res.victims:
            self._note_tenant("preempted", v)
            self.recorder.event(
                v.uid, NORMAL, "Preempted",
                f"Preempted by {preemptor.uid} on node {res.node_name}",
                **self._trace_extra(),
            )

    def _fits_now(self, node_name: str, delta: dict) -> bool:
        """Host-truth capacity re-check before INLINE-committing a
        SPECULATIVE preemption result: its dry-run saw the post-scan state,
        so a strict-tail commit landing on the chosen node after dispatch
        could invalidate it (the victims are already evicted from host
        truth when this runs).  A failed check falls back to the
        nominate-and-retry path, which validates itself."""
        rec = self.cache.nodes.get(node_name)
        if rec is None:
            return False
        h = self.builder.host
        row = rec.row
        req = delta["req"]
        free = h["alloc"][row, : req.shape[0]] - h["req"][row, : req.shape[0]]
        if ((req > 0) & (req > free)).any():
            return False
        return h["num_pods"][row] < h["allowed_pods"][row]

    def _can_commit_inline(self, qp: QueuedPodInfo) -> bool:
        """Inline preemptor commit is limited to pods with no Permit group
        and no relevant Reserve plugin — those chains run on the
        nominate-and-retry path, which stays the general route."""
        g, _pl = self._permit_group(qp.pod)
        if g is not None:
            return False
        return not any(rp.relevant(qp.pod, self) for rp in self._reserve_for(qp.pod))

    def _commit_preempted(
        self, qp: QueuedPodInfo, outcome, res, delta, now: float
    ) -> None:
        """Commit a successful preemptor onto its freed node in THIS batch
        (perf mode; see inline_preempt_commit).  The victims were already
        deleted synchronously by preempt_batch, so this is exactly what the
        nominated retry would do next batch — minus a full device pass."""
        self._journal_bind(qp.pod, res.node_name)
        m = self.metrics
        m.preemptions += 1
        self._emit_preempted(qp.pod, res)
        self.cache.assume_pod(
            qp.pod, res.node_name, device_already=False, delta=delta
        )
        # A live nomination from an earlier nominate-path round is spent
        # now (the placed path pops it on assume; a bound pod would leak
        # the claim forever otherwise).
        self.nominator.pop(qp.pod.uid, None)
        qp.pod.spec.node_name = res.node_name
        qp.pod.status.nominated_node_name = ""
        self.cache.finish_binding(qp.pod.uid)
        self.queue.done(qp.pod.uid)
        # NoExecute judgment at bind, after the binding bookkeeping (an
        # immediate eviction deletes the cache entry).
        self.taint_eviction.handle_pod_assigned(qp.pod, res.node_name)
        outcome.node_name = res.node_name
        outcome.nominated_node = res.node_name
        outcome.victims = len(res.victims)
        outcome.victim_uids = tuple(v.uid for v in res.victims)
        outcome.victim_names = tuple(
            f"{v.namespace}/{v.name}" for v in res.victims
        )
        # The failure loop already counted this outcome unschedulable.
        m.unschedulable -= 1
        if m.scheduled == 0:
            m.first_scheduled_ts = now
        m.scheduled += 1
        m.last_scheduled_ts = now
        lat = now - qp.initial_attempt_timestamp
        m.e2e_latency_samples.append(lat)
        m.registry.scheduling_sli.observe(lat)
        self._note_bound(qp.pod, res.node_name)
        self.recorder.event(
            qp.pod.uid, NORMAL, "Scheduled",
            f"Successfully assigned {qp.pod.uid} to {res.node_name} "
            "(inline preemption commit)",
        )

    def _permit_group(self, pod: t.Pod):
        """The (group, owning PermitPlugin) a pod waits under, or
        (None, None) when no registered plugin claims it.  Plugins run only
        for profiles listing them at the permit point (the per-profile
        framework: a profile without the plugin simply lacks it)."""
        from .framework.config import PLUGIN_POINTS

        permitted = (self._profile_for(pod) or self.profile).permit
        for pl in self.permit_plugins:
            name = getattr(pl, "name", None)
            # Only config-addressable plugins are subject to the profile's
            # permit list; programmatically-registered ones (the generic
            # host-plugin surface) always run.
            if name in PLUGIN_POINTS and name not in permitted:
                continue
            g = pl.group_of(pod)
            if g is not None:
                return g, pl
        return None, None

    def _reserve_for(self, pod: t.Pod) -> list:
        """Reserve plugins enabled for the pod's profile (profile.reserve —
        the per-profile Reserve list, types.go Plugins.Reserve).  Plugins
        not addressable from config (no registered name) always run."""
        from .framework.config import PLUGIN_POINTS

        enabled = (self._profile_for(pod) or self.profile).reserve
        return [
            rp for rp in self.reserve_plugins
            if getattr(rp, "name", None) not in PLUGIN_POINTS
            or rp.name in enabled
        ]

    def expire_waiting_gangs(self, timeout_s: float | None = None) -> int:
        """WaitOnPermit timeout: forget and re-park members of groups whose
        missing peers never arrived (framework.go:1503 WaitOnPermit;
        coscheduling's PermitWaitingTimeSeconds).  Each group expires on
        its owning plugin's timeout; the plugin owns the requeue."""
        now = time.monotonic()
        default = self.permit_plugins[0] if self.permit_plugins else None
        expired = []
        for g, since in self.permit_wait_since.items():
            pl = self.permit_wait_owner.get(g, default)
            timeout = pl.timeout_s(self) if timeout_s is None else timeout_s
            if now - since > timeout:
                expired.append((g, pl))
        n = 0
        for g, pl in expired:
            self.permit_wait_since.pop(g, None)
            self.permit_wait_owner.pop(g, None)
            for qp, _node, _s, _f in self.permit_waiting.pop(g, ()):
                self.cache.forget_pod(qp.pod.uid)
                pl.on_rollback(qp, self)
                n += 1
        return n

    def notify_prebind(self, keys) -> list[ScheduleOutcome]:
        """Resolve PreBind wait keys (an informer event satisfied them —
        e.g. the provisioner's PV arrived).  Entries whose last key
        resolves complete their bind.  The outcomes are ALSO queued for the
        next schedule_batch return (outcome-consuming drivers observe
        wait-mode binds there); the returned list is informational."""
        done: list[ScheduleOutcome] = []
        if not self.prebind_waiting:
            return done
        keys = set(keys)
        now = time.monotonic()
        for uid in list(self.prebind_waiting):
            entry = self.prebind_waiting[uid]
            entry["keys"] -= keys
            if entry["keys"]:
                continue
            del self.prebind_waiting[uid]
            done.append(self._complete_prebind(entry, now))
        self._prebind_outcomes.extend(done)
        return done

    def _complete_prebind(self, entry: dict, now: float) -> ScheduleOutcome:
        """The bind tail a parked pod skipped (finish_binding + metrics)."""
        qp = entry["qp"]
        g = entry["g"]
        m = self.metrics
        self._journal_bind(qp.pod, entry["node"])
        qp.pod.spec.node_name = entry["node"]
        self.cache.finish_binding(qp.pod.uid)
        self.taint_eviction.handle_pod_assigned(qp.pod, entry["node"])
        if qp.pod.spec.pod_group:
            self.gang_bound[qp.pod.spec.pod_group] = (
                self.gang_bound.get(qp.pod.spec.pod_group, 0) + 1
            )
        if g:
            # Group-mates still waiting?  This bind stays revocable until
            # the whole group lands (all-or-nothing gang contract).
            if any(e["g"] == g for e in self.prebind_waiting.values()):
                self.prebind_done_pending.setdefault(g, []).append(
                    {"qp": qp, "undos": entry["undos"], "node": entry["node"]}
                )
            else:
                self.prebind_done_pending.pop(g, None)
        if m.scheduled == 0:
            m.first_scheduled_ts = now
        m.scheduled += 1
        m.last_scheduled_ts = now
        lat = now - qp.initial_attempt_timestamp
        m.e2e_latency_samples.append(lat)
        m.registry.scheduling_sli.observe(lat)
        self._note_bound(qp.pod, entry["node"])
        self.recorder.event(
            qp.pod.uid, NORMAL, "Scheduled",
            f"Successfully assigned {qp.pod.uid} to {entry['node']} "
            "(PreBind wait completed)",
        )
        return ScheduleOutcome(
            qp.pod, entry["node"], entry["score"], entry["feasn"]
        )

    def _unwind_reserved(self, uid: str, undos, was_bound: bool) -> None:
        """Revert a pod's Reserve chain + cache assume (the shared unwind of
        the PreBind-timeout paths).  ``was_bound`` keeps the throughput
        metrics honest: a finalized bind that reverts post-batch leaves
        ``scheduled``."""
        for rp, u in reversed(undos):
            rp.unreserve(u, self)
        if uid in self.cache.pods:
            self.cache.forget_pod(uid)
        m = self.metrics
        if was_bound:
            m.scheduled -= 1
        m.unschedulable += 1

    def expire_waiting_prebinds(self, timeout_s: float | None = None) -> int:
        """Time out PreBind waits (the bindTimeout unwind: Unreserve +
        requeue, volume_binding.go PreBind error path).  A gang member's
        timeout rolls its whole group back — the gang contract is
        all-or-nothing, so batch-mates bound immediately AND members whose
        own waits already completed (prebind_done_pending) revert like a
        lost PV race."""
        now = time.monotonic()
        limit = self.prebind_timeout_s if timeout_s is None else timeout_s
        n = 0
        for uid in [
            u for u, e in self.prebind_waiting.items()
            if now - e["since"] > limit
        ]:
            entry = self.prebind_waiting.pop(uid, None)
            if entry is None:
                continue  # a mate's rollback already consumed it
            n += 1
            self._unwind_reserved(uid, entry["undos"], was_bound=False)
            qp, g, gpl = entry["qp"], entry["g"], entry["gpl"]
            if g:
                gpl.on_rollback(qp, self)
                for qp2, _out2, undos2 in entry["mates"]:
                    self._unwind_reserved(qp2.pod.uid, undos2, was_bound=True)
                    qp2.pod.spec.node_name = None
                    self._debit_gang(g)
                    gpl.on_rollback(qp2, self)
                # Fellow parked members of the SAME group revert too.
                for uid2 in [
                    u for u, e in self.prebind_waiting.items() if e["g"] == g
                ]:
                    e2 = self.prebind_waiting.pop(uid2)
                    self._unwind_reserved(uid2, e2["undos"], was_bound=False)
                    gpl.on_rollback(e2["qp"], self)
                # Members whose own provisioning completed while the group
                # was still pending revert with it.
                for d in self.prebind_done_pending.pop(g, ()):
                    qp3 = d["qp"]
                    self._unwind_reserved(
                        qp3.pod.uid, d["undos"], was_bound=True
                    )
                    qp3.pod.spec.node_name = None
                    self._debit_gang(g)
                    gpl.on_rollback(qp3, self)
                self.queue.readmit_gang(g)
            else:
                # done() dropped the queue's info entry when the pod
                # parked — restore_backoff re-owns it.
                self.queue.restore_backoff(qp)
        return n

    def _profile_for(self, pod: t.Pod) -> Profile | None:
        """frameworkForPod (schedule_one.go:379): exact schedulerName match;
        an UNSET name (the API default "default-scheduler") falls to the
        default profile, any other unknown name is not our pod."""
        p = self.profiles.get(pod.spec.scheduler_name)
        if p is not None:
            return p
        if pod.spec.scheduler_name == "default-scheduler":
            return self.profile
        return None

    def _schedule_one_extender(self, qp: QueuedPodInfo) -> ScheduleOutcome:
        """One reference scheduling cycle with an extender chain: eval-only
        device pass → host extender filter/prioritize → host selectHost →
        assume → Reserve plugins → bind (findNodesThatPassExtenders,
        schedule_one.go:704; prioritizeNodes, :799).  Unschedulable pods
        run PostFilter preemption with extender ProcessPreemption veto
        (schedule_one.go:749); gang Permit semantics remain batch-path
        only (an extender profile schedules pod-at-a-time)."""
        from .extender import run_extender_chain

        profile = self._profile_for(qp.pod) or self.profile
        m = self.metrics
        m.schedule_attempts += 1
        m.batches += 1
        t0 = time.perf_counter()
        # Resolve the pod's own nomination to a row (like _inject_nomrows)
        # — only worth the lookup when any nominated claims exist.
        nomrow = self._resolve_nomrow(qp.pod) if self.nominator else -1
        batch, deltas, active, inv, feasible, total, t1 = self._run_eval_pass(
            qp.pod, profile, nomrow
        )
        m.featurize_time_s += t1 - t0
        m.device_time_s += time.perf_counter() - t1
        rows = np.nonzero(feasible)[0]
        names = [self.cache.node_name_at_row(int(r)) for r in rows]
        scores = {nm: int(total[r]) for nm, r in zip(names, rows)}
        now = time.monotonic()
        try:
            nodes, combined, _unres = run_extender_chain(
                self.extenders, qp.pod, names, scores
            )
        except Exception:
            # A non-ignorable extender failed: a cycle ERROR, not pod-level
            # unschedulability — retry on a timer (handleSchedulingFailure).
            self.queue.add_backoff(qp)
            m.unschedulable += 1
            return ScheduleOutcome(qp.pod, None, 0, len(names))
        if not nodes:
            m.unschedulable += 1
            # Extender rejections requeue on any event (schedule_one.go:528).
            plugins = {"Extender"} if names else set(profile.filters)
            self.recorder.event(
                qp.pod.uid, WARNING, "FailedScheduling",
                f"0/{self.cache.node_count()} nodes available: rejected by "
                + ", ".join(sorted(plugins)),
                plugins=sorted(plugins),
                **self._trace_extra(),
            )
            qp.delta = deltas[0]
            outcome = ScheduleOutcome(
                qp.pod, None, 0, len(names),
                diagnosis=Diagnosis(unschedulable_plugins=plugins),
            )
            # PostFilter (schedule_one.go:749): extender profiles run
            # preemption too; extenders with a preempt verb veto the chosen
            # candidate (ProcessPreemption, preemption.go:249).
            if (
                self.preemption is not None
                and "DefaultPreemption" in profile.post_filter
            ):
                rows = {
                    k: [np.asarray(v)[0]] for k, v in batch.items() if k != "valid"
                }
                preempt_exts = [
                    ex
                    for ex in self.extenders
                    if getattr(ex, "supports_preemption", False)
                    and ex.is_interested(qp.pod)
                ]

                def _ext_ok(pod, node_name, victims) -> bool:
                    want = {v.uid for v in victims}
                    for ex in preempt_exts:
                        try:
                            kept = ex.process_preemption(
                                pod, {node_name: victims}
                            )
                        except Exception:
                            if ex.ignorable:
                                continue
                            return False
                        # The engine picked a MINIMAL victim set: the node
                        # survives only if the extender keeps all of it.
                        if node_name not in kept or set(
                            kept[node_name]
                        ) != want:
                            return False
                    return True

                res = self.preemption.preempt_batch(
                    [qp.pod], rows, active, inv, profile=profile,
                    candidate_filter=_ext_ok if preempt_exts else None,
                )[0]
                # A zero-victim "candidate" here means the node was already
                # engine-feasible and only the EXTENDER rejected it — a
                # retry would hot-loop against the same rejection, so only
                # an eviction counts as progress on this path.
                if res is not None and res.victims:
                    self._record_preemption(qp, outcome, res, deltas[0])
                    if res.node_name in self.cache.nodes:
                        freed = {self.cache.nodes[res.node_name].row}
                        self.queue.on_event(
                            Event.POD_DELETE, self._free_ctx(freed)
                        )
                    return outcome
            self.queue.add_unschedulable(qp, plugins)
            return outcome
        best = max(enumerate(nodes), key=lambda p: (combined[p[1]], -p[0]))[1]
        self.cache.assume_pod(qp.pod, best, device_already=False, delta=deltas[0])

        def _fail_bind(undos):
            for rp2, u2 in reversed(undos):
                rp2.unreserve(u2, self)
            self.cache.forget_pod(qp.pod.uid)
            self.queue.add_backoff(qp)
            m.unschedulable += 1
            return ScheduleOutcome(qp.pod, None, 0, len(nodes))

        # Reserve through the same plugin chain the batch path runs.
        undos: list = []
        for rp in self._reserve_for(qp.pod):
            if not rp.relevant(qp.pod, self):
                continue
            u = rp.reserve(qp.pod, best, self)
            if u is None:
                return _fail_bind(undos)
            undos.append((rp, u))
        binder = next((ex for ex in self.extenders if getattr(ex, "bind_verb", "")), None)
        if binder is not None and not binder.bind(qp.pod, best):
            return _fail_bind(undos)
        self._journal_bind(qp.pod, best)
        qp.pod.spec.node_name = best
        self.cache.finish_binding(qp.pod.uid)
        self.taint_eviction.handle_pod_assigned(qp.pod, best)
        self.queue.done(qp.pod.uid)
        if m.scheduled == 0:
            m.first_scheduled_ts = now
        m.scheduled += 1
        m.last_scheduled_ts = now
        m.e2e_latency_samples.append(now - qp.initial_attempt_timestamp)
        self._note_bound(qp.pod, best)
        self.recorder.event(
            qp.pod.uid, NORMAL, "Scheduled",
            f"Successfully assigned {qp.pod.uid} to {best}",
        )
        if (
            self.consistency_check_every
            and m.batches % self.consistency_check_every == 0
        ):
            self.check_consistency()
        return ScheduleOutcome(qp.pod, best, combined[best], len(nodes))

    # -- fleet protocol surface (fleet/owner.py) ---------------------------
    #
    # A shard owner schedules pods it does not own end to end: the router
    # scatter-gathers per-shard PROPOSALS (eval-only per-node verdicts),
    # makes the global selectHost decision itself, and commits on the
    # winning shard — so an N-shard fleet reproduces the single
    # scheduler's choice whenever per-node scores are shard-independent
    # (trivially true for the filter-only golden profile; score ops that
    # normalize over the candidate set trade this for partition locality,
    # the Tesserae compromise documented in fleet/router.py).

    def _resolve_nomrow(self, pod: t.Pod) -> int:
        """The pod's own nominated node as a snapshot row (-1 when unset
        or unknown) — without it, a retrying preemptor's nominated claim
        in the fit overlay makes its freed node look full to itself."""
        nn = pod.status.nominated_node_name
        if nn:
            rec_n = self.cache.nodes.get(nn)
            if rec_n is not None:
                return rec_n.row
        return -1

    def _run_eval_pass(self, pod: t.Pod, profile, nomrow: int):
        """One-pod eval-only device pass (build_eval_pass, cached per
        (profile, schema, res_col, active)): featurize, run, fetch.
        Shared by the extender path (_schedule_one_extender) and the
        fleet propose path so the cache key and nomination handling
        cannot drift apart.  Returns (batch, deltas, active, inv,
        feasible, total, t_featurized) — the timestamp splits featurize
        from device time for the callers that meter them."""
        from .engine.pass_ import build_eval_pass

        if self._predispatched is None:
            # the propose path never runs schedule_batch: its claims'
            # rows settle here (reserved sharers: reserve_proposed)
            self._settle_csi_claims()
        batch, deltas, active = build_pod_batch(
            [pod], self.builder, profile, 1
        )
        inv = self._full_inv()
        t_feat = time.perf_counter()
        state = self.builder.state()
        key = (
            profile, self.builder.schema,
            tuple(sorted(self.builder.res_col.items())), active,
        )
        run = self._eval_passes.get(key)
        if run is None:
            run = build_eval_pass(
                profile, self.builder.schema, self.builder.res_col, active
            )
            self._eval_passes[key] = run
        pf = {k: np.asarray(v)[0] for k, v in batch.items() if k != "valid"}
        pf["nominated_row"] = np.int32(nomrow)
        feasible, total = device_fetch(run(state, pf, inv))
        self._dispatch_counter.inc(kind="eval")
        return batch, deltas, active, inv, feasible, total, t_feat

    def propose_pod(self, pod: t.Pod, span: Trace | None = None) -> dict:
        """Eval-only proposal: this shard's per-node verdicts for one pod
        — feasible node names (snapshot row order), their total scores,
        and the pod's resolved nomination when locally feasible.  No
        commit, no queue interaction; the same compiled eval pass the
        extender path uses (_run_eval_pass).  ``span`` (the fleet op
        span the router's trace context opened) gains Featurize /
        DevicePass children — the sidecar leg of the joined
        router→owner→sidecar tree — and the result carries the
        feat_s/dev_s split for the owner's flight record."""
        if not self.cache.nodes:
            return {"feasible": [], "scores": [], "nominated": None}
        profile = self._profile_for(pod) or self.profile
        nomrow = self._resolve_nomrow(pod)
        t0 = time.perf_counter()
        batch, _deltas, _active, _inv, feasible, total, t_feat = (
            self._run_eval_pass(pod, profile, nomrow)
        )
        t_end = time.perf_counter()
        if span is not None:
            # Post-hoc children over the measured boundaries: the eval
            # pass ran featurize then the device program; the sub-spans
            # carry those exact windows.
            feat = span.nest("Featurize")
            feat._t0, feat._t_end = t0, t_feat
            dev = span.nest("DevicePass")
            dev._t0, dev._t_end = t_feat, t_end
        rows = np.nonzero(feasible)[0]
        names = [self.cache.node_name_at_row(int(r)) for r in rows]
        nn = pod.status.nominated_node_name
        return {
            "feasible": names,
            "scores": [int(total[r]) for r in rows],
            "nominated": nn if nomrow >= 0 and bool(feasible[nomrow]) else None,
            # The pod's featurized request vector — the router's queue
            # needs it for the precise fit-wake hint (queue._fit_hint),
            # which the single scheduler gets from its own deltas.
            "req": [int(x) for x in np.asarray(batch["req"])[0]],
            # The featurize/device wall split, for the owner's per-op
            # flight record (phase attribution in the merged fleet
            # timeline; wall-derived — never hashed).
            "feat_s": round(t_feat - t0, 6),
            "dev_s": round(t_end - t_feat, 6),
        }

    # -- decision provenance (framework/provenance.py) ---------------------

    def arm_provenance(self, capacity: int = 4096) -> None:
        """Start recording decision capsules (explain-this-binding).
        Idempotent; OFF by default — unarmed runs pay one `is not None`
        test per bind and stay byte-identical."""
        if self.provenance is None:
            from .framework.provenance import ProvenanceRing

            self.provenance = ProvenanceRing(capacity)

    def _tie_step_of(self, i, ctx, batch) -> int:
        """The device tie-break step for batch slot ``i`` — cycle base
        plus the slot's step offset, the exact value select_and_commit
        hashed.  -1 on the pinned fast path (no per-step scan seed)."""
        soff = batch.get("step_offset")
        if soff is None:
            return -1
        return (
            int(ctx.get("cycle0", 0)) + int(np.asarray(soff)[i])
        ) & 0xFFFFFFFF

    def _provenance_capture(
        self, uid, node_name, row, i, ctx, batch, scores, feas, fails, profile
    ) -> None:
        """Record one live decision into the armed ring — called from the
        commit path only when arm_provenance() ran."""
        from .framework.provenance import DecisionCapsule

        tie_step = self._tie_step_of(i, ctx, batch)
        cap = DecisionCapsule(
            uid=uid,
            node=node_name,
            row=int(row),
            score=int(scores[i]),
            feasn=int(feas[i]),
            fail_mask=int(fails[i]),
            tie_step=tie_step,
            profile=profile.name,
            nomrow=int(ctx["nomrow"][i]),
            kind="pinned" if ctx.get("pinned") else "batch",
        )
        cap.preemption = self.provenance.take_pending_preemption(uid)
        self.provenance.record(cap)

    def _run_attribution_pass(self, pod: t.Pod, profile, nomrow: int):
        """One-pod attribution pass (build_attribution_pass, cached like
        _eval_passes): featurize, run, fetch.  Returns (active, ok_cols
        (F,N), feasible (N,), score_cols (S,N), total (N,))."""
        from .engine.pass_ import build_attribution_pass

        batch, _deltas, active = build_pod_batch(
            [pod], self.builder, profile, 1
        )
        inv = self._full_inv()
        state = self.builder.state()
        key = (
            profile, self.builder.schema,
            tuple(sorted(self.builder.res_col.items())), active,
        )
        run = self._attr_passes.get(key)
        if run is None:
            run = build_attribution_pass(
                profile, self.builder.schema, self.builder.res_col, active
            )
            self._attr_passes[key] = run
        pf = {k: np.asarray(v)[0] for k, v in batch.items() if k != "valid"}
        pf["nominated_row"] = np.int32(nomrow)
        ok_cols, feasible, score_cols, total = device_fetch(
            run(state, pf, inv)
        )
        self._dispatch_counter.inc(kind="eval")
        return active, ok_cols, feasible, score_cols, total

    def _provenance_sibling(self) -> "TPUScheduler":
        """A fresh, journal-less scheduler with this one's compiled-pass
        configuration — the reconstruction target for journal-mode
        explain.  The sibling never schedules; it only holds replayed
        state for the attribution pass."""
        return type(self)(
            profile=self.profile,
            batch_size=self.batch_size,
            chunk_size=self.chunk_size,
            profiles=[
                p
                for n, p in sorted(self.profiles.items())
                if n != self.profile.name
            ],
            feature_gates=self.feature_gates,
            enable_preemption=self.preemption is not None,
        )

    def explain_pod(
        self,
        uid: str,
        seq: int | None = None,
        mode: str | None = None,
        pod: t.Pod | None = None,
    ) -> dict:
        """The structured decision record for one pod: re-run its
        Filter+Score through the attribution pass against the CURRENT
        store, or (``mode="journal"``, or automatically when the armed
        ring recorded the bind's journal seq) against a journal-
        reconstructed store as of just before its bind record — per-op
        per-node filter verdicts with the rejecting plugin named, per-op
        normalized score columns, the selectHost tie-break trace, and
        the recorded live decision when provenance was armed.  Read
        path only: nothing commits, no queue state moves."""
        from .engine.pass_ import filter_op_names, score_op_names
        from .framework import provenance as prov

        cap = self.provenance.get(uid) if self.provenance is not None else None
        # Local pod wins over a caller-supplied one (fleet scatter passes
        # ``pod=`` so a shard that never saw the pod can still attribute
        # it against its partition of nodes).
        pr = self.cache.pods.get(uid)
        if pr is not None:
            pod = pr.pod
        else:
            qp = self.queue._info.get(uid)
            if qp is not None:
                pod = qp.pod
        if pod is None:
            return {"uid": uid, "error": "unknown pod (not bound, not queued)"}
        upto = None
        if seq is not None and seq > 0:
            upto = seq - 1
            # An explicit seq targets ONE decision; a ring capsule
            # stamped with a different seq describes another (newer)
            # bind of this uid and must not color this record.
            if cap is not None and cap.seq is not None and cap.seq != seq:
                cap = None
        elif (
            mode != "current"
            and cap is not None
            and cap.seq is not None
            and self.journal is not None
        ):
            upto = cap.seq - 1
        if mode == "journal" and upto is None:
            return {
                "uid": uid,
                "error": (
                    "journal mode needs a journaled, provenance-recorded "
                    "bind (or an explicit seq)"
                ),
            }
        target, used_mode, notes = self, "current", []
        wal_tie: int | None = None
        from .api import serialize

        if upto is not None and self.journal is not None:
            from . import journal as journal_mod

            sib = self._provenance_sibling()
            try:
                journal_mod.reconstruct_at(sib, self.journal, upto)
                target, used_mode = sib, "journal"
                # The bind record (seq upto+1) serialized the pod BEFORE
                # spec.node_name was stamped — that pre-bind pod is what
                # the device actually featurized — and carries the tie-
                # break step, so the selectHost trace is exact without
                # an armed ring.
                for rec_j in self.journal.replay(count=False)[1]:
                    if (
                        rec_j["q"] == upto + 1
                        and rec_j["t"] == "bind"
                        and rec_j["d"].get("uid") == uid
                    ):
                        pod = serialize.pod_from_data(rec_j["d"]["pod"])
                        wal_tie = rec_j["d"].get("tie")
                        break
            except ValueError as exc:
                # The snapshot barrier passed the bind seq: the WAL
                # prefix is gone — degrade to the current store, loudly.
                used_mode = "current"
                notes.append(f"reconstruction unavailable: {exc}")
        if used_mode == "current" and pr is not None:
            # Already placed: re-filtering the live pod would pin
            # NodeName to its bound node and double-count its own
            # committed usage.  Strip the binding on a copy; the
            # verdicts still include the pod's own resources.
            pod = serialize.pod_from_data(serialize.to_dict(pod))
            pod.spec.node_name = ""
            notes.append(
                "pod already placed: current-mode verdicts include its "
                "own committed usage (use journal mode for bit-identity)"
            )
        profile = self._profile_for(pod) or self.profile
        # A surviving capsule describes THIS decision (a mismatched-seq
        # one was dropped above), so its recorded nomination row wins —
        # the reconstructed store resolves nominations as of the replay
        # point, not as the device saw them at decision time.
        if used_mode == "journal" and cap is not None:
            nomrow = cap.nomrow
        else:
            nomrow = target._resolve_nomrow(pod)
        if not target.cache.nodes:
            return {"uid": uid, "mode": used_mode, "error": "no nodes"}
        active, ok_cols, feasible, score_cols, total = (
            target._run_attribution_pass(pod, profile, nomrow)
        )
        # Trim the schema's padding rows: real nodes only, row order
        # preserved (padding rows are never feasible, so the kth-tie
        # cumsum over the filtered arrays is unchanged).
        rows = [
            r
            for r in range(int(np.asarray(total).shape[0]))
            if target.cache.node_name_at_row(r) is not None
        ]
        names = [target.cache.node_name_at_row(r) for r in rows]
        idx = np.asarray(rows, np.int64)
        pos_of = {r: p for p, r in enumerate(rows)}
        ok_f = (
            np.asarray(ok_cols)[:, idx]
            if np.asarray(ok_cols).size
            else np.zeros((0, len(rows)), bool)
        )
        sc_f = (
            np.asarray(score_cols)[:, idx]
            if np.asarray(score_cols).size
            else np.zeros((0, len(rows)), np.int64)
        )
        rec = prov.assemble_record(
            uid=uid,
            mode=used_mode,
            profile=profile,
            active=active,
            node_names=names,
            filter_names=filter_op_names(profile, active),
            score_ops=score_op_names(profile, active),
            ok_cols=ok_f,
            feasible=np.asarray(feasible)[idx],
            score_cols=sc_f,
            total=np.asarray(total)[idx],
            nomrow=pos_of.get(int(nomrow), -1),
            capsule=cap,
            truncated=self._truncated,
            tie_step=wal_tie,
        )
        rec["bound_node"] = pr.node_name if pr is not None else None
        if self.provenance is None:
            notes.append(
                "provenance unarmed: no recorded live decision; "
                "tie step recovered from the bind WAL record"
                if wal_tie is not None
                else "provenance unarmed: no recorded live decision; "
                "tie-break trace degrades to kth=0"
            )
        if notes:
            rec["note"] = "; ".join(notes)
        return rec

    def reserve_proposed(self, pod: t.Pod, node_name: str, gang: str = "") -> bool:
        """Phase 1 of the fleet's two-phase commit: assume the pod onto
        the node and run the Reserve chain, journaling a ``gang_reserve``
        INTENT first — a crash between phases leaves the intent without a
        bind record, which recovery resolves as presumed-abort (the
        assume was never durable truth).  Returns False (fully unwound)
        when a Reserve plugin refuses."""
        self._journal_append(
            "gang_reserve", uid=pod.uid, node=node_name, gang=gang
        )
        delta = self.builder.pod_delta_vectors(pod)
        self._note_claims(pod, +1)  # a foreign pod is known from here on
        self.cache.assume_pod(pod, node_name, device_already=False, delta=delta)
        undos: list = []
        for rp in self._reserve_for(pod):
            if not rp.relevant(pod, self):
                continue
            u = rp.reserve(pod, node_name, self)
            if u is None:
                for rp2, u2 in reversed(undos):
                    rp2.unreserve(u2, self)
                self.cache.forget_pod(pod.uid)
                self._note_claims(pod, -1)
                return False
            undos.append((rp, u))
        self._fleet_reserved[pod.uid] = {
            "pod": pod, "node": node_name, "undos": undos, "gang": gang,
        }
        return True

    def abort_reserved(self, uid: str) -> None:
        """2PC abort: unwind the Reserve chain and forget the assume.
        Journaled (``gang_abort``) so replay distinguishes a resolved
        intent from a crash-orphaned one — either way nothing durable
        was applied, so replay applies nothing."""
        entry = self._fleet_reserved.pop(uid, None)
        if entry is None:
            return
        self._journal_append("gang_abort", uid=uid, gang=entry["gang"])
        for rp, u in reversed(entry["undos"]):
            rp.unreserve(u, self)
        self.cache.forget_pod(uid)
        self._note_claims(entry["pod"], -1)

    def commit_reserved(self, uid: str) -> ScheduleOutcome | None:
        """Phase 2: the binding becomes durable truth — journal the bind
        record, then finish the binding (WAL journal-before-apply)."""
        entry = self._fleet_reserved.pop(uid, None)
        if entry is None:
            return None
        pod, node_name = entry["pod"], entry["node"]
        self._journal_bind(pod, node_name)
        self.nominator.pop(pod.uid, None)
        pod.spec.node_name = node_name
        pod.status.nominated_node_name = ""
        self.cache.finish_binding(pod.uid)
        self.taint_eviction.handle_pod_assigned(pod, node_name)
        g = pod.spec.pod_group
        if g:
            self.gang_bound[g] = self.gang_bound.get(g, 0) + 1
        m = self.metrics
        now = time.monotonic()
        if m.scheduled == 0:
            m.first_scheduled_ts = now
        m.scheduled += 1
        m.last_scheduled_ts = now
        self._note_bound(pod, node_name)
        self.recorder.event(
            pod.uid, NORMAL, "Scheduled",
            f"Successfully assigned {pod.uid} to {node_name}",
        )
        # One fleet commit ≈ one reference scheduling cycle (the extender
        # path counts the same way).  The checkpoint's gate is consulted
        # here, or a fleet owner's WAL would grow forever — the router
        # never drives schedule_batch, so the batch-loop call site can't
        # fire.
        self.metrics.batches += 1
        self.maybe_snapshot()
        return ScheduleOutcome(pod, node_name)

    def commit_proposed(self, pod: t.Pod, node_name: str) -> ScheduleOutcome | None:
        """One-phase commit for a routed singleton pod (no gang): reserve
        + immediate commit, the fleet analog of the extender path's bind
        tail."""
        self.metrics.schedule_attempts += 1
        if not self.reserve_proposed(pod, node_name):
            self.metrics.unschedulable += 1
            return None
        return self.commit_reserved(pod.uid)

    def preempt_propose(self, pod: t.Pod) -> dict | None:
        """Dry-run preemption for a foreign pod against THIS shard's
        nodes: the best local candidate (node + victim identities +
        the pickOneNode comparison key material) or None.  Nothing is
        applied — the router compares candidates across shards and calls
        execute_preemption on the winner only."""
        if self.preemption is None or not self.cache.nodes:
            return None
        profile = self._profile_for(pod) or self.profile
        batch, _deltas, active = build_pod_batch([pod], self.builder, profile, 1)
        rows = {k: [np.asarray(v)[0]] for k, v in batch.items() if k != "valid"}
        res = self.preemption.preempt_batch(
            [pod], rows, active, self._full_inv(), profile=profile,
            dry_run=True,
        )[0]
        if res is None:
            return None
        return {
            "node": res.node_name,
            "victims": [
                {
                    "uid": v.uid,
                    "name": f"{v.namespace}/{v.name}",
                    "priority": v.spec.priority,
                    "start_time": v.status.start_time,
                    "pod_group": v.spec.pod_group,
                }
                for v in res.victims
            ],
            # pickOneNodeForPreemption's lexicographic key over THIS
            # candidate (preemption.py eval_one, chunk==1 branch), so the
            # router's cross-shard arbitration reproduces the global
            # pick: per-shard minimization then a key compare across the
            # shard winners equals one global minimization, because every
            # criterion is a per-candidate property.
            "key": self._preempt_key(res.victims),
        }

    def _preempt_key(self, victims) -> list[int]:
        """[pdb violations, max victim priority, priority sum, victim
        count, negated-earliest-start] — ascending-lexicographic, exactly
        the device's chunk==1 narrowing order (latest earliest-start
        among the HIGHEST-priority victims wins, in microseconds)."""
        violations = 0
        for pdb in self.pdbs.values():
            cnt = sum(
                1
                for v in victims
                if v.namespace == pdb.namespace
                and t.label_selector_matches(pdb.selector, v.metadata.labels)
            )
            violations += max(0, cnt - pdb.disruptions_allowed)
        prios = [v.spec.priority for v in victims]
        max_prio = max(prios) if prios else -1
        starts = [
            v.status.start_time
            for v in victims
            if v.spec.priority == max_prio and v.status.start_time is not None
        ]
        if starts:
            start_key = int(-min(starts) * 1e6)
        else:
            start_key = -(2**61)
        return [violations, max_prio, sum(prios), len(victims), start_key]

    def execute_preemption(
        self, pod: t.Pod, node_name: str, victim_uids: list[str]
    ) -> dict:
        """Apply a chosen preemption on THIS shard (the victim owner's
        half of the cross-shard protocol): delete the victims (each
        deletion write-ahead journaled by delete_pod), debit PDB budgets,
        journal the preemptor's NOMINATION claim, and protect the freed
        node in the fit overlay so a same-round pod cannot steal it."""
        victims = []
        for uid in victim_uids:
            pr = self.cache.pods.get(uid)
            if pr is not None:
                victims.append(pr.pod)
        debits: dict[str, int] = {}
        if self.provenance is not None and victims:
            # Rationale BEFORE the deletes: _preempt_key reads the PDB
            # budgets the loop below debits.
            self.provenance.note_preemption(
                pod.uid,
                {
                    "node": node_name,
                    "victims": [v.uid for v in victims],
                    "key": self._preempt_key(victims),
                },
            )
        for vic in victims:
            self.delete_pod(vic.uid, notify=False)
            for name, n in self.debit_matching_pdbs(vic).items():
                debits[name] = debits.get(name, 0) + n
        self._journal_append(
            "preempt",
            uid=pod.uid,
            node=node_name,
            priority=pod.spec.priority,
            victims=[v.uid for v in victims],
        )
        self.metrics.preemptions += 1
        pod.status.nominated_node_name = node_name
        self.nominator[pod.uid] = (
            node_name,
            self.builder.pod_delta_vectors(pod),
            pod.spec.priority,
        )
        rec = self.cache.nodes.get(node_name)
        if rec is not None:
            self.queue.on_event(Event.POD_DELETE, self._free_ctx({rec.row}))
        for v in victims:
            self._note_tenant("preempted", v)
            self.recorder.event(
                v.uid, NORMAL, "Preempted",
                f"Preempted by {pod.uid} on node {node_name}",
            )
        return {
            "node": node_name,
            "victims": [v.uid for v in victims],
            # Evicted gang members: the router debits its FLEET-wide
            # quorum credit (the local _debit_gang ran inside delete_pod).
            "victim_groups": [
                v.spec.pod_group for v in victims if v.spec.pod_group
            ],
            # Raw victim tenant ids — the router feeds them through ITS
            # bounded labeler into the fleet-aggregated preempted counter
            # (the victim pods live only on this shard).
            "victim_tenants": [pod_tenant(v) or "" for v in victims],
            # PDB state is cluster-global but budgets are debited where
            # the victim died — the router broadcasts these to the other
            # shards (apply_pdb_debit) so every owner's pickOneNode
            # violation counts match the single scheduler's.
            "pdb_debits": [{"name": n, "n": c} for n, c in sorted(debits.items())],
            # Freed capacity on the victims' node, nominated claims
            # already subtracted — the router's POD_DELETE wake hint.
            "freed": self.fleet_free_ctx([node_name]),
        }

    def debit_matching_pdbs(self, pod: t.Pod) -> dict[str, int]:
        """Debit every budget matching ``pod`` by one disruption and
        return {pdb name: debit} — the single accounting shared by the
        preemption path (execute_preemption) and the fleet owner's
        eviction path (fleet/owner.py _on_eviction); the router
        broadcasts the returned debits to the other shards."""
        debits: dict[str, int] = {}
        for pdb in self.pdbs.values():
            if pod.namespace == pdb.namespace and t.label_selector_matches(
                pdb.selector, pod.metadata.labels
            ):
                pdb.disruptions_allowed -= 1
                debits[pdb.name] = debits.get(pdb.name, 0) + 1
        return debits

    def apply_pdb_debit(self, name: str, n: int) -> None:
        """Mirror a foreign shard's preemption debit on the local PDB copy
        (the router broadcasts execute_preemption's pdb_debits)."""
        pdb = self.pdbs.get(name)
        if pdb is not None:
            pdb.disruptions_allowed -= n

    def fleet_free_ctx(self, node_names: list[str]) -> dict | None:
        """JSON-able free-capacity summary of the named nodes (the
        EventCtx payload, queue.py) — the router rebuilds an EventCtx from
        it to drive ITS queue's precise fit-wake hints, since only the
        owning shard can see the node's host arrays."""
        rows = {
            self.cache.nodes[nm].row
            for nm in node_names
            if nm in self.cache.nodes
        }
        if not rows:
            return None
        ctx = self._free_ctx(rows)
        return {
            "max_free": [int(x) for x in ctx.max_free],
            "max_slots": int(ctx.max_slots),
        }

    def _dom_placeholder(self) -> tuple:
        """Schema-shaped zero (group_dom, et_dom) arrays for rebuild-path
        dispatches — the compiled pass takes the carry operands either way
        (ONE program; the cond picks rebuild when dom_valid is False)."""
        s = self.builder.schema
        key = (s.G, s.TK, s.DV, s.ET)
        ph = self._dom_zeros.get(key)
        if ph is None:
            if len(self._dom_zeros) > 4:
                self._dom_zeros.clear()
            ph = (
                jnp.zeros((s.G, s.TK, s.DV), jnp.float32),
                jnp.zeros((s.ET, s.DV), jnp.float32),
            )
            self._dom_zeros[key] = ph
        return ph

    def _full_inv(self) -> dict:
        """Batch invariants, plus — in truncated (parity) mode only — the
        scan-order inputs (zone-interleaved positions, rotating start); the
        full-evaluation pass never reads them, so skip the O(N) rebuild.
        Always carries the nominated-pod overlay (zeros when empty, so the
        compiled program never changes shape)."""
        inv = self.builder.batch_invariants()
        if self._truncated:
            inv["order_pos"] = self.cache.order_pos(self.builder.schema.N)
            inv["scan_start"] = np.uint32(self._next_start)
        s = self.builder.schema
        nom_req = np.zeros((s.N, s.R), np.int64)
        nom_cnt = np.zeros(s.N, np.int32)
        nom_prio = np.full(s.N, -(2**31), np.int32)
        for _uid, (node_name, delta, prio) in self.nominator.items():
            rec = self.cache.nodes.get(node_name)
            if rec is None:
                continue
            d = delta["req"]
            nom_req[rec.row, : d.shape[0]] += d
            nom_cnt[rec.row] += 1
            nom_prio[rec.row] = max(nom_prio[rec.row], prio)
        inv["nom_req"], inv["nom_cnt"], inv["nom_prio"] = nom_req, nom_cnt, nom_prio
        return inv

    def _resident_inv(self) -> tuple[dict, dict | None, tuple | None]:
        """The batch invariants for a dispatch: (host arrays, their device
        copy or None, the key they may stay resident under or None).  The
        device keeps them (builder.resident) while the schema and the term
        vocabulary stand and the nominator is empty, the overlay then being
        the constant it is; on a hit the host arrays are not rebuilt
        either.  A nominated pod, or truncated mode (order_pos and
        scan_start move with every batch), builds and ships them whole."""
        if self._truncated or self.nominator:
            return self._full_inv(), None, None
        b = self.builder
        held = b.resident.get("inv")
        if held is not None and held[0] == (b.schema, len(b.interns.terms)):
            return held[1], held[2], held[0]
        inv = self._full_inv()  # may grow the schema: the key reads it after
        return inv, None, (b.schema, len(b.interns.terms))

    def schedule_batch(self) -> list[ScheduleOutcome]:
        """Pop up to batch_size pods and schedule them in one device pass
        per profile (pods group by .spec.scheduler_name).  Binds completed
        between batches by informer-driven notify_prebind are prepended to
        the returned outcomes."""
        j = self.journal
        jbase = (
            (j.appends, j.fsyncs, j.append_latency.total, j.fsync_s,
             j.writes, j.fence_checks)
            if j is not None
            else None
        )
        acc = self._flight_acc = {
            "phases": {}, "plugins": {}, "pods": 0,
            "scheduled": 0, "unschedulable": 0, "dispatches": [],
        }
        self.spans.open(acc)
        try:
            out = self._schedule_batch_inner()
            if self._prebind_outcomes:
                out = self._prebind_outcomes + list(out)
                self._prebind_outcomes = []
            # Pipeline safety net: a staged commit group never outlives
            # its schedule_batch call (the outcomes below report applied,
            # durable binds; the snapshot must see them too).  Normally a
            # no-op — _batch_traced_inner drained already.
            self._drain_pending(overlapped=False)
            # Checkpoint at the quiescent point between batches (assume/
            # forget deltas settled); the cadence gate inside keeps this
            # free when journaling is off or the log hasn't grown, and
            # the `pipeline/snapshot` span opens behind it.
            self.maybe_snapshot()
            if PROCESS.heap_armed and acc["scheduled"]:
                with self.span("pipeline/heap_settle"):
                    PROCESS.settle_heap()
        finally:
            self._flight_acc = None
            wall = self.spans.close()
            # One record per batch that actually dispatched (empty polls
            # and the per-pod extender path stay off the ring).
            if acc["pods"]:
                self._record_flight(acc, wall, jbase)
        return out

    def _schedule_batch_inner(self) -> list[ScheduleOutcome]:
        if self.permit_wait_since:
            self.expire_waiting_gangs()
        if self.prebind_waiting:
            self.expire_waiting_prebinds()
        now = time.monotonic()
        if now >= self._next_assumed_sweep:
            # cache.go:42 starts cleanupAssumedPods on a 1s ticker; the batch
            # loop's analog is a time-gated sweep at the top of each batch.
            # Permit-room waiters are assumed deliberately (gang quorum) and
            # expire through expire_waiting_gangs, not the TTL.
            self._next_assumed_sweep = now + 1.0
            if self.node_lifecycle.armed:
                # One lifecycle tick chains the whole failure-response
                # clock (transitions → eviction deadlines → GC sweep) on
                # the logical Lease clock.
                self.node_lifecycle.tick()
            else:
                if self.taint_eviction.pending:
                    self.taint_eviction.tick(self._now())
                if self.pod_gc.armed:
                    self.pod_gc.sweep(self._now())
            waiting = {
                e[0].pod.uid
                for entries in self.permit_waiting.values()
                for e in entries
            }
            # PreBind-waiting pods are deliberately assumed too; they
            # expire through expire_waiting_prebinds, not the TTL.
            waiting |= set(self.prebind_waiting)
            for pod in self.cache.cleanup_assumed(self.assume_ttl_s, skip=waiting):
                # No informer to re-deliver the still-pending pod (the
                # reference relies on the apiserver watch for that) — requeue
                # directly so the pod gets another cycle.
                self.queue.add(pod)
        pre = self._prefetched
        self._prefetched = None
        pd = self._predispatched
        self._predispatched = None
        if pd is None:
            # Nothing dispatched is uncommitted on the host (the last
            # batch's group drained with its call): the one point at which
            # the shared-claim rows may move.  A prefetched batch
            # featurized under the old rows is refeaturized (csi_epoch is
            # part of the feature version).
            self._settle_csi_claims()
        if pd is not None:
            # A device pass dispatched one cycle early (the pipeline's
            # double buffer) — validated or re-dispatched below.  infos
            # is the ORIGINAL pop order (the packer may have permuted
            # the dispatched ctx's copy).
            infos = pd.infos
            work = None
            self._mark_inflight(infos)
        elif pre is not None:
            infos, work = pre
            self._mark_inflight(infos)
        else:
            infos = self._pop_batch()
            work = None
        if not infos:
            return []
        # Cycle span (utiltrace "Scheduling" + LogIfLong,
        # schedule_one.go:412): step log emitted only past the threshold.
        # schedule_batch covers a whole BATCH, so the default threshold is
        # per-batch, not per-pod.  When a remote caller's trace context is
        # installed (the sidecar envelope's trace_id/parent_span_id) this
        # root span joins that trace, so a slow server-side cycle logs the
        # CLIENT's trace id — on EVERY path: single-profile, multi-profile,
        # and the extender chain all share the one root span contract.
        tp = self.trace_parent
        with Trace(
            "ScheduleBatch", self.trace_threshold_s,
            trace_id=tp[0] if tp else None,
            parent_span_id=tp[1] if tp else None,
            on_slow=self._note_slow_span,
            pods=len(infos),
        ) as tr:
            self.last_batch_span = tr
            self.spans.trace = tr
            if self.extenders:
                # Extender chain: per-pod eval-only path (see extender.py).
                out: list[ScheduleOutcome] = []
                for qp in infos:
                    out.append(self._schedule_one_extender(qp))
                tr.step("extender chain complete")
                return out
            if len(self.profiles) == 1:
                try:
                    return self._batch_traced(tr, infos, work, pd)
                except Exception as exc:
                    return self._recover_batch(infos, self.profile, exc)
            by_profile: dict[str, list[QueuedPodInfo]] = {}
            for qp in infos:
                prof = self._profile_for(qp.pod) or self.profile
                by_profile.setdefault(prof.name, []).append(qp)
            out = []
            for name, group in by_profile.items():
                with tr.nest("ProfileBatch", profile=name, pods=len(group)):
                    try:
                        out.extend(
                            self._schedule_infos(group, self.profiles[name])
                        )
                    except Exception as exc:
                        out.extend(
                            self._recover_batch(group, self.profiles[name], exc)
                        )
            return out

    def _batch_traced(
        self, tr: Trace, infos: list[QueuedPodInfo], work: dict | None,
        pd=None,
    ) -> list[ScheduleOutcome]:
        """One single-profile batch under the cycle span (exception-safe:
        Trace.__exit__ emits the step log for slow batches even when the
        batch raises — exactly the batches an operator needs timed)."""
        self._inflight_uids = frozenset(qp.pod.uid for qp in infos)
        try:
            return self._batch_traced_inner(tr, infos, work, pd)
        finally:
            self._inflight_uids = frozenset()

    def _batch_traced_inner(
        self, tr: Trace, infos: list[QueuedPodInfo], work: dict | None,
        pd=None,
    ) -> list[ScheduleOutcome]:
        if pd is not None:
            from .engine.pipeline import predispatch_valid

            if predispatch_valid(self, pd):
                # Nothing the early dispatch read has changed: complete
                # the in-flight pass as-is (its device time overlapped
                # the previous batch's drain and the inter-call gap).
                ctx = pd.ctx
                self._pipeline_predispatch_counter.inc(result="hit")
                self._pd_consec_invalid = 0
                tr.step("picked up predispatched device pass")
            else:
                # Host state moved under the early dispatch (informer
                # mutation, taint write, nomination change): discard the
                # pass, rewind the tie-break cycle counter, and dispatch
                # against current truth — exactly what the serial loop
                # would compute, so bindings stay bit-identical.
                self._cycle = pd.cycle0
                self._pipeline_predispatch_counter.inc(result="invalidated")
                # Each miss burned a device pass: back the gate off for
                # a few batches (capped so it always re-probes; a hit
                # resets instantly).
                self._pd_consec_invalid = min(
                    self._pd_consec_invalid + 4, 16
                )
                tr.step("re-dispatching invalidated predispatch")
                ctx = self._dispatch_batch(infos, self.profile, None)
        else:
            ctx = self._dispatch_batch(infos, self.profile, work)
        # Overlap victim packing + transfer with the in-flight device pass
        # when recent batches needed preemption (the dispatch is async; the
        # ~O(nodes) packing walk rides inside the pass's device time).
        prepacked = None
        if (
            self.preemption is not None
            and self.chunk_size > 1
            and self.preemption.expect_failures
            and self.preemption.worth_prepacking(qp.pod for qp in infos)
        ):
            prepacked = self.preemption.pack_victims(self.profile, ctx["active"])
            tr.step("prepacked victim tensors")
        ctx["prepacked"] = prepacked
        if prepacked is not None:
            # Chain the dry-run on the in-flight pass's device verdicts —
            # its compute overlaps the main fetch + strict tail, and its
            # results ride the first host round trip (ADVICE: the three
            # fetches of a failing batch collapse toward one).
            ctx["spec"] = self.preemption.dispatch_speculative(ctx, prepacked)
            if ctx["spec"] is not None:
                tr.step("dispatched speculative preemption")
        if self.post_dispatch_hook is not None:
            # Deserialization/admission work rides the in-flight pass
            # (and feeds the queue the prefetch below pops from).
            self.post_dispatch_hook()
            tr.step("ran post-dispatch hook")
        # Overlap featurize(k+1) with device(k) — the VERDICT r1 host
        # ceiling.  Gated off when the active ops read host catalogs that
        # every batch's binds mutate (DRA allocations bump the feature
        # version every batch, which would drop the prefetch anyway; a
        # volume batch bumps it only where a bind changes what a later
        # pod's featurization reads, and the version check at dispatch
        # drops the prefetch then), and while a claim's users crossed
        # between one and several: its row settles with nothing in flight
        # (_settle_csi_claims), so the next batch is featurized after this
        # pass has committed.
        if (
            self._prefetch_enabled
            and "DynamicResources" not in ctx["active"]
            and not self.builder.csi_unsettled
        ):
            nxt = self._pop_batch()
            if nxt:
                # Prefetched gang members still count as "coming" for
                # the WaitOnPermit quorum (gang_pending) until their
                # batch actually runs.
                for qp in nxt:
                    if qp.pod.spec.pod_group:
                        self.queue._track_gang_member(qp)
                with self.span("batch/prefetch") as sp:
                    nxt_work = self._featurize_batch(nxt, self.profile)
                nxt_work["feat_s"] = sp.dur_s
                self._prefetched = (nxt, nxt_work)
        out = self._complete_batch(ctx, defer_drain=self._pipeline_active())
        # Pipeline depth >= 2: dispatch batch k+1 BEFORE draining batch
        # k's staged commit group, so the group fsync and the apply loop
        # run while the device crunches the next pass.  With no next
        # batch (queue dry) the drain runs inline — still one fsync for
        # the whole group.
        predispatched = False
        ticket = self._pending_ticket
        if (
            ticket is not None
            and not ticket.drained
            and self._pipeline_active()
        ):
            predispatched = self._predispatch_next()
        self._drain_pending(overlapped=predispatched)
        return out

    def _settle_csi_claims(self) -> None:
        """Let the shared-claim rows follow the claims' known users
        (builder.settle_csi_claims), under the `pipeline/csi_settle` span.
        Free when no claim's users crossed between one and several since
        the last call, which is every batch of a workload whose pods each
        have claims of their own.

        Called only while no dispatched pass is uncommitted on the host.
        A pod that names a claim ANOTHER pod of an in-flight pass names
        arrives while that pass runs (the host does not know the first
        pod's node yet): the claim stays unsettled, no batch is prefetched
        behind the pass (_batch_traced_inner), the pass commits as it was
        featurized, and the next batch starts here: a drain first, then a
        row filled from where the first pod landed."""
        b = self.builder
        if not b.csi_unsettled:
            return
        with self.span("pipeline/csi_settle") as sp:
            promoted, released = b.settle_csi_claims(self._claim_spots)
            sp.set("promoted", promoted)
            sp.set("released", released)

    def _claim_spots(self, claim_uid: str, pod_uids):
        """(node row, driver id), once for every pod of ``pod_uids`` whose
        delta the host counts hold (bound or assumed: the cache's records)
        and that names the claim."""
        for uid in pod_uids:
            pr = self.cache.pods.get(uid)
            if pr is None:
                continue
            row = self.cache.nodes[pr.node_name].row
            for cuid, did in pr.delta.get("csivols", ()):
                if cuid == claim_uid:
                    yield row, did

    def _note_claims(self, pod: t.Pod, sign: int) -> None:
        """A pod became known (+1: pending, in flight or bound) or left
        (-1).  The one place the claims' known users are kept from
        (builder.csi_users): a claim two known pods reference needs a row
        of its own."""
        if pod.spec.volumes:
            self.builder.note_claim_users(pod, sign)

    def restore_queue(self, state: dict) -> int:
        """The journal's restore of the pending pods, which enter past
        add_pod and are known all the same."""
        n = self.queue.restore_state(state)
        for qp in self.queue._info.values():
            self._note_claims(qp.pod, +1)
        return n

    def _pop_batch(self) -> list[QueuedPodInfo]:
        """Pop the next batch, and add to the open record's queue_wait how
        long its pods waited since the server first held them (a hint
        frame's arrival, else the queue add).  A prefetched batch is
        popped in the call before the one that completes it, so a record
        counts the pods popped during its call."""
        with self.span("batch/pop"):
            infos = self.queue.pop_batch(self.batch_size)
            acc = self._flight_acc
            if infos and acc is not None:
                total, longest = self.queue.held_for(infos)
                qw = acc.setdefault("queue_wait", [0, 0.0, 0.0])
                qw[0] += len(infos)
                qw[1] += total
                qw[2] = max(qw[2], longest)
        return infos

    def _featurize_batch(self, infos: list[QueuedPodInfo], profile: Profile) -> dict:
        """Host featurization for one batch — separable from dispatch so the
        driver can overlap featurize(k+1) with device(k).  Featurization may
        grow vocab/schema (forcing a state rebuild at dispatch).  The host
        arrays always have the full batch size (one batch shape → one XLA
        program); a one-template batch is a broadcast view of its one row
        under a fresh ``valid``, and what of it reaches the device is
        _dispatch_pass's to decide.  The caller's span times it and sets
        ``feat_s``."""
        # ~10% of batches record per-plugin featurize durations
        # (plugin_execution_duration_seconds, metrics.go:256).
        sample = (
            {} if self.metrics.registry.sample_plugins("featurize") else None
        )
        info: dict = {}
        batch, deltas, active = build_pod_batch(
            [qp.pod for qp in infos], self.builder, profile, self.batch_size,
            sample_into=sample, info=info,
        )
        if sample:
            for op_name, secs in sample.items():
                self._observe_plugin(op_name, "Featurize", secs)
        return {
            "batch": batch, "deltas": deltas, "active": active,
            "version": self.builder.feature_version(),
            # the one cache key the batch's pods share, where every row is
            # the same row (the signature, with what it leaves out of the
            # pods' claims put back), else None
            "uniform_key": info["uniform_key"],
        }

    @staticmethod
    def _pin_name(pod: t.Pod) -> str | None:
        """See engine.features.pin_name (PreFilterResult node-set reduction,
        schedule_one.go:504).  spec.nodeName pods never reach the queue
        (they arrive bound)."""
        from .engine.features import pin_name

        return pin_name(pod)

    def _pin_rows(
        self, infos: list[QueuedPodInfo]
    ) -> tuple[np.ndarray, np.ndarray] | None:
        """(batch,) pinned row per pod plus a nominated-pin mask, or None
        unless EVERY pod resolves to one candidate row (-1 rows mean the
        pin names no live node — immediately infeasible).

        Two pin sources: the pod's own constraints (NodeName / the
        metadata.name matchFields shape — PreFilterResult node-set
        reduction, schedule_one.go:504), and a LIVE NOMINATION with its
        claim still held (evaluateNominatedNode, schedule_one.go:547: the
        nominated node is evaluated alone first).  A nominated pin that
        fails falls back to the full pass next batch (upstream falls back
        to the full node list in the same cycle), so the completion path
        requeues those instead of running PostFilter again."""
        rows = np.full(self.batch_size, -1, np.int32)
        nom = np.zeros(self.batch_size, np.bool_)
        for i, qp in enumerate(infos):
            name = self._pin_name(qp.pod)
            if name is None:
                nn = qp.pod.status.nominated_node_name
                if (
                    nn
                    and qp.pod.uid in self.nominator
                    and not getattr(qp, "nom_pin_failed", False)
                ):
                    rec = self.cache.nodes.get(nn)
                    if rec is not None:
                        rows[i] = rec.row
                        nom[i] = True
                        continue
                return None
            rec = self.cache.nodes.get(name)
            rows[i] = rec.row if rec is not None else -1
        return rows, nom

    def _inject_nomrows(self, work: dict, infos: list[QueuedPodInfo]) -> None:
        """Resolve nominated node names to ROW indices at DISPATCH time, not
        featurize time: a remove_node/add_node pair between prefetch and
        dispatch can reuse a freed row for a different node, so rows resolved
        at prefetch would point the nominated fast path (and the nominator
        self-exclusion) at the wrong node (ADVICE r2).  Nomination is pod
        STATUS — the featurize cache keys on (namespace, labels, spec) only —
        so injection after featurization is always required anyway."""
        nomrow = np.full(self.batch_size, -1, np.int32)
        if self.nominator:
            for i, qp in enumerate(infos):
                nn = qp.pod.status.nominated_node_name
                if nn:
                    rec = self.cache.nodes.get(nn)
                    if rec is not None:
                        nomrow[i] = rec.row
        work["batch"]["nominated_row"] = nomrow
        work["nomrow"] = nomrow

    def _dispatch_batch(
        self, infos: list[QueuedPodInfo], profile: Profile, work: dict | None = None
    ) -> dict:
        """Flush state and dispatch the device pass (async).  A prefetched
        ``work`` is dropped when anything featurization reads changed since
        (catalog binds, vocab growth from another profile's batch).
        Two spans tile it: `batch/featurize` (the `featurize` phase of the
        record that completes this pass) and `pass/dispatch`, whose start
        is the `device` phase's."""
        with self.span("batch/featurize", label="") as sp_f:
            if self.fault_injector is not None:
                # Injected engine faults fire HERE — before featurization
                # and any state mutation — so the recovery path retries
                # against clean state, exactly like an exception thrown by
                # the real featurize/dispatch code below would.
                self.fault_injector.on_engine_dispatch([qp.pod for qp in infos])
            if work is not None and work["version"] != self.builder.feature_version():
                work = None  # stale prefetch
            fresh = work is None
            if fresh:
                work = self._featurize_batch(infos, profile)
            self._inject_nomrows(work, infos)
        if fresh:
            work["feat_s"] = sp_f.dur_s
        with self.span("pass/dispatch", pods=len(infos)) as sp_d:
            ctx = self._dispatch_pass(infos, profile, work)
        ctx["t1"] = sp_d.t0
        ctx["feat_phase_s"] = sp_f.dur_s
        return ctx

    def _dispatch_pass(
        self, infos: list[QueuedPodInfo], profile: Profile, work: dict
    ) -> dict:
        """From the featurized rows to the jitted call returning (async),
        as five child spans of `pass/dispatch`: `dispatch/inv` (the batch
        invariants, or the resident copy's key check), `dispatch/state`
        (the dirty-row flush), packing, `dispatch/put` (ONE device_put of
        the inputs whose device copy missed), `dispatch/expand` (the
        uniform batch's broadcast, on a miss; both in _pass_inputs) and
        `dispatch/call`.

        An input that did not change since the last dispatch is neither
        rebuilt nor sent nor broadcast again; the device array of that
        dispatch is passed again (builder.resident):

        - the invariants (_resident_inv), under (schema, terms interned),
          with an empty nominator and outside truncated mode;
        - a uniform batch's broadcast feature arrays, under (the batch's
          one _featsig, the featurization's version, the active ops, the
          profile, the schema): the last template's;
        - by shape alone: the identity step_offset, the all -1
          nominated_row, the two values of a flag (uniform_all, dom_valid),
          and the mask `arange < n` for each pod count seen (at most 128).

        Each is used only where the batch's own host arrays show it is the
        same (offsets the packer did not write, no nominated row, a prefix
        mask), so a packed, nominated, non-uniform or pinned batch sends
        what it always sent.  In steady state a uniform batch sends the
        cycle counter, and a mask the first time a pod count is seen.
        _count_pass_inputs says what crossed."""
        # Batch invariants (interned term → topo slot) may grow TK/DV: build
        # them after featurization, before the state flush.
        with self.span("dispatch/inv"):
            inv, inv_d, inv_key = self._resident_inv()
        # Carried-DomTables validity must be judged BEFORE state() clears
        # the dirty flags: the carry is sound only when nothing host-side
        # mutated since it was stashed (mutation_epoch) AND no dirty rows
        # are about to be flushed into the device state under it.
        dom_ok = (
            self._dom_carry is not None
            and self._dom_token
            == (self.builder.schema, self.builder.mutation_epoch)
            and not self.builder._dirty_all
            and not self.builder._dirty_rows
        )
        with self.span("dispatch/state"):
            state = self.builder.state()
        # Pinned fast path (PreFilterResult node-set reduction): every pod
        # resolved to one candidate row and no active op needs the domain
        # tables ⇒ one vmapped own-row evaluation instead of the (K, N)
        # scan.  Decision-identical (see build_pinned_pass); truncated
        # (parity) mode keeps the full pass for its processed-node counters.
        from .engine.pass_ import PINNED_SAFE_OPS

        if not self._truncated and work["active"] <= PINNED_SAFE_OPS:
            pins = self._pin_rows(infos)
            if pins is not None:
                pin_rows, nom_pinned = pins
                work["batch"]["pin_row"] = pin_rows
                run = self.passes.get_pinned(
                    profile, self.builder.schema, self.builder.res_col,
                    work["active"],
                )
                batch_d, inv_d = jax.device_put((work["batch"], inv))
                new_state, result = run(state, batch_d, inv_d)
                self._cycle += len(infos)
                # The pinned pass commits on device without returning its
                # domain tables — the carry no longer matches device state.
                self._dom_carry = None
                self.metrics.pinned_batches += 1
                self._dispatch_counter.inc(kind="pinned")
                return dict(
                    work, infos=infos, profile=profile, inv=inv, inv_d=inv_d,
                    new_state=new_state, result=result,
                    schema=self.builder.schema, chunk=self.chunk_size,
                    pinned=True, nom_pinned=nom_pinned,
                )
        chunk = self.chunk_size
        cycle0 = self._cycle
        pack_s = 0.0
        if chunk > 1 and work["active"] & {
            "PodTopologySpread", "InterPodAffinity", "NodePorts"
        }:
            # Conflict-aware chunk packing (engine/packing.py): same-class
            # pods (the hard write→read signals the device defers on) land
            # in DIFFERENT chunk slices at the widest collision-free width,
            # with class-relative order preserved — the scan stays
            # sequential-equivalent and the deferral cascade never forms.
            # Replaces the old duplicate-count chunk halving, which shrank
            # device parallelism exactly when affinity workloads needed it
            # most (and re-walked every pod per halving iteration on this
            # hot path).
            with self.span("batch/pack", label="") as sp_p:
                npods = len(infos)
                plan = pack_batch(work["batch"], npods, chunk)
                chunk = plan.width
                if plan.perm is not None:
                    perm = plan.perm
                    infos = [infos[j] for j in perm]
                    work["deltas"] = [work["deltas"][j] for j in perm]
                    full_perm = np.arange(self.batch_size, dtype=np.int64)
                    full_perm[:npods] = perm
                    work["batch"] = {
                        key2: np.asarray(arr)[full_perm]
                        for key2, arr in work["batch"].items()
                    }
                    # Tie-break seeds ride the pod: row r re-draws the seed
                    # of its ORIGINAL dispatch position, so the packed scan
                    # picks exactly what the sequential scan would have.
                    soff = np.arange(self.batch_size, dtype=np.int32)
                    soff[:npods] = perm
                    work["batch"]["step_offset"] = soff
                    self.metrics.packed_batches += 1
                    self._flight_add("packed", 1)
                self.metrics.pack_collisions += plan.collisions
                self.metrics.pack_width = plan.width
                self.metrics.pack_classes = plan.n_classes
            pack_s = sp_p.dur_s
        run = self.passes.get(
            profile, self.builder.schema, self.builder.res_col, work["active"],
            chunk, carry_dom=True,
        )
        featsig = None
        if chunk > 1 and not self._truncated:
            # Template-batch flag for the pass's all-fail shortcut: every
            # pod featurization-identical (pass_.py uniform_all), which is
            # the featurizer's to say (one cache key for the whole batch).
            # Pods without a signature memo (pinned shapes) count as
            # distinct, a lone one included.
            featsig = work.get("uniform_key")
            work["batch"]["uniform_all"] = np.bool_(featsig is not None)
        batch_d, inv_d, flag = self._pass_inputs(
            work, profile, inv, inv_d, inv_key, featsig
        )
        dom_in = self._dom_carry if dom_ok else self._dom_placeholder()
        with self.span("dispatch/call"):
            new_state, result, dom_out = run(
                state, batch_d, inv_d, np.uint32(cycle0), dom_in[0], dom_in[1],
                flag[dom_ok],
            )
        if dom_ok:
            self.metrics.dom_carry_hits += 1
        else:
            self.metrics.dom_carry_rebuilds += 1
        self._cycle += len(infos)
        self._dispatch_counter.inc(kind="batch")
        return dict(
            work, infos=infos, profile=profile, inv=inv, inv_d=inv_d,
            batch_d=batch_d, new_state=new_state, result=result,
            schema=self.builder.schema, chunk=chunk,
            cycle0=cycle0, pack_s=pack_s, dom_out=dom_out,
        )

    def _pass_inputs(
        self, work: dict, profile: Profile, inv: dict, inv_d: dict | None,
        inv_key: tuple | None, featsig,
    ) -> tuple[dict, dict, tuple]:
        """The device side of a dispatch's batch and invariants, and the two
        device values of a flag (``flag[b]`` for bool ``b``).

        The pass's inputs stay on the device from one dispatch to the next
        (builder.resident, dropped with the device mirror): of each this
        asks "is the device's copy still this?", by a key the batch itself
        shows, and ships in ONE device_put only what missed.  The pass
        donates nothing, so an array may be passed any number of times;
        what it receives is bit for bit what a full ship gives.
        ``featsig``: the one signature of a uniform batch whose pods carry
        one, else None.  Counts what crossed (_count_pass_inputs), the
        cycle counter of the call included."""
        batch_np = work["batch"]
        k = self.batch_size
        res = self.builder.resident
        ship: dict = {}
        if inv_d is None:
            ship["inv"] = inv
        # Constants of shape: identity offsets (ONE compiled program shape
        # whether or not the packer reordered this batch), the all -1
        # nominated rows, the two flags (uniform_all, dom_valid).
        const = res.get("const")
        if const is None:
            ship["const"] = {
                "step_offset": np.arange(k, dtype=np.int32),
                "nominated_row": np.full(k, -1, np.int32),
                "flag": (np.bool_(False), np.bool_(True)),
            }
        identity_soff = "step_offset" not in batch_np
        if identity_soff:
            batch_np["step_offset"] = np.arange(k, dtype=np.int32)
        else:
            ship["step_offset"] = batch_np["step_offset"]
        nom_const = int(batch_np["nominated_row"].max()) < 0
        if not nom_const:
            ship["nominated_row"] = batch_np["nominated_row"]
        # `valid` of n pods is the mask arange < n: the few masks a window
        # uses stay resident, one a pod count.
        valid = batch_np["valid"]
        n_valid = int(np.count_nonzero(valid))
        prefix = not valid[n_valid:].any()
        masks = res.setdefault("valid", {})
        valid_d = masks.get(n_valid) if prefix else None
        if valid_d is None:
            ship["valid"] = valid
        fkeys = tuple(sorted(kk for kk in batch_np if kk not in _PER_BATCH_KEYS))
        uniform = bool(batch_np.get("uniform_all", False))
        feats_d = None
        if uniform:
            # A uniform batch's feature rows are identical by the same
            # signature equality the all-fail shortcut trusts: ONE
            # representative row is shipped and broadcast on the device
            # (_expand_uniform), and the broadcast arrays stay resident for
            # the next batch of the same template under the same
            # vocabularies (the featurization cache's own key).
            ukey = (
                featsig, work["version"], work["active"], profile,
                self.builder.schema,
            )
            held = res.get("uniform")
            if featsig is not None and held is not None and held[0] == ukey:
                feats_d = held[1]
            else:
                ship["small"] = {
                    kk: np.ascontiguousarray(batch_np[kk][:1]) for kk in fkeys
                }
        else:
            ship["batch"] = {kk: batch_np[kk] for kk in fkeys}
        with self.span("dispatch/put"):
            put = jax.device_put(ship) if ship else ship
        if "inv" in put:
            inv_d = put["inv"]
            if inv_key is not None:
                res["inv"] = (inv_key, inv, inv_d)
        if const is None:
            const = res["const"] = put["const"]
        soff_d = const["step_offset"] if identity_soff else put["step_offset"]
        nom_d = const["nominated_row"] if nom_const else put["nominated_row"]
        if valid_d is None:
            valid_d = put["valid"]
            if prefix:
                if len(masks) >= 128:
                    masks.clear()
                masks[n_valid] = valid_d
        if not uniform:
            feats_d = put["batch"]
        elif feats_d is None:
            with self.span("dispatch/expand"):
                full = _expand_uniform(put["small"], valid_d, nom_d, k)
            feats_d = {kk: full[kk] for kk in fkeys}
            if featsig is not None:
                res["uniform"] = (ukey, feats_d)
        batch_d = dict(
            feats_d, valid=valid_d, nominated_row=nom_d, step_offset=soff_d
        )
        if "uniform_all" in batch_np:
            batch_d["uniform_all"] = const["flag"][uniform]
        leaves = jax.tree_util.tree_leaves(ship)
        self._count_pass_inputs(
            len(batch_d) + len(inv_d) + 2, len(leaves) + 1,
            sum(x.nbytes for x in leaves) + 4,
        )
        return batch_d, inv_d, const["flag"]

    def _schedule_infos(
        self, infos: list[QueuedPodInfo], profile: Profile | None = None
    ) -> list[ScheduleOutcome]:
        profile = profile or self.profile
        return self._complete_batch(self._dispatch_batch(infos, profile))

    # -- poison-batch recovery ---------------------------------------------

    def _recover_batch(
        self, infos: list[QueuedPodInfo], profile: Profile, exc: Exception
    ) -> list[ScheduleOutcome]:
        """An engine exception failed a whole batch: isolate the poison
        pod(s) and complete the healthy remainder, so one bad pod can
        never wedge the cluster (handleSchedulingFailure's keep-making-
        progress contract, applied to a batch).

        An ``EngineFault`` that names its pods is split directly; an
        anonymous exception is bisected — halve, retry, recurse — which
        terminates in O(k log k) sub-batches and quarantines exactly the
        singletons that still raise alone.  The device mirror is rebuilt
        from host truth before every retry: a mid-batch failure leaves it
        suspect, and host staging is the authoritative cache.

        The XLA runtime's own errors (a program the compiler refuses,
        RESOURCE_EXHAUSTED, a lost device) are the ENGINE's, not a pod's:
        no sub-batch would fare better, so they propagate to the caller
        instead of ending as every pod quarantined on a run that exits 0."""
        # A deferred commit group staged before the exception holds real,
        # reserve-complete binds: drain it first (journal + apply) so the
        # cached-placement check below sees them as the committed pods
        # they are — not as retriable in-flight state.
        self._drain_pending(overlapped=False)
        self._engine_fault_counter.inc()
        self.flight.record_marker(
            "engine_fault",
            error=f"{type(exc).__name__}: {exc}",
            pods=len(infos),
            **self._trace_extra(),
        )
        if isinstance(exc, jax.errors.JaxRuntimeError):
            if not self._recovering:
                self.flight.dump("engine_fault")
            raise exc
        # Every retry below dispatches on the device: a backend that
        # failed to initialise raises again here, by name, instead of
        # failing each bisected half in turn.
        jax.devices()
        outer = not self._recovering
        self._recovering = True
        try:
            self.rebuild_device_state()
            # A mid-COMMIT failure (_complete_batch phase 2+) leaves part
            # of the batch already assumed in the host cache;
            # re-dispatching those pods would double-apply their resource
            # deltas.  They are committed — report their cached placement
            # instead of retrying.
            out: list[ScheduleOutcome] = []
            uncommitted: list[QueuedPodInfo] = []
            for qp in infos:
                pr = self.cache.pods.get(qp.pod.uid)
                if pr is not None and pr.node_name:
                    out.append(ScheduleOutcome(qp.pod, pr.node_name))
                else:
                    uncommitted.append(qp)
            infos = uncommitted
            if not infos:
                return out
            if isinstance(exc, EngineFault) and exc.pod_uids:
                poison = [qp for qp in infos if qp.pod.uid in exc.pod_uids]
                healthy = [
                    qp for qp in infos if qp.pod.uid not in exc.pod_uids
                ]
                if poison:
                    out.extend(
                        self._quarantine_poison(qp, exc) for qp in poison
                    )
                    if healthy:
                        out.extend(self._schedule_safe(healthy, profile))
                    return out
            if len(infos) == 1:
                out.append(self._quarantine_poison(infos[0], exc))
                return out
            mid = len(infos) // 2
            for half in (infos[:mid], infos[mid:]):
                out.extend(self._schedule_safe(half, profile))
            return out
        finally:
            if outer:
                self._recovering = False
                # ONE dump per incident, written after the whole recovery
                # (bisect + quarantines) so the artifact carries every
                # marker — not one file per halving or per poison pod.
                self.flight.dump("engine_fault")

    def _schedule_safe(
        self, infos: list[QueuedPodInfo], profile: Profile
    ) -> list[ScheduleOutcome]:
        try:
            return self._schedule_infos(infos, profile)
        except Exception as exc:
            return self._recover_batch(infos, profile, exc)

    def _quarantine_poison(
        self, qp: QueuedPodInfo, exc: Exception
    ) -> ScheduleOutcome:
        """Park one poison pod in the queue's quarantine pool and narrate
        it: a FailedScheduling event carrying the exception (the operator's
        why-is-my-pod-stuck surface) plus the quarantine counters."""
        if self.journal is not None:
            from .api import serialize

            # Write-ahead: quarantine is a durable decision — a restart
            # must not feed a known-poison pod back into a batch.
            self.journal.append(
                "quarantine",
                {
                    "uid": qp.pod.uid,
                    "attempts": qp.attempts,
                    "pod": serialize.pod_to_data(qp.pod),
                },
            )
        self.queue.quarantine(qp)
        self._quarantine_counter.inc()
        self._unsched_reasons.inc(plugin="EngineFault")
        # Marker only: quarantine is always reached inside the batch-
        # recovery path, whose outermost exit writes the one dump for the
        # whole incident (quarantine markers included).
        self.flight.record_marker(
            "quarantine",
            pod=qp.pod.uid,
            error=f"{type(exc).__name__}: {exc}",
            **self._trace_extra(),
        )
        # The failed batch never reached _complete_batch's per-pod attempt
        # accounting: count the attempt here so the exported
        # scheduler_schedule_attempts_total cells keep summing to the
        # attempt total.
        self.metrics.schedule_attempts += 1
        self.metrics.unschedulable += 1
        self.recorder.event(
            qp.pod.uid, WARNING, "FailedScheduling",
            f"pod quarantined: engine dispatch raised "
            f"{type(exc).__name__}: {exc}",
            quarantined=True,
            **self._trace_extra(),
        )
        return ScheduleOutcome(
            qp.pod, None,
            diagnosis=Diagnosis(unschedulable_plugins={"EngineFault"}),
        )

    def _complete_batch(
        self, ctx: dict, defer_drain: bool = False
    ) -> list[ScheduleOutcome]:
        infos, profile = ctx["infos"], ctx["profile"]
        batch, deltas, active = ctx["batch"], ctx["deltas"], ctx["active"]
        nomrow, inv = ctx["nomrow"], ctx["inv"]
        new_state, result, t1 = ctx["new_state"], ctx["result"], ctx["t1"]
        # One fetch, one sync per batch, for all result arrays (never
        # sync field-by-field) — the speculative preemption results ride
        # the same fetch.
        spec = ctx.get("spec")
        if spec is not None:
            (picks, scores, feas, fails, processed, steps_run,
             sp_picks, sp_vmask) = device_fetch(
                (result.picks, result.scores, result.feasible_counts,
                 result.fail_masks, result.processed, result.scan_steps,
                 spec["out"].picks, spec["out"].vic_mask),
                span=self.span,
            )
            ctx["spec_res"] = (sp_picks, sp_vmask)
        else:
            picks, scores, feas, fails, processed, steps_run = device_fetch(
                (result.picks, result.scores, result.feasible_counts,
                 result.fail_masks, result.processed, result.scan_steps),
                span=self.span,
            )
        if not ctx.get("pinned"):
            self._count_scan_steps(steps_run, len(picks) // ctx["chunk"])
        if self._truncated:
            # Advance the rotating start by this batch's processedNodes sum
            # (modular sums compose across the scan's per-step updates).
            self._next_start = (self._next_start + int(processed.sum())) % max(
                self.cache.node_count(), 1
            )
        # Strict tail: chunk-deferred pods (pick == -2) re-run through the
        # sequential-equivalent chunk=1 pass against the committed state, in
        # original order, until none remain (a deferred pod never defers
        # again there).  The tail REORDERS commits after later chunks, so the
        # deferred pods are RE-FEATURIZED against the now-complete term/group
        # vocabularies — a pod's original features only matched the terms
        # interned before it, which is sound solely under batch-order commits.
        deferred = [i for i in range(len(infos)) if picks[i] == -2]
        if deferred and ctx.get("pinned"):
            # Pinned same-row overflow mates retry next batch (an earlier
            # mate's failure may have freed their room; the strict-tail
            # machinery keys on scan internals the pinned pass lacks).
            picks = picks.copy()
            for i in deferred:
                self.queue.reactivate(infos[i])
                picks[i] = -3  # handled: neither bind nor failure
            deferred = []
        # Prefetch featurization of batch k+1 may have GROWN the schema
        # while batch k was in flight; the compiled tail/preemption programs
        # for the old shapes cannot mix with the rebuilt state.  Rare (a
        # vocab crossed a power-of-two bucket): requeue the affected pods —
        # they reschedule next batch under the grown schema.
        schema_grew = ctx["schema"] != self.builder.schema
        tail_placed = False  # did the strict tail COMMIT anything?
        if deferred and schema_grew:
            for i in deferred:
                self.queue.reactivate(infos[i])
            picks = picks.copy()
            picks[deferred] = -3  # handled: neither bind nor failure
            deferred = []
        if deferred:
            picks, scores, feas, fails = (
                picks.copy(), scores.copy(), feas.copy(), fails.copy()
            )
            self.metrics.deferred += len(deferred)
            self._flight_add("deferred", len(deferred))

            def run_tail(idx_list: list[int], chunk_level: int, size: int) -> list[int]:
                """Re-featurize + re-run the given pods against the committed
                state; fills the result arrays and returns indices that
                deferred AGAIN (possible only when chunk_level > 1).

                Seeds: the tail re-run IS each pod's real decision, so it
                draws the pod's ORIGINAL step seed (batch seed base +
                per-pod step offset) — tie-breaks agree with the
                sequential chunk=1 scan, and the tail never advances
                ``_cycle`` (the next batch's seeds stay aligned with the
                parity oracle's).  The main pass's domain tables thread
                through as a valid carry: nothing host-side mutates
                between the scan and its tail."""
                nonlocal new_state
                run2 = self.passes.get(
                    profile, self.builder.schema, self.builder.res_col,
                    active, chunk_level, carry_dom=True,
                )
                soff_batch = np.asarray(batch["step_offset"], np.int32)
                still: list[int] = []
                for lo in range(0, len(idx_list), size):
                    idx = idx_list[lo : lo + size]
                    sub, sub_deltas, _ = build_pod_batch(
                        [infos[i].pod for i in idx], self.builder, profile,
                        size, force_active=active,
                    )
                    sub["nominated_row"] = np.full(size, -1, np.int32)
                    sub["nominated_row"][: len(idx)] = nomrow[idx]
                    for j, i in enumerate(idx):
                        deltas[i] = sub_deltas[j]
                    # Per-pod bucket dims (own terms, devices) are padded to
                    # the sub-batch max; pad up to the original batch's
                    # shapes so the compiled pass sees one shape set.
                    from .ops.common import FEATURE_FILLS

                    for key2, arr in sub.items():
                        tgt = batch[key2].shape[1:]
                        if arr.shape[1:] != tgt:
                            padw = [(0, 0)] + [
                                (0, tg - cur) for cur, tg in zip(arr.shape[1:], tgt)
                            ]
                            sub[key2] = np.pad(
                                arr, padw, constant_values=FEATURE_FILLS.get(key2, 0)
                            )
                    sub["step_offset"] = np.zeros(size, np.int32)
                    sub["step_offset"][: len(idx)] = soff_batch[idx]
                    sub_d = jax.device_put(sub)  # one coalesced transfer
                    dom_cur = ctx["dom_out"]
                    new_state, res, ctx["dom_out"] = run2(
                        new_state, sub_d, ctx["inv_d"], np.uint32(ctx["cycle0"]),
                        dom_cur[0], dom_cur[1], np.bool_(True),
                    )
                    p2, s2, f2, fl2, steps2 = device_fetch(
                        (res.picks, res.scores, res.feasible_counts,
                         res.fail_masks, res.scan_steps)
                    )
                    self._dispatch_counter.inc(kind="tail")
                    self._count_scan_steps(steps2, size // chunk_level)
                    picks[idx], scores[idx], feas[idx], fails[idx] = (
                        p2[: len(idx)], s2[: len(idx)], f2[: len(idx)], fl2[: len(idx)],
                    )
                    still.extend(i for j, i in enumerate(idx) if p2[j] == -2)
                return still

            # Round 1 — large bursts replay through the SAME chunked program
            # against the committed state: most deferrals are positional
            # (e.g. a freshly-added empty node attracting every chunk-mate,
            # the churn-workload magnet); once earlier commits are visible
            # they place cleanly in one pass instead of one scan step each.
            all_deferred = list(deferred)
            with self.span("pass/tail", pods=len(deferred)):
                if ctx["chunk"] > 1 and len(deferred) > self.tail_size:
                    deferred = run_tail(deferred, ctx["chunk"], self.batch_size)
                # Round 2 — strict sequential-equivalent finisher (chunk=1
                # never defers, so this always terminates).
                if deferred:
                    run_tail(deferred, 1, self.tail_size)
            tail_placed = any(picks[i] >= 0 for i in all_deferred)
        bit_names = filter_op_names(profile, active)
        self._count_filter_rejections(fails, picks, len(infos), bit_names)
        # The commit stage starts where the pass ends: this span's start
        # closes the `device` phase (dispatch to fetched, tails included).
        with self.span("commit/stage", phase="commit") as cs:
            t2 = cs.t0
            self._last_batch_meta = (
                {
                    k: (v.shape, np.asarray(v).dtype)
                    for k, v in batch.items()
                    if k != "uniform_all"  # scalar flag, not a feature row
                },
                active,
            )
            self.builder.absorb_device_state(new_state)
            # Carry the scan-maintained domain tables into the next batch —
            # valid only under the exact (schema, mutation_epoch) they were
            # stashed at; any host mutation in between forces a device-side
            # rebuild.  A batch whose prefetch grew the schema mid-flight
            # drops the carry (its arrays are shaped for the old buckets).
            if ctx.get("pinned"):
                pass  # carry already dropped at dispatch
            elif ctx["schema"] == self.builder.schema and "dom_out" in ctx:
                self._dom_carry = ctx["dom_out"]
                self._dom_token = (
                    self.builder.schema, self.builder.mutation_epoch
                )
            else:
                self._dom_carry = None

            outcomes: list[ScheduleOutcome] = []
            now = time.monotonic()
            # The batch's staged commit group (engine/pipeline.CommitTicket):
            # binds that pass Permit + Reserve stage here and journal + apply
            # together under ONE group fsync — at the serial point below
            # (depth 1, or any batch with failures), or deferred under the
            # next batch's in-flight device pass (_batch_traced_inner).
            from .engine.pipeline import CommitTicket

            ticket = CommitTicket(now=now)
            if self.queue.admission is not None:
                # This batch's weighted-fair debits (pop order), captured by
                # the batch's OWN uids — at depth 2 the prefetch has already
                # popped batch k+1, whose intents must ride k+1's ticket.
                # Failed pods' debits stay in: an admission attempt costs
                # credit whether or not the bind lands.
                ticket.admission = self.queue.admission.take_intents(
                    [qp.pod.uid for qp in infos]
                )
            self._pending_ticket = ticket
            m = self.metrics
            m.batches += 1
            m.featurize_time_s += ctx["feat_s"]
            m.device_time_s += t2 - t1
            m.registry.observe_point("Featurize", ctx["feat_s"])
            m.registry.observe_point("DevicePass", t2 - t1)
            m.registry.attempt_duration.observe(t2 - t1 + ctx["feat_s"])
            failed: list[tuple[int, QueuedPodInfo, ScheduleOutcome]] = []
            nom_pinned = ctx.get("nom_pinned")
            # Phase 1 — assume every pick (cache.go:361 AssumePod; the device
            # already committed the deltas in-scan).
            placed: list[tuple[int, QueuedPodInfo, str]] = []
            for i, qp in enumerate(infos):
                m.schedule_attempts += 1
                row = int(picks[i])
                if row < 0 and row != -3 and nom_pinned is not None and nom_pinned[i]:
                    # The nominated node alone failed: fall back to the FULL
                    # node list next batch (schedule_one.go:547 does so in the
                    # same cycle) — NOT the failure path, whose PostFilter
                    # would preempt again on top of a live nomination.
                    qp.nom_pin_failed = True
                    self.queue.reactivate(qp)
                    continue
                if row >= 0:
                    node_name = self.cache.node_name_at_row(row)
                    assert node_name is not None, f"pick={row} maps to no node"
                    self.cache.assume_pod(qp.pod, node_name, device_already=True, delta=deltas[i])
                    # A placed pod's nomination is spent (nominator.go deletes
                    # on assume).
                    if self.nominator:
                        self.nominator.pop(qp.pod.uid, None)
                    qp.pod.status.nominated_node_name = ""
                    placed.append((i, qp, node_name))
                    if self.journal is not None or self.provenance is not None:
                        self._tie_pending[qp.pod.uid] = self._tie_step_of(
                            i, ctx, batch
                        )
                    if self.provenance is not None:
                        self._provenance_capture(
                            qp.pod.uid, node_name, row, i, ctx, batch,
                            scores, feas, fails, profile,
                        )
                elif row == -3:
                    continue  # already requeued (schema grew mid-flight)
                else:
                    failed.append((i, qp, None))

            # Phase 2 — Permit (RunPermitPlugins, runtime/framework.go:1443;
            # reference extension-point order: Permit precedes PreBind, so a
            # cancelled group never durably binds volumes).  Each registered
            # PermitPlugin judges the batch's placed pods and returns
            # group-level allow/wait/reject; the loop owns only the generic
            # mechanics (waiting room, rollback, timeouts).
            rollback: set[str] = set()
            wait: set[str] = set()
            admitted: set[str] = set()
            owner: dict[str, object] = {}
            if placed or self.permit_waiting:
                placed_view = [(qp, node) for _i, qp, node in placed]
                decisions = [
                    (plugin, plugin.judge_batch(placed_view, self))
                    for plugin in self.permit_plugins
                ]
                # Most-restrictive-wins across plugins (RunPermitPlugins stops
                # at the first reject; any wait holds the pod): reject > wait >
                # admit, with the group owned by its most restrictive decider.
                for plugin, dec in decisions:
                    for g in dec.reject:
                        rollback.add(g)
                        owner[g] = plugin
                for plugin, dec in decisions:
                    for g in dec.wait - rollback:
                        wait.add(g)
                        owner.setdefault(g, plugin)
                for plugin, dec in decisions:
                    for g in dec.admit - rollback - wait:
                        admitted.add(g)
                        owner.setdefault(g, plugin)

            # Waiters of rejected groups roll back with their group; waiters of
            # admitted groups join this batch's finalize list.
            entries: list[tuple[QueuedPodInfo, str, int, int]] = [
                (qp, node, int(scores[i]), int(feas[i])) for i, qp, node in placed
            ]
            for g in rollback:
                self.permit_wait_since.pop(g, None)
                pl = owner.get(g) or self.permit_wait_owner.get(g)
                self.permit_wait_owner.pop(g, None)
                for qp, _node, _s, feasn in self.permit_waiting.pop(g, ()):
                    self.cache.forget_pod(qp.pod.uid)
                    outcomes.append(ScheduleOutcome(qp.pod, None, 0, feasn))
                    pl.on_rollback(qp, self)
            for g in admitted:
                self.permit_wait_since.pop(g, None)
                self.permit_wait_owner.pop(g, None)
                entries.extend(self.permit_waiting.pop(g, ()))
            for g in wait:
                # One GangWaiting per group per batch (the coscheduling
                # plugin's Permit-wait narration); the ring aggregates repeats.
                self.recorder.event(
                    f"podgroup/{g}", NORMAL, "GangWaiting",
                    f"gang {g} waiting on Permit for quorum",
                )

            # Phase 3 — Reserve + PreBind + bind: each registered ReservePlugin
            # reserves host-side state on the chosen node (VolumeBinding PreBind
            # volume_binding.go:521, DRA claim allocation), unwinding in reverse
            # on failure (RunReservePluginsUnreserve).  A pod that lost a
            # same-batch race is forgotten and retried — the assume/forget
            # protocol (cache.go:404 ForgetPod).  If the loser belongs to a
            # permit group, the whole group rolls back with it — including
            # reverting peers' reservations — so a gang never lands partially
            # bound below minMember (ADVICE r1).
            finalized_by_group: dict[str, list] = {}
            race_rollback: set[str] = set()  # transient (PV race): retry on timer
            prebind_parked: set[str] = set()  # pods gone to the PreBind wait room
            prebind_s = 0.0
            # Per-plugin sampled Reserve durations: ONE gate per batch (the
            # reference samples per scheduling attempt, schedule_one.go:104).
            sample_rp = bool(entries) and m.registry.sample_plugins("reserve")
            for qp, node_name, score, feasn in entries:
                g, gpl = self._permit_group(qp.pod)
                if g in rollback:
                    self.cache.forget_pod(qp.pod.uid)
                    outcomes.append(ScheduleOutcome(qp.pod, None, 0, feasn))
                    # Plugin rollback (not add_unschedulable): an ex-waiter's
                    # queue._info entry was dropped by done() when it entered the
                    # waiting room and must be restored with the original qp.
                    gpl.on_rollback(qp, self)
                    continue
                if g in wait:
                    # WaitOnPermit: off-queue, still assumed, until quorum or
                    # the owning plugin's timeout (expire_waiting_gangs).
                    self.queue.done(qp.pod.uid)
                    self.permit_waiting.setdefault(g, []).append(
                        (qp, node_name, score, feasn)
                    )
                    self.permit_wait_since.setdefault(g, now)
                    self.permit_wait_owner[g] = owner.get(g, gpl)
                    continue
                undos: list = []  # [(plugin, undo)] in reserve order
                reserve_failed = False
                relevant = [
                    rp for rp in self._reserve_for(qp.pod) if rp.relevant(qp.pod, self)
                ]
                t_pb = time.perf_counter() if relevant else 0.0
                for rp in relevant:
                    t_rp = time.perf_counter() if sample_rp else 0.0
                    u = rp.reserve(qp.pod, node_name, self)
                    if sample_rp:
                        self._observe_plugin(
                            getattr(rp, "name", type(rp).__name__), "Reserve",
                            time.perf_counter() - t_rp,
                        )
                    if u is None:
                        for rp2, u2 in reversed(undos):
                            rp2.unreserve(u2, self)
                        reserve_failed = True
                        break
                    undos.append((rp, u))
                if relevant:
                    prebind_s += time.perf_counter() - t_pb
                if reserve_failed:
                    # Reserve lost a same-batch race (PV or claim allocation).
                    self.cache.forget_pod(qp.pod.uid)
                    outcomes.append(ScheduleOutcome(qp.pod, None, 0, feasn))
                    if g:
                        # The whole group retries together, with peers'
                        # reservations reverted.
                        rollback.add(g)
                        race_rollback.add(g)
                        gpl.on_rollback(qp, self)
                        # Same-batch mates are still STAGED (their journal
                        # records unwritten, gang credit uncounted): unstage
                        # — nothing on the log or in spec to unwind.
                        for qp2, out2, undos2 in finalized_by_group.pop(g, ()):
                            for rp2, u2 in reversed(undos2):
                                rp2.unreserve(u2, self)
                            ticket.unstage(qp2.pod.uid)
                            self.cache.forget_pod(qp2.pod.uid)
                            out2.node_name, out2.score = None, 0
                            gpl.on_rollback(qp2, self)
                        # Same-batch mates already parked in the PreBind wait
                        # room revert with the group too.
                        for uid2 in [
                            u for u, e in self.prebind_waiting.items()
                            if e["g"] == g
                        ]:
                            e = self.prebind_waiting.pop(uid2)
                            prebind_parked.discard(uid2)
                            for rp2, u2 in reversed(e["undos"]):
                                rp2.unreserve(u2, self)
                            self.cache.forget_pod(uid2)
                            outcomes.append(
                                ScheduleOutcome(e["qp"].pod, None, 0, e["feasn"])
                            )
                            gpl.on_rollback(e["qp"], self)
                    else:
                        self.queue.add_backoff(qp)
                    continue
                pending = set()
                for rp, u in undos:
                    hook = getattr(rp, "prebind_pending", None)
                    if hook is not None:
                        pending.update(hook(qp.pod, u, self))
                if pending:
                    # PreBind wait (RunPreBindPlugins inside the detached
                    # bindingCycle, volume_binding.go:521): the pod stays
                    # ASSUMED off-queue until every key resolves
                    # (notify_prebind) or the bind timeout unreserves it —
                    # the batch itself never blocks.
                    self.queue.done(qp.pod.uid)
                    self.prebind_waiting[qp.pod.uid] = {
                        "qp": qp, "node": node_name, "score": score,
                        "feasn": feasn, "undos": undos, "keys": pending,
                        "g": g, "gpl": gpl, "since": time.monotonic(),
                        "mates": [],
                    }
                    prebind_parked.add(qp.pod.uid)
                    continue
                # Write-ahead at GROUP scope (engine/pipeline.drain_commit):
                # the bind STAGES here; its journal record and its apply
                # (spec mutation + finish_binding + queue/gang bookkeeping)
                # both happen at the drain, where the whole group's records
                # go durable under ONE fsync before any of them applies —
                # the crash analog of etcd acknowledging a batched txn
                # before the scheduler trusts any write in it.
                outcome = ScheduleOutcome(qp.pod, node_name, score, feasn)
                outcomes.append(outcome)
                ticket.stage(qp, node_name, outcome)
                if g:
                    finalized_by_group.setdefault(g, []).append(
                        (qp, outcome, undos)
                    )
            # A parked gang member pins its batch-mates' records so a PreBind
            # timeout can roll the whole gang back (the repo's gang contract is
            # all-or-nothing; mates bound this batch revert like a lost PV race).
            for uid in prebind_parked:
                entry = self.prebind_waiting.get(uid)
                if entry is not None and entry["g"]:
                    entry["mates"] = list(finalized_by_group.get(entry["g"], ()))
            # A group rolled back by a transient PV race re-admits behind backoff
            # right away — no cluster event will ever fire in a quiet cluster,
            # and the race loser's next attempt resolves against the updated
            # volume catalog.
            for g in race_rollback:
                self.queue.readmit_gang(g)
            # Plugins see their groups that are now waiting (e.g. coscheduling
            # re-attempts queue admission: waiter credit grew).
            for plugin in self.permit_plugins:
                plugin_waits = {g for g in wait if owner.get(g) is plugin}
                if plugin_waits:
                    plugin.post_batch(plugin_waits, self)
            if prebind_s:
                m.registry.observe_point("PreBind", prebind_s)
        # Drain the staged commit group at the SERIAL point — unless the
        # pipeline defers it under the next dispatch.  Any batch with
        # failures drains here regardless: PostFilter's victim deletes
        # journal with their own fsyncs, and the WAL's replay order must
        # keep this batch's bind records AHEAD of them (delete-then-bind
        # replay would resurrect a preempted pod).
        if not defer_drain or failed:
            self._drain_pending(overlapped=False)
        with self.span("commit/failed", phase="commit", failed=len(failed)):
            # Metrics after rollbacks settled (success = outcome kept its
            # node).  Staged successes are accounted by the drain (inline
            # above at depth 1, under the next device pass at depth >= 2).
            for outcome in outcomes:
                if outcome.node_name:
                    if ticket.holds(outcome.pod.uid):
                        continue  # success accounting rides the drain
                    # Not staged: an inline preemptor commit
                    # (_commit_preempted journals + applies directly) —
                    # its success accounting happens here.
                    if m.scheduled == 0:
                        m.first_scheduled_ts = now
                    m.scheduled += 1
                    m.last_scheduled_ts = now
                    self._note_bound(outcome.pod, outcome.node_name)
                    self.recorder.event(
                        outcome.pod.uid, NORMAL, "Scheduled",
                        f"Successfully assigned {outcome.pod.uid} to "
                        f"{outcome.node_name}",
                    )
                else:
                    m.unschedulable += 1
                    # Rollback/race failures carry no device diagnosis; the
                    # engine-rejected failures get theirs (with the plugin
                    # set) in the diagnosis loop below.
                    self.recorder.event(
                        outcome.pod.uid, WARNING, "FailedScheduling",
                        f"0/{self.cache.node_count()} nodes available "
                        "(batch rollback or lost race)",
                        **self._trace_extra(),
                    )
            # Diagnosis from the device's per-op fail bitmask (bit order =
            # ``bit_names``): which plugins rejected nodes this cycle.  A
            # uniform failing batch (5k no-fit pods, the Unschedulable shape)
            # produces ONE distinct mask — build each mask's plugin set once.
            mask_sets: dict[int, set] = {}
            failed2 = []
            for i, qp, _ in failed:
                mask = int(fails[i])
                plugins = mask_sets.get(mask)
                if plugins is None:
                    plugins = {
                        name for b, name in enumerate(bit_names) if mask & (1 << b)
                    }
                    mask_sets[mask] = plugins
                diag = Diagnosis(unschedulable_plugins=plugins)
                outcome = ScheduleOutcome(qp.pod, None, 0, int(feas[i]), diagnosis=diag)
                m.unschedulable += 1
                for name in sorted(plugins):
                    self._unsched_reasons.inc(plugin=name)
                # FailedScheduling with the diagnosis plugin set (the fitError
                # message shape: "0/N nodes are available: ...").
                self.recorder.event(
                    qp.pod.uid, WARNING, "FailedScheduling",
                    f"0/{self.cache.node_count()} nodes available: rejected by "
                    + (", ".join(sorted(plugins)) if plugins else "no feasible nodes"),
                    plugins=sorted(plugins),
                    **self._trace_extra(),
                )
                outcomes.append(outcome)
                failed2.append((i, qp, outcome))
            failed = failed2

            # PostFilter: one batched preemption pass for every failure
            # (schedule_one.go:196 RunPostFilterPlugins → DefaultPreemption).
            results = [None] * len(failed)
            ran_postfilter = False
            t_post = time.perf_counter()
            # (Preemption also sits out a schema-grown batch: its pass would mix
            # old-shape feature rows with rebuilt state; failures just requeue.)
            spec_applied = False
            if (
                failed
                and self.preemption is not None
                and "DefaultPreemption" in profile.post_filter
                and not schema_grew
            ):
                ran_postfilter = True
                if spec is not None and "spec_res" in ctx:
                    # The dry-run already ran, chained on the scan's verdicts;
                    # interpret its results for the pods that FINALLY failed
                    # (tail placements simply never apply theirs).
                    by_index = self.preemption.collect_speculative(
                        spec, ctx["spec_res"],
                        {i: qp.pod for i, qp, _ in failed},
                    )
                    results = [by_index.get(i) for i, _qp, _ in failed]
                    spec_applied = True
                else:
                    rows = {
                        key: [np.asarray(arr)[i] for i, _, _ in failed]
                        for key, arr in batch.items()
                        if key not in ("valid", "pin_row", "uniform_all")
                    }
                    results = self.preemption.preempt_batch(
                        [qp.pod for _, qp, _ in failed], rows, active,
                        ctx["inv_d"], profile=profile,
                        prepacked=ctx.get("prepacked"),
                    )
            if self.preemption is not None:
                # Prepack victim tensors next batch only while failures recur.
                self.preemption.expect_failures = bool(failed)
            any_victims = False
            # A SPECULATIVE result's dry-run predates the strict tail.  Inline
            # commit needs its verdict still valid against post-tail truth:
            # resources re-check via _fits_now always; hard filters that read
            # MUTABLE node state (affinity/spread/ports/volumes/DRA) cannot be
            # re-checked host-side, so when the tail actually placed something
            # AND such an op is active, speculative results take the
            # nominate-and-retry path (which re-validates on device).
            spec_inline_ok = not spec_applied or not tail_placed or not (
                active & DYNAMIC_HARD_OPS
            )
            for (i, qp, outcome), res in zip(failed, results):
                if res is not None:
                    if self.provenance is not None:
                        # pickOneNode rationale BEFORE the commit path's
                        # victim deletes debit the PDB budgets the key reads.
                        self.provenance.note_preemption(
                            qp.pod.uid,
                            {
                                "node": res.node_name,
                                "victims": [v.uid for v in res.victims],
                                "key": self._preempt_key(res.victims),
                            },
                        )
                    if (
                        self.inline_preempt_commit
                        and self._can_commit_inline(qp)
                        and (
                            not spec_applied
                            or (
                                spec_inline_ok
                                and self._fits_now(res.node_name, deltas[i])
                            )
                        )
                    ):
                        self._commit_preempted(qp, outcome, res, deltas[i], now)
                    else:
                        # The fit overlay protects the freed node from same/
                        # next-batch stealers, and the retry's fast path takes
                        # it (nominator.go AddNominatedPod).
                        self._record_preemption(qp, outcome, res, deltas[i])
                    any_victims = any_victims or bool(res.victims)
                elif self.preemption is not None and schema_grew:
                    # Preemption sat this batch out (its compiled pass cannot
                    # mix old-shape feature rows with the rebuilt state) — the
                    # failure must RETRY next batch rather than park: in a
                    # quiet cluster no event would ever wake it, while the
                    # reference would have run PostFilter on this very cycle.
                    self.queue.reactivate(qp)
                else:
                    # Precise requeue hints: wait only on events the plugins that
                    # actually rejected nodes care about (isPodWorthRequeuing,
                    # scheduling_queue.go:406).  Empty diagnosis (e.g. zero valid
                    # nodes) falls back to the whole filter set.
                    plugins = outcome.diagnosis.unschedulable_plugins if outcome.diagnosis else set()
                    qp.delta = deltas[i]  # the object-aware hints read req
                    self.queue.add_unschedulable(
                        qp, plugins or set(profile.filters)
                    )
            if any_victims:
                # One batched POD_DELETE for every victim this pass, carrying
                # the affected nodes' post-eviction free capacity (minus the
                # preemptors' nominated claims) so the fit hint wakes only pods
                # the freed space could actually seat — without this, every
                # victim deletion re-activates the whole unschedulable pool
                # (the preemption-async churn VERDICT r2 weak-1 named).
                freed_rows = {
                    self.cache.nodes[res.node_name].row
                    for res in results
                    if res is not None and res.victims
                    and res.node_name in self.cache.nodes
                }
                self.queue.on_event(Event.POD_DELETE, self._free_ctx(freed_rows))
            if ran_postfilter:
                m.registry.observe_point("PostFilter", time.perf_counter() - t_post)
            if (
                self.consistency_check_every
                and m.batches % self.consistency_check_every == 0
            ):
                # Quiescent point: host assume/forget deltas all applied.
                self.check_consistency()
        acc = self._flight_acc
        if acc is not None:
            # Flight tiling for this dispatch→complete unit.  The spans
            # above fed `commit` (and the drain its own phase) as they
            # ended; the dispatch side's spans may have run in the
            # PREVIOUS call (a predispatched pass), so their seconds ride
            # the ctx to the record that completes the pass: `featurize`
            # is batch/featurize, `packing` batch/pack, and `device` runs
            # from pass/dispatch's start to commit/stage's, less the
            # packer (multi-profile batches accumulate one unit per group;
            # `other` in _record_flight absorbs the gaps between spans).
            ph = acc["phases"]
            pack_s = ctx.get("pack_s", 0.0)
            ph["featurize"] = ph.get("featurize", 0.0) + ctx["feat_phase_s"]
            if pack_s > 0.0:
                ph["packing"] = ph.get("packing", 0.0) + pack_s
            ph["device"] = ph.get("device", 0.0) + (t2 - t1 - pack_s)
            self.spans.add("pass/inflight", t1, t2)
            acc["pods"] += len(infos)
            acc["scheduled"] += sum(1 for o in outcomes if o.node_name)
            acc["unschedulable"] += sum(
                1 for o in outcomes if not o.node_name
            )
            acc["dispatches"].append(
                "pinned" if ctx.get("pinned") else "batch"
            )
        return outcomes

    def schedule_all_pending(
        self, max_rounds: int = 10_000, wait_backoff: bool = False
    ) -> list[ScheduleOutcome]:
        """Drain the active queue (benchmark driver).  With ``wait_backoff``
        the loop also sleeps through backoff expiries (so preempted pods get
        their retry) until only unschedulable/gated pods remain."""
        all_outcomes: list[ScheduleOutcome] = []
        for _ in range(max_rounds):
            out = self.schedule_batch()
            if out:
                all_outcomes.extend(out)
                continue
            if len(self.queue) or self.has_inflight_work:
                # A whole batch can yield zero outcomes (members moved to
                # the WaitOnPermit room) while pods remain active,
                # prefetched, or predispatched.
                if (
                    self.queue.last_pop_throttled
                    and not self.has_inflight_work
                ):
                    # Pods remain but every tenant is credit-blocked
                    # (weighted-fair admission): looping cannot admit
                    # them — only the logical clock can, via refill or
                    # the aging escape.  Stop instead of spinning.
                    break
                continue
            if wait_backoff and self.queue.sleep_until_backoff():
                continue
            break
        return all_outcomes

