"""The soak harness: drive the deployment with open-loop traffic and
measure it like a service.

One-shot replays answer "how fast can it drain"; this answers the
production questions the ROADMAP's sustained-traffic item asks:

- **SLO percentiles** — per-decision serving latency (p50/p99/p999)
  against a configured budget, measured open-loop: arrivals come on
  their own schedule (arrivals.py), so a scheduler falling behind
  accrues backlog and its tail degrades honestly.
- **The speculation miss-rate knee** — a decision-cache miss costs a
  full wire round trip + device pass while a hit costs a local map
  pop (what each costs on the chip is PERF.md's business).  The knee
  sweep ramps the invalidation intensity (scenarios.py) across phases
  and records where the hit rate collapses and the latency crosses the
  miss cost — the number nothing measured before this PR.
- **Journal growth under an unbounded stream** — the driver retires old
  bound pods (the live-pod cap) so binds+deletes append forever; the
  WAL must stay bounded through snapshot+truncate compaction cycles
  (journal.py), observed directly as the sampled ``journal.wal`` size.

Determinism: the full wire-operation sequence (hints, per-pod decisions,
retirements, scenario events) is a pure function of the seed — events
execute in pre-computed schedule order, and real-time pacing only delays
WHEN an operation is issued, never which or in what order.  Re-running
with one seed therefore reproduces the arrival schedule exactly and
lands bit-identical final bindings, in either pacing mode.  The
deterministic push consumer below is part of that contract: pushes are
written to the subscriber socket under the dispatch lock BEFORE the
triggering call's response, so once a wire call returns, every frame it
caused is already buffered — a non-blocking drain sees a deterministic
prefix of the stream (the threaded ``DecisionCache`` trades that for
always-on draining; the single-threaded driver doesn't need it).

Deployments: ``two_process=True`` spawns the real ``serve
--journal-dir --speculate`` CLI as a child and drives it over the unix
socket (the acceptance configuration); ``two_process=False`` hosts the
SidecarServer in-process (tier-1 smoke, ``soak --in-process``).
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import zlib
from collections import deque
from dataclasses import asdict, dataclass, field

import numpy as np

from ..api.wrappers import make_node, make_pod
from ..framework.config import named_extra_profiles, profile_scheduler_name
from ..framework.flight import merge_fleet
from ..framework.metrics import (
    TENANT_FALLBACK,
    MetricsRegistry,
    TenantMetrics,
    pod_tenant,
)
from ..journal import Journal
from ..sidecar.host import DecisionCache, ResyncingClient
from ..sidecar.server import SidecarClient
from .arrivals import (
    _rng,
    burst_offsets,
    coalesce,
    diurnal_offsets,
    poisson_offsets,
)
from .checkpoint import CheckpointWriter, load_checkpoint, state_digest
from .scenarios import DEFAULT_INV_MIX, build_events, one_shot_events
from .workloads import WorkloadMix


@dataclass
class SoakConfig:
    seed: int = 6
    # Fleet: `nodes` serving nodes + `churn_nodes` flap targets.
    nodes: int = 200
    zones: int = 10
    churn_nodes: int = 8
    # Arrivals (open-loop).
    rate_pods_per_s: float = 60.0
    diurnal: bool = False
    diurnal_peak_factor: float = 2.0  # peak = factor × base rate
    diurnal_period_s: float = 120.0
    hint_coalesce_s: float = 0.25
    mix: str = "basic"
    # Phases: one sustained phase (the SLO source), then the knee sweep.
    duration_s: float = 60.0
    knee_points: tuple[float, ...] = (0.5, 2.0, 8.0, 32.0, 128.0)
    knee_phase_s: float = 20.0
    # Background churn during EVERY phase.
    invalidation_rate_per_s: float = 0.1
    node_flap_period_s: float = 30.0
    flap_down_s: float = 2.0
    cold_consumer_period_s: float = 0.0
    # Node-DEATH scenario (ISSUE 9): a churn node stops heartbeating
    # (the object stays), the server's node-lifecycle controller writes
    # the NotReady/Unreachable taints, its pods evict + requeue +
    # reschedule on survivors, and a revive clears the taints.  Armed by
    # node_grace_s > 0; Leases renew every lease_interval_s stamping the
    # SCENARIO clock (liveness is a pure function of the op stream).
    node_death_period_s: float = 0.0
    node_death_down_s: float = 8.0
    lease_interval_s: float = 1.0
    node_grace_s: float = 0.0  # 0 = lifecycle disarmed (pre-ISSUE-9 soak)
    node_unreachable_s: float = 0.0  # 0 = grace × 2.5
    gc_horizon_s: float = 0.0  # 0 = grace × 6
    # Elastic-fleet autoscaler (ISSUE 11; fleet soak only).  armed by
    # autoscale=True: the driver ticks the shard autoscaler every
    # autoscale_interval_s of SCENARIO time, and hot_fraction of
    # arrivals carry a node selector only the hot pool (the serving
    # nodes shard 0 owns at build time) satisfies — the diurnal crest
    # concentrates their load on one shard until a split trips.
    autoscale: bool = False
    hot_fraction: float = 0.0
    autoscale_interval_s: float = 5.0
    autoscale_split_hi: float = 1.6
    autoscale_merge_lo: float = 0.25
    autoscale_cooldown_s: float = 30.0
    autoscale_window_s: float = 60.0
    autoscale_budget: int = 2
    autoscale_min_decisions: int = 12
    autoscale_max_shards: int = 4
    # A deterministic pre-bound population scheduled BEFORE the measured
    # window (hot-marked like the stream): the owners' stores start
    # saturated, so the per-owner snapshot pause — the tail-latency
    # mechanism the split halves — is in force from the first window
    # instead of only materializing late in the run.
    preload_bound: int = 0
    # Pre/post comparison window for the split-recovery evidence block,
    # and the settle gap that separates the RESIZE TRANSITION (the
    # journaled import re-fsyncs every moved binding — a real, bounded,
    # one-time cost the artifact reports explicitly) from the
    # steady-state window the recovery claim compares.
    autoscale_compare_window_s: float = 30.0
    autoscale_compare_settle_s: float = 10.0
    # The unbounded-stream bound: completed (bound) pods beyond this cap
    # retire oldest-first, so capacity recycles and the journal sees a
    # perpetual bind+delete append stream.
    live_pod_cap: int = 2000
    # SLO.
    slo_budget_ms: float = 250.0
    # Engine shape.
    batch_size: int = 512
    chunk_size: int = 64
    warm_pods: int = 256
    # Software pipeline (ISSUE 15): depth 1 = serial parity; depth 2
    # overlaps the group-committed journal drain with the next batch's
    # in-flight device pass (bindings bit-identical either way).
    pipeline_depth: int = 1
    # Deployment.
    two_process: bool = False
    journal_dir: str = ""  # empty → a temp dir (two-process always journals)
    journal_fsync: str = "always"
    snapshot_every: int = 64
    # "real" paces operations to the arrival schedule's wall deadlines
    # (latency includes backlog); "virtual" issues them back to back
    # (latency = service time) — same operation sequence either way.
    pace: str = "real"
    # Artifact directory (flight dumps, final flight ring); empty → temp.
    out_dir: str = ""
    # -- tenant attribution (ISSUE 12) ----------------------------------
    # Weighted tenant draw for the stream: ((name, weight), ...) — every
    # arrival carries the scheduler.tpu/tenant label, drawn by its own
    # seeded stream (the template draw sequence is untouched).
    tenants: tuple = ()
    # Per-tenant arrival STREAMS (the tenant_starvation scenario; fleet
    # soak only): tuple of dicts {"name", "rate_pods_per_s", and
    # optionally "burst_factor"/"burst_start_s"/"burst_end_s", plus
    # "workload_class" — the throughput-matrix row its fairness weight
    # derives from when admission is armed} — each tenant arrives on its
    # own seeded schedule (steady Poisson, or a piecewise burst), merged
    # time-ordered.  Non-empty replaces the single
    # rate_pods_per_s/diurnal schedule.
    tenant_streams: tuple = ()
    # Weighted-fair admission (ISSUE 17): arm framework/fairness on the
    # fleet router's queue.  Dict of FairAdmission knobs —
    # {"rate_pods_per_s", "burst", "aging_max_wait_s",
    # "slo_wait_budget_s"}; weights derive from the synthetic throughput
    # matrix over the tenant_streams' workload_class mapping (uniform
    # when unmapped).  None ⇒ UNARMED: the pre-fairness FIFO admission,
    # bit-identical to pre-PR runs.
    admission: dict | None = None
    # Hashed tail tier for the tenant labeler (TenantLabeler
    # hash_buckets): 0 keeps pure top-K + "-" overflow; > 0 routes
    # over-cap tenants into that many crc32 buckets (~NN labels) — the
    # thousands-of-tenants leg's bounded-cardinality contract.
    tenant_hash_buckets: int = 0
    # Master observability switch: tenant attribution, fleet tracing and
    # flight logical-clock stamping.  Decisions are bit-identical with
    # it on or off — the tenant artifact's obs-off leg asserts exactly
    # that (observability must observe, never steer).
    observability: bool = True
    # -- heterogeneous clusters (ISSUE 14) ------------------------------
    # Accelerator-class pools for the serving/churn fleet:
    # ((accel_class, int_weight), ...) — nodes deal their
    # ``scheduler.tpu/accel`` label deterministically by index.  Empty ⇒
    # homogeneous (the pre-ISSUE-14 fleet).
    hetero_pools: tuple = ()
    # Extra registered profile served beside the default ("" |
    # "throughput-aware" | "learned-scorer"); the stream selects it by
    # schedulerName (WorkloadMix.scheduler_name).  Pair with
    # mix="hetero" + hetero_pools for the heterogeneous soak.
    profile: str = ""
    # -- warm-standby owner pool (ISSUE 18; fleet soak only) ------------
    # > 0 arms fleet/standby.py: that many pre-forked, pre-warmed serve
    # children (XLA compiled against the live featurization schema,
    # journal dir pre-created, lease unclaimed) kept behind the
    # autoscaler's owner_provider and revive_owner's takeover path —
    # promotion is a journaled handoff + lease claim (O(handoff)), not a
    # ~15s cold boot.  0 ⇒ unarmed: both paths cold-spawn exactly as
    # before, byte-identical to the pre-ISSUE-18 soak.
    standby_pool: int = 0
    standby_dir: str = ""  # pool WAL + mirror dir; empty → tmp/standby
    # -- resumable driver (ISSUE 18) ------------------------------------
    # Non-empty arms loadgen/checkpoint.py: every checkpoint_every_ops
    # executed ops the driver atomically checkpoints its FULL
    # deterministic state (op cursor, logical clock, RNG generator
    # states, SLO/latency accumulators, per-tenant ledgers) plus the
    # wall-derived observability accumulators.  resume=True replays the
    # checkpointed op prefix in virtual pace against fresh journal dirs,
    # verifies the regenerated state digest, restores the observability
    # accumulators, and continues — bit-identical to an uninterrupted
    # same-seed run.
    checkpoint_path: str = ""
    checkpoint_every_ops: int = 0
    resume: bool = False
    # Test hook (run_fault_matrix.py --standby-kill; tests/test_soak.py):
    # SIGKILL the driver process immediately after executing op N
    # (post-checkpoint-write when N lands on a boundary).  0 = disarmed.
    kill_after_op: int = 0
    # Extra scripted one-shot scenario events merged into the generated
    # stream: ((t, kind, data), ...) — the production-day composition
    # uses this for the scripted cold router restart and node deaths.
    scripted_events: tuple = ()


def _accel_label(cfg: SoakConfig, w, i: int):
    """Deal the accelerator-class label over the configured pools
    (ISSUE 14) — the SAME weighted deal the bench fleets use
    (benchmarks.harness.hetero_accel_for), so soak and sweep node
    distributions can never drift apart.  Deterministic by node index:
    a re-add mid-soak (capacity toggle, epoch label, fleet re-feed)
    reproduces the node's class.  No-op without hetero_pools."""
    pools = tuple((a, int(wt)) for a, wt in cfg.hetero_pools)
    if not pools:
        return w
    from ..benchmarks.harness import hetero_accel_for
    from ..ops.throughput import ACCEL_LABEL_KEY

    return w.label(ACCEL_LABEL_KEY, hetero_accel_for(i, pools))


def _sha(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True).encode()
    ).hexdigest()


def _pct(values: list[float], q: float) -> float:
    if not values:
        return 0.0
    return float(np.percentile(np.asarray(values, np.float64), q))


def _lat_summary(values: list[float]) -> dict:
    return {
        "decisions": len(values),
        "p50_ms": round(_pct(values, 50) * 1e3, 3),
        "p99_ms": round(_pct(values, 99) * 1e3, 3),
        "p999_ms": round(_pct(values, 99.9) * 1e3, 3),
        "mean_ms": round(
            float(np.mean(values)) * 1e3 if values else 0.0, 3
        ),
        "max_ms": round(max(values) * 1e3 if values else 0.0, 3),
    }


def _slo_families(registry: MetricsRegistry, budget_ms: float):
    """The soak SLO families — ONE construction site shared by the
    single-scheduler driver and the fleet soak (metrics hygiene: one
    registration per name).  Both latency families carry the bounded
    ``tenant`` label next to ``phase`` (ISSUE 12: whose p99 blew up)."""
    hist = registry.histogram(
        "scheduler_slo_decision_latency_seconds",
        "Per-decision serving latency of the open-loop soak driver "
        "(arrival deadline to decision), by phase, tenant and component "
        "(total = queue_wait + service: queue_wait is time spent waiting "
        "for admission — driver backlog or a fairness rate cap — and "
        "service is the scheduler's own time, so a capped tenant's "
        "self-inflicted wait is attributed to the cap, not to "
        "scheduler slowness).",
    )
    violations = registry.counter(
        "scheduler_slo_violations_total",
        "Soak decisions whose serving latency exceeded the SLO "
        "budget, by phase and tenant.",
    )
    registry.gauge(
        "scheduler_slo_budget_seconds",
        "Configured SLO latency budget for the soak driver.",
    ).set(budget_ms / 1e3)
    return hist, violations


def _tenant_summary(phases: list["_PhaseResult"]) -> dict:
    """Aggregate the phases' per-tenant splits into the artifact's
    tenants block: decisions/bound/violations + the latency percentile
    split, keyed by raw tenant id ("-" = untagged)."""
    lat: dict[str, list] = {}
    cnt: dict[str, int] = {}
    bound: dict[str, int] = {}
    viol: dict[str, int] = {}
    for p in phases:
        for k, v in p.tenant_latencies.items():
            lat.setdefault(k, []).extend(v)
        for k, v in p.tenant_counts.items():
            cnt[k] = cnt.get(k, 0) + v
        for k, v in p.tenant_bound.items():
            bound[k] = bound.get(k, 0) + v
        for k, v in p.tenant_violations.items():
            viol[k] = viol.get(k, 0) + v
    return {
        k: dict(
            _lat_summary(lat[k]),
            arrivals=cnt.get(k, 0),
            bound=bound.get(k, 0),
            violations=viol.get(k, 0),
        )
        for k in sorted(lat)
    }


class PushConsumer:
    """Single-threaded push-stream consumer (the deterministic sibling
    of ``DecisionCache``): subscribes its own connection and drains
    whatever is already buffered, non-blocking.  Apply semantics are the
    stream contract shared with DecisionCache._apply — invalidations
    first, then the epoch, then the frame's decisions."""

    def __init__(self, path: str):
        self.client = SidecarClient(path)
        self.client.subscribe()
        self.sock = self.client.sock
        self.sock.setblocking(False)
        self.buf = bytearray()
        self.map: dict = {}
        self.epoch = 0
        self.frames = 0
        self.dead = False

    def drain_available(self) -> int:
        """Apply every complete frame currently buffered (never blocks).
        Frames a completed wire call emitted are guaranteed present —
        the sidecar wrote them before that call's response."""
        if self.dead:
            return 0
        while True:
            try:
                chunk = self.sock.recv(1 << 20)
            except BlockingIOError:
                break
            except OSError:
                self.dead = True
                break
            if not chunk:  # EOF: the stream is a dead epoch
                self.dead = True
                break
            self.buf += chunk
        frames, self.buf = DecisionCache._frames_from(self.buf)
        for push in frames:
            if push.invalidate_all:
                self.map.clear()
            for uid in push.invalidate_uids:
                self.map.pop(uid, None)
            self.epoch = push.epoch
            for d in push.decisions:
                self.map[d.pod_uid] = d
        self.frames += len(frames)
        return len(frames)

    def pop(self, uid: str):
        return self.map.pop(uid, None)

    def close(self) -> None:
        self.client.close()


@dataclass
class _PhaseResult:
    name: str
    invalidation_rate_per_s: float
    wall_s: float = 0.0
    decisions: int = 0
    bound: int = 0
    hits: int = 0
    misses: int = 0
    latencies: list = field(default_factory=list)
    miss_latencies: list = field(default_factory=list)
    violations: int = 0
    retired: int = 0
    events_applied: dict = field(default_factory=dict)
    # Per-tenant split (raw tenant id → samples/counts; "-" = untagged).
    tenant_latencies: dict = field(default_factory=dict)
    tenant_counts: dict = field(default_factory=dict)
    tenant_bound: dict = field(default_factory=dict)
    tenant_violations: dict = field(default_factory=dict)


class _Driver:
    """One soak run's host side: the ResyncingClient, the push consumer,
    the retirement window, and the journal-size sampler."""

    def __init__(self, cfg: SoakConfig, sock: str, journal_dir: str):
        self.cfg = cfg
        self.registry = MetricsRegistry()
        # The SLO families (README metrics catalog): per-decision serving
        # latency by phase AND tenant, violations against the budget, the
        # budget gauge.
        self._slo_hist, self._slo_violations = _slo_families(
            self.registry, cfg.slo_budget_ms
        )
        # Driver-side tenant attribution (bounded labeler + admission
        # counters mirroring the server's); None with observability off.
        self.tenant_metrics = (
            TenantMetrics(self.registry) if cfg.observability else None
        )
        self.client = ResyncingClient(
            sock, deadline_s=120.0, seed=cfg.seed, registry=self.registry
        )
        self.consumer = PushConsumer(sock)
        self.cold_consumers = 0
        self.journal_dir = journal_dir
        self.wal_samples: list[int] = []
        self.compactions_observed = 0
        self._wal_prev = 0
        # Node objects by name (re-adds must diff against the live shape).
        self.node_objs: dict[str, object] = {}
        self._cap_toggle: dict[int, int] = {}
        self._label_epoch: dict[int, int] = {}
        self._ns_epoch = 0
        self.mix = WorkloadMix(
            cfg.mix,
            seed=cfg.seed * 7919 + 11,
            tenants=cfg.tenants,
            scheduler_name=profile_scheduler_name(cfg.profile),
        )
        # Node-death bookkeeping: churn nodes currently silenced, the
        # cumulative scenario-clock offset (Lease stamps must stay
        # monotone across phases), and event counts.
        self.dead: set[str] = set()
        self.time_base = 0.0
        self.node_deaths = 0
        self.node_revives = 0
        self.lease_renewals = 0
        self.pods_by_uid: dict[str, object] = {}
        # Bound uids, oldest first.  A deque: the retirement window
        # front-pops once per decision at steady state, and an O(n)
        # list.pop(0) over live_pod_cap entries would tax the paced
        # serving path itself.
        self.live: deque[str] = deque()
        self.retired = 0

    # -- fleet -------------------------------------------------------------

    def _accel_label(self, w, i: int):
        return _accel_label(self.cfg, w, i)

    def _serving_node(self, i: int, cpu: str = "16", label_epoch: int = 0):
        w = (
            make_node(f"lgn-{i}")
            .capacity({"cpu": cpu, "memory": "64Gi", "pods": 110})
            .zone(f"zone-{i % self.cfg.zones}")
            .region("region-1")
        )
        w = self._accel_label(w, i)
        if label_epoch:
            w = w.label("loadgen.tpu/epoch", str(label_epoch))
        return w.obj()

    def _churn_node(self, i: int):
        return self._accel_label(
            make_node(f"churn-{i}")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
            .zone(f"zone-{i % self.cfg.zones}")
            .region("region-1"),
            i,
        ).obj()

    def build_fleet(self) -> None:
        for i in range(self.cfg.nodes):
            n = self._serving_node(i)
            self.node_objs[n.metadata.name] = n
            self.client.add("Node", n)
        for i in range(self.cfg.churn_nodes):
            n = self._churn_node(i)
            self.node_objs[n.metadata.name] = n
            self.client.add("Node", n)
        if self.cfg.node_grace_s > 0:
            from ..api import types as t
            from ..controllers import (
                NODE_NOT_READY,
                NODE_UNREACHABLE,
                lifecycle_taints,
            )

            # Pre-seed the lifecycle taint keys into the featurization
            # vocab BEFORE warmup compiles the device programs: the
            # first mid-soak transition would otherwise grow the taint
            # schema and pay a full XLA recompile inside the measured
            # window (the same trap the fleet soak's label-epoch
            # pre-seeding closes).
            import dataclasses

            probe = self.node_objs["churn-0"]
            tainted = dataclasses.replace(
                probe,
                spec=dataclasses.replace(
                    probe.spec,
                    taints=lifecycle_taints(NODE_NOT_READY)
                    + lifecycle_taints(NODE_UNREACHABLE),
                ),
            )
            self.client.add("Node", tainted)
            self.client.add("Node", probe)
            # Only churn nodes carry Leases: the lifecycle controller
            # governs exactly the pool the death scenario targets, and
            # the serving fleet stays exempt (unleased nodes are never
            # tainted).
            for i in range(self.cfg.churn_nodes):
                self.client.add("Lease", t.Lease(f"churn-{i}", 0.0))

    def _renew_alive_leases(self, ts: float) -> None:
        from ..api import types as t

        for i in range(self.cfg.churn_nodes):
            name = f"churn-{i}"
            if name not in self.dead and name in self.node_objs:
                self.client.add("Lease", t.Lease(name, ts))
                self.lease_renewals += 1

    def warmup(self) -> None:
        """Compile the device programs and the speculative machinery out
        of the measured window, then retire the warm wave so phase 0
        starts from an empty live set (and the deletes are exercised
        before anything is measured)."""
        from ..framework.metrics import TENANT_LABEL_KEY

        # Tenant labels grow the pod-label vocab — warm them too, or the
        # first tagged arrival recompiles inside the measured window.
        warm_tenants = [name for name, _w in self.cfg.tenants]
        warm = []
        if self.cfg.profile:
            # Heterogeneous warm wave (ISSUE 14): one pod per MIX
            # TEMPLATE round-robin, so every (label set, workload class)
            # group — and the class-active compiled program — lands in
            # warmup.  This is the wire-side half of the accel-vocab
            # pre-seed: the first hetero pod's featurize interns the
            # matrix's accelerator classes and backfills the labeled
            # node rows' topo slots HERE, not inside the measured
            # window (the PR 9/PR 10 taint-vocab trap).
            from ..api import types as t
            from .workloads import MIXES, TEMPLATES

            names = [n for n, _w in MIXES[self.cfg.mix]]
            sched_name = profile_scheduler_name(self.cfg.profile)
            for i in range(self.cfg.warm_pods):
                p = TEMPLATES[names[i % len(names)]](10**6 + i)
                p.metadata.name = f"lgwarm-{i}"
                p.metadata.labels = dict(p.metadata.labels or {})
                if sched_name:
                    p.spec.scheduler_name = sched_name
                if warm_tenants:
                    p.metadata.labels[TENANT_LABEL_KEY] = warm_tenants[
                        i % len(warm_tenants)
                    ]
                p.spec.containers[0].requests = {
                    "cpu": t.parse_quantity("50m", "cpu"),
                    "memory": t.parse_quantity("64Mi", "memory"),
                }
                warm.append(p)
        else:
            for i in range(self.cfg.warm_pods):
                w = make_pod(f"lgwarm-{i}").req({"cpu": "50m", "memory": "64Mi"})
                if warm_tenants:
                    w = w.label(
                        TENANT_LABEL_KEY, warm_tenants[i % len(warm_tenants)]
                    )
                warm.append(w.obj())
        half = len(warm) // 2
        self.client.add_pending_batch(warm[:half])
        for p in warm[:half]:
            self.client.schedule([p], drain=False)
        if len(warm) > half:
            self.client.schedule(warm[half:], drain=True)
        for p in warm:
            self.client.remove("Pod", p.uid)
        self.consumer.drain_available()
        self.consumer.map.clear()

    # -- scenario application ----------------------------------------------

    def apply_event(self, ev) -> None:
        if ev.kind == "inv_capacity":
            i = ev.data % self.cfg.nodes
            self._cap_toggle[i] = 1 - self._cap_toggle.get(i, 0)
            n = self._serving_node(
                i,
                cpu="15" if self._cap_toggle[i] else "16",
                label_epoch=self._label_epoch.get(i, 0),
            )
            self.node_objs[n.metadata.name] = n
            self.client.add("Node", n)
        elif ev.kind == "inv_label":
            i = ev.data % self.cfg.nodes
            self._label_epoch[i] = self._label_epoch.get(i, 0) + 1
            n = self._serving_node(
                i,
                cpu="15" if self._cap_toggle.get(i) else "16",
                label_epoch=self._label_epoch[i],
            )
            self.node_objs[n.metadata.name] = n
            self.client.add("Node", n)
        elif ev.kind == "inv_ns":
            self._ns_epoch += 1
            self.client.set_namespace_labels(
                "loadgen-churn", {"epoch": str(self._ns_epoch)}
            )
        elif ev.kind == "flap_down":
            name = f"churn-{ev.data}"
            # The node's bound pods vanish with it (engine contract);
            # drop them from the retirement window too.
            gone = {
                uid
                for uid in self.live
                if getattr(
                    self.pods_by_uid.get(uid), "_lg_node", None
                ) == name
            }
            if gone:
                self.live = deque(
                    u for u in self.live if u not in gone
                )
                for u in gone:
                    self.pods_by_uid.pop(u, None)
            self.client.remove("Node", name)
        elif ev.kind == "flap_up":
            n = self._churn_node(ev.data)
            self.node_objs[n.metadata.name] = n
            self.client.add("Node", n)
        elif ev.kind == "cold_consumer":
            # The push consumer restarts cold mid-stream: decision map
            # gone, fresh subscription, misses until the stream re-warms.
            self.consumer.close()
            self.consumer = PushConsumer(self.client.path)
            self.cold_consumers += 1
        elif ev.kind == "node_death":
            # The node object STAYS; its heartbeat goes silent.  The
            # server's lifecycle controller must detect the staleness,
            # taint, evict, and reschedule its pods — nothing else in
            # the op stream touches the dead node.
            self.dead.add(f"churn-{ev.data % max(1, self.cfg.churn_nodes)}")
            self.node_deaths += 1
        elif ev.kind == "node_revive":
            from ..api import types as t

            name = f"churn-{ev.data % max(1, self.cfg.churn_nodes)}"
            self.dead.discard(name)
            # A fresh renewal at the current scenario clock clears the
            # lifecycle taints (the node rejoined).
            self.client.add("Lease", t.Lease(name, self.time_base + ev.t))
            self.lease_renewals += 1
            self.node_revives += 1
        elif ev.kind == "lease_tick":
            self._renew_alive_leases(self.time_base + ev.t)
        else:
            raise ValueError(f"unknown scenario event {ev.kind!r}")

    # -- decisions ----------------------------------------------------------

    def decide(self, pod, res: _PhaseResult, deadline: float | None) -> None:
        """Serve one arrival: local map first (the plugin's PreFilter
        path), wire on miss.  Latency is measured from the arrival's
        schedule deadline (real pace — backlog included) or from issue
        (virtual pace)."""
        uid = pod.uid
        t_issue = time.perf_counter()
        self.consumer.drain_available()
        d = self.consumer.pop(uid)
        node = None
        if d is None:
            res.misses += 1
            results = self.client.schedule([pod], drain=False)
            for r in results:
                if r.pod_uid == uid and r.node_name:
                    node = r.node_name
            self.consumer.drain_available()
            t_done = time.perf_counter()
            res.miss_latencies.append(t_done - t_issue)
        else:
            res.hits += 1
            node = d.node_name or None
            t_done = time.perf_counter()
        base = t_issue if deadline is None else min(deadline, t_issue)
        lat = t_done - base
        res.latencies.append(lat)
        tenant = pod_tenant(pod)
        tlabel = (
            self.tenant_metrics.labeler.label_for(tenant)
            if self.tenant_metrics is not None
            else TENANT_FALLBACK
        )
        tkey = tenant or "-"
        res.tenant_latencies.setdefault(tkey, []).append(lat)
        res.tenant_counts[tkey] = res.tenant_counts.get(tkey, 0) + 1
        if self.tenant_metrics is not None:
            # The driver-side mirror of the server's admission counter
            # (one arrival = one admission in the open-loop stream).
            self.tenant_metrics.note("admitted", tenant)
            if node:
                self.tenant_metrics.note("bound", tenant)
        # Component split: total = queue_wait + service.  queue_wait is
        # the pre-service wait (driver backlog under real pace — the
        # deadline predating issue), service the serving call itself.
        self._slo_hist.observe(
            lat, phase=res.name, tenant=tlabel, component="total"
        )
        self._slo_hist.observe(
            max(0.0, t_issue - base),
            phase=res.name, tenant=tlabel, component="queue_wait",
        )
        self._slo_hist.observe(
            t_done - t_issue,
            phase=res.name, tenant=tlabel, component="service",
        )
        if lat > self.cfg.slo_budget_ms / 1e3:
            res.violations += 1
            res.tenant_violations[tkey] = (
                res.tenant_violations.get(tkey, 0) + 1
            )
            self._slo_violations.inc(phase=res.name, tenant=tlabel)
        res.decisions += 1
        if node:
            res.bound += 1
            res.tenant_bound[tkey] = res.tenant_bound.get(tkey, 0) + 1
            pod._lg_node = node
            self.pods_by_uid[uid] = pod
            self.live.append(uid)
            while len(self.live) > self.cfg.live_pod_cap:
                old = self.live.popleft()
                self.pods_by_uid.pop(old, None)
                self.client.remove("Pod", old)
                res.retired += 1
                self.retired += 1

    # -- journal growth ------------------------------------------------------

    def sample_wal(self) -> None:
        if not self.journal_dir:
            return
        try:
            size = os.path.getsize(
                os.path.join(self.journal_dir, Journal.WAL)
            )
        except OSError:
            size = 0
        if size < self._wal_prev:
            # Truncation happened between samples: one observed
            # compaction cycle (snapshot + truncate).
            self.compactions_observed += 1
        self._wal_prev = size
        self.wal_samples.append(size)

    def close(self) -> None:
        try:
            self.consumer.close()
        except OSError:
            pass
        self.client.close()


def _phase_specs(cfg: SoakConfig) -> list[tuple[str, float, float]]:
    specs = [("sustained", cfg.duration_s, cfg.invalidation_rate_per_s)]
    for k, rate in enumerate(cfg.knee_points):
        specs.append((f"knee-{k}", cfg.knee_phase_s, float(rate)))
    return specs


def _run_phase(
    driver: _Driver,
    cfg: SoakConfig,
    phase_index: int,
    name: str,
    duration_s: float,
    inv_rate: float,
    arrival_base: int,
) -> tuple[_PhaseResult, list[float]]:
    """Merge the phase's arrival schedule, hint windows, and scenario
    script into one time-ordered operation list and execute it."""
    seed = cfg.seed * 1_000_003 + phase_index
    if cfg.diurnal:
        offsets = diurnal_offsets(
            cfg.rate_pods_per_s,
            cfg.rate_pods_per_s * cfg.diurnal_peak_factor,
            cfg.diurnal_period_s,
            duration_s,
            seed,
        )
    else:
        offsets = poisson_offsets(cfg.rate_pods_per_s, duration_s, seed)
    pods = [driver.mix.pod(arrival_base + i) for i in range(len(offsets))]
    armed = cfg.node_grace_s > 0
    scenario = build_events(
        duration_s,
        seed + 500_009,
        nodes=cfg.nodes,
        churn_nodes=cfg.churn_nodes,
        invalidation_rate_per_s=inv_rate,
        inv_mix=DEFAULT_INV_MIX,
        node_flap_period_s=cfg.node_flap_period_s,
        flap_down_s=cfg.flap_down_s,
        cold_consumer_period_s=cfg.cold_consumer_period_s,
        node_death_period_s=cfg.node_death_period_s if armed else 0.0,
        node_death_down_s=cfg.node_death_down_s,
        lease_interval_s=cfg.lease_interval_s if armed else 0.0,
    )
    # Merge: (t, class, idx) — hints flush at their window start ahead
    # of same-instant decisions; scenario events order between them by
    # their own timestamps.  The tuple sort is total and seed-stable.
    ops: list[tuple[float, int, int, object]] = []
    for w_start, idxs in coalesce(offsets, cfg.hint_coalesce_s):
        ops.append((w_start, 0, idxs[0], idxs))
    for j, ev in enumerate(scenario):
        ops.append((ev.t, 1, j, ev))
    for i, off in enumerate(offsets):
        ops.append((off, 2, i, i))
    ops.sort(key=lambda e: (e[0], e[1], e[2]))

    res = _PhaseResult(name=name, invalidation_rate_per_s=inv_rate)
    t0 = time.perf_counter()
    for t_ev, klass, _idx, payload in ops:
        if cfg.pace == "real":
            delay = (t0 + t_ev) - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        if klass == 0:
            driver.client.add_pending_batch(
                [pods[i] for i in payload]
            )
            driver.sample_wal()
        elif klass == 1:
            driver.apply_event(payload)
            res.events_applied[payload.kind] = (
                res.events_applied.get(payload.kind, 0) + 1
            )
            driver.sample_wal()
        else:
            deadline = t0 + t_ev if cfg.pace == "real" else None
            driver.decide(pods[payload], res, deadline)
    driver.sample_wal()
    # Lease stamps must stay monotone across phases: advance the
    # scenario-clock base by this phase's span.
    driver.time_base += duration_s
    res.wall_s = round(time.perf_counter() - t0, 3)
    return res, offsets


def _knee_analysis(
    phases: list[_PhaseResult], miss_cost_ms: float
) -> dict:
    """The knee curve: hit rate and latency per invalidation intensity,
    plus the located knee — the first intensity where the hit rate
    drops below 0.5 (the cache serves less than it misses) or the
    median decision costs more than a miss (speculation stopped
    paying)."""
    points = []
    knee = None
    for p in phases:
        total = p.hits + p.misses
        hit_rate = p.hits / total if total else 0.0
        point = {
            "intensity_per_s": p.invalidation_rate_per_s,
            "hit_rate": round(hit_rate, 4),
            "decisions": total,
            "p50_ms": round(_pct(p.latencies, 50) * 1e3, 3),
            "p99_ms": round(_pct(p.latencies, 99) * 1e3, 3),
            "mean_ms": round(
                float(np.mean(p.latencies)) * 1e3 if p.latencies else 0.0,
                3,
            ),
        }
        points.append(point)
        collapsed = hit_rate < 0.5 or (
            miss_cost_ms > 0 and point["p50_ms"] > miss_cost_ms
        )
        if knee is None and collapsed:
            knee = p.invalidation_rate_per_s
    return {
        "miss_cost_ms": round(miss_cost_ms, 3),
        "points": points,
        "knee_intensity_per_s": knee,
    }


def _lifecycle_argv(cfg: SoakConfig) -> list[str]:
    """The `serve` lifecycle-arming flags a node-loss soak needs (shared
    by the single-process and fleet child spawns)."""
    if cfg.node_grace_s <= 0:
        return []
    return [
        "--node-grace-s", str(cfg.node_grace_s),
        "--node-unreachable-s",
        str(cfg.node_unreachable_s or cfg.node_grace_s * 2.5),
        "--gc-horizon-s", str(cfg.gc_horizon_s or cfg.node_grace_s * 6),
    ]


def _launch_serve(
    argv: list[str], out_dir: str, sock: str, label: str,
    deadline_s: float,
):
    """Spawn one `serve` child and wait for its socket.  Output goes to
    a per-child LOG FILE in the artifact directory, never an unread
    PIPE — a chatty child (cycle-span logging, takeover restarts) would
    otherwise block on a full pipe mid-soak and read as a hung owner."""
    from ..utils import refuse_if_holding_device

    refuse_if_holding_device(f"serve child {label!r}")
    log_path = os.path.join(out_dir, f"{label}.log")
    env = dict(os.environ)
    env["TPU_FLIGHT_DIR"] = out_dir
    log = open(log_path, "a", encoding="utf-8")
    try:
        proc = subprocess.Popen(
            argv,
            stdout=log,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))),
            env=env,
        )
    finally:
        log.close()  # the child holds its own dup
    deadline = time.monotonic() + deadline_s
    while not os.path.exists(sock):
        if proc.poll() is not None:
            try:
                with open(log_path, encoding="utf-8") as f:
                    out = f.read()
            except OSError:
                out = ""
            raise RuntimeError(
                f"{label} exited rc={proc.returncode}: {out[-2000:]}"
            )
        if time.monotonic() > deadline:
            proc.kill()
            raise RuntimeError(f"{label} never bound its socket")
        time.sleep(0.05)
    return proc


def _spawn_serve(cfg: SoakConfig, sock: str, journal_dir: str, out_dir: str):
    """The real deployment: ``python -m kubernetes_tpu serve`` as a
    child process, journaled and speculative, flight dumps into the
    artifact directory."""
    argv = [
        sys.executable, "-m", "kubernetes_tpu", "serve",
        "--socket", sock,
        "--speculate",
        "--batch-size", str(cfg.batch_size),
        "--chunk-size", str(cfg.chunk_size),
        "--journal-dir", journal_dir,
        "--journal-fsync", cfg.journal_fsync,
        "--snapshot-every", str(cfg.snapshot_every),
        "--pipeline-depth", str(cfg.pipeline_depth),
    ] + (["--profile", cfg.profile] if cfg.profile else []) + _lifecycle_argv(cfg)
    return _launch_serve(argv, out_dir, sock, "serve", deadline_s=180.0)


def run_soak(cfg: SoakConfig) -> dict:
    """Execute one soak and return the artifact document (the soak
    artifact schema README documents)."""
    tmp = tempfile.TemporaryDirectory(prefix="tpu-soak-")
    out_dir = cfg.out_dir or tmp.name
    os.makedirs(out_dir, exist_ok=True)
    # Only dumps shed by THIS run count as its incidents — a persistent
    # out_dir may hold earlier runs' flight dumps (names embed the
    # child's pid, so they are never overwritten).
    pre_existing = set(os.listdir(out_dir))
    journal_dir = cfg.journal_dir or os.path.join(tmp.name, "journal")
    sock = os.path.join(tmp.name, "soak.sock")
    proc = None
    srv = None
    t_setup = time.perf_counter()
    if cfg.two_process:
        proc = _spawn_serve(cfg, sock, journal_dir, out_dir)
    else:
        from ..framework.leaderelection import FileLease, read_epoch
        from ..sidecar.server import SidecarServer

        os.makedirs(journal_dir, exist_ok=True)
        lease_path = os.path.join(journal_dir, "lease")
        lease = FileLease(lease_path, identity=f"soak-{os.getpid()}")
        lease.acquire(block=True)
        journal = Journal(
            journal_dir,
            epoch=lease.epoch,
            fence=lambda: read_epoch(lease_path),
            fsync=cfg.journal_fsync == "always",
        )
        srv = SidecarServer(
            sock,
            batch_size=cfg.batch_size,
            chunk_size=cfg.chunk_size,
            pipeline_depth=cfg.pipeline_depth,
            profiles=named_extra_profiles(cfg.profile),
            speculate=True,
            journal=journal,
            snapshot_every_batches=cfg.snapshot_every,
        )
        if cfg.node_grace_s > 0:
            srv.scheduler.node_lifecycle.arm(
                grace_period_s=cfg.node_grace_s,
                unreachable_after_s=(
                    cfg.node_unreachable_s or cfg.node_grace_s * 2.5
                ),
            )
            srv.scheduler.pod_gc.arm(
                gc_horizon_s=cfg.gc_horizon_s or cfg.node_grace_s * 6
            )
        srv.serve_background()

    driver = None
    phases: list[_PhaseResult] = []
    arrival_hashes: list[str] = []
    all_offsets: list[list[float]] = []
    try:
        driver = _Driver(cfg, sock, journal_dir)
        driver.build_fleet()
        driver.warmup()
        setup_s = round(time.perf_counter() - t_setup, 3)
        arrival_base = 0
        for k, (name, dur, rate) in enumerate(_phase_specs(cfg)):
            res, offsets = _run_phase(
                driver, cfg, k, name, dur, rate, arrival_base
            )
            arrival_base += len(offsets)
            phases.append(res)
            arrival_hashes.append(_sha([round(o, 9) for o in offsets]))
            all_offsets.append(offsets)
        if cfg.node_grace_s > 0:
            # Run to quiescence before measuring loop closure: requeued
            # eviction victims still in flight — or rolled back by the
            # final phase's invalidation churn — get their final
            # placements, so `reschedules` counts completed loops, not
            # the instant's pool state.  (Deterministic: the drain is
            # part of the op sequence in both same-seed runs.)
            driver.client.schedule([], drain=True)
        dump = driver.client.dump()
        bindings = {
            uid: rec["node"]
            for uid, rec in dump.get("pods", {}).items()
            if rec.get("node")
        }
        flight = driver.client.flight()
        flight_path = os.path.join(out_dir, "soak-flight.json")
        with open(flight_path, "w", encoding="utf-8") as f:
            json.dump(flight, f, indent=1, sort_keys=True)
    finally:
        if driver is not None:
            driver.close()
        if srv is not None:
            srv.close()
            lease.release()
        if proc is not None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)

    sustained = phases[0]
    knee_phases = phases[1:]
    miss_cost_ms = round(
        float(np.mean(sustained.miss_latencies)) * 1e3
        if sustained.miss_latencies
        else 0.0,
        3,
    )
    slo = dict(
        _lat_summary(sustained.latencies),
        budget_ms=cfg.slo_budget_ms,
        violations=sustained.violations,
        violation_rate=round(
            sustained.violations / max(1, sustained.decisions), 4
        ),
    )
    spec_stats = dump.get("speculation") or {}
    total_hits = sum(p.hits for p in phases)
    total_misses = sum(p.misses for p in phases)
    incidents = sorted(
        f
        for f in os.listdir(out_dir)
        if f.startswith("flight-")
        and f.endswith(".json")
        and f not in pre_existing
    )
    wal_max = max(driver.wal_samples) if driver.wal_samples else 0
    journal_stats = dump.get("journal") or {}
    node_loss = None
    if cfg.node_grace_s > 0:
        # Evictions counted by the server (taint eviction + GC); a
        # RESCHEDULE is a live pod whose final binding differs from the
        # node the driver first saw it bound to.
        lifecycle = dump.get("node_lifecycle") or {}
        gc_stats = dump.get("pod_gc") or {}
        moved = sum(
            1
            for uid, node in bindings.items()
            if uid in driver.pods_by_uid
            and getattr(driver.pods_by_uid[uid], "_lg_node", node) != node
        )
        gc_collected = sum(
            (gc_stats.get("collected") or {}).values()
        )
        ev = dump.get("evictions") or {}
        node_loss = {
            "node_deaths": driver.node_deaths,
            "node_revives": driver.node_revives,
            "lease_renewals": driver.lease_renewals,
            "lifecycle": lifecycle,
            "pod_gc": gc_stats,
            "evictions": ev.get("total", 0),
            "gc_collected": gc_collected,
            # Loop closure per pod (server-counted): distinct evicted
            # uids, and how many of them are bound AGAIN at the end —
            # eviction → requeue → reschedule completed.
            "evicted_uids": ev.get("evicted_uids", 0),
            "reschedules": ev.get("rebound", 0),
            # Broader churn: live pods whose final placement differs
            # from the first-delivered decision (includes speculative
            # full-rollback re-placements, not just evictions).
            "placements_moved": moved,
        }
    artifact = {
        "metric": "soak_slo_knee_journal",
        "seed": cfg.seed,
        "config": asdict(cfg),
        "setup_s": setup_s,
        "wall_s": round(sum(p.wall_s for p in phases), 3),
        "slo": slo,
        "sustained_pods_per_sec": round(
            sustained.decisions / sustained.wall_s
            if sustained.wall_s
            else 0.0,
            1,
        ),
        "speculation": {
            "hits": total_hits,
            "misses": total_misses,
            "miss_rate": round(
                total_misses / max(1, total_hits + total_misses), 4
            ),
            "sidecar": spec_stats,
        },
        "knee": _knee_analysis(knee_phases, miss_cost_ms),
        "journal": {
            "dir_sampled": bool(driver.wal_samples),
            "wal_bytes_max": wal_max,
            "wal_bytes_final": (
                driver.wal_samples[-1] if driver.wal_samples else 0
            ),
            "compactions_observed": driver.compactions_observed,
            # Bounded = compaction cycled repeatedly AND the final size
            # sits strictly below the high-water mark (a WAL that grows
            # monotonically to the end compacted too early to count).
            "bounded": bool(
                driver.compactions_observed >= 2
                and driver.wal_samples
                and driver.wal_samples[-1] < wal_max
            ),
            "stats": journal_stats,
        },
        "phases": [
            {
                "name": p.name,
                "invalidation_rate_per_s": p.invalidation_rate_per_s,
                "wall_s": p.wall_s,
                "decisions": p.decisions,
                "bound": p.bound,
                "hits": p.hits,
                "misses": p.misses,
                "retired": p.retired,
                "violations": p.violations,
                "events": dict(sorted(p.events_applied.items())),
                "latency": _lat_summary(p.latencies),
            }
            for p in phases
        ],
        "workload_mix": dict(driver.mix.counts),
        "tenants": (
            dict(
                per_tenant=_tenant_summary(phases),
                counters=(
                    driver.tenant_metrics.snapshot()
                    if driver.tenant_metrics is not None
                    else {}
                ),
                mix=dict(driver.mix.tenant_counts),
            )
            if cfg.tenants
            else None
        ),
        "node_loss": node_loss,
        "cold_consumers": driver.cold_consumers,
        "retired_total": driver.retired,
        "bound_final": len(bindings),
        "determinism": {
            "arrival_sha256": _sha(arrival_hashes),
            "bindings_sha256": _sha(sorted(bindings.items())),
            "arrivals_total": sum(len(o) for o in all_offsets),
        },
        "incidents": incidents,
        "flight": os.path.basename(flight_path),
        "pace": cfg.pace,
    }
    # Keep the raw offsets available to callers (the determinism smoke
    # compares them across runs) without bloating the JSON artifact.
    artifact["_arrival_offsets"] = all_offsets
    return artifact


# -- the partitioned-fleet soak ---------------------------------------------

FLEET_INV_MIX: tuple[tuple[str, float], ...] = (
    # The fleet feed has no namespace-label op (owners take the KINDS
    # surface only), so the churn budget splits over the two node-shaped
    # invalidations.
    ("inv_capacity", 0.7),
    ("inv_label", 0.3),
)


def _spawn_shard_serve(
    cfg: SoakConfig,
    shard: int,
    shards: int,
    sock: str,
    map_path: str,
    journal_dir: str,
    out_dir: str,
):
    """One REAL fleet owner: ``python -m kubernetes_tpu serve --shard-of
    k/N`` as a child process — its own journal, the shared shard-map
    file, the lifecycle flags armed per owner when the soak injects node
    deaths, flight dumps + the child's log into the artifact
    directory."""
    argv = [
        sys.executable, "-m", "kubernetes_tpu", "serve",
        "--socket", sock,
        "--shard-of", f"{shard}/{shards}",
        "--shard-map", map_path,
        "--batch-size", str(cfg.batch_size),
        "--chunk-size", "1",
        "--journal-dir", journal_dir,
        "--journal-fsync", cfg.journal_fsync,
        "--snapshot-every", str(cfg.snapshot_every),
    ] + ([] if cfg.observability else ["--no-observability"]) \
      + (["--profile", cfg.profile] if cfg.profile else []) \
      + _lifecycle_argv(cfg)
    return _launch_serve(
        argv, out_dir, sock, f"serve-shard{shard}", deadline_s=300.0
    )


def _spawn_standby_serve(cfg: SoakConfig, sock: str, out_dir: str, slot: int):
    """One warm-standby fleet child: ``serve --standby`` — engine booted
    and compiled, no shard, no journal, lease unclaimed — parked until a
    promotion's adopt_shard frame (fleet/standby.py).  Lifecycle knobs
    ride the adopt payload, not the argv: a slot is shard-agnostic."""
    argv = [
        sys.executable, "-m", "kubernetes_tpu", "serve",
        "--socket", sock,
        "--standby",
        "--batch-size", str(cfg.batch_size),
        "--chunk-size", "1",
    ] + ([] if cfg.observability else ["--no-observability"]) \
      + (["--profile", cfg.profile] if cfg.profile else [])
    return _launch_serve(
        argv, out_dir, sock, f"standby{slot}", deadline_s=300.0
    )


def _standby_warm_objs(
    cfg: SoakConfig, warm_tenants, hot: bool, armed: bool, epoch_hi: int = 4
):
    """The standby warm wave (ISSUE 18): every label-schema axis the
    live stream can reach — zones, accelerator classes, epoch labels,
    the hot selector, lifecycle taints, tenant/template label combos —
    built as objects a parked child exercises BEFORE promotion, so
    adoption never pays an XLA recompile mid-incident.  Mirrors
    run_fleet_soak's own warmup (same WorkloadMix template space,
    disjoint index range + ``sbwarm-`` node names: everything here is
    removed again after compiling, leaving only the grown vocab)."""
    nodes = []
    for i in range(max(cfg.zones, 12)):
        w = (
            make_node(f"sbwarm-{i}")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
            .zone(f"zone-{i % max(cfg.zones, 1)}")
            .region("region-1")
        )
        w = _accel_label(cfg, w, i)
        if hot:
            w = w.label("loadgen.tpu/hot", "1")
        nodes.append(w.obj())
    epoch_nodes = []
    for epoch in range(1, epoch_hi + 1):
        w = (
            make_node("sbwarm-0")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
            .zone("zone-0")
            .region("region-1")
            .label("loadgen.tpu/epoch", str(epoch))
        )
        if hot:
            w = w.label("loadgen.tpu/hot", "1")
        epoch_nodes.append(_accel_label(cfg, w, 0).obj())
    tainted = []
    if armed:
        import dataclasses

        from ..controllers import (
            NODE_NOT_READY,
            NODE_UNREACHABLE,
            lifecycle_taints,
        )

        probe = nodes[0]
        tainted.append(
            dataclasses.replace(
                probe,
                spec=dataclasses.replace(
                    probe.spec,
                    taints=lifecycle_taints(NODE_NOT_READY)
                    + lifecycle_taints(NODE_UNREACHABLE),
                ),
            )
        )
    warm_mix = WorkloadMix(
        cfg.mix,
        seed=cfg.seed * 104_729 + 31,
        scheduler_name=profile_scheduler_name(cfg.profile),
    )
    n_warm = min(cfg.warm_pods, 48)
    pods = [
        warm_mix.pod(
            30_000_000 + i,
            # Block-assigned tenants — the same combo-coverage argument
            # as the fleet warmup's own wave.
            tenant=(
                warm_tenants[
                    min(
                        (i * len(warm_tenants)) // max(n_warm, 1),
                        len(warm_tenants) - 1,
                    )
                ]
                if warm_tenants
                else None
            ),
        )
        for i in range(n_warm)
    ]
    if hot:
        for j, p in enumerate(pods):
            if j % 2 == 0:
                p.spec.node_selector["loadgen.tpu/hot"] = "1"
    preemptor = (
        make_pod("sbwarm-preemptor").req({"cpu": "12"}).priority(100).obj()
    )
    probe_pod = warm_mix.pod(
        30_900_000, tenant=warm_tenants[0] if warm_tenants else None
    )
    return nodes, epoch_nodes, tainted, pods, preemptor, probe_pod


def _warm_standby_sched(
    cfg: SoakConfig, sched, warm_tenants, hot: bool, armed: bool,
    epoch_hi: int = 4,
) -> None:
    """Warm an IN-PROCESS standby scheduler: add every schema-growing
    node variant, bind + delete a combo-covering pod wave, dry-run the
    preemptor, remove the warm nodes, and absorb the dirty-row flush
    with one eval-only probe — the promoted owner's journal recovery
    then replays real objects into an already-compiled engine."""
    nodes, epoch_nodes, tainted, pods, preemptor, probe = _standby_warm_objs(
        cfg, warm_tenants, hot, armed, epoch_hi
    )
    for n in nodes:
        sched.add_node(n)
    for n in epoch_nodes:
        sched.add_node(n)
    sched.add_node(nodes[0])  # restore sbwarm-0's epoch-free shape
    for n in tainted:
        sched.add_node(n)
    if tainted:
        sched.add_node(nodes[0])
    for p in pods:
        sched.update_pod(p)
    sched.schedule_all_pending()
    sched.preempt_propose(preemptor)
    for p in pods:
        sched.delete_pod(p.uid)
    for n in nodes:
        sched.remove_node(n.metadata.name)
    sched.propose_pod(probe)


def _warm_standby_wire(
    cfg: SoakConfig, sock: str, warm_tenants, hot: bool, armed: bool,
    epoch_hi: int = 4,
) -> None:
    """The two-process twin of ``_warm_standby_sched``: drive the same
    warm wave into a parked `serve --standby` child over its socket
    (the preempt dry-run rides the fleet frame, which StandbyServe
    allows pre-adoption for exactly this)."""
    from ..api import serialize

    nodes, epoch_nodes, tainted, pods, preemptor, probe = _standby_warm_objs(
        cfg, warm_tenants, hot, armed, epoch_hi
    )
    client = SidecarClient(sock, deadline_s=300.0)
    try:
        for n in nodes:
            client.add("Node", n)
        for n in epoch_nodes:
            client.add("Node", n)
        client.add("Node", nodes[0])
        for n in tainted:
            client.add("Node", n)
        if tainted:
            client.add("Node", nodes[0])
        client.schedule(pods, drain=True)
        client.fleet("preempt_propose", {"pod": serialize.to_dict(preemptor)})
        for p in pods:
            client.remove("Pod", p.uid)
        for n in nodes:
            client.remove("Node", n.metadata.name)
        client.schedule([probe], drain=True)
        client.remove("Pod", probe.uid)
    finally:
        client.close()


def run_fleet_soak(cfg: SoakConfig, shards: int = 2) -> dict:
    """Soak the PARTITIONED fleet (kubernetes_tpu/fleet): open-loop
    arrivals scatter-gathered by the router over ``shards`` journaled
    shard owners, with the existing loadgen scenarios re-aimed at the
    fleet's failure surfaces —

    - **node flaps hit ONE shard**: the churn pool is pinned to shard 0
      by shard-map overrides, so a flapping shard's SLO degrades while
      the others' hold (visible in the per-shard percentiles);
    - **node DEATHS inside a shard** (``node_grace_s > 0``): churn-node
      heartbeats go silent, the OWNING shard's lifecycle controller
      writes the taints and evicts, and the router requeues the evicted
      pods to rebind on whichever shard has room — the cross-shard half
      of the failure-response loop, counted per shard;
    - **cold router restarts** (the fleet's cold-consumer analog): the
      ``cold_consumer`` scenario event tears the router down mid-stream
      and rebuilds it from the owners' truth (adopt_bindings) — pending
      pods re-feed, bound pods must not double-schedule, absorbed-but-
      unbound evictions re-adopt;
    - **per-shard SLO percentiles + WAL growth**: each decision's latency
      is attributed to the shard that committed it, and every owner's
      journal is sampled for bounded-compaction evidence.

    ``cfg.two_process=True`` runs the REAL multi-process fleet: N
    ``serve --shard-of k/N`` children over the unix-socket wire, driven
    through ``WireShardOwner`` with per-call deadlines — a hung or dead
    owner degrades to TAKEOVER (the child restarts, recovers its own
    journal before its first frame, and the router re-adopts) instead of
    wedging scatter-gather.

    Same determinism contract as run_soak: the operation sequence is a
    pure function of the seed, so same-seed runs land bit-identical
    final bindings (the --shards determinism cross-check in
    scripts/run_soak.py asserts exactly that)."""
    from ..fleet import (
        AutoscalerConfig,
        FleetAutoscaler,
        FleetOwnerUnreachable,
        FleetRouter,
        ShardMap,
        ShardOwner,
        WireShardOwner,
    )
    from ..scheduler import TPUScheduler

    ckpt_prior = None
    resume_from = 0
    if cfg.resume:
        if not cfg.checkpoint_path:
            raise ValueError("SoakConfig.resume requires checkpoint_path")
        ckpt_prior = load_checkpoint(cfg.checkpoint_path)
        if ckpt_prior is None:
            raise RuntimeError(
                f"resume requested but no checkpoint at {cfg.checkpoint_path}"
            )
        resume_from = int(ckpt_prior["state"]["det"]["op_index"])
    tmp = tempfile.TemporaryDirectory(prefix="tpu-fleet-soak-")
    out_dir = cfg.out_dir or tmp.name
    os.makedirs(out_dir, exist_ok=True)
    journal_root = cfg.journal_dir or os.path.join(tmp.name, "journal")
    if cfg.resume:
        # Replay regenerates every owner journal from op 0 — recovering a
        # prior run's journals UNDERNEATH the replay would double-apply
        # its state, so a resumed run always writes fresh shard journals,
        # keyed by the checkpoint generation it resumed from.
        journal_root = os.path.join(
            journal_root, f"resume-g{int(ckpt_prior['generation'])}"
        )
    armed = cfg.node_grace_s > 0
    lifecycle = (
        {
            "node_grace_s": cfg.node_grace_s,
            "node_unreachable_s": cfg.node_unreachable_s,
            "gc_horizon_s": cfg.gc_horizon_s,
        }
        if armed
        else None
    )
    smap = ShardMap(n_shards=shards)
    for i in range(cfg.churn_nodes):
        smap.assign(f"churn-{i}", 0)  # flaps/deaths land on shard 0 only
    # The hot pool (ISSUE 11's hot-spot scenario): the serving nodes the
    # INITIAL map buckets onto shard 0 carry the hot label, and
    # hot_fraction of arrivals select on it — their load concentrates
    # there until the autoscaler's split moves half the pool (bucketed,
    # not pinned: pins survive splits by design and would anchor it).
    hot_serving = (
        {i for i in range(cfg.nodes) if smap.owner_of(f"lgn-{i}") == 0}
        if cfg.hot_fraction > 0
        else set()
    )
    registry = MetricsRegistry()
    owners: dict[int, object] = {}
    procs: dict[int, object] = {}
    socks: dict[int, str] = {}
    map_path = os.path.join(tmp.name, "shardmap.json")

    def spawn_owner(k: int):
        if not cfg.two_process:
            return ShardOwner(
                k,
                TPUScheduler(
                    batch_size=cfg.batch_size,
                    chunk_size=1,
                    tenant_attribution=cfg.observability,
                    profiles=named_extra_profiles(cfg.profile),
                ),
                smap,
                state_dir=os.path.join(journal_root, f"shard{k}"),
                journal_fsync=cfg.journal_fsync == "always",
                snapshot_every_batches=cfg.snapshot_every,
                lifecycle=lifecycle,
                observability=cfg.observability,
            )
        socks[k] = os.path.join(tmp.name, f"shard{k}.sock")
        procs[k] = _spawn_shard_serve(
            cfg, k, shards, socks[k], map_path,
            os.path.join(journal_root, f"shard{k}"), out_dir,
        )
        return WireShardOwner(
            path=socks[k],
            deadline_s=120.0,
            max_retries=2,
            registry=registry,
            shard_id=k,
        )

    if cfg.two_process:
        smap.save(map_path)  # shared ownership record, before any child
    for k in range(shards):
        owners[k] = spawn_owner(k)
    # Children die with the run, success or not: any exception out of
    # the warmup or the op loop (a protocol desync, an assertion, a
    # KeyboardInterrupt) must not leak N serve processes holding
    # journal leases and sockets.
    standby = None
    ckpt = None
    try:
        mix = WorkloadMix(
            cfg.mix,
            seed=cfg.seed * 7919 + 11,
            tenants=cfg.tenants,
            scheduler_name=profile_scheduler_name(cfg.profile),
        )
        slo_hist, slo_violations = _slo_families(
            registry, cfg.slo_budget_ms
        )
        tenant_metrics = (
            TenantMetrics(registry, hash_buckets=cfg.tenant_hash_buckets)
            if cfg.observability
            else None
        )
        node_objs: dict[str, object] = {}
        feed_order: list[str] = []
        router_restarts = 0
        owner_takeovers = 0
        # Durable admission order across router rebuilds: a cold restart
        # rebuilds the router (fresh fairness ledger — deterministic, the
        # restart is a seeded scenario event), so the run-wide order is
        # the concatenation of every router generation's admitted_log.
        admission_order: list[str] = []

        def mk_admission_policy():
            """One FairAdmission per router generation: weights are
            accelerator-time shares from the synthetic throughput matrix
            over the streams' workload_class mapping and the configured
            hetero pools (uniform fallback when unmapped); clock is the
            router's logical clock (arm_admission injects it); metrics
            ride the soak registry when observability is on — and only
            observe: decisions are identical with it off."""
            from ..framework.fairness import FairAdmission, weights_from_matrix
            from ..ops.throughput import DEFAULT_THROUGHPUT_MATRIX

            a = dict(cfg.admission or {})
            classes = {
                str(ts["name"]): str(ts["workload_class"])
                for ts in cfg.tenant_streams
                if ts.get("workload_class")
            }
            pools = (
                {str(ac): int(wt) for ac, wt in cfg.hetero_pools} or None
            )
            return FairAdmission(
                weights=weights_from_matrix(
                    DEFAULT_THROUGHPUT_MATRIX, classes, pools
                ),
                rate_pods_per_s=float(a.get("rate_pods_per_s", 0.0)),
                burst=float(a.get("burst", 8.0)),
                aging_max_wait_s=float(a.get("aging_max_wait_s", 30.0)),
                slo_wait_budget_s=float(a.get("slo_wait_budget_s", 60.0)),
                registry=registry if tenant_metrics is not None else None,
                labeler=(
                    tenant_metrics.labeler
                    if tenant_metrics is not None
                    else None
                ),
            )

        def mk_router() -> FleetRouter:
            r = FleetRouter(
                owners, smap, batch_size=cfg.batch_size, registry=registry,
                observability=cfg.observability,
            )
            if cfg.two_process:
                from ..framework.config import DEFAULT_PROFILE

                r.profile_filters = tuple(DEFAULT_PROFILE.filters)
            else:
                r.profile_filters = tuple(owners[0].sched.profile.filters)
            return r

        def feed_node(r: FleetRouter, n) -> None:
            name = n.metadata.name
            if name not in node_objs:
                feed_order.append(name)
            node_objs[name] = n
            r.add_object("Node", n)

        router = mk_router()
        # Build/warmup flight records sort ahead of the measured window
        # on the logical axis.
        router.note_logical_time(-1.0)
        autoscaler = None  # built below, once the sampling dicts exist
        for i in range(cfg.nodes):
            w = (
                make_node(f"lgn-{i}")
                .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
                .zone(f"zone-{i % cfg.zones}")
                .region("region-1")
            )
            w = _accel_label(cfg, w, i)
            if i in hot_serving:
                w = w.label("loadgen.tpu/hot", "1")
            feed_node(router, w.obj())
        for i in range(cfg.churn_nodes):
            feed_node(
                router,
                _accel_label(
                    cfg,
                    make_node(f"churn-{i}")
                    .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
                    .zone(f"zone-{i % cfg.zones}")
                    .region("region-1"),
                    i,
                ).obj(),
            )
        if armed:
            from ..api import types as t
            from ..controllers import (
                NODE_NOT_READY,
                NODE_UNREACHABLE,
                lifecycle_taints,
            )

            # Pre-seed the lifecycle taint keys into EVERY owner's
            # featurization vocab BEFORE warmup compiles the device
            # programs.  Two traps close here: (1) the first mid-soak
            # transition would otherwise grow the taint schema and pay a
            # full XLA recompile inside the measured window (PR 9's
            # single-scheduler trap); (2) TaintToleration's is_active gate
            # keys on the LOCAL vocab — a shard that never interned a taint
            # would skip the op while the churn shard runs it, skewing the
            # reverse-normalized baseline (+MaxNodeScore×weight on the
            # tainted shard's nodes) and funnelling every decision there.
            # With the vocab uniform, lifecycle taints carry exactly
            # upstream's score semantics: none (only PreferNoSchedule
            # counts), so per-shard normalization agrees.
            import dataclasses

            def preseed(name: str) -> None:
                probe = node_objs[name]
                tainted = dataclasses.replace(
                    probe,
                    spec=dataclasses.replace(
                        probe.spec,
                        taints=lifecycle_taints(NODE_NOT_READY)
                        + lifecycle_taints(NODE_UNREACHABLE),
                    ),
                )
                router.add_object("Node", tainted)
                router.add_object("Node", probe)

            preseed("churn-0")  # shard 0 (the pinned churn pool)
            seeded = {smap.owner_of("churn-0")}
            for i in range(cfg.nodes):
                name = f"lgn-{i}"
                k = smap.owner_of(name)
                if k not in seeded:
                    seeded.add(k)
                    preseed(name)
                if len(seeded) == shards:
                    break
            # Only churn nodes carry Leases: the per-owner lifecycle loop
            # governs exactly the death-eligible pool; the serving fleet
            # stays exempt (unleased nodes are never tainted).
            for i in range(cfg.churn_nodes):
                router.add_object("Lease", t.Lease(f"churn-{i}", 0.0))

        # Warm the compiled eval passes out of the measured window.  Two
        # things force a recompile mid-stream if not warmed here: a pod
        # class whose active-op set first appears inside the window, and the
        # inv_label scenario's epoch labels growing the node-label vocab
        # (a new schema keys a new compiled pass — one ~20s CPU-box compile
        # lands squarely on the measured percentiles).  So the warm wave
        # draws from the SAME WorkloadMix templates (renamed far outside the
        # stream's index space) and the vocab is pre-seeded with the epoch
        # label values the scenario can reach, then the node is restored.
        warm_mix = WorkloadMix(
            cfg.mix,
            seed=cfg.seed * 104_729 + 31,
            scheduler_name=profile_scheduler_name(cfg.profile),
        )
        for epoch in range(1, 5):
            w = (
                make_node("lgn-0")
                .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
                .zone("zone-0")
                .region("region-1")
                .label("loadgen.tpu/epoch", str(epoch))
            )
            if 0 in hot_serving:
                w = w.label("loadgen.tpu/hot", "1")
            feed_node(router, w.obj())
        # Tenant labels grow the pod-label vocab: the warm wave must
        # carry every tenant the stream will, or the first tenant-tagged
        # arrival pays a full XLA recompile inside the measured window
        # (the same trap the epoch/hot-label pre-seeds close).
        warm_tenants = [
            str(ts["name"]) for ts in cfg.tenant_streams
        ] or [name for name, _w in mix.tenants]
        n_warm = min(cfg.warm_pods, 48)
        warm = [
            warm_mix.pod(
                10_000_000 + i,
                # BLOCK-assigned (not cycled): the group vocab interns
                # label SETS, so every (template-label, tenant) combo
                # must appear in warmup — a cycled assignment correlates
                # tenant with the template's i%10 label and covers only
                # half the combos, leaving a schema growth (and its XLA
                # recompile) for the first unlucky mid-window arrival.
                tenant=(
                    warm_tenants[
                        min(
                            (i * len(warm_tenants)) // max(n_warm, 1),
                            len(warm_tenants) - 1,
                        )
                    ]
                    if warm_tenants
                    else None
                ),
            )
            for i in range(n_warm)
        ]
        if hot_serving:
            # Half the warm wave carries the hot selector so the
            # NodeAffinity op and its selector schema compile OUTSIDE
            # the measured window (a first hot arrival would otherwise
            # pay the XLA compile mid-soak).
            for j, p in enumerate(warm):
                if j % 2 == 0:
                    p.spec.node_selector["loadgen.tpu/hot"] = "1"
        for p in warm:
            router.add_pod(p)
        router.schedule_all_pending()
        # Compile the preemption dry-run programs too (they otherwise first
        # fire when the cluster fills, deep inside the measured window).
        # preempt_propose is eval-only: nothing is deleted or nominated.
        from ..api import serialize

        warm_preemptor = (
            make_pod("lgwarm-preemptor").req({"cpu": "12"}).priority(100).obj()
        )
        for owner in owners.values():
            owner.call(
                "preempt_propose", {"pod": serialize.to_dict(warm_preemptor)}
            )
        for p in warm:
            if p.uid in router._pod_shard:
                router.remove_object("Pod", p.uid)
            else:
                router.queue.delete(p.uid)
        # Restore lgn-0 to its serving shape (epoch label cleared).
        w = (
            make_node("lgn-0")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
            .zone("zone-0")
            .region("region-1")
        )
        if 0 in hot_serving:
            w = w.label("loadgen.tpu/hot", "1")
        feed_node(router, w.obj())
        # The warm deletions above marked node rows dirty: the NEXT eval
        # pass pays the dirty-row scatter-flush XLA compile (~0.5s/owner
        # on this box — the single scheduler's warm_tail covers this,
        # fleet owners never call it).  One throwaway propose per owner
        # absorbs it outside the measured window; propose is eval-only.
        flush_probe = warm_mix.pod(
            10_900_000,
            tenant=warm_tenants[0] if warm_tenants else None,
        )
        for owner in owners.values():
            owner.call("propose", {"pod": serialize.to_dict(flush_probe)})
        if cfg.admission is not None:
            # Arm AFTER warmup: the warm wave must flood through
            # unthrottled (finite burst credits at a frozen logical clock
            # would starve half the label-combo compiles out of the warm
            # window) and the measured window must open on a clean
            # fairness ledger.
            router.arm_admission(mk_admission_policy())

        # -- warm-standby owner pool (ISSUE 18) ------------------------
        # Built AFTER warmup so the slots compile against the same live
        # schema the fleet just finished growing.  The schema version is
        # a crc32 over every axis the warm wave covers — when the live
        # vocab outgrows it mid-run (an epoch label past the warm range),
        # stale slots are retired + respawned against the wider range,
        # never promoted.
        standby_promotions: list[dict] = []
        standby_cold = 0
        warm_epoch_hi = [4]

        def _live_schema() -> int:
            return zlib.crc32(
                json.dumps(
                    [
                        sorted(warm_tenants),
                        sorted(str(a) for a, _w in cfg.hetero_pools),
                        cfg.profile,
                        bool(hot_serving),
                        cfg.admission is not None,
                        armed,
                        warm_epoch_hi[0],
                    ],
                    sort_keys=True,
                ).encode("utf-8")
            )

        if cfg.standby_pool > 0:
            from ..fleet.standby import StandbyPool

            def _standby_factory(slot_id: int):
                if not cfg.two_process:
                    sb_sched = TPUScheduler(
                        batch_size=cfg.batch_size,
                        chunk_size=1,
                        tenant_attribution=cfg.observability,
                        profiles=named_extra_profiles(cfg.profile),
                    )
                    _warm_standby_sched(
                        cfg, sb_sched, warm_tenants, bool(hot_serving),
                        armed, warm_epoch_hi[0],
                    )
                    return {"sched": sb_sched}
                sb_sock = os.path.join(tmp.name, f"standby{slot_id}.sock")
                sb_proc = _spawn_standby_serve(cfg, sb_sock, out_dir, slot_id)
                _warm_standby_wire(
                    cfg, sb_sock, warm_tenants, bool(hot_serving), armed,
                    warm_epoch_hi[0],
                )
                return {"sock": sb_sock, "proc": sb_proc}

            def _standby_retire(payload) -> None:
                sb_proc = payload.get("proc")
                if sb_proc is not None and sb_proc.poll() is None:
                    sb_proc.send_signal(signal.SIGTERM)
                sb_sock = payload.get("sock")
                if sb_sock and os.path.exists(sb_sock):
                    os.unlink(sb_sock)

            standby = StandbyPool(
                cfg.standby_dir or os.path.join(tmp.name, "standby"),
                _standby_factory,
                size=cfg.standby_pool,
                schema_version=_live_schema(),
                registry=registry,
                retire=_standby_retire,
                mirror_path=(
                    f"{map_path}.standby.json" if cfg.two_process else None
                ),
            )

        def promote_owner(k: int, reason: str):
            """Draw a warm child from the standby pool for shard ``k``
            (autoscale split or takeover revive): journaled claim +
            adopt_shard handoff + lease claim — O(handoff), not a cold
            boot.  A pool miss falls back to the cold spawn path the
            fleet always had (counted, never hidden)."""
            nonlocal standby_cold
            t0p = time.perf_counter()
            payload = standby.promote(k, reason)
            if payload is None:
                standby_cold += 1
                o = spawn_owner(k)
                standby_promotions.append(
                    {
                        "shard": k, "reason": reason, "from_pool": False,
                        "latency_s": round(time.perf_counter() - t0p, 4),
                        "t": round(router.lc() if router else -1.0, 3),
                    }
                )
                return o
            sdir = os.path.join(journal_root, f"shard{k}")
            if not cfg.two_process:
                o = ShardOwner(
                    k,
                    payload["sched"],
                    smap,
                    state_dir=sdir,
                    journal_fsync=cfg.journal_fsync == "always",
                    snapshot_every_batches=cfg.snapshot_every,
                    lifecycle=lifecycle,
                    observability=cfg.observability,
                )
            else:
                socks[k] = payload["sock"]
                procs[k] = payload["proc"]
                o = WireShardOwner(
                    path=socks[k],
                    deadline_s=120.0,
                    max_retries=2,
                    registry=registry,
                    shard_id=k,
                )
                o.call(
                    "adopt_shard",
                    {
                        "shard_id": k,
                        "map_path": map_path,
                        "journal_dir": sdir,
                        "journal_fsync": cfg.journal_fsync == "always",
                        "snapshot_every": cfg.snapshot_every,
                        "lifecycle": lifecycle,
                    },
                )
            standby_promotions.append(
                {
                    "shard": k, "reason": reason, "from_pool": True,
                    "latency_s": round(time.perf_counter() - t0p, 4),
                    "t": round(router.lc() if router else -1.0, 3),
                }
            )
            return o

        cap_toggle: dict[int, int] = {}
        label_epoch: dict[int, int] = {}
        live: deque[str] = deque()
        pods_by_uid: dict[str, object] = {}
        pending: dict[str, object] = {}  # decided-but-unbound, for restarts
        dead: set[str] = set()  # churn nodes with silenced heartbeats
        node_deaths = 0
        node_revives = 0
        lease_renewals = 0
        per_shard_lat: dict[int, list[float]] = {k: [] for k in owners}
        wal_prev: dict[int, int] = {k: 0 for k in owners}
        wal_samples: dict[int, list[int]] = {k: [] for k in owners}
        compactions: dict[int, int] = {k: 0 for k in owners}

        def sample_wal() -> None:
            for k in owners:
                try:
                    size = os.path.getsize(
                        os.path.join(journal_root, f"shard{k}", Journal.WAL)
                    )
                except OSError:
                    size = 0
                if size < wal_prev[k]:
                    compactions[k] += 1
                wal_prev[k] = size
                wal_samples[k].append(size)

        autoscale_actions: list[dict] = []
        lat_trace: list[tuple[float, int, float]] = []  # (t, shard, lat)

        def autoscale_provider(k: int):
            """Owner for a split-created shard: the same spawn path the
            build uses (a real `serve --shard-of k/N` child in the
            multi-process fleet — the map file may predate the split;
            the router's set_map push closes that before the import),
            plus fresh sampling slots.  With the standby pool armed the
            owner comes pre-warmed from the pool instead (ISSUE 18) —
            the split's new shard skips the child's cold boot."""
            o = (
                promote_owner(k, "autoscale-split")
                if standby is not None
                else spawn_owner(k)
            )
            owners[k] = o
            wal_prev.setdefault(k, 0)
            wal_samples.setdefault(k, [])
            compactions.setdefault(k, 0)
            per_shard_lat.setdefault(k, [])
            return o

        def autoscale_retirer(k: int, owner) -> None:
            """A merged-away shard's owner drains and stops; its serve
            child (if any) terminates now and is reaped with the rest."""
            owners.pop(k, None)
            try:
                owner.close()
            except OSError:
                pass
            proc = procs.get(k)
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)

        if cfg.autoscale:
            autoscaler = FleetAutoscaler(
                router,
                AutoscalerConfig(
                    split_imbalance_hi=cfg.autoscale_split_hi,
                    merge_imbalance_lo=cfg.autoscale_merge_lo,
                    decide_every_s=cfg.autoscale_interval_s,
                    cooldown_s=cfg.autoscale_cooldown_s,
                    window_s=cfg.autoscale_window_s,
                    max_actions_per_window=cfg.autoscale_budget,
                    min_window_decisions=cfg.autoscale_min_decisions,
                    max_shards=cfg.autoscale_max_shards,
                ),
                map_path=map_path if cfg.two_process else None,
                owner_provider=autoscale_provider,
                owner_retirer=autoscale_retirer,
                registry=registry,
                state_path=os.path.join(out_dir, "autoscaler.json"),
            )

        if cfg.preload_bound:
            # The pre-bound population: seeded, hot-marked like the
            # stream, scheduled through the real router path (journals
            # and all) before the window opens.  Rides the live-pod cap
            # like any stream binding, so retirement churns it.
            pre_mix = WorkloadMix(
                cfg.mix,
                seed=cfg.seed * 31 + 7,
                scheduler_name=profile_scheduler_name(cfg.profile),
            )
            pre_rng = _rng(cfg.seed * 1_000_003 + 313_131)
            pre_draws = pre_rng.random(cfg.preload_bound)
            for i in range(cfg.preload_bound):
                p = pre_mix.pod(20_000_000 + i)
                if cfg.hot_fraction > 0 and pre_draws[i] < cfg.hot_fraction:
                    p.spec.node_selector["loadgen.tpu/hot"] = "1"
                router.add_pod(p)
            for o in router.schedule_all_pending():
                if o.node_name:
                    o.pod._lg_node = o.node_name
                    pods_by_uid[o.pod.uid] = o.pod
                    live.append(o.pod.uid)
            if autoscaler is not None:
                # Preload binds are setup, not window signal: the first
                # decision window opens at the stream.
                autoscaler.rebind_router(router)

        def serving_node(i: int):
            w = (
                make_node(f"lgn-{i}")
                .capacity(
                    {
                        "cpu": "15" if cap_toggle.get(i) else "16",
                        "memory": "64Gi",
                        "pods": 110,
                    }
                )
                .zone(f"zone-{i % cfg.zones}")
                .region("region-1")
            )
            if label_epoch.get(i):
                w = w.label("loadgen.tpu/epoch", str(label_epoch[i]))
            if i in hot_serving:
                # Hot-pool membership is fixed at build time — an
                # invalidation re-feed must not quietly shrink it.
                w = w.label("loadgen.tpu/hot", "1")
            return w.obj()

        def rebuild_router() -> FleetRouter:
            """A fresh front door over the owners' truth (cold restart or
            post-takeover re-adopt): node positions re-derive from the
            recorded feed order (the row-allocator mirror must land where
            the dead router's did), parked journal bindings re-apply,
            bindings re-adopt, crash-surfaced evictions drain, the dead
            router's absorbed-but-unbound evictions re-adopt, and
            still-pending pods re-feed."""
            prior_evicted = dict(router.evicted_pending) if router else {}
            if router and router.queue.admission is not None:
                # Harvest the dying generation's admitted order before the
                # fresh ledger starts from zero: the run-wide admission
                # order is the concatenation across generations.
                admission_order.extend(router.queue.admission.admitted_log)
            r = mk_router()
            if cfg.admission is not None:
                # Mid-run rebuilds arm at build (no warm wave to protect):
                # the re-fed pending pods below enqueue straight into the
                # fresh generation's ledger.
                r.arm_admission(mk_admission_policy())
            # The logical clock follows the front door: adoption-time
            # flight records keep the scenario axis.
            r.note_logical_time(router.lc() if router else -1.0)
            for name in feed_order:
                if name in node_objs:
                    r.add_object("Node", node_objs[name])
            if armed:
                # The owners keep their own heartbeat state; the router only
                # needs its clock high-water mark back so the next renewal's
                # broadcast gate behaves — harmless extra ticks otherwise.
                r._lifecycle_hw = router._lifecycle_hw if router else 0.0
            r.reconcile_recovered()
            r.adopt_bindings()
            r.drain_evictions()
            r.readopt_evictions(prior_evicted)
            for uid in sorted(pending):
                r.add_pod(pending[uid])
            if autoscaler is not None:
                # The control loop follows the front door: fresh commit
                # counters mean the next window starts at the restart.
                autoscaler.rebind_router(r)
            return r

        def revive_owner(k: int) -> None:
            """Bounded-retry exhausted on shard ``k`` (hung or dead child):
            TAKEOVER — kill whatever is left, restart the serve child (it
            recovers its own journal before the first frame), and rebuild
            the router over the recovered truth."""
            nonlocal router, owner_takeovers
            proc = procs.get(k)
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
            try:
                owners[k].close()
            except OSError:
                pass
            if socks.get(k) and os.path.exists(socks[k]):
                os.unlink(socks[k])
            # With the standby pool armed, the replacement comes WARM
            # (ISSUE 18): promotion = journaled handoff + lease claim
            # over the dead owner's journal dir, and the recovery replay
            # lands in an already-compiled engine — the ~15s boot the
            # takeover used to pay mid-incident disappears.
            owners[k] = (
                promote_owner(k, "revive")
                if standby is not None
                else spawn_owner(k)
            )
            owner_takeovers += 1
            router = rebuild_router()

        def apply_event(ev) -> None:
            nonlocal router, router_restarts, node_deaths, node_revives
            nonlocal lease_renewals
            if ev.kind == "inv_capacity":
                i = ev.data % cfg.nodes
                cap_toggle[i] = 1 - cap_toggle.get(i, 0)
                feed_node(router, serving_node(i))
            elif ev.kind == "inv_label":
                i = ev.data % cfg.nodes
                label_epoch[i] = label_epoch.get(i, 0) + 1
                feed_node(router, serving_node(i))
                if standby is not None and label_epoch[i] > warm_epoch_hi[0]:
                    # The epoch label grew past the warm range: the live
                    # featurization schema is now ahead of the pool's
                    # compiled programs.  Stale slots retire + respawn
                    # against the widened range — NEVER promote — so a
                    # later promotion still lands in a current engine.
                    warm_epoch_hi[0] = label_epoch[i]
                    standby.sync_schema(_live_schema())
            elif ev.kind == "node_death":
                # The Node object STAYS; its heartbeat goes silent.  The
                # OWNING shard's lifecycle controller must detect the
                # staleness, taint, evict — and the router must rebind the
                # evicted pods on surviving shards.
                dead.add(f"churn-{ev.data % max(1, cfg.churn_nodes)}")
                node_deaths += 1
            elif ev.kind == "node_revive":
                from ..api import types as t

                name = f"churn-{ev.data % max(1, cfg.churn_nodes)}"
                dead.discard(name)
                router.add_object("Lease", t.Lease(name, ev.t))
                lease_renewals += 1
                node_revives += 1
            elif ev.kind == "lease_tick":
                from ..api import types as t

                for i in range(cfg.churn_nodes):
                    name = f"churn-{i}"
                    if name not in dead and name in node_objs:
                        router.add_object("Lease", t.Lease(name, ev.t))
                        lease_renewals += 1
            elif ev.kind == "flap_down":
                name = f"churn-{ev.data}"
                gone = sorted(
                    uid
                    for uid in live
                    if getattr(pods_by_uid.get(uid), "_lg_node", None) == name
                )
                if gone:
                    gone_set = set(gone)
                    for u in gone:
                        pods_by_uid.pop(u, None)
                    live_kept = deque(u for u in live if u not in gone_set)
                    live.clear()
                    live.extend(live_kept)
                if name in node_objs and name in router._node_pos:
                    router.remove_object("Node", name)
            elif ev.kind == "flap_up":
                feed_node(router, node_objs[f"churn-{ev.data}"])
            elif ev.kind == "cold_consumer":
                # Cold ROUTER restart: the front door is rebuilt from the
                # owners' truth mid-stream — bound pods must not
                # double-schedule, and absorbed-but-unbound evictions
                # survive the restart (readopt_evictions).
                router = rebuild_router()
                router_restarts += 1
            elif ev.kind == "autoscale_tick":
                # The elastic control loop, on the scenario clock: the
                # binding-rate window is a pure function of the op
                # stream, so the split/merge history replays same-seed.
                if autoscaler is not None:
                    for act in autoscaler.tick(ev.t):
                        autoscale_actions.append(dict(act, t=ev.t))
            elif ev.kind == "owner_kill":
                # Scripted owner SIGKILL (the production-day incident
                # schedule): a serve child dies mid-stream.  Two-process,
                # the NEXT op that touches its shard exhausts bounded
                # retry and takes over — drawing the replacement from
                # the standby pool when armed; in-process the takeover
                # is synchronous (there is no child to die under us).
                k = sorted(owners)[ev.data % len(owners)]
                if cfg.two_process:
                    proc = procs.get(k)
                    if proc is not None and proc.poll() is None:
                        proc.kill()
                else:
                    revive_owner(k)
            else:
                raise ValueError(f"unknown fleet scenario event {ev.kind!r}")

        res = _PhaseResult(
            name="fleet-sustained",
            invalidation_rate_per_s=cfg.invalidation_rate_per_s,
        )
        # The burst window (first bursting tenant stream), for the
        # in-burst/off-burst per-tenant split: FIFO queueing is shared,
        # so the honest starvation evidence is WHERE the queueing lands
        # (the burst window) and WHOSE traffic dominates it.
        burst_win = next(
            (
                (float(ts["burst_start_s"]), float(ts["burst_end_s"]))
                for ts in cfg.tenant_streams
                if float(ts.get("burst_factor", 1.0)) != 1.0
            ),
            None,
        )
        burst_lat: dict[tuple[str, bool], list] = {}

        # Arrival metadata of decided-but-unbound pods (rate-capped or
        # unschedulable): uid → (deadline, arrival t_ev, arrival issue
        # stamp, raw tenant).  When a LATER decide's scheduling round
        # finally binds one, its full latency is accounted from the
        # ORIGINAL arrival — queue_wait for the capped span, service for
        # the round that bound it.
        pending_meta: dict[str, tuple] = {}

        def _observe_split(
            tlabel: str, total: float, qwait: float, svc: float
        ) -> None:
            slo_hist.observe(
                total, phase=res.name, tenant=tlabel, component="total"
            )
            slo_hist.observe(
                qwait, phase=res.name, tenant=tlabel, component="queue_wait"
            )
            slo_hist.observe(
                svc, phase=res.name, tenant=tlabel, component="service"
            )

        def _retire_overflow() -> None:
            while len(live) > cfg.live_pod_cap:
                old = live.popleft()
                pods_by_uid.pop(old, None)
                pending.pop(old, None)
                pending_meta.pop(old, None)
                if old in router._pod_shard:
                    router.remove_object("Pod", old)
                res.retired += 1

        def decide(pod, deadline: float | None, t_ev: float = 0.0) -> None:
            uid = pod.uid
            t_issue = time.perf_counter()
            router.add_pod(pod)
            outs = router.schedule_all_pending()
            node = None
            late_binds: list[tuple[str, str]] = []
            for o in outs:
                if o.pod.uid == uid and o.node_name:
                    node = o.node_name
                elif o.node_name and o.pod.uid in pending:
                    # A deferred pod (rate-capped on an earlier arrival)
                    # bound in THIS round: full accounting below, from
                    # its original arrival stamps.
                    late_binds.append((o.pod.uid, o.node_name))
                elif o.node_name and o.pod.uid in pods_by_uid:
                    # A rebind (an evicted pod rescheduled mid-decision):
                    # keep the live-window's node attribution current, or a
                    # later flap of the DEAD node would prune the survivor.
                    pods_by_uid[o.pod.uid]._lg_node = o.node_name
            shard = router._pod_shard.get(uid)
            t_done = time.perf_counter()
            base = t_issue if deadline is None else min(deadline, t_issue)
            lat = t_done - base
            tenant = pod_tenant(pod)
            tlabel = (
                tenant_metrics.labeler.label_for(tenant)
                if tenant_metrics is not None
                else TENANT_FALLBACK
            )
            tkey = tenant or "-"
            res.tenant_counts[tkey] = res.tenant_counts.get(tkey, 0) + 1
            # Armed admission defers an unbound pod's SLO sample to its
            # BIND (the exactly-once accounting below) — sampling the
            # arrival attempt too would double-count the pod and bury
            # the capped span's queue_wait.  Unarmed keeps the pre-
            # fairness accounting bit for bit.
            sample_now = node is not None or router.queue.admission is None
            if sample_now:
                res.latencies.append(lat)
                res.tenant_latencies.setdefault(tkey, []).append(lat)
                if burst_win is not None:
                    in_burst = burst_win[0] <= t_ev < burst_win[1]
                    burst_lat.setdefault((tkey, in_burst), []).append(lat)
                _observe_split(
                    tlabel, lat, max(0.0, t_issue - base), t_done - t_issue
                )
                if shard is not None:
                    per_shard_lat.setdefault(shard, []).append(lat)
                    if autoscaler is not None:
                        autoscaler.note_latency(shard, lat)
                    lat_trace.append((t_ev, shard, lat))
                if lat > cfg.slo_budget_ms / 1e3:
                    res.violations += 1
                    res.tenant_violations[tkey] = (
                        res.tenant_violations.get(tkey, 0) + 1
                    )
                    slo_violations.inc(phase=res.name, tenant=tlabel)
            res.decisions += 1
            if node:
                res.bound += 1
                res.tenant_bound[tkey] = res.tenant_bound.get(tkey, 0) + 1
                pod._lg_node = node
                pods_by_uid[uid] = pod
                pending.pop(uid, None)
                pending_meta.pop(uid, None)
                live.append(uid)
                _retire_overflow()
            else:
                pending[uid] = pod
                pending_meta[uid] = (deadline, t_ev, t_issue, tenant)
            for buid, bnode in late_binds:
                bpod = pending.pop(buid, None)
                meta = pending_meta.pop(buid, None)
                if bpod is None:
                    continue
                res.bound += 1
                bpod._lg_node = bnode
                pods_by_uid[buid] = bpod
                live.append(buid)
                if meta is not None:
                    b_deadline, b_t_ev, b_issue, b_tenant = meta
                    b_base = (
                        b_issue
                        if b_deadline is None
                        else min(b_deadline, b_issue)
                    )
                    # The capped span (arrival → this round) is
                    # queue_wait; only this round's scheduling time is
                    # service — the cap's cost lands on the cap.
                    b_qwait = max(0.0, t_issue - b_base)
                    b_svc = t_done - t_issue
                    b_lat = b_qwait + b_svc
                    b_tkey = b_tenant or "-"
                    b_tlabel = (
                        tenant_metrics.labeler.label_for(b_tenant)
                        if tenant_metrics is not None
                        else TENANT_FALLBACK
                    )
                    res.latencies.append(b_lat)
                    res.tenant_latencies.setdefault(b_tkey, []).append(
                        b_lat
                    )
                    res.tenant_bound[b_tkey] = (
                        res.tenant_bound.get(b_tkey, 0) + 1
                    )
                    if burst_win is not None:
                        b_in = burst_win[0] <= b_t_ev < burst_win[1]
                        burst_lat.setdefault((b_tkey, b_in), []).append(
                            b_lat
                        )
                    _observe_split(b_tlabel, b_lat, b_qwait, b_svc)
                    if b_lat > cfg.slo_budget_ms / 1e3:
                        res.violations += 1
                        res.tenant_violations[b_tkey] = (
                            res.tenant_violations.get(b_tkey, 0) + 1
                        )
                        slo_violations.inc(
                            phase=res.name, tenant=b_tlabel
                        )
            if late_binds:
                _retire_overflow()

        seed = cfg.seed * 1_000_003
        tenant_of_arrival: list[str | None] = []
        if cfg.tenant_streams:
            # The tenant-starvation shape: each tenant arrives on its
            # OWN seeded schedule (steady Poisson or a piecewise burst),
            # merged time-ordered — (t, stream index, intra-stream
            # index) is a total, seed-stable order.
            streams: list[tuple[str, list[float]]] = []
            for j, ts in enumerate(cfg.tenant_streams):
                rate = float(ts["rate_pods_per_s"])
                factor = float(ts.get("burst_factor", 1.0))
                sseed = seed + 8_627 + j * 1_009
                if factor != 1.0:
                    offs = burst_offsets(
                        rate,
                        rate * factor,
                        float(ts.get("burst_start_s", 0.0)),
                        float(ts.get("burst_end_s", 0.0)),
                        cfg.duration_s,
                        sseed,
                    )
                else:
                    offs = poisson_offsets(rate, cfg.duration_s, sseed)
                streams.append((str(ts["name"]), offs))
            merged_arrivals = sorted(
                (t_off, j, k)
                for j, (_name, offs) in enumerate(streams)
                for k, t_off in enumerate(offs)
            )
            offsets = [a[0] for a in merged_arrivals]
            tenant_of_arrival = [streams[a[1]][0] for a in merged_arrivals]
            pods = [
                mix.pod(i, tenant=tenant_of_arrival[i])
                for i in range(len(offsets))
            ]
        else:
            if cfg.diurnal:
                offsets = diurnal_offsets(
                    cfg.rate_pods_per_s,
                    cfg.rate_pods_per_s * cfg.diurnal_peak_factor,
                    cfg.diurnal_period_s,
                    cfg.duration_s,
                    seed,
                )
            else:
                offsets = poisson_offsets(
                    cfg.rate_pods_per_s, cfg.duration_s, seed
                )
            pods = [mix.pod(i) for i in range(len(offsets))]
        if cfg.hot_fraction > 0:
            # A dedicated seeded stream marks hot arrivals (a pure
            # function of (seed, arrival schedule) — the hot-spot skew
            # replays).  Under diurnal arrivals the hot PROBABILITY
            # rides the same day/night swing as the rate: off-crest
            # traffic spreads fleet-wide (imbalance in-band), the crest
            # concentrates on the hot pool — so the split trips exactly
            # when the skew hurts, not at the first quiet tick.
            from .arrivals import diurnal_rate

            hot_rng = _rng(seed + 424_243)
            draws = hot_rng.random(len(offsets))
            for i, p in enumerate(pods):
                p_hot = (
                    diurnal_rate(
                        offsets[i], 0.0, cfg.hot_fraction,
                        cfg.diurnal_period_s,
                    )
                    if cfg.diurnal
                    else cfg.hot_fraction
                )
                if draws[i] < p_hot:
                    p.spec.node_selector["loadgen.tpu/hot"] = "1"
        scenario = build_events(
            cfg.duration_s,
            seed + 500_009,
            nodes=cfg.nodes,
            churn_nodes=cfg.churn_nodes,
            invalidation_rate_per_s=cfg.invalidation_rate_per_s,
            inv_mix=FLEET_INV_MIX,
            node_flap_period_s=cfg.node_flap_period_s,
            flap_down_s=cfg.flap_down_s,
            cold_consumer_period_s=cfg.cold_consumer_period_s,
            node_death_period_s=cfg.node_death_period_s if armed else 0.0,
            node_death_down_s=cfg.node_death_down_s,
            lease_interval_s=cfg.lease_interval_s if armed else 0.0,
            autoscale_interval_s=(
                cfg.autoscale_interval_s if cfg.autoscale else 0.0
            ),
        )
        if cfg.scripted_events:
            # Hand-placed production-day incidents (owner kills, cold
            # router restarts, node deaths at scripted seconds) merged
            # into the generated stream.  Only re-sorted when armed: the
            # legacy schedule stays byte-identical otherwise.
            scenario = sorted(
                list(scenario) + one_shot_events(cfg.scripted_events),
                key=lambda e: (e.t, e.kind, e.data),
            )
        ops: list[tuple[float, int, int, object]] = []
        for j, ev in enumerate(scenario):
            ops.append((ev.t, 1, j, ev))
        for i, off in enumerate(offsets):
            ops.append((off, 2, i, i))
        ops.sort(key=lambda e: (e[0], e[1], e[2]))

        # -- resumable-driver state (ISSUE 18) -------------------------
        # The driver is (lint-enforced) a pure function of (config,
        # seed, logical clock): every RNG draw is pre-computed above, so
        # the deterministic state is exactly the op cursor plus the
        # replayable accumulators — digest-verified on resume.  The
        # wall-derived observability accumulators ride a separate block,
        # restored verbatim (a replay cannot re-measure the past).
        def _det_state(op_index: int, clock: float) -> dict:
            adm: list[str] = []
            if router.queue.admission is not None:
                adm = list(router.queue.admission.admitted_log)
            return {
                "op_index": int(op_index),
                "clock": round(float(clock), 9),
                "decisions": res.decisions,
                "bound": res.bound,
                "retired": res.retired,
                "tenant_counts": dict(sorted(res.tenant_counts.items())),
                "tenant_bound": dict(sorted(res.tenant_bound.items())),
                "events_applied": dict(sorted(res.events_applied.items())),
                "router_restarts": router_restarts,
                "node_deaths": node_deaths,
                "node_revives": node_revives,
                "lease_renewals": lease_renewals,
                "cap_toggle": sorted(cap_toggle.items()),
                "label_epoch": sorted(label_epoch.items()),
                "dead": sorted(dead),
                "live_sha": _sha(list(live)),
                "pending_sha": _sha(sorted(pending)),
                "bindings_sha": _sha(sorted(router.bindings().items())),
                "admission_sha": _sha(list(admission_order) + adm),
                "autoscale_sha": _sha(
                    [
                        [
                            a.get("op"), a.get("from"), a.get("to"),
                            round(float(a.get("t", 0.0)), 9),
                        ]
                        for a in autoscale_actions
                    ]
                ),
                "shards": sorted(owners),
            }

        def _obs_state() -> dict:
            return {
                "latencies": list(res.latencies),
                "violations": res.violations,
                "tenant_latencies": {
                    k: list(v)
                    for k, v in sorted(res.tenant_latencies.items())
                },
                "tenant_violations": dict(
                    sorted(res.tenant_violations.items())
                ),
                "per_shard_lat": {
                    str(k): list(v)
                    for k, v in sorted(per_shard_lat.items())
                },
                "lat_trace": [[t, s, l] for t, s, l in lat_trace],
                "burst_lat": {
                    f"{tk}\x1f{int(b)}": list(v)
                    for (tk, b), v in sorted(burst_lat.items())
                },
                "owner_takeovers": owner_takeovers,
                "wal_samples": {
                    str(k): list(v) for k, v in sorted(wal_samples.items())
                },
                "wal_prev": {
                    str(k): v for k, v in sorted(wal_prev.items())
                },
                "compactions": {
                    str(k): v for k, v in sorted(compactions.items())
                },
            }

        def _restore_obs(obs: dict) -> None:
            nonlocal owner_takeovers
            res.latencies[:] = [float(v) for v in obs["latencies"]]
            res.violations = int(obs["violations"])
            res.tenant_latencies.clear()
            res.tenant_latencies.update(
                {k: [float(v) for v in vs]
                 for k, vs in obs["tenant_latencies"].items()}
            )
            res.tenant_violations.clear()
            res.tenant_violations.update(
                {k: int(v) for k, v in obs["tenant_violations"].items()}
            )
            per_shard_lat.clear()
            per_shard_lat.update(
                {int(k): [float(v) for v in vs]
                 for k, vs in obs["per_shard_lat"].items()}
            )
            lat_trace[:] = [
                (float(t), int(s), float(l)) for t, s, l in obs["lat_trace"]
            ]
            burst_lat.clear()
            for key, vs in obs["burst_lat"].items():
                tk, b = key.split("\x1f")
                burst_lat[(tk, bool(int(b)))] = [float(v) for v in vs]
            owner_takeovers = int(obs["owner_takeovers"])
            wal_samples.clear()
            wal_samples.update(
                {int(k): [int(v) for v in vs]
                 for k, vs in obs["wal_samples"].items()}
            )
            wal_prev.clear()
            wal_prev.update(
                {int(k): int(v) for k, v in obs["wal_prev"].items()}
            )
            compactions.clear()
            compactions.update(
                {int(k): int(v) for k, v in obs["compactions"].items()}
            )

        digest_verified = None
        if cfg.checkpoint_path:
            ckpt = CheckpointWriter(cfg.checkpoint_path)
            if ckpt_prior is not None:
                ckpt.generation = int(ckpt_prior["generation"])
        t0 = time.perf_counter()

        def execute(klass: int, payload, t_ev: float) -> None:
            # Flight records downstream of this op (router batch, owner
            # propose/commit, handoff markers) carry the SCENARIO clock —
            # the logical axis the merged fleet timeline orders on.
            router.note_logical_time(t_ev)
            if klass == 1:
                apply_event(payload)
                res.events_applied[payload.kind] = (
                    res.events_applied.get(payload.kind, 0) + 1
                )
                sample_wal()
            else:
                deadline = (
                    t0 + t_ev
                    if cfg.pace == "real" and not replay_active[0]
                    else None
                )
                decide(pods[payload], deadline, t_ev)

        # Replay prefix (resume): ops [0, resume_from) re-execute in
        # virtual pace — deterministic regeneration of the driver and
        # fleet state, sleeps skipped — then the regenerated digest is
        # verified against the checkpoint, the wall-derived accumulators
        # restore, and the wall origin rebases so the remaining ops pace
        # exactly as the uninterrupted run's would have.
        replay_active = [resume_from > 0]
        op_i = 0
        last_t = 0.0
        for t_ev, klass, _idx, payload in ops:
            replay_active[0] = op_i < resume_from
            if cfg.pace == "real" and not replay_active[0]:
                delay = (t0 + t_ev) - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            try:
                execute(klass, payload, t_ev)
            except FleetOwnerUnreachable as exc:
                # Bounded retry exhausted on one owner: takeover (restart
                # the serve child — it recovers its journal before the first
                # frame — and re-adopt), then re-issue the op once against
                # the recovered fleet.  Idempotent by the same contracts the
                # kill matrix proves: bound pods re-adopt, adds upsert.
                shard = getattr(exc, "shard_id", None)
                if shard is None or not cfg.two_process:
                    raise
                if autoscaler is not None:
                    # Stale stats never drive a resize: the autoscaler
                    # holds the shard out of actions while takeover
                    # owns its fate.
                    autoscaler.note_unreachable(shard)
                revive_owner(shard)
                execute(klass, payload, t_ev)
            op_i += 1
            last_t = t_ev
            if replay_active[0] and op_i == resume_from:
                # End of the replayed prefix: the regenerated driver
                # state must hash exactly to what the checkpoint
                # recorded, or the resume would silently diverge.
                want = ckpt_prior["state"]["det"]
                got = _det_state(op_i, t_ev)
                if state_digest(got) != state_digest(want):
                    diffs = [
                        k
                        for k in sorted(set(got) | set(want))
                        if got.get(k) != want.get(k)
                    ]
                    raise RuntimeError(
                        "resume digest mismatch at op "
                        f"{op_i}: replay diverged on {diffs}"
                    )
                _restore_obs(ckpt_prior["state"]["obs"])
                digest_verified = True
                t0 = time.perf_counter() - t_ev
            if (
                ckpt is not None
                and cfg.checkpoint_every_ops > 0
                and op_i > resume_from
                and op_i % cfg.checkpoint_every_ops == 0
            ):
                ckpt.write(
                    {"det": _det_state(op_i, t_ev), "obs": _obs_state()}
                )
            if (
                cfg.kill_after_op
                and op_i == cfg.kill_after_op
                and op_i > resume_from
            ):
                # Test hook (--standby-kill ckpt cells; tests/test_soak):
                # die HARD right here — after the boundary checkpoint
                # when op_i lands on one, mid-interval otherwise.
                os.kill(os.getpid(), signal.SIGKILL)
        if cfg.resume and not digest_verified:
            raise RuntimeError(
                f"resume op index {resume_from} was never reached "
                f"({op_i} ops in schedule) — checkpoint/config mismatch"
            )
        sample_wal()
        res.wall_s = round(time.perf_counter() - t0, 3)
        driver_state_sha = state_digest(_det_state(op_i, last_t))
        standby_status = standby.status() if standby is not None else None

        bindings = router.bindings()
        stats = router.stats()
        autoscale = None
        if cfg.autoscale and autoscaler is not None:
            W = cfg.autoscale_compare_window_s

            def _win_p99(shard_ids, lo: float, hi: float) -> dict:
                lats = [
                    lat
                    for t, s, lat in lat_trace
                    if lo <= t < hi and (shard_ids is None or s in shard_ids)
                ]
                return {
                    "decisions": len(lats),
                    "p99_ms": round(_pct(lats, 99) * 1e3, 3),
                    "p50_ms": round(_pct(lats, 50) * 1e3, 3),
                }

            # Split-recovery evidence: for each split, the SPLIT shard's
            # SLO in the window before vs the strictest honest "after" —
            # the worst of the two shards now sharing its load, measured
            # AFTER the settle gap (the transition window, where the
            # journaled import re-fsyncs every moved binding, is
            # reported separately — a resize is not free, it is bounded
            # and crash-safe).
            settle = cfg.autoscale_compare_settle_s
            recovery = []
            for act in autoscale_actions:
                if act["op"] != "split":
                    continue
                ts = act["t"]
                src, dst = act["from"], act["to"]
                pre = _win_p99({src}, ts - W, ts)
                post_src = _win_p99({src}, ts + settle, ts + settle + W)
                post_dst = _win_p99({dst}, ts + settle, ts + settle + W)
                post = max(
                    (post_src, post_dst), key=lambda d: d["p99_ms"]
                )
                recovery.append(
                    {
                        "t_split": round(ts, 3),
                        "shard": src,
                        "new_shard": dst,
                        "window_s": W,
                        "settle_s": settle,
                        "pre": pre,
                        "transition": _win_p99(
                            {src, dst}, ts, ts + settle
                        ),
                        "post_worst_of_pair": post,
                        "post_src": post_src,
                        "post_new": post_dst,
                        "global_pre": _win_p99(None, ts - W, ts),
                        "global_post": _win_p99(
                            None, ts + settle, ts + settle + W
                        ),
                        "p99_recovered": (
                            post["p99_ms"] < pre["p99_ms"]
                            if pre["decisions"] and post["decisions"]
                            else None
                        ),
                    }
                )
            autoscale = {
                "enabled": True,
                "hot_fraction": cfg.hot_fraction,
                "hot_serving_nodes": len(hot_serving),
                "actions": autoscale_actions,
                "splits": sum(
                    1 for a in autoscale_actions if a["op"] == "split"
                ),
                "merges": sum(
                    1 for a in autoscale_actions if a["op"] == "merge"
                ),
                "deferrals": dict(sorted(autoscaler.deferrals.items())),
                "split_recovery": recovery,
                "status": autoscaler.status(),
            }
        node_loss = None
        if armed:
            lc = router.lifecycle_stats()
            node_loss = {
                "node_deaths": node_deaths,
                "node_revives": node_revives,
                "lease_renewals": lease_renewals,
                "evictions_absorbed": lc["evictions_absorbed"],
                "rebinds": lc["rebinds"],
                "cross_shard_rebinds": lc["cross_shard_rebinds"],
                "pending_rebinds": lc["pending_rebinds"],
                "per_shard_lifecycle": lc["per_shard"],
            }
        fleet_timeline = None
        merged_sha = None
        if cfg.observability:
            # The federated flight merge: every owner's ring (over the
            # wire for serve children) + the router's, folded into one
            # fleet timeline on the scenario clock with per-phase
            # overlap and critical-path attribution.  The deterministic
            # timeline hash rides the determinism block — two same-seed
            # runs must merge byte-identically.
            snaps, names = router.fleet_flight_snapshots()
            merged = merge_fleet(snaps, names)
            merged["slow_spans"] = list(router.slow_spans)
            merged_sha = merged["timeline_sha256"]
            # The Perfetto twin: the merged timeline rendered as
            # trace-event JSON on the logical timebase (wall fields
            # stripped — same-seed runs export byte-identically), written
            # next to the merged doc and stamped into it so the fleet
            # renderer (scripts/profile_report.py) can link the artifact.
            from ..framework import trace_export

            trace_name = "fleet-trace.json"
            merged["perfetto"] = trace_name
            merged_path = os.path.join(out_dir, "fleet-flight-merged.json")
            with open(merged_path, "w", encoding="utf-8") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            with open(
                os.path.join(out_dir, trace_name), "w", encoding="utf-8"
            ) as f:
                f.write(trace_export.render(merged, timebase="logical"))
            # Flight-derived measured throughput over the same rings
            # (empty matrix when the scenario has no hetero classes).
            mt = router.measured_throughput()
            fleet_timeline = {
                "file": os.path.basename(merged_path),
                "perfetto": trace_name,
                "timeline_sha256": merged_sha,
                "events": merged["timeline_events"],
                "components": merged["components"],
                "wall": merged["wall"],
                "critical_path_top": merged["critical_path"][:8],
                "measured_throughput": {
                    "matrix": mt["matrix"],
                    "binds": mt["window"]["binds"],
                    "source_sha256": mt["source"]["sha256"],
                },
            }
        registry_summary = router.registry.summary()
    finally:
        if standby is not None:
            try:
                standby.close()  # retires (SIGTERMs) un-promoted slots
            except OSError:
                pass
        if ckpt is not None:
            ckpt.close()
        for owner in owners.values():
            try:
                owner.close()
            except OSError:
                pass
        for proc in procs.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in procs.values():
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
    slo = dict(
        _lat_summary(res.latencies),
        budget_ms=cfg.slo_budget_ms,
        violations=res.violations,
        violation_rate=round(res.violations / max(1, res.decisions), 4),
    )
    artifact = {
        "metric": "fleet_soak_slo_per_shard",
        "seed": cfg.seed,
        "shards": shards,
        "config": asdict(cfg),
        "wall_s": res.wall_s,
        "decisions": res.decisions,
        "bound": res.bound,
        "retired": res.retired,
        "sustained_pods_per_sec": round(
            res.decisions / res.wall_s if res.wall_s else 0.0, 1
        ),
        "slo": slo,
        "per_shard": {
            str(k): {
                "slo": _lat_summary(per_shard_lat[k]),
                "wal_bytes_max": max(wal_samples[k], default=0),
                "wal_bytes_final": (
                    wal_samples[k][-1] if wal_samples[k] else 0
                ),
                "compactions_observed": compactions[k],
                "owner": stats["shards"][str(k)],
            }
            for k in sorted(owners)
        },
        "events": dict(sorted(res.events_applied.items())),
        "router_restarts": router_restarts,
        "owner_takeovers": owner_takeovers,
        "deployment": (
            "multi-process" if cfg.two_process else "in-process"
        ),
        "autoscale": autoscale,
        "node_loss": node_loss,
        "tenants": (
            dict(
                per_tenant=_tenant_summary([res]),
                counters=(
                    tenant_metrics.snapshot()
                    if tenant_metrics is not None
                    else {}
                ),
                per_shard_commits={
                    str(k): (stats["shards"][str(k)].get("tenants") or {})
                    for k in sorted(owners)
                },
                burst_split=(
                    {
                        "window_s": list(burst_win),
                        "per_tenant": {
                            tkey: {
                                "in_burst": _lat_summary(
                                    burst_lat.get((tkey, True), [])
                                ),
                                "off_burst": _lat_summary(
                                    burst_lat.get((tkey, False), [])
                                ),
                            }
                            for tkey in sorted(
                                {k for k, _b in burst_lat}
                            )
                        },
                        # Whose traffic the burst window's queueing
                        # lands on: each tenant's share of the window's
                        # decisions.
                        "in_burst_share": {
                            tkey: round(
                                len(burst_lat.get((tkey, True), []))
                                / max(
                                    1,
                                    sum(
                                        len(v)
                                        for (_k, b), v in burst_lat.items()
                                        if b
                                    ),
                                ),
                                4,
                            )
                            for tkey in sorted(
                                {k for k, b in burst_lat if b}
                            )
                        },
                    }
                    if burst_win is not None
                    else None
                ),
            )
            if (cfg.tenants or cfg.tenant_streams)
            else None
        ),
        "fleet_timeline": fleet_timeline,
        "fleet_metrics": registry_summary,
        "admission": (
            dict(
                armed=True,
                status=router.queue.admission.status(),
                # Run-wide admission order: every dead generation's
                # harvested log plus the final router's — the cross-run
                # determinism oracle for WFQ ordering.
                admission_order_sha256=_sha(
                    list(admission_order)
                    + list(router.queue.admission.admitted_log)
                ),
                admitted_total=(
                    len(admission_order)
                    + len(router.queue.admission.admitted_log)
                ),
            )
            if cfg.admission is not None
            and router.queue.admission is not None
            else None
        ),
        "standby": (
            dict(
                enabled=True,
                pool=standby_status,
                promotions=standby_promotions,
                served_from_pool=sum(
                    1 for p in standby_promotions if p["from_pool"]
                ),
                cold_fallbacks=standby_cold,
                promotion_latency=_lat_summary(
                    [
                        p["latency_s"]
                        for p in standby_promotions
                        if p["from_pool"]
                    ]
                ),
            )
            if standby is not None
            else None
        ),
        "resume": (
            dict(
                enabled=True,
                resumed=bool(cfg.resume),
                resume_op_index=resume_from,
                checkpoint_generation=(
                    ckpt.generation if ckpt is not None else 0
                ),
                checkpoint_every_ops=cfg.checkpoint_every_ops,
                digest_verified=digest_verified,
            )
            if cfg.checkpoint_path
            else None
        ),
        "determinism": {
            "arrival_sha256": _sha([round(o, 9) for o in offsets]),
            "bindings_sha256": _sha(sorted(bindings.items())),
            "timeline_sha256": merged_sha,
            # The driver's own final-state digest (ISSUE 18): the same
            # function the resume checkpoint verifies — equal between a
            # --resume'd run and its uninterrupted same-seed twin.
            "driver_state_sha256": driver_state_sha,
            "arrivals_total": len(offsets),
        },
        "bound_final": len(bindings),
        "pace": cfg.pace,
    }
    artifact["_arrival_offsets"] = [list(offsets)]
    # Raw (t, shard, latency) samples for callers that window SLOs
    # around incidents (run_soak.py --prod's per-phase evidence) —
    # underscore-keyed: strip_private drops it from the committed JSON.
    artifact["_lat_trace"] = [[t, s, lat] for t, s, lat in lat_trace]
    return artifact


def strip_private(artifact: dict) -> dict:
    """The committed-artifact view: drop the underscore-keyed raw data
    callers use in-process, and normalize to JSON-native types (config
    tuples become lists) so the document round-trips byte-stable."""
    return json.loads(
        json.dumps(
            {k: v for k, v in artifact.items() if not k.startswith("_")}
        )
    )
