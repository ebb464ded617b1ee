"""scheduler_perf-style benchmark harness.

Mirrors the reference's config-driven workload runner
(test/integration/scheduler_perf/scheduler_perf.go): a workload is a list of
ops — createNodes, createPods (optionally measured), churn, barrier — and the
headline metric is SchedulingThroughput: pods scheduled per second, with
avg/p50/p90/p99 computed over 1-second windows exactly like
scheduler_perf's util.go:629 collector.  Output is a JSON DataItems list in
the same spirit (util.go:191).

Workloads include TPU-native ports of the upstream performance-config.yaml
cases whose thresholds are recorded in BASELINE.md, plus the five
BASELINE.json A/B configs.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from ..api import types as t
from ..api.wrappers import make_node, make_pod, make_pv, make_pvc
from ..framework.config import DEFAULT_PROFILE, Profile, fit_only_profile
from ..ops.common import registered_subset
from ..scheduler import TPUScheduler

ZONE = "topology.kubernetes.io/zone"


@dataclass
class Workload:
    name: str
    baseline_pods_per_sec: float  # upstream threshold (BASELINE.md) or 0
    build: Callable[[], TPUScheduler]
    nodes: Callable[[TPUScheduler], None]
    warmup: Callable[[TPUScheduler], None]
    measured: Callable[[TPUScheduler], int]  # returns expected pod count
    wait_backoff: bool = False
    # Background churn (scheduler_perf's churn op, scheduler_perf.go:89):
    # invoked between measured batches with the batch index.
    churn: Callable[[TPUScheduler, int], None] | None = None


def _throughput_percentiles(samples: list[float]) -> dict:
    if not samples:
        return {"avg": 0.0, "p50": 0.0, "p90": 0.0, "p99": 0.0}
    a = np.asarray(samples, np.float64)
    return {
        "avg": float(a.mean()),
        "p50": float(np.percentile(a, 50)),
        "p90": float(np.percentile(a, 90)),
        "p99": float(np.percentile(a, 99)),
    }


def run_workload(w: Workload, pipeline_depth: int | None = None) -> dict:
    """``pipeline_depth`` overrides the scheduler's batch-loop pipelining
    (ISSUE 15): depth 2 drains each batch's group-committed journal
    records under the next batch's in-flight device pass."""
    sched = w.build()
    if pipeline_depth is not None:
        sched.pipeline_depth = max(1, int(pipeline_depth))
    w.nodes(sched)
    w.warmup(sched)
    sched.schedule_all_pending(wait_backoff=w.wait_backoff)
    sched.warm_tail()
    # Reset measurement state after warmup compilations.  The registry
    # resets IN PLACE (histograms/counters cleared, collectors and event
    # counter handles kept) so the per-extension-point p50/p99 embedded in
    # the result cover the measured window only.
    m = sched.metrics
    m.batches = m.schedule_attempts = m.scheduled = m.unschedulable = 0
    m.preemptions = m.deferred = 0
    m.packed_batches = m.pack_collisions = 0
    m.dom_carry_hits = m.dom_carry_rebuilds = 0
    m.device_time_s = m.featurize_time_s = 0.0
    m.e2e_latency_samples = []
    m.registry.reset()
    sched.slow_spans.clear()

    expected = w.measured(sched)
    windows: list[tuple[float, int]] = []  # (timestamp, scheduled so far)
    t0 = time.perf_counter()
    scheduled = 0
    batch_i = 0
    while True:
        # stopCollectingMetrics semantics (scheduler_perf.go): the clock
        # stops when every measured pod is scheduled; background churn
        # (woken unschedulable pods re-failing) continues outside the
        # measured window, exactly as upstream's collector treats it.
        if scheduled >= expected:
            break
        out = sched.schedule_batch()
        if not out:
            if len(sched.queue) or sched.has_inflight_work:
                continue  # WaitOnPermit or in-flight (prefetched /
                # predispatched) batch; keep going
            if w.wait_backoff and sched.queue.sleep_until_backoff():
                continue
            break
        scheduled += sum(1 for o in out if o.node_name)
        windows.append((time.perf_counter(), scheduled))
        if w.churn is not None:
            w.churn(sched, batch_i)
        batch_i += 1
    dt = time.perf_counter() - t0

    # 1-second-window throughput samples (util.go:629): resample the batch
    # completion curve onto a 1s grid.  The curve starts at (0, 0) and is
    # linear within each batch interval, so a single long batch contributes
    # its true rate to every window instead of collapsing to zeros (the r1
    # percentile bug VERDICT §weak-8 called out).  Runs shorter than one
    # window fall back to the overall rate.
    samples: list[float] = []
    if windows and dt > 0:
        if dt < 1.0:
            samples = [scheduled / dt]
        else:
            ts = np.asarray([0.0] + [w_[0] - t0 for w_ in windows])
            counts = np.asarray([0.0] + [w_[1] for w_ in windows], np.float64)
            prev = 0.0
            for g in np.arange(1.0, dt + 1e-9, 1.0):
                c = float(np.interp(g, ts, counts, right=counts[-1]))
                samples.append(c - prev)
                prev = c
            tail = dt - float(int(dt))
            if tail > 0.05:  # rate over the final partial window
                samples.append((scheduled - prev) / tail)
    pct = _throughput_percentiles(samples)

    # Per-pod e2e scheduling latency (enqueue→bind), the SLI companion metric
    # (pod_scheduling_sli_duration_seconds, metrics/metrics.go:225).
    lat = np.asarray(m.e2e_latency_samples, np.float64)
    latency_ms = (
        {
            "p50": round(float(np.percentile(lat, 50)) * 1e3, 1),
            "p90": round(float(np.percentile(lat, 90)) * 1e3, 1),
            "p99": round(float(np.percentile(lat, 99)) * 1e3, 1),
        }
        if lat.size
        else None
    )

    # Flight-recorder phase attribution over the measured window: the
    # per-batch tiled segments (featurize/device/commit/snapshot/other)
    # summed from the scheduler_phase_duration_seconds family — their sum
    # over wall time is the coverage the bench guard reports (journal
    # append/fsync and the speculative frontend's hint_decode are
    # sub-slices of / overlap the tiled phases and stay out of the sum;
    # so does every span observed under its own name, `layer/name`).
    phases: dict[str, float] = {}
    fam = m.registry.histograms.get("scheduler_phase_duration_seconds")
    if fam is not None:
        for key, h in sorted(fam.cells.items()):
            label = dict(key).get("phase")
            if label and h.n and "/" not in label:
                phases[label] = round(h.total, 6)
    tiled = sum(
        v for k, v in phases.items()
        if k not in ("journal_append", "journal_fsync", "hint_decode")
    )
    # With the pipeline on, tiled stage seconds can EXCEED wall time —
    # the excess is the wall the overlap saved vs running the stages
    # serially (coverage > 1.0 is the pipeline working, not a leak).
    overlap_saved = max(tiled - dt, 0.0)
    phase_attribution = {
        "phases": phases,
        "tiled_s": round(tiled, 6),
        "wall_s": round(dt, 6),
        "coverage": round(tiled / dt, 4) if dt > 0 else 0.0,
        "overlap": {
            "saved_s": round(overlap_saved, 6),
            "coverage": round(overlap_saved / tiled, 4) if tiled > 0 else 0.0,
        },
    }

    return {
        "name": w.name,
        "scheduled": scheduled,
        "expected": expected,
        "seconds": round(dt, 3),
        "phase_attribution": phase_attribution,
        "pods_per_sec": round(scheduled / dt, 1) if dt > 0 else 0.0,
        "throughput": {k: round(v, 1) for k, v in pct.items()},
        "latency_ms": latency_ms,
        "baseline": w.baseline_pods_per_sec,
        "vs_baseline": round(scheduled / dt / w.baseline_pods_per_sec, 2)
        if dt > 0 and w.baseline_pods_per_sec
        else None,
        "device_s": round(m.device_time_s, 3),
        "featurize_s": round(m.featurize_time_s, 3),
        "batches": m.batches,
        "preemptions": m.preemptions,
        "deferred": m.deferred,
        # A row that quarantined pods or recovered from an engine fault
        # measured the recovery path, not the workload: the entry points
        # exit non-zero on either (the registry was reset after warmup,
        # so these cover the measured window).
        "engine_faults": int(sched._engine_fault_counter.total()),
        "quarantined": sched.queue.depths()["quarantine"],
        # Conflict-aware packing + carried DomTables (ISSUE 13): how many
        # measured batches reordered, the residual same-chunk collisions
        # their plans accepted, and the carry hit/rebuild split — the
        # sweep-level evidence that deferral cascades stay eliminated.
        "packed_batches": m.packed_batches,
        "pack_collisions": m.pack_collisions,
        "dom_carry": {
            "hits": m.dom_carry_hits,
            "rebuilds": m.dom_carry_rebuilds,
        },
        # Software pipeline (ISSUE 15): predispatch double-buffer hits vs
        # invalidations, drain placement, and the wall seconds overlap
        # saved over the measured window.
        "pipeline": {
            "depth": sched.pipeline_depth,
            "predispatch_hits": int(
                sched._pipeline_predispatch_counter.get(result="hit")
            ),
            "predispatch_invalidated": int(
                sched._pipeline_predispatch_counter.get(result="invalidated")
            ),
            "drains_overlapped": int(
                sched._pipeline_drain_counter.get(kind="overlapped")
            ),
            "drains_inline": int(
                sched._pipeline_drain_counter.get(kind="inline")
            ),
            "overlap_saved_s": round(
                sched._pipeline_overlap_counter.total(), 6
            ),
        },
        # Registry summary over the measured window: per-extension-point
        # p50/p99, attempt-duration and SLI histograms (with overflow
        # counts), sampled per-plugin series, and the event counters — the
        # BENCH_*.json trajectory carries these from this PR onward.
        "metrics_summary": round_floats(m.registry.summary()),
        # Span stats: slow-cycle count + the worst recorded span tree
        # (threshold = sched.trace_threshold_s).
        "spans": {
            "slow_cycles": len(sched.slow_spans),
            "slowest": max(
                (s for s in sched.slow_spans),
                key=lambda s: s["duration_ms"],
                default=None,
            ),
        },
    }


def round_floats(obj, ndigits: int = 6):
    """Round every float in a nested summary (raw perf_counter deltas make
    the JSON lines needlessly long)."""
    if isinstance(obj, float):
        return round(obj, ndigits)
    if isinstance(obj, dict):
        return {k: round_floats(v, ndigits) for k, v in obj.items()}
    if isinstance(obj, list):
        return [round_floats(v, ndigits) for v in obj]
    return obj


# --------------------------------------------------------------------------
# Workload definitions
# --------------------------------------------------------------------------


def _basic_nodes(n: int, zones: int = 3, cpu: str = "16", mem: str = "64Gi"):
    def add(s: TPUScheduler):
        for i in range(n):
            s.add_node(
                make_node(f"node-{i}")
                .capacity({"cpu": cpu, "memory": mem, "pods": 110})
                .zone(f"zone-{i % zones}")
                .region("region-1")
                .obj()
            )

    return add


def _warm(template: Callable[[int], t.Pod], count: int = 2048):
    def warm(s: TPUScheduler):
        for i in range(count):
            p = template(10**6 + i)
            p.metadata.name = f"warm-{i}"
            s.add_pod(p)

    return warm


def _measured(template: Callable[[int], t.Pod], count: int):
    def measure(s: TPUScheduler) -> int:
        for i in range(count):
            s.add_pod(template(i))
        return count

    return measure


def _pod_basic(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "900m", "memory": "2Gi"})
        .label("app", f"app-{i % 10}")
        .obj()
    )


def _pod_anti_affinity(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("color", f"c{i % 100}")
        .pod_anti_affinity_in("color", [f"c{i % 100}"], ZONE)
        .obj()
    )


def _pod_affinity(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("color", f"c{i % 50}")
        .pod_affinity_in("color", [f"c{i % 50}"], ZONE)
        .obj()
    )


def _pod_pref_affinity(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("color", f"c{i % 50}")
        .preferred_pod_affinity_in("color", [f"c{i % 50}"], ZONE, weight=10)
        .obj()
    )


def _pod_spread(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("app", f"app-{i % 10}")
        .spread_constraint(1, ZONE, t.DO_NOT_SCHEDULE, "app", [f"app-{i % 10}"])
        .obj()
    )


def _pod_node_affinity(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "900m", "memory": "2Gi"})
        .node_affinity_in(ZONE, [f"zone-{i % 3}"])
        .obj()
    )


def _default(batch: int = 4096, chunk: int = 64) -> Callable[[], TPUScheduler]:
    return lambda: TPUScheduler(
        profile=registered_subset(DEFAULT_PROFILE), batch_size=batch,
        chunk_size=chunk,
    )


def _fit(batch: int = 4096, chunk: int = 64) -> Callable[[], TPUScheduler]:
    return lambda: TPUScheduler(
        profile=fit_only_profile(), batch_size=batch, chunk_size=chunk
    )


WORKLOADS: dict[str, Workload] = {}


def _register(w: Workload) -> None:
    WORKLOADS[w.name] = w


# BASELINE config #1: SchedulingBasic 500 nodes / 1k pods, fit-only.
_register(
    Workload(
        name="basic_500n_1kpods_fitonly",
        baseline_pods_per_sec=270.0,
        build=_fit(1024),
        nodes=_basic_nodes(500),
        warmup=_warm(_pod_basic, 1024),
        measured=_measured(lambda i: make_pod(f"m-{i}").req({"cpu": "500m", "memory": "1Gi"}).obj(), 1000),
    )
)

# Upstream SchedulingBasic shape: 5k nodes / 10k pods, default plugins.
_register(
    Workload(
        name="basic_5kn_10kpods",
        baseline_pods_per_sec=270.0,
        build=_default(),
        nodes=_basic_nodes(5000),
        warmup=_warm(_pod_basic),
        measured=_measured(_pod_basic, 10000),
    )
)

# BASELINE config #2: spread + node affinity, 1k nodes / 5k pods, 3 zones.
def _pod_spread_na(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("app", f"app-{i % 10}")
        .spread_constraint(2, ZONE, t.DO_NOT_SCHEDULE, "app", [f"app-{i % 10}"])
        .node_affinity_in(ZONE, ["zone-0", "zone-1", "zone-2"])
        .obj()
    )


_register(
    Workload(
        name="spread_nodeaffinity_1kn_5kpods",
        baseline_pods_per_sec=85.0,
        build=_default(),
        nodes=_basic_nodes(1000),
        warmup=_warm(_pod_spread_na, 1024),
        measured=_measured(_pod_spread_na, 5000),
    )
)

# BASELINE config #3: InterPodAffinity-heavy, 1k nodes / 10k pods.  Every
# pod is schedulable by construction (the r1 workload wasn't — VERDICT
# weak-3): anti-affinity colors repeat ≤5× over 10 zones; affinity pods
# colocate with their own color (lonely-pod exception seats the first).
def _pod_ipa_heavy(i: int) -> t.Pod:
    if i % 2:
        j = i // 2
        return (
            make_pod(f"pod-{i}")
            .req({"cpu": "100m", "memory": "256Mi"})
            .label("acolor", f"a{j % 50}")
            .pod_affinity_in("acolor", [f"a{j % 50}"], ZONE)
            .obj()
        )
    j = i // 2
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("color", f"c{j % 1000}")
        .pod_anti_affinity_in("color", [f"c{j % 1000}"], ZONE)
        .obj()
    )


_register(
    Workload(
        name="interpodaffinity_1kn_10kpods",
        baseline_pods_per_sec=35.0,
        build=_default(),
        nodes=_basic_nodes(1000, zones=10),
        warmup=_warm(_pod_ipa_heavy, 1024),
        measured=_measured(_pod_ipa_heavy, 10000),
    )
)

# BASELINE config #4 (headline): 5k nodes / 30k pods, full default profile.
_register(
    Workload(
        name="density_5kn_30kpods_default",
        baseline_pods_per_sec=270.0,
        build=_default(),
        nodes=_basic_nodes(5000),
        warmup=_warm(_pod_basic),
        measured=_measured(_pod_basic, 30000),
    )
)

# BASELINE config #5: 15k pods in 150 real gangs of 100 (all-or-nothing
# PodGroups co-scheduled through the gang pool → Permit quorum path).
def _gang_measured(s: TPUScheduler) -> int:
    for g in range(150):
        s.add_pod_group(t.PodGroup(name=f"gang-{g}", min_member=100))
        for i in range(100):
            s.add_pod(
                make_pod(f"gp-{g}-{i}")
                .req({"cpu": "900m", "memory": "2Gi"})
                .label("app", f"gang-{g}")
                .pod_group(f"gang-{g}")
                .obj()
            )
    return 15000


def _gang_warm(s: TPUScheduler) -> None:
    # Pre-grow the label-group vocabulary to the measured gangs' 150 groups
    # (plus warm slack) so the G-bucket growth — and its XLA recompile —
    # happens here, not inside the measured window.
    for i in range(2048):
        s.add_pod(
            make_pod(f"warm-{i}")
            .req({"cpu": "900m", "memory": "2Gi"})
            .label("app", f"gang-{i % 200}")
            .obj()
        )


_register(
    Workload(
        name="gang_15kpods_batch",
        baseline_pods_per_sec=270.0,
        build=_default(8192),
        nodes=_basic_nodes(5000),
        warmup=_gang_warm,
        measured=_gang_measured,
    )
)

# Upstream SchedulingPodAntiAffinity: 5k nodes / 2k pods.
_register(
    Workload(
        name="pod_anti_affinity_5kn_2kpods",
        baseline_pods_per_sec=70.0,
        build=_default(2048),
        nodes=_basic_nodes(5000, zones=100),
        warmup=_warm(_pod_anti_affinity, 512),
        measured=_measured(_pod_anti_affinity, 2000),
    )
)

# Upstream SchedulingPodAffinity: 5k nodes / 5k pods.
_register(
    Workload(
        name="pod_affinity_5kn_5kpods",
        baseline_pods_per_sec=35.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=50),
        warmup=_warm(_pod_affinity, 1024),
        measured=_measured(_pod_affinity, 5000),
    )
)

# Upstream SchedulingPreferredPodAffinity: 5k nodes / 5k pods.
_register(
    Workload(
        name="preferred_pod_affinity_5kn_5kpods",
        baseline_pods_per_sec=90.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=50),
        warmup=_warm(_pod_pref_affinity, 1024),
        measured=_measured(_pod_pref_affinity, 5000),
    )
)

# Upstream TopologySpreading: 5k nodes / 5k pods.
_register(
    Workload(
        name="topology_spreading_5kn_5kpods",
        baseline_pods_per_sec=85.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=10),
        warmup=_warm(_pod_spread, 1024),
        measured=_measured(_pod_spread, 5000),
    )
)

# Upstream SchedulingNodeAffinity: 5k nodes / 10k pods.
_register(
    Workload(
        name="node_affinity_5kn_10kpods",
        baseline_pods_per_sec=220.0,
        build=_default(),
        nodes=_basic_nodes(5000),
        warmup=_warm(_pod_node_affinity, 1024),
        measured=_measured(_pod_node_affinity, 10000),
    )
)

# Upstream PreemptionBasic: 500 nodes, low-priority fill then high-priority wave.
def _preemption_nodes(s: TPUScheduler):
    _basic_nodes(500, cpu="4", mem="16Gi")(s)


def _preemption_warm(s: TPUScheduler):
    for i in range(2000):
        s.add_pod(
            make_pod(f"bg-{i}").req({"cpu": "1", "memory": "2Gi"}).priority(1)
            .start_time(float(i)).obj()
        )
    # Drain the background fill FIRST, then add the warm preemptor: a
    # high-priority pod pops ahead of everything (QueueSort), so added
    # together it would bind to a still-empty node and the preemption pass
    # would pay its XLA compile inside the measured window (r2: the
    # 1.9s PostFilter outlier in preemption_async).
    s.schedule_all_pending(wait_backoff=True)
    s.add_pod(
        make_pod("warm-vip").req({"cpu": "2", "memory": "4Gi"}).priority(1000).obj()
    )


def _preemption_measured(s: TPUScheduler) -> int:
    for i in range(500):
        s.add_pod(
            make_pod(f"vip-{i}").req({"cpu": "2", "memory": "4Gi"}).priority(1000).obj()
        )
    return 500


_register(
    Workload(
        name="preemption_500n",
        baseline_pods_per_sec=18.0,
        build=_fit(512),
        nodes=_preemption_nodes,
        warmup=_preemption_warm,
        measured=_preemption_measured,
        wait_backoff=True,
    )
)

# Upstream Unschedulable: 5k nodes, 10k pods that cannot schedule + churn pods.
def _unsched_measured(s: TPUScheduler) -> int:
    for i in range(5000):
        s.add_pod(
            make_pod(f"stuck-{i}").req({"cpu": "999", "memory": "2Gi"}).obj()
        )
    for i in range(5000):
        s.add_pod(_pod_basic(i))
    return 5000


_register(
    Workload(
        name="unschedulable_5kn_10kpods",
        baseline_pods_per_sec=200.0,
        build=_default(),
        nodes=_basic_nodes(5000),
        warmup=_warm(_pod_basic),
        measured=_unsched_measured,
    )
)


# ---------------------------------------------------------------------------
# Upstream performance-config.yaml ports (one per BASELINE.md row).
# ---------------------------------------------------------------------------

# SchedulingSecrets: upstream pods mount two Secret volumes; Secrets are
# invisible to scheduling decisions (no scheduler plugin reads them), so the
# scheduling-side workload is the basic-pod shape at the Secrets row's scale.
_register(
    Workload(
        name="secrets_5kn_10kpods",
        baseline_pods_per_sec=260.0,
        build=_default(),
        nodes=_basic_nodes(5000),
        warmup=_warm(_pod_basic),
        measured=_measured(_pod_basic, 10000),
    )
)


# SchedulingInTreePVs: one pre-bound zonal PV/PVC pair per pod (VolumeZone +
# VolumeRestrictions + VolumeBinding on the bound path).
def _pv_pod(i: int, driver: str = "") -> t.Pod:
    return make_pod(f"pvpod-{i}").req({"cpu": "100m", "memory": "256Mi"}).pvc_volume(f"claim-{i}").obj()


def _pv_measured(count: int, zones: int = 10, driver: str = ""):
    def measure(s: TPUScheduler) -> int:
        for i in range(count):
            pv_name = f"pv-{i}"
            s.add_pv(
                make_pv(pv_name, zone=f"zone-{i % zones}", csi_driver=driver)
            )
            pvc = make_pvc(f"claim-{i}", volume_name=pv_name)
            s.add_pvc(pvc)
            s.add_pod(_pv_pod(i, driver))
        return count

    return measure


def _pv_warm(zones: int = 10, driver: str = ""):
    """Volume-workload warmup: schedule a volume-ACTIVE wave (so the
    VB/VZ/NVL-active XLA program compiles here, not in the measured
    window).  No shape follows the number of claims the row brings: each
    is a per-node count."""

    def warm(s: TPUScheduler) -> None:
        for i in range(512):
            pv_name = f"warmpv-{i}"
            s.add_pv(make_pv(pv_name, zone=f"zone-{i % zones}", csi_driver=driver))
            s.add_pvc(make_pvc(f"warmclaim-{i}", volume_name=pv_name))
            s.add_pod(
                make_pod(f"warm-{i}").req({"cpu": "100m", "memory": "256Mi"})
                .pvc_volume(f"warmclaim-{i}").obj()
            )

    return warm


_register(
    Workload(
        name="intree_pvs_5kn_2kpods",
        baseline_pods_per_sec=90.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=10),
        warmup=_pv_warm(),
        measured=_pv_measured(2000),
    )
)

# SchedulingMigratedInTreePVs: bound PVs fronted by a CSI driver (migration),
# so NodeVolumeLimits counts them against CSINode attach limits.
def _migrated_nodes(s: TPUScheduler):
    _basic_nodes(5000, zones=10)(s)
    for i in range(5000):
        s.add_csinode(
            t.CSINode(name=f"node-{i}", driver_limits={"pd.csi.storage.gke.io": 39})
        )


_register(
    Workload(
        name="migrated_intree_pvs_5kn_5kpods",
        baseline_pods_per_sec=35.0,
        build=_default(),
        nodes=_migrated_nodes,
        warmup=_pv_warm(driver="pd.csi.storage.gke.io"),
        measured=_pv_measured(5000, driver="pd.csi.storage.gke.io"),
    )
)


# SchedulingCSIPVs: WaitForFirstConsumer claims dynamically provisioned at
# PreBind (volumebinding's delayed path).
def _csi_warm(s: TPUScheduler) -> None:
    s.add_storage_class(
        t.StorageClass(
            name="csi-sc",
            provisioner="ebs.csi.aws.com",
            binding_mode=t.BINDING_WAIT_FOR_FIRST_CONSUMER,
        )
    )
    for i in range(512):
        s.add_pvc(make_pvc(f"warmcsi-{i}", storage_class="csi-sc"))
        s.add_pod(
            make_pod(f"warm-{i}").req({"cpu": "100m", "memory": "256Mi"})
            .pvc_volume(f"warmcsi-{i}").obj()
        )


def _csi_measured(count: int):
    def measure(s: TPUScheduler) -> int:
        s.add_storage_class(
            t.StorageClass(
                name="csi-sc",
                provisioner="ebs.csi.aws.com",
                binding_mode=t.BINDING_WAIT_FOR_FIRST_CONSUMER,
            )
        )
        for i in range(count):
            s.add_pvc(make_pvc(f"csiclaim-{i}", storage_class="csi-sc"))
            s.add_pod(
                make_pod(f"csipod-{i}")
                .req({"cpu": "100m", "memory": "256Mi"})
                .pvc_volume(f"csiclaim-{i}")
                .obj()
            )
        return count

    return measure


_register(
    Workload(
        name="csi_pvs_5kn_5kpods",
        baseline_pods_per_sec=48.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=10),
        warmup=_csi_warm,
        measured=_csi_measured(5000),
    )
)


# SchedulingPreferredPodAntiAffinity.
def _pod_pref_anti(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("color", f"c{i % 50}")
        .preferred_pod_affinity_in("color", [f"c{i % 50}"], ZONE, weight=10, anti=True)
        .obj()
    )


_register(
    Workload(
        name="preferred_pod_anti_affinity_5kn_5kpods",
        baseline_pods_per_sec=90.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=50),
        warmup=_warm(_pod_pref_anti, 1024),
        measured=_measured(_pod_pref_anti, 5000),
    )
)


# SchedulingDaemonset: 15k nodes, one daemon pod per node pinned via the
# metadata.name matchField (what the DaemonSet controller emits).
def _daemonset_measured(s: TPUScheduler) -> int:
    for i in range(15000):
        s.add_pod(
            make_pod(f"ds-{i}")
            .req({"cpu": "100m", "memory": "128Mi"})
            .node_name_affinity(f"node-{i}")
            .obj()
        )
    return 15000


def _daemonset_warm(s: TPUScheduler) -> None:
    # Warm with the measured shape — matchFields-pinned pods — so the
    # NodeAffinity-active program compiles here, spread across nodes.
    for i in range(512):
        s.add_pod(
            make_pod(f"warm-{i}")
            .req({"cpu": "100m", "memory": "128Mi"})
            .node_name_affinity(f"node-{i}")
            .obj()
        )


_register(
    Workload(
        name="daemonset_15kn",
        baseline_pods_per_sec=390.0,
        build=_default(),
        nodes=_basic_nodes(15000),
        warmup=_daemonset_warm,
        measured=_daemonset_measured,
    )
)


# PreferredTopologySpreading (ScheduleAnyway).
def _pod_pref_spread(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("app", f"app-{i % 10}")
        .spread_constraint(1, ZONE, t.SCHEDULE_ANYWAY, "app", [f"app-{i % 10}"])
        .obj()
    )


_register(
    Workload(
        name="preferred_topology_spreading_5kn_5kpods",
        baseline_pods_per_sec=125.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=10),
        warmup=_warm(_pod_pref_spread, 1024),
        measured=_measured(_pod_pref_spread, 5000),
    )
)


# MixedSchedulingBasePod: base pods measured against a warm state holding
# affinity/anti-affinity/spread pods (performance-config.yaml:615).
def _mixed_warm(s: TPUScheduler):
    for i in range(400):
        s.add_pod(_pod_basic(10**6 + i))
    for i in range(400):
        p = _pod_affinity(2 * i + 1)
        p.metadata.name = f"mwa-{i}"
        s.add_pod(p)
    for i in range(400):
        p = _pod_pref_anti(i)
        p.metadata.name = f"mwpa-{i}"
        s.add_pod(p)
    for i in range(400):
        p = _pod_spread(i)
        p.metadata.name = f"mws-{i}"
        s.add_pod(p)
    # Drain the mixed pods FIRST, then warm a basic-only wave: the measured
    # batches are basic pods, whose (smaller) batch-active op set compiles
    # its own XLA program — that compile must land in warmup.
    s.schedule_all_pending()
    for i in range(2048):
        s.add_pod(_pod_basic(2 * 10**6 + i))


_register(
    Workload(
        name="mixed_scheduling_base_pod_5kn_5kpods",
        baseline_pods_per_sec=140.0,
        build=_default(),
        nodes=_basic_nodes(5000, zones=10),
        warmup=_mixed_warm,
        measured=_measured(_pod_basic, 5000),
    )
)


# PreemptionPVs: victims carry bound PVs (500 nodes, shape of PreemptionBasic).
def _preemption_pv_warm(s: TPUScheduler):
    for i in range(2000):
        pv_name = f"bgpv-{i}"
        s.add_pv(make_pv(pv_name, zone=f"zone-{i % 3}"))
        s.add_pvc(make_pvc(f"bgclaim-{i}", volume_name=pv_name))
        s.add_pod(
            make_pod(f"bg-{i}").req({"cpu": "1", "memory": "2Gi"}).priority(1)
            .start_time(float(i)).pvc_volume(f"bgclaim-{i}").obj()
        )
    s.schedule_all_pending(wait_backoff=True)  # see _preemption_warm
    s.add_pod(
        make_pod("warm-vip").req({"cpu": "2", "memory": "4Gi"}).priority(1000).obj()
    )


_register(
    Workload(
        name="preemption_pvs_500n",
        baseline_pods_per_sec=18.0,
        build=_fit(512),
        nodes=_preemption_nodes,
        warmup=_preemption_pv_warm,
        measured=_preemption_measured,
        wait_backoff=True,
    )
)


# PreemptionAsync: 5k nodes saturated with low-priority pods, 1k preemptors.
def _preemption_async_warm(s: TPUScheduler):
    for i in range(20000):
        s.add_pod(
            make_pod(f"bg-{i}").req({"cpu": "3900m", "memory": "15Gi"}).priority(1)
            .start_time(float(i)).obj()
        )
    # Drain first so the warm preemptor finds full nodes and actually
    # compiles the preemption pass + nominated-retry path in warmup.
    s.schedule_all_pending(wait_backoff=True)
    s.add_pod(
        make_pod("warm-vip").req({"cpu": "2", "memory": "4Gi"}).priority(1000).obj()
    )


def _preemption_async_measured(s: TPUScheduler) -> int:
    for i in range(1000):
        s.add_pod(
            make_pod(f"vip-{i}").req({"cpu": "2", "memory": "4Gi"}).priority(1000).obj()
        )
    return 1000


_register(
    Workload(
        name="preemption_async_5kn",
        baseline_pods_per_sec=200.0,
        # chunk 128 re-ranked as the sweet spot after the fused tail +
        # uniform all-fail shortcut landed (interleaved 128/256 draws;
        # collision deferrals now resolve on-device, so the old
        # 512-explodes-the-tail constraint is gone).
        build=lambda: TPUScheduler(
            profile=fit_only_profile(), batch_size=1024, chunk_size=128
        ),
        nodes=lambda s: _basic_nodes(5000, cpu="4", mem="16Gi")(s),
        warmup=_preemption_async_warm,
        measured=_preemption_async_measured,
        wait_backoff=True,
    )
)


# ---------------------------------------------------------------------------
# Heterogeneous clusters (ISSUE 14): mixed accelerator-class node pools +
# the ThroughputAware / LearnedScorer profiles, selected by schedulerName
# through the multi-profile map (its own compiled XLA program family).
# ---------------------------------------------------------------------------

# Pool deal for the mixed fleets: 50% tpu-v4, 30% tpu-v5e, 20% gpu-a100
# (deterministic by node index — the same fleet every run).
HETERO_POOLS: tuple[tuple[str, int], ...] = (
    ("tpu-v4", 5), ("tpu-v5e", 3), ("gpu-a100", 2),
)


def hetero_accel_for(i: int, pools: tuple[tuple[str, int], ...] = HETERO_POOLS) -> str:
    """Accelerator class of node ``i`` under the weighted pool deal."""
    total = max(sum(w for _a, w in pools), 1)
    r = i % total
    for accel, w in pools:
        if r < w:
            return accel
        r -= w
    return pools[-1][0]


def _hetero_nodes(n: int, zones: int = 10):
    from ..ops.throughput import ACCEL_LABEL_KEY

    def add(s: TPUScheduler):
        for i in range(n):
            s.add_node(
                make_node(f"node-{i}")
                .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
                .zone(f"zone-{i % zones}")
                .region("region-1")
                .label(ACCEL_LABEL_KEY, hetero_accel_for(i))
                .obj()
            )

    return add


def _pod_hetero(i: int, scheduler_name: str = "throughput-aware-scheduler") -> t.Pod:
    from ..ops.throughput import (
        DEFAULT_THROUGHPUT_MATRIX,
        WORKLOAD_CLASS_LABEL_KEY,
    )

    classes = [w for w, _row in DEFAULT_THROUGHPUT_MATRIX]
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("app", f"app-{i % 10}")
        .label(WORKLOAD_CLASS_LABEL_KEY, classes[i % len(classes)])
        .scheduler(scheduler_name)
        .obj()
    )


def _pod_hetero_learned(i: int) -> t.Pod:
    return _pod_hetero(i, scheduler_name="learned-scorer-scheduler")


def _hetero_build(batch: int = 4096, chunk: int = 64):
    def build() -> TPUScheduler:
        from ..ops.learned import learned_scorer_profile
        from ..ops.throughput import throughput_aware_profile

        return TPUScheduler(
            profile=registered_subset(DEFAULT_PROFILE),
            profiles=[throughput_aware_profile(), learned_scorer_profile()],
            batch_size=batch,
            chunk_size=chunk,
        )

    return build


def _hetero_warm(template: Callable[[int], t.Pod], count: int = 1024):
    def warm(s: TPUScheduler) -> None:
        from ..ops.throughput import preseed_hetero_vocab

        # Pre-seed the accelerator-class + workload-class vocabularies
        # (and the throughput-matrix row keys) BEFORE the warm wave
        # compiles the device programs — without it the first mid-window
        # heterogeneous pod grows the topo/label vocab and pays the XLA
        # recompile inside the measured window (the PR 9/PR 10
        # taint-vocab trap, heterogeneity edition).
        preseed_hetero_vocab(s.builder)
        _warm(template, count)(s)

    return warm


_register(
    Workload(
        name="hetero_1kn_5kpods",
        baseline_pods_per_sec=270.0,
        build=_hetero_build(),
        nodes=_hetero_nodes(1000),
        warmup=_hetero_warm(_pod_hetero),
        measured=_measured(_pod_hetero, 5000),
    )
)

_register(
    Workload(
        name="hetero_5kn_10kpods",
        baseline_pods_per_sec=270.0,
        build=_hetero_build(),
        nodes=_hetero_nodes(5000),
        warmup=_hetero_warm(_pod_hetero, 2048),
        measured=_measured(_pod_hetero, 10000),
    )
)

_register(
    Workload(
        name="hetero_learned_1kn_5kpods",
        baseline_pods_per_sec=270.0,
        build=_hetero_build(),
        nodes=_hetero_nodes(1000),
        warmup=_hetero_warm(_pod_hetero_learned),
        measured=_measured(_pod_hetero_learned, 5000),
    )
)


# SchedulingWithMixedChurn: node churn interleaved with measured batches
# (the churn op, scheduler_perf.go:89).
def _node_churn(s: TPUScheduler, i: int) -> None:
    name = f"churn-{i}"
    s.add_node(
        make_node(name).capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
        .zone(f"zone-{i % 3}").obj()
    )
    if i > 0:
        s.remove_node(f"churn-{i - 1}")


_register(
    Workload(
        name="mixed_churn_5kn_10kpods",
        baseline_pods_per_sec=265.0,
        build=_default(),
        nodes=_basic_nodes(5000),
        warmup=_warm(_pod_basic),
        measured=_measured(_pod_basic, 10000),
        churn=_node_churn,
    )
)


# NSSelector affinity cases: terms select pods across namespaces via a
# namespaceSelector (performance-config.yaml:857-1022).  Six namespaces
# labeled team=red/blue.
def _ns_setup(s: TPUScheduler) -> None:
    for j in range(6):
        s.builder.set_namespace_labels(
            f"ns-{j}", {"team": "red" if j % 2 else "blue", "idx": str(j)}
        )


def _ns_pod(i: int, anti: bool, preferred: bool) -> t.Pod:
    w = make_pod(f"pod-{i}", namespace=f"ns-{i % 6}").req(
        {"cpu": "100m", "memory": "256Mi"}
    ).label("color", f"c{i % 100 if anti else i % 40}")
    kwargs = dict(preferred_weight=10) if preferred else {}
    return w.ns_selector_pod_affinity_in(
        "color",
        [f"c{i % 100 if anti else i % 40}"],
        ZONE,
        "team",
        ["red", "blue"],
        anti=anti,
        **kwargs,
    ).obj()


def _ns_workload(name: str, baseline: float, anti: bool, preferred: bool, count: int):
    def nodes(s: TPUScheduler):
        _ns_setup(s)
        _basic_nodes(5000, zones=100)(s)

    _register(
        Workload(
            name=name,
            baseline_pods_per_sec=baseline,
            build=_default(2048),
            nodes=nodes,
            warmup=_warm(lambda i: _ns_pod(i, anti, preferred), 512),
            measured=_measured(lambda i: _ns_pod(i, anti, preferred), count),
        )
    )


_ns_workload("ns_required_anti_affinity_5kn_2kpods", 24.0, True, False, 2000)
_ns_workload("ns_preferred_anti_affinity_5kn_2kpods", 55.0, True, True, 2000)
_ns_workload("ns_required_affinity_5kn_2kpods", 35.0, False, False, 2000)
_ns_workload("ns_preferred_affinity_5kn_5kpods", 90.0, False, True, 5000)


# SchedulingWithNodeInclusionPolicy: half the nodes are tainted; spread
# constraints honor node taints when counting domains.
def _inclusion_nodes(s: TPUScheduler):
    for i in range(5000):
        w = make_node(f"node-{i}").capacity(
            {"cpu": "16", "memory": "64Gi", "pods": 110}
        ).zone(f"zone-{i % 10}")
        if i % 2:
            w = w.taint("dedicated", "gpu", t.EFFECT_NO_SCHEDULE)
        s.add_node(w.obj())


def _pod_inclusion(i: int) -> t.Pod:
    return (
        make_pod(f"pod-{i}")
        .req({"cpu": "100m", "memory": "256Mi"})
        .label("app", f"app-{i % 10}")
        .spread_constraint(
            1, ZONE, t.DO_NOT_SCHEDULE, "app", [f"app-{i % 10}"],
            node_taints_policy=t.POLICY_HONOR,
        )
        .obj()
    )


_register(
    Workload(
        name="node_inclusion_policy_5kn",
        baseline_pods_per_sec=68.0,
        build=_default(),
        nodes=_inclusion_nodes,
        warmup=_warm(_pod_inclusion, 1024),
        measured=_measured(_pod_inclusion, 5000),
    )
)


# SchedulingWhileGated: one huge node, 10k gated pods parked in the
# PreEnqueue pool, throughput measured on schedulable pods.
def _gated_nodes(s: TPUScheduler):
    s.add_node(
        make_node("node-0").capacity(
            {"cpu": "4000", "memory": "4000Gi", "pods": 30000}
        ).zone("zone-0").obj()
    )


def _gated_measured(with_affinity: bool):
    def measure(s: TPUScheduler) -> int:
        for i in range(10000):
            w = make_pod(f"gated-{i}").req({"cpu": "1m"}).scheduling_gate("example.com/hold")
            if with_affinity:
                w = w.label("color", f"g{i % 100}").pod_affinity_in(
                    "color", [f"g{i % 100}"], "kubernetes.io/hostname"
                )
            s.add_pod(w.obj())
        for i in range(2000):
            s.add_pod(make_pod(f"m-{i}").req({"cpu": "1m"}).obj())
        return 2000

    return measure


_register(
    Workload(
        name="gated_1node_10kgated",
        baseline_pods_per_sec=130.0,
        build=_default(2048),
        nodes=_gated_nodes,
        warmup=_warm(lambda i: make_pod(f"w-{i}").req({"cpu": "1m"}).obj(), 512),
        measured=_gated_measured(False),
    )
)

_register(
    Workload(
        name="gated_affinity_1node_10kgated",
        baseline_pods_per_sec=110.0,
        build=_default(2048),
        nodes=_gated_nodes,
        warmup=_warm(lambda i: make_pod(f"w-{i}").req({"cpu": "1m"}).obj(), 512),
        measured=_gated_measured(True),
    )
)


def row_failed(r: dict) -> bool:
    """A result row that must fail its run: the child died or hung
    (``error``), or the measured window hit the engine-fault recovery
    path."""
    return bool(
        r.get("error") or r.get("engine_faults") or r.get("quarantined")
    )


def _refuse_unknown(names: list[str] | None) -> None:
    unknown = [n for n in names or () if n not in WORKLOADS]
    if unknown:
        raise SystemExit(
            f"unknown workload(s): {unknown}; available: {sorted(WORKLOADS)}"
        )


def main(
    names: list[str] | None = None, pipeline_depth: int | None = None
) -> list[dict]:
    """Run workloads IN THIS PROCESS (the sweep's subprocess leaf, and
    ``python -m kubernetes_tpu bench``).  Asks for the device first —
    no accelerator is an error unless JAX_PLATFORMS names cpu — and
    every printed row names it."""
    from ..utils import require_device

    _refuse_unknown(names)
    device = require_device()
    results = []
    for name, w in WORKLOADS.items():
        if names and name not in names:
            continue
        r = {**run_workload(w, pipeline_depth=pipeline_depth), **device}
        print(json.dumps(r), flush=True)
        results.append(r)
    return results


# Per-row wall limit of the isolated sweep: the slowest recorded row is
# minutes, so a child past this is hung, not slow.
ROW_TIMEOUT_S = 1800.0


def main_isolated(
    names: list[str] | None = None, pipeline_depth: int | None = None
) -> list[dict]:
    """Run each workload in a FRESH subprocess — the sweep analog of
    scheduler_perf's per-case process isolation.  A long-lived process
    accumulates host allocator/GC pressure that degrades later workloads
    versus their solo numbers; XLA compiles stay warm across processes
    via the persistent compilation cache (kubernetes_tpu/__init__.py).
    This parent never touches the device — each child is, in turn, the
    one process that holds it.  A child that dies or outlasts
    ROW_TIMEOUT_S becomes an ``error`` row and the sweep carries on;
    the caller exits non-zero if any row failed (``row_failed``)."""
    import subprocess
    import sys as _sys

    _refuse_unknown(names)
    from ..utils import refuse_if_holding_device

    refuse_if_holding_device("a benchmark child")
    results = []
    for name in WORKLOADS:
        if names and name not in names:
            continue
        argv = [_sys.executable, "-m", "kubernetes_tpu.benchmarks.harness", name]
        if pipeline_depth is not None:
            argv += ["--pipeline-depth", str(pipeline_depth)]
        try:
            proc = subprocess.run(
                argv, capture_output=True, text=True, timeout=ROW_TIMEOUT_S
            )
        except subprocess.TimeoutExpired:
            row = {"name": name, "error": f"timed out after {ROW_TIMEOUT_S}s"}
        else:
            lines = [
                ln.strip() for ln in proc.stdout.splitlines()
                if ln.strip().startswith("{")
            ]
            row = json.loads(lines[-1]) if lines else {"name": name}
            if (proc.returncode or not lines) and not row_failed(row):
                row["error"] = (
                    f"rc={proc.returncode}: "
                    + (proc.stderr or "no output")[-400:]
                )
        print(json.dumps(row), flush=True)
        results.append(row)
    return results


if __name__ == "__main__":
    import sys

    args = sys.argv[1:]
    depth = None
    if "--pipeline-depth" in args:
        i = args.index("--pipeline-depth")
        depth = int(args[i + 1])
        args = args[:i] + args[i + 2:]
    if args and args[0] == "--isolated":
        rows = main_isolated(args[1:] or None, pipeline_depth=depth)
    elif not args:
        rows = main_isolated(None, pipeline_depth=depth)  # default sweep
    else:
        # named workload(s): in-process (the subprocess leaf)
        rows = main(args, pipeline_depth=depth)
    sys.exit(1 if any(row_failed(r) for r in rows) else 0)
