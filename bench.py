"""Benchmark driver: ONE JSON line for the headline metric.

Headline: pods scheduled/sec at 5k-node/30k-pod scale with the full default
plugin profile on one TPU chip (BASELINE config #4; upstream CI threshold for
the closest case, SchedulingBasic 5000Nodes_10000Pods, is 270 pods/s —
test/integration/scheduler_perf/config/performance-config.yaml:51).

The device is asked for first (kubernetes_tpu.utils.require_device): no
accelerator is an error unless JAX_PLATFORMS names cpu, and the payload
names the platform it ran on.  A phase that raises fails the run; so does
a measured window that hit the engine-fault recovery path
(``engine_faults`` / ``quarantined`` non-zero).

Run ``python -m kubernetes_tpu.benchmarks.harness`` for the full
scheduler_perf-style suite (each workload prints its own JSON DataItem).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def _flagship_block() -> dict:
    """The explicitly-named worst case (BASELINE config #3,
    interpodaffinity_1kn_10kpods) rides every headline payload, so a
    regression on the flagship row shows up here instead of hiding until
    the next full sweep."""
    from kubernetes_tpu.benchmarks import WORKLOADS, run_workload

    r = run_workload(
        WORKLOADS["interpodaffinity_1kn_10kpods"], pipeline_depth=2
    )
    return {
        "name": r["name"],
        "value": r["pods_per_sec"],
        "vs_baseline": r["vs_baseline"],
        "seconds": r["seconds"],
        "device_s": r["device_s"],
        "featurize_s": r["featurize_s"],
        "batches": r["batches"],
        "deferred": r["deferred"],
        "packed_batches": r["packed_batches"],
        "pack_collisions": r["pack_collisions"],
        "dom_carry": r["dom_carry"],
        "phase_attribution": r["phase_attribution"],
        "engine_faults": r["engine_faults"],
        "quarantined": r["quarantined"],
    }


def _lint_clean() -> bool:
    """Zero unsuppressed tpulint findings (scripts/check_lint.py --json)?
    Rides the bench payload so a recorded trajectory point also certifies
    the invariants (WAL ordering, kernel determinism, metrics hygiene,
    wire exhaustiveness) held when the number was taken."""
    import subprocess

    script = os.path.join(
        os.path.dirname(__file__), "scripts", "check_lint.py"
    )
    proc = subprocess.run(
        [sys.executable, script, "--json"],
        capture_output=True,
        text=True,
        timeout=120,
    )
    return bool(json.loads(proc.stdout)["clean"])


def _slo_block() -> dict:
    """Serving percentiles for the trajectory: a short seeded in-process
    soak (loadgen/) rides every headline payload, so the recorded points
    carry p50/p99/p999 decision latency and the speculation miss rate
    next to the throughput number.  Budget comes from TPU_SLO_BUDGET_MS
    (default 250)."""
    budget_ms = float(os.environ.get("TPU_SLO_BUDGET_MS", "250"))
    from kubernetes_tpu.loadgen.soak import SoakConfig, run_soak

    art = run_soak(
        SoakConfig(
            seed=6,
            nodes=64,
            zones=8,
            churn_nodes=2,
            rate_pods_per_s=100.0,
            duration_s=4.0,
            knee_points=(8.0,),
            knee_phase_s=1.0,
            invalidation_rate_per_s=0.25,
            node_flap_period_s=0.0,
            live_pod_cap=300,
            slo_budget_ms=budget_ms,
            batch_size=128,
            chunk_size=32,
            warm_pods=128,
            two_process=False,
            pace="virtual",
            journal_fsync="never",
        )
    )
    slo = art["slo"]
    block = {
        "p50_ms": slo["p50_ms"],
        "p99_ms": slo["p99_ms"],
        "p999_ms": slo["p999_ms"],
        "budget_ms": budget_ms,
        "violations": slo["violations"],
        "decisions": slo["decisions"],
        "miss_rate": art["speculation"]["miss_rate"],
    }
    if block["p99_ms"] > budget_ms:
        print(
            f"bench: soak p99 {block['p99_ms']}ms exceeds the "
            f"{budget_ms}ms SLO budget ({block['violations']} violations "
            f"in {block['decisions']} decisions)",
            file=sys.stderr,
        )
    return block


def _load_sentinel():
    """Import scripts/bench_sentinel.py by file path (stdlib-only, the
    profile_report idiom) — the declarative guard table lives there so
    the tier-1 ``--check`` gate and this embedding share one table."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(__file__), "scripts", "bench_sentinel.py"
    )
    spec = importlib.util.spec_from_file_location("_bench_sentinel", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _measured_provenance() -> dict | None:
    """Provenance of the committed measured-matrix artifact
    (framework/measured.py), riding every bench payload from this PR on:
    the artifact file's sha plus its derivation window and source sha,
    so a trajectory point records WHICH measured matrix was current.
    None when no artifact is committed yet."""
    import hashlib

    path = os.path.join(os.path.dirname(__file__), "measured_matrix.json")
    try:
        with open(path, "rb") as f:
            raw = f.read()
        doc = json.loads(raw)
    except (OSError, ValueError):
        return None
    return {
        "file": os.path.basename(path),
        "sha256": hashlib.sha256(raw).hexdigest(),
        "version": doc.get("version"),
        "window": doc.get("window"),
        "source_sha256": (doc.get("source") or {}).get("sha256"),
    }


def main() -> int:
    from kubernetes_tpu.benchmarks import WORKLOADS, run_workload
    from kubernetes_tpu.utils import require_device

    device = require_device()
    # The headline runs WITH the write-ahead journal armed (fsync on
    # every append) so the recorded trajectory carries journaling's true
    # overhead.  Snapshot cadence 4: the 30k-pod run is ~8 batches at
    # batch 4096, so the serve default of 64 would never checkpoint
    # inside the window — 4 puts a couple of full-store snapshot writes
    # INTO the measured number.
    with tempfile.TemporaryDirectory() as td:
        from kubernetes_tpu.journal import Journal

        journal = Journal(td, epoch=1)

        def attach(sched) -> None:
            sched.attach_journal(journal, snapshot_every_batches=4)

        # Pipeline depth 2 (ISSUE 15): featurize(k+1) and the group-
        # committed journal drain of batch k both overlap device(k+1);
        # bindings bit-identical to depth 1 (the parity oracle
        # tests/test_pipeline.py holds).
        r = run_workload(
            WORKLOADS["density_5kn_30kpods_default"], attach=attach,
            pipeline_depth=2,
        )
        jstats = journal.stats()
        append_p50_us = round(journal.append_latency.quantile(0.50) * 1e6, 3)
    flagship = _flagship_block()
    payload = {
        "metric": "scheduling_throughput_5k_nodes_30k_pods_default_plugins",
        "value": r["pods_per_sec"],
        "unit": "pods/s",
        "vs_baseline": r["vs_baseline"],
        # The device the numbers were taken on, as JAX reports it.
        **device,
        # Non-zero means the window measured the recovery path, not the
        # workload: the run exits non-zero (headline + flagship summed).
        "engine_faults": r["engine_faults"] + flagship["engine_faults"],
        "quarantined": r["quarantined"] + flagship["quarantined"],
        # The flagship worst-case row (BASELINE #3).
        "flagship": flagship,
        "lint_clean": _lint_clean(),
        # Serving percentiles (loadgen short soak): p50/p99/p999
        # decision latency + speculation miss rate, with a stderr
        # warning when p99 blows the configured budget.
        "slo": _slo_block(),
        # Per-phase attribution of the measured window (flight recorder
        # tiling): which phase a future regression ate.  coverage = tiled
        # phases / measured wall time; the acceptance bar is >= 0.95
        # (warned below, not exit-gated).  With the pipeline on, coverage
        # > 1.0 is the overlap working: the excess is wall time saved vs
        # serial.
        "phase_attribution": r["phase_attribution"],
        # Software pipeline (ISSUE 15): predispatch hit rate, drain
        # placement, and overlap seconds saved.
        "pipeline": r["pipeline"],
        "detail": {
            "scheduled": r["scheduled"],
            "seconds": r["seconds"],
            "throughput": r["throughput"],
            "device_s": r["device_s"],
            "featurize_s": r["featurize_s"],
            "batches": r["batches"],
            # Per-extension-point latency histograms (p50/p99 + overflow)
            # and span stats ride the headline payload.
            "extension_points": r["metrics_summary"][
                "extension_point_duration_seconds"
            ],
            "attempt_duration": r["metrics_summary"][
                "scheduling_attempt_duration_seconds"
            ],
            "slow_cycles": r["spans"]["slow_cycles"],
            # Journal overhead for the whole run (warmup included;
            # appends ride the commit path, so the per-append p99 is the
            # durability tax on a binding).
            "journal": {
                "appends": jstats["appends"],
                "fsyncs": jstats["fsyncs"],
                # Group commit: one write and one fsync barrier per
                # staged commit group instead of one per binding (the
                # append latencies below are one observation a write).
                "writes": jstats["writes"],
                "group_commits": jstats["group_commits"],
                "max_group_size": jstats["max_group_size"],
                "snapshots": jstats["snapshots"],
                "journal_append_p99_us": jstats["append_p99_us"],
                "append_p50_us": append_p50_us,
                "wal_bytes": jstats["wal_bytes"],
            },
        },
    }
    # The declarative sentinel (ISSUE 16): every guard the table names,
    # evaluated against THIS payload + the committed references; its hard
    # floors are the exit decision.
    sentinel = payload["sentinel"] = _load_sentinel().evaluate(payload)
    payload["measured_matrix"] = _measured_provenance()
    print(json.dumps(payload))
    if r["phase_attribution"]["coverage"] < 0.95:
        print(
            f"bench: phase attribution covers only "
            f"{r['phase_attribution']['coverage']:.1%} of measured wall "
            "time (target >= 95%) — the tiling is leaking",
            file=sys.stderr,
        )
    if payload["engine_faults"] or payload["quarantined"]:
        print(
            f"bench: engine_faults={payload['engine_faults']} "
            f"quarantined={payload['quarantined']} inside the measured "
            "window — the number is the recovery path's, not the workload's",
            file=sys.stderr,
        )
        return 1
    if sentinel["hard_failures"]:
        print(
            "bench guard HARD FAIL: sentinel floors breached — "
            f"{', '.join(sentinel['hard_failures'])} (see the sentinel "
            "block / bench_sentinel.py)",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
