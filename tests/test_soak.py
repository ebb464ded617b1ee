"""Tier-1 soak smoke (loadgen/): a seconds-scale seeded soak runs end
to end in-process, populates the SLO-percentile and miss-rate-knee
fields, and is deterministic — the same seed reproduces the arrival
schedule exactly and lands bit-identical final bindings.
scripts/run_soak.py makes the minutes-scale two-process run; this is
the always-on guard that the harness itself stays correct and
replayable."""

import json

import pytest

from kubernetes_tpu.loadgen.arrivals import (
    coalesce,
    diurnal_offsets,
    poisson_offsets,
)
from kubernetes_tpu.loadgen.scenarios import build_events
from kubernetes_tpu.loadgen.soak import SoakConfig, run_soak, strip_private
from kubernetes_tpu.loadgen.workloads import WorkloadMix


def smoke_config(seed: int = 3) -> SoakConfig:
    return SoakConfig(
        seed=seed,
        nodes=16,
        zones=4,
        churn_nodes=2,
        rate_pods_per_s=100.0,
        duration_s=2.0,
        knee_points=(2.0, 20.0),
        knee_phase_s=1.0,
        invalidation_rate_per_s=0.5,
        node_flap_period_s=1.0,
        flap_down_s=0.3,
        cold_consumer_period_s=1.5,
        live_pod_cap=60,
        batch_size=32,
        chunk_size=8,
        warm_pods=32,
        two_process=False,
        pace="virtual",  # no sleeping: the smoke is seconds-scale
        snapshot_every=4,
        journal_fsync="never",  # container fsync is ~10ms; smoke stays fast
    )


# -- the generators alone ---------------------------------------------------


def test_poisson_schedule_is_seeded_and_sorted():
    a = poisson_offsets(50.0, 10.0, seed=7)
    b = poisson_offsets(50.0, 10.0, seed=7)
    c = poisson_offsets(50.0, 10.0, seed=8)
    assert a == b
    assert a != c
    assert a == sorted(a)
    assert all(0.0 <= t < 10.0 for t in a)
    # Rate sanity: ~500 expected, Poisson sd ~22.
    assert 350 < len(a) < 650


def test_diurnal_schedule_modulates_rate():
    offs = diurnal_offsets(
        base_rate=10.0, peak_rate=100.0, period_s=10.0, duration_s=10.0,
        seed=5,
    )
    assert offs == diurnal_offsets(10.0, 100.0, 10.0, 10.0, seed=5)
    # The crest (middle of the period) must carry several times the
    # trough's arrivals.
    trough = sum(1 for t in offs if t < 2.0 or t >= 8.0)
    crest = sum(1 for t in offs if 3.0 <= t < 7.0)
    assert crest > 2 * max(1, trough)


def test_coalesce_windows_preserve_indices():
    offs = [0.05, 0.1, 0.3, 0.31, 0.9]
    windows = coalesce(offs, 0.25)
    assert [idxs for _t, idxs in windows] == [[0, 1], [2, 3], [4]]
    assert [t for t, _ in windows] == [0.0, 0.25, 0.75]


def test_scenario_script_is_seeded():
    kw = dict(
        nodes=8, churn_nodes=2, invalidation_rate_per_s=5.0,
        node_flap_period_s=1.0, cold_consumer_period_s=2.0,
    )
    a = build_events(5.0, seed=11, **kw)
    assert a == build_events(5.0, seed=11, **kw)
    assert a != build_events(5.0, seed=12, **kw)
    kinds = {e.kind for e in a}
    assert "flap_down" in kinds and "flap_up" in kinds
    assert "cold_consumer" in kinds
    assert kinds & {"inv_capacity", "inv_label", "inv_ns"}
    assert [e.t for e in a] == sorted(e.t for e in a)


def test_autoscale_ticks_ride_the_scenario_clock():
    """ISSUE 11: the elastic control loop's cadence is scripted like
    every other scenario event — interval-regular, merged in time
    order, absent when disarmed."""
    kw = dict(nodes=8, churn_nodes=2, invalidation_rate_per_s=1.0)
    a = build_events(10.0, seed=11, autoscale_interval_s=2.5, **kw)
    ticks = [e for e in a if e.kind == "autoscale_tick"]
    assert [e.t for e in ticks] == [2.5, 5.0, 7.5]
    assert [e.data for e in ticks] == [0, 1, 2]
    assert [e.t for e in a] == sorted(e.t for e in a)
    off = build_events(10.0, seed=11, **kw)
    assert not [e for e in off if e.kind == "autoscale_tick"]


def test_workload_mix_is_seeded_and_renames():
    a = WorkloadMix("mixed", seed=4)
    b = WorkloadMix("mixed", seed=4)
    pods_a = [a.pod(i) for i in range(40)]
    pods_b = [b.pod(i) for i in range(40)]
    assert [p.uid for p in pods_a] == [p.uid for p in pods_b]
    assert all(p.metadata.name == f"lg-{i}" for i, p in enumerate(pods_a))
    assert a.counts == b.counts
    assert sum(a.counts.values()) == 40
    with pytest.raises(ValueError):
        WorkloadMix("no-such-mix", seed=0)


# -- the harness end to end -------------------------------------------------


@pytest.fixture(scope="module")
def soak_artifacts():
    """Run the smoke soak TWICE with one seed (the determinism
    contract is the expensive half of the assertion set — share the
    runs across tests)."""
    return run_soak(smoke_config()), run_soak(smoke_config())


def test_soak_runs_end_to_end_and_populates_fields(soak_artifacts):
    art, _ = soak_artifacts
    slo = art["slo"]
    assert slo["decisions"] > 100
    assert slo["p50_ms"] >= 0.0
    assert slo["p99_ms"] >= slo["p50_ms"]
    assert slo["p999_ms"] >= slo["p99_ms"]
    assert slo["budget_ms"] == 250.0
    assert art["sustained_pods_per_sec"] > 0
    # Knee fields: one point per configured intensity, each populated.
    knee = art["knee"]
    assert [p["intensity_per_s"] for p in knee["points"]] == [2.0, 20.0]
    for p in knee["points"]:
        assert p["decisions"] > 0
        assert 0.0 <= p["hit_rate"] <= 1.0
        assert p["p99_ms"] >= p["p50_ms"] >= 0.0
    assert knee["miss_cost_ms"] > 0
    # Speculation served from the cache at least once, missed at least
    # once (the knee needs both sides).
    spec = art["speculation"]
    assert spec["hits"] > 0 and spec["misses"] > 0
    assert 0.0 < spec["miss_rate"] < 1.0
    # The sidecar's own stats rode the dump.
    assert spec["sidecar"]["speculated"] > 0
    # Journal growth was observed and stayed bounded (the snapshot
    # cadence truncated at least twice over the stream).
    j = art["journal"]
    assert j["dir_sampled"]
    assert j["compactions_observed"] >= 2
    assert j["stats"]["truncations"] >= 2
    assert j["bounded"]
    # Retirement kept the live set capped.
    assert art["retired_total"] > 0
    assert art["bound_final"] <= smoke_config().live_pod_cap
    # Scenario machinery actually fired.
    assert art["cold_consumers"] >= 1
    flaps = sum(
        p["events"].get("flap_down", 0) for p in art["phases"]
    )
    assert flaps >= 1


def test_soak_same_seed_same_schedule_and_bindings(soak_artifacts):
    a, b = soak_artifacts
    # Identical arrival schedule, offset for offset…
    assert a["_arrival_offsets"] == b["_arrival_offsets"]
    assert (
        a["determinism"]["arrival_sha256"]
        == b["determinism"]["arrival_sha256"]
    )
    # …and bit-identical final bindings.
    assert (
        a["determinism"]["bindings_sha256"]
        == b["determinism"]["bindings_sha256"]
    )
    assert a["bound_final"] == b["bound_final"]
    assert a["determinism"]["arrivals_total"] > 0


def test_soak_artifact_is_json_clean(soak_artifacts):
    art, _ = soak_artifacts
    doc = strip_private(art)
    assert "_arrival_offsets" not in doc
    # The committed-artifact view must round-trip as plain JSON.
    assert json.loads(json.dumps(doc)) == doc


def test_different_seed_changes_schedule(soak_artifacts):
    a, _ = soak_artifacts
    c = run_soak(smoke_config(seed=4))
    assert (
        c["determinism"]["arrival_sha256"]
        != a["determinism"]["arrival_sha256"]
    )


# -- the resumable driver (ISSUE 18): kill/resume bit-identity --------------
#
# The checkpointer's whole claim is that a SIGKILLed soak driver,
# resumed from its last atomic checkpoint, finishes bit-identical to an
# uninterrupted same-seed run — at a checkpoint BOUNDARY kill (the
# checkpoint is the last executed op) and a MID-INTERVAL kill (ops past
# the checkpoint are re-derived by the deterministic prefix replay).
# Subprocesses, real SIGKILL: the in-process path cannot fake dying.

import os
import signal
import subprocess
import sys

RESUME_BASE = dict(
    seed=7,
    nodes=40,
    zones=4,
    churn_nodes=4,
    rate_pods_per_s=30.0,
    duration_s=6.0,
    knee_points=(),
    invalidation_rate_per_s=0.2,
    node_flap_period_s=0.0,
    pace="virtual",
    batch_size=64,
    chunk_size=16,
    warm_pods=32,
    live_pod_cap=400,
    journal_fsync="never",
    scripted_events=((3.0, "owner_kill", 1),),
    checkpoint_every_ops=40,
)

RESUME_CHILD = """
import dataclasses, json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, sys.argv[2])
from kubernetes_tpu.loadgen.soak import SoakConfig, run_fleet_soak
art = run_fleet_soak(SoakConfig(**json.loads(sys.argv[1])), 2)
print("RESULT:" + json.dumps(
    {"determinism": art["determinism"], "resume": art["resume"]}
))
"""


def _run_resume_child(cfg_dict):
    repo = os.path.join(os.path.dirname(__file__), "..")
    return subprocess.run(
        [sys.executable, "-c", RESUME_CHILD, json.dumps(cfg_dict), repo],
        capture_output=True,
        text=True,
        timeout=600,
    )


def _child_result(proc):
    line = [
        ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")
    ][-1]
    return json.loads(line[len("RESULT:"):])


DET_KEYS = (
    "arrival_sha256",
    "bindings_sha256",
    "timeline_sha256",
    "driver_state_sha256",
    "arrivals_total",
)


@pytest.fixture(scope="module")
def resume_twin(tmp_path_factory):
    """The uninterrupted same-seed twin every kill/resume leg is
    compared against (one subprocess, shared across the legs)."""
    tmp = tmp_path_factory.mktemp("resume-twin")
    cfg = dict(
        RESUME_BASE,
        out_dir=str(tmp / "out"),
        journal_dir=str(tmp / "journal"),
        checkpoint_path=str(tmp / "soak.ckpt"),
    )
    proc = _run_resume_child(cfg)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return _child_result(proc)


@pytest.mark.parametrize(
    "kill_after_op",
    [
        pytest.param(40, id="checkpoint-boundary"),
        pytest.param(57, id="mid-interval"),
    ],
)
def test_soak_driver_killed_and_resumed_is_bit_identical(
    resume_twin, tmp_path, kill_after_op
):
    cfg = dict(
        RESUME_BASE,
        out_dir=str(tmp_path / "out"),
        journal_dir=str(tmp_path / "journal"),
        checkpoint_path=str(tmp_path / "soak.ckpt"),
    )
    killed = _run_resume_child(dict(cfg, kill_after_op=kill_after_op))
    # The driver really died mid-run, and an atomic checkpoint survived.
    assert killed.returncode == -signal.SIGKILL, (
        killed.returncode,
        killed.stderr[-2000:],
    )
    assert os.path.exists(cfg["checkpoint_path"])
    resumed = _run_resume_child(dict(cfg, resume=True))
    assert resumed.returncode == 0, resumed.stderr[-4000:]
    doc = _child_result(resumed)
    rs = doc["resume"]
    assert rs["resumed"] and rs["digest_verified"], rs
    # Resumed strictly from the checkpoint, not from scratch — and for
    # the mid-interval kill, from BEFORE the kill point (ops 41..57 are
    # re-derived by the deterministic prefix replay).
    assert rs["resume_op_index"] == 40
    for key in DET_KEYS:
        assert doc["determinism"][key] == resume_twin["determinism"][key], key
