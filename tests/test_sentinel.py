"""The declarative bench/SLO regression sentinel (ISSUE 16 tentpole c):
one guard table over a bench.py payload + the committed SOAK
artifacts — pass / warn / hard-floor semantics, missing-artifact
handling, the bench.py ``sentinel`` payload block, and the tier-1
``--check`` gate."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_sentinel.py")


def load_sentinel():
    spec = importlib.util.spec_from_file_location("_tpu_sentinel", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sentinel = load_sentinel()


def synthetic_payload() -> dict:
    """A synthetic payload of bench.py's schema (the fields the guard
    table reads), built fresh per call — no repo-root record needed."""
    return {
        "metric": "scheduling_throughput_5k_nodes_30k_pods_default_plugins",
        "value": 1000.0,
        "unit": "pods/s",
        "platform": "cpu",
        "engine_faults": 0,
        "quarantined": 0,
        "flagship": {"name": "interpodaffinity_1kn_10kpods", "value": 500.0},
        "slo": {"p50_ms": 2.0, "p99_ms": 40.0, "p999_ms": 60.0,
                "budget_ms": 250.0, "violations": 0, "decisions": 400,
                "miss_rate": 0.01},
        "phase_attribution": {
            "coverage": 1.2,
            "overlap": {"saved_s": 4.0, "coverage": 0.17},
        },
        "detail": {"journal": {"appends": 32048, "fsyncs": 9,
                               "group_commits": 9}},
    }


# -- guard semantics ---------------------------------------------------------


def test_committed_trajectory_passes_every_guard():
    block = sentinel.evaluate(synthetic_payload())
    assert block["ok"], block
    assert block["hard_failures"] == []
    assert block["missing"] == []
    assert {g["name"] for g in block["guards"]} == {
        "journal_fsyncs", "overlap_coverage",
        "slo_p99", "fair_steady_p99",
        "fair_starvation",
        "prod_service_p99", "prod_recovery_p99", "prod_promotion_max",
        "lint_findings", "lint_suppressions",
    }


def test_warn_band_reports_without_failing():
    """Twice the group-commit fsync budget: beyond the warn band (16),
    inside the hard floor (64) — reported as warn, never an exit
    failure."""
    payload = synthetic_payload()
    payload["detail"]["journal"]["fsyncs"] = 32
    block = sentinel.evaluate(payload)
    assert "journal_fsyncs" in block["warnings"]
    assert block["ok"] and block["hard_failures"] == []


def test_hard_floor_breach_fails():
    """A disengaged pipeline + a per-append fsync regression: two hard
    floors breached, ok=False."""
    payload = synthetic_payload()
    payload["phase_attribution"]["overlap"]["coverage"] = 0.0
    payload["detail"]["journal"]["fsyncs"] = 32048
    block = sentinel.evaluate(payload)
    assert set(block["hard_failures"]) >= {
        "overlap_coverage", "journal_fsyncs"
    }
    assert not block["ok"]
    statuses = {g["name"]: g["status"] for g in block["guards"]}
    assert statuses["overlap_coverage"] == "hard_fail"
    assert statuses["journal_fsyncs"] == "hard_fail"


def test_slo_guard_scales_off_the_recorded_budget():
    payload = synthetic_payload()
    budget = payload["slo"]["budget_ms"]
    payload["slo"]["p99_ms"] = budget * 4 + 1  # past the 4x hard ceiling
    block = sentinel.evaluate(payload)
    assert "slo_p99" in block["hard_failures"]


def test_missing_artifacts_report_as_missing_not_failure(tmp_path):
    """Against an empty root every reference/source guard degrades to
    'missing' — visible, but never a hard failure (a fresh checkout
    without artifacts must not hard-fail the gate)."""
    block = sentinel.evaluate(synthetic_payload(), root=str(tmp_path))
    assert block["ok"]
    assert set(block["missing"]) >= {
        "prod_service_p99", "fair_steady_p99", "fair_starvation",
    }


def test_missing_payload_fields_report_as_missing():
    block = sentinel.evaluate({})
    statuses = {g["name"]: g["status"] for g in block["guards"]}
    assert statuses["overlap_coverage"] == "missing"
    assert statuses["journal_fsyncs"] == "missing"
    assert statuses["slo_p99"] == "missing"
    # artifact-sourced, payload-free
    assert statuses["fair_starvation"] == "pass"
    assert block["ok"]  # missing is loud, not fatal


def test_lint_guards_ride_the_live_tree():
    """The lint guard rows are live-sourced (they run tpulint, not a
    payload field): zero unsuppressed findings, and the suppression
    count stays inside its warn band so pragma creep surfaces here."""
    block = sentinel.evaluate(synthetic_payload())
    guards = {g["name"]: g for g in block["guards"]}
    assert guards["lint_findings"]["status"] == "pass"
    assert guards["lint_findings"]["value"] == 0
    assert guards["lint_suppressions"]["status"] == "pass"
    assert guards["lint_suppressions"]["value"] >= 1


def test_lint_guards_degrade_to_missing_off_tree(tmp_path):
    """Against a root with no lintable tree the live source reports
    missing — loud, never a hard failure (same contract as artifacts)."""
    block = sentinel.evaluate(synthetic_payload(), root=str(tmp_path))
    statuses = {g["name"]: g["status"] for g in block["guards"]}
    assert statuses["lint_findings"] == "missing"
    assert statuses["lint_suppressions"] == "missing"


def test_newest_artifact_picks_the_highest_round(tmp_path):
    for n in (2, 10, 9):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text("{}")
    got = sentinel.newest_artifact(str(tmp_path), "BENCH_r*.json")
    assert os.path.basename(got) == "BENCH_r10.json"


# -- the CLI gate ------------------------------------------------------------


def run_cli(*args, stdin: str | None = None):
    return subprocess.run(
        [sys.executable, SCRIPT, *args],
        capture_output=True,
        text=True,
        timeout=60,
        input=stdin,
    )


def test_check_gate_passes_on_the_committed_trajectory():
    """The tier-1 gate: `bench_sentinel.py --check` exits 0 on the
    repo's own committed artifacts — with no payload, only the guards
    that read none (artifacts + the live tree) are evaluated."""
    proc = run_cli("--check")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "fair_starvation" in proc.stdout and "lint_findings" in proc.stdout
    assert "journal_fsyncs" not in proc.stdout


def test_check_gate_fails_on_a_synthetic_regression(tmp_path):
    payload = synthetic_payload()
    payload["detail"]["journal"]["fsyncs"] = 32048
    fixture = tmp_path / "regressed.json"
    fixture.write_text(json.dumps(payload))
    proc = run_cli("--payload", str(fixture))
    assert proc.returncode == 1
    assert "HARD FAIL" in proc.stderr


def test_payload_stdin_and_json_mode():
    proc = run_cli("--payload", "-", "--json",
                   stdin=json.dumps(synthetic_payload()))
    assert proc.returncode == 0, proc.stderr
    block = json.loads(proc.stdout)
    assert block["ok"] and block["hard_failures"] == []
