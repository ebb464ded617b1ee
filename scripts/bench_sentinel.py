#!/usr/bin/env python
"""Declarative bench/SLO regression sentinel (ISSUE 16 tentpole c).

ONE declarative guard table evaluated over a bench.py payload and the
committed SOAK_* artifacts:

  journal_fsyncs     group commit must stay group commit (a per-append
                     fsync regression is ~3 orders of magnitude)
  overlap_coverage   the pipeline's overlap must stay engaged
  slo_p99            decision latency vs the recorded budget
  fair_steady_p99    fairness isolation: the steady tenant's p99 under a
                     capped burst vs its recorded solo-baseline tolerance
  fair_starvation    starvation-SLO violations in the fairness soak (= 0)
  lint_findings      tpulint unsuppressed findings on the tree (= 0)
  lint_suppressions  tpulint suppression budget (pragmas are documented
                     exceptions, not a pressure valve)

There is no throughput-ratio row: the table guards structure and
recorded gates, and speed is judged from chip runs of the benchmark
(ROADMAP S0), not against a committed CPU-box number.  For the same
reason there is no observability-tax row: what the instrumentation costs
is read on the chip, the same seed traced and untraced (PERF.md).

Each guard has a WARN boundary (reported) and a HARD floor (exit 1: a
real regression).  ``bench.py`` embeds the same evaluation as a
``sentinel`` block in every payload it prints, and the tier-1 gate runs
``--check`` against the committed artifacts — a regressing PR fails
BEFORE it records an artifact.

Stdlib-only (loaded by file path from bench.py and the tier-1 test):

    python scripts/bench_sentinel.py --check
    python scripts/bench_sentinel.py --payload fresh_payload.json
    python bench.py | python scripts/bench_sentinel.py --payload -
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

# ---------------------------------------------------------------------------
# The guard table.  ``value`` paths index into the bench payload under
# test; ``source`` guards read their value from a committed artifact
# family instead (newest round wins).  Ops:
#   ratio_paths_max — value / denom (``denom_path``, SAME source doc)
#               must stay <= warn / hard — for artifacts that record
#               their own baseline next to the measurement
#   max       — value must stay <= warn / hard
#   min       — value must stay >= warn / hard
# ``budget_key`` (slo_p99) scales warn/hard off the payload's recorded
# budget instead of a constant.
GUARDS = (
    {
        "name": "journal_fsyncs",
        "value": ("detail", "journal", "fsyncs"),
        "op": "max",
        "warn": 16,
        "hard": 64,
        "why": "group commit: one fsync barrier per staged group — a "
        "per-append regression is O(appends) barriers",
    },
    {
        "name": "overlap_coverage",
        "value": ("phase_attribution", "overlap", "coverage"),
        "op": "min",
        "warn": 0.10,
        "hard": 0.02,
        "why": "the pipeline's stage overlap must stay engaged "
        "(PR 15's whole point)",
    },
    {
        "name": "slo_p99",
        "value": ("slo", "p99_ms"),
        "op": "max",
        "budget_key": ("slo", "budget_ms"),
        "warn": 1.0,   # x budget
        "hard": 4.0,   # x budget
        "why": "decision latency p99 vs the recorded SLO budget",
    },
    {
        "name": "fair_steady_p99",
        "source": {
            "family": "SOAK_TENANT_r*.json",
            "path": ("fairness", "steady_p99_ms"),
            "denom_path": ("fairness", "steady_tolerance_ms"),
        },
        "op": "ratio_paths_max",
        "warn": 0.85,
        "hard": 1.0,
        "why": "fairness isolation: the steady tenant's p99 under a "
        "capped x8 burst vs its recorded solo-baseline tolerance "
        "(>= 1.0 means the burst moved a bystander's tail)",
    },
    {
        "name": "fair_starvation",
        "source": {
            "family": "SOAK_TENANT_r*.json",
            "path": ("fairness", "starvation_violations"),
        },
        "op": "max",
        "warn": 0,
        "hard": 0,
        "why": "starvation-SLO violations in the committed fairness "
        "soak: rate caps may throttle but aging escape must keep "
        "every tenant's wait under its SLO budget",
    },
    {
        "name": "prod_service_p99",
        "source": {
            "family": "SOAK_PROD_r*.json",
            "path": ("service_slo", "worst_p99_ms"),
            "denom_path": ("slo", "budget_ms"),
        },
        "op": "ratio_paths_max",
        "warn": 1.0,
        "hard": 1.5,
        "why": "production day: the worst per-tenant SERVICE p99 (the "
        "component split strips each throttled tenant's cap-attributed "
        "queue wait) vs the recorded SLO budget — the composed chaos "
        "must not erode the scheduler's own service time "
        "(r18 recorded 253ms/250ms = 1.01, a standing warn)",
    },
    {
        "name": "prod_recovery_p99",
        "source": {
            "family": "SOAK_PROD_r*.json",
            "path": ("incident_windows", "worst_recovery_p99_ms"),
            "denom_path": ("incident_windows", "steady", "p99_ms"),
        },
        "op": "ratio_paths_max",
        "warn": 3.0,
        "hard": 10.0,
        "why": "production day: the worst post-incident recovery "
        "window's p99 vs steady state — every incident's tail must "
        "SETTLE, not smear into the next window",
    },
    {
        "name": "lint_findings",
        "live": "lint",
        "path": ("findings",),
        "op": "max",
        "warn": 0,
        "hard": 0,
        "why": "tpulint unsuppressed findings: the static invariants "
        "(WAL ordering, determinism, metrics/wire hygiene, JAX device "
        "discipline) hold on the tree under test — the only live-"
        "measured guard, since lint state is not a committed artifact",
    },
    {
        "name": "lint_suppressions",
        "live": "lint",
        "path": ("suppressions",),
        "op": "max",
        "warn": 3,
        "hard": 8,
        "why": "tpulint suppression budget: pragmas are documented "
        "exceptions (the committed tree carries three), not a pressure "
        "valve — growth past the hard cap means an invariant is being "
        "argued with instead of upheld",
    },
    {
        "name": "prod_promotion_max",
        "source": {
            "family": "SOAK_PROD_r*.json",
            "path": ("standby", "promotion_latency", "max_ms"),
        },
        "op": "max",
        "warn": 5000,
        "hard": 7500,
        "why": "production day: worst warm-standby promotion latency "
        "(ms) — a promotion drifting toward the ~15s cold boot means "
        "the pool stopped being warm",
    },
)


_LINT_CACHE: dict = {}


def _lint_stats(root: str) -> dict | None:
    """Live tpulint roll-up (finding/suppression counts) for the
    ``live: lint`` guards — the one source kind that measures the tree
    under test itself rather than a committed artifact.  Loads the
    runner by file path (stdlib-only stays stdlib-only), memoized per
    root since two guards share one lint run."""
    if root in _LINT_CACHE:
        return _LINT_CACHE[root]
    stats = None
    try:
        import importlib.util

        runner = os.path.join(root, "scripts", "check_lint.py")
        spec = importlib.util.spec_from_file_location("_sentinel_check_lint", runner)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        tpulint = mod.load_tpulint()
        baseline = tpulint.load_baseline(os.path.join(root, mod.BASELINE_NAME))
        result = tpulint.run_lint(
            root, baseline=baseline, cache=mod.make_cache(root)
        )
        stats = {
            "findings": len(result.findings),
            "suppressions": result.suppressed,
        }
    except Exception:
        stats = None  # surfaces as a ``missing`` guard, not a crash
    _LINT_CACHE[root] = stats
    return stats


def newest_artifact(root: str, family: str) -> str | None:
    """The newest committed round of one artifact family
    (``BENCH_r*.json`` → the highest ``r<N>``)."""
    rx = re.compile(re.escape(family).replace(r"\*", r"(\d+)") + r"$")
    best, best_n = None, -1
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return None
    for name in names:
        m = rx.match(name)
        if m and int(m.group(1)) > best_n:
            best, best_n = name, int(m.group(1))
    return os.path.join(root, best) if best else None


def load_payload(path: str) -> dict:
    """One bench payload — raw, or the recorded-trajectory wrapper
    (``{"parsed": payload}``, the driver's capture format)."""
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    return doc.get("parsed") or doc


def _dig(doc, path):
    cur = doc
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return cur


def _eval_guard(guard: dict, payload: dict | None, root: str) -> dict:
    out = {
        "name": guard["name"],
        "op": guard["op"],
        "why": guard["why"],
        "status": "pass",
    }
    # The value under test: from the payload, or from a committed
    # artifact family (the fairness and production soaks — the payload
    # never carries them).
    denom = None
    if "live" in guard:
        stats = _lint_stats(root)
        value = _dig(stats or {}, guard["path"])
        if value is None:
            out["status"] = "missing"
            out["missing"] = f"live:{guard['live']}"
            return out
    elif "source" in guard:
        src = newest_artifact(root, guard["source"]["family"])
        if src is None:
            out["status"] = "missing"
            out["missing"] = guard["source"]["family"]
            return out
        out["source_file"] = os.path.basename(src)
        try:
            src_doc = load_payload(src)
        except (OSError, ValueError):
            src_doc = None
        value = _dig(src_doc or {}, guard["source"]["path"])
        if "denom_path" in guard["source"]:
            denom = _dig(src_doc or {}, guard["source"]["denom_path"])
    else:
        value = _dig(payload or {}, guard["value"])
    if value is None:
        out["status"] = "missing"
        out["missing"] = "/".join(guard.get("value", guard.get("source", {}).get("path", ())))
        return out
    out["value"] = value
    warn, hard = guard["warn"], guard["hard"]
    if "budget_key" in guard:
        budget = _dig(payload or {}, guard["budget_key"])
        if budget is None:
            out["status"] = "missing"
            out["missing"] = "/".join(guard["budget_key"])
            return out
        warn, hard = warn * budget, hard * budget
    if guard["op"] == "ratio_paths_max":
        if not denom:
            out["status"] = "missing"
            out["missing"] = "/".join(guard["source"]["denom_path"])
            return out
        out["reference"] = denom
        ratio = float(value) / float(denom)
        out["ratio"] = round(ratio, 4)
        out["warn_above"], out["hard_above"] = warn, hard
        if ratio > hard:
            out["status"] = "hard_fail"
        elif ratio > warn:
            out["status"] = "warn"
        return out
    out["warn_limit"], out["hard_limit"] = warn, hard
    v = float(value)
    if guard["op"] == "max":
        if v > hard:
            out["status"] = "hard_fail"
        elif v > warn:
            out["status"] = "warn"
    elif guard["op"] == "min":
        if v < hard:
            out["status"] = "hard_fail"
        elif v < warn:
            out["status"] = "warn"
    else:
        raise ValueError(f"unknown guard op {guard['op']!r}")
    return out


def evaluate(payload: dict | None, root: str = REPO) -> dict:
    """Evaluate the guard table against one bench payload.  ``None`` =
    only the guards that read no payload (committed artifacts and the
    live tree).  The returned block is what bench.py embeds as
    ``payload["sentinel"]``."""
    guards = [
        _eval_guard(g, payload, root) for g in GUARDS
        if payload is not None or "value" not in g
    ]
    hard = [g["name"] for g in guards if g["status"] == "hard_fail"]
    warns = [g["name"] for g in guards if g["status"] == "warn"]
    missing = [g["name"] for g in guards if g["status"] == "missing"]
    return {
        "guards": guards,
        "hard_failures": hard,
        "warnings": warns,
        "missing": missing,
        "ok": not hard,
    }


def check_committed(root: str = REPO) -> dict:
    """``--check``: the tier-1 gate.  No payload: the artifact-sourced
    guards (obs tax, the fairness and production soaks) re-verify that
    the committed artifacts still clear the table and the live guards
    measure the tree; any unreadable or schema-drifted artifact surfaces
    as ``missing``."""
    return evaluate(None, root)


def _print_table(block: dict) -> None:
    for g in block["guards"]:
        mark = {"pass": "ok  ", "warn": "WARN", "hard_fail": "FAIL",
                "missing": "miss"}[g["status"]]
        if "ratio" in g:
            lim = f"warn>{g['warn_above']} hard>{g['hard_above']}"
            detail = (
                f"ratio {g['ratio']} vs {g.get('reference')} "
                f"({g.get('source_file', '?')}; {lim})"
            )
        elif "value" in g:
            lim = (
                f"warn>{g['warn_limit']} hard>{g['hard_limit']}"
                if g["op"] == "max"
                else f"warn<{g['warn_limit']} hard<{g['hard_limit']}"
            )
            src = f" ({g['source_file']})" if "source_file" in g else ""
            detail = f"value {g['value']}{src} ({lim})"
        else:
            detail = f"missing {g.get('missing', '?')}"
        print(f"sentinel: {mark} {g['name']:<18} {detail}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument(
        "--check", action="store_true",
        help="evaluate the committed artifacts (the tier-1 gate)",
    )
    mode.add_argument(
        "--payload", metavar="FILE",
        help="evaluate one bench payload JSON ('-' = stdin) against the "
        "committed references",
    )
    ap.add_argument(
        "--root", default=REPO,
        help="repo root holding the committed artifacts",
    )
    ap.add_argument(
        "--json", action="store_true", help="print the sentinel block as JSON"
    )
    args = ap.parse_args(argv)
    if args.check:
        block = check_committed(args.root)
    else:
        if args.payload == "-":
            doc = json.load(sys.stdin)
            payload = doc.get("parsed") or doc
        else:
            payload = load_payload(args.payload)
        block = evaluate(payload, args.root)
    if args.json:
        print(json.dumps(block, indent=1, sort_keys=True))
    else:
        _print_table(block)
    if block["hard_failures"]:
        print(
            f"sentinel: HARD FAIL — {', '.join(block['hard_failures'])} "
            "breached the floor",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
