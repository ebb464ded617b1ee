#!/usr/bin/env python
"""Quantify the observability tax (ISSUE 12 satellite; re-recorded for
ISSUE 16 and again for ISSUE 20): the headline bench workload run with
the observability surfaces ON (the default — per-tenant counters at
admission/bind/preempt/defer, plus PR 16's per-batch hetero flight
fields and pipeline stage counts) vs OFF, interleaved A/B so box
weather averages out.  Gate: the enabled run must cost <= 2%
throughput (reported; exit 1 beyond the gate).

The ON leg additionally pays the PR 16 EXPORT surfaces after the run —
a full Perfetto trace render (framework/trace_export.py) and a
measured-matrix derivation (framework/measured.py) over the whole
flight ring — and, since ISSUE 20, runs with the decision-provenance
ring ARMED (arm_provenance: a DecisionCapsule recorded per bind) and
pays one explain_pod readout after the run, attribution-pass compile
included.  The A/B compares the ON leg's ALL-IN rate (scheduled pods
over run seconds + export seconds + explain seconds) against the OFF
leg, so the recorded tax covers recorder, exporter AND the provenance
surface; ``explain_tax`` breaks out a WARM explain readout's share
(the recurring cost, pass already compiled) for the bench sentinel's
dedicated guard row.

Fleet tracing's cost does not ride the single-scheduler headline — its
surface (span fan-out + flight lc stamps on the router/owner path) is
exercised and bounded by the fleet soak instead, whose observability
on-vs-off leg proves bit-identical bindings (scripts/run_soak.py
--tenant).

    JAX_PLATFORMS=cpu python scripts/obs_tax.py --out OBS_TAX_r16.json
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


GATE = 0.02  # <= 2% throughput cost


def run_once(obs: bool) -> dict:
    import time

    from kubernetes_tpu.benchmarks import WORKLOADS, run_workload

    holder: dict = {}

    def attach(sched) -> None:
        holder["sched"] = sched
        if obs:
            # The ON leg records a DecisionCapsule per bind (ISSUE 20)
            # — the per-bind cost the unarmed leg must not pay.
            sched.arm_provenance()
        if not obs:
            # The off leg: no tenant machinery at all (the ctor flag's
            # effect, applied post-construction because the harness owns
            # scheduler construction).
            sched.tenant_metrics = None
            sched.queue.tenant_note = None

    r = run_workload(WORKLOADS["density_5kn_30kpods_default"], attach=attach)
    out = {
        "pods_per_sec": float(r["pods_per_sec"]),
        "seconds": float(r["seconds"]),
        "scheduled": int(r["scheduled"]),
    }
    if obs:
        # The ON leg pays the export surfaces too: one full Perfetto
        # render + one measured-matrix derivation over the ring.
        from kubernetes_tpu.framework import measured, trace_export

        snap = holder["sched"].flight.snapshot()
        t0 = time.perf_counter()
        text = trace_export.render(snap)
        t1 = time.perf_counter()
        measured.derive(snap)
        t2 = time.perf_counter()
        # One armed explain readout (ISSUE 20), compile and all: the
        # first explain builds the eval-only attribution pass, so this
        # charges the provenance surface's true worst-case cost.
        sched = holder["sched"]
        uid = next(
            (u for u, pr in sorted(sched.cache.pods.items()) if pr.bound),
            None,
        )
        rec = sched.explain_pod(uid) if uid is not None else {"error": "no binds"}
        t3 = time.perf_counter()
        # A second, WARM readout: the pass is compiled now, so this is
        # the recurring per-explain cost — what the explain_tax guard
        # holds under the gate (the compile above still rides the
        # all-in rate, so the headline tax charges it regardless).
        rec2 = sched.explain_pod(uid) if uid is not None else {"error": "no binds"}
        t4 = time.perf_counter()
        out["export"] = {
            "records": snap["count"],
            "trace_s": round(t1 - t0, 6),
            "trace_bytes": len(text),
            "derive_s": round(t2 - t1, 6),
            "explain_compile_s": round(t3 - t2, 6),
            "explain_warm_s": round(t4 - t3, 6),
            "explain_ok": "error" not in rec and "error" not in rec2,
        }
        export_s = t4 - t0
        out["pods_per_sec_all_in"] = round(
            out["scheduled"] / (out["seconds"] + export_s), 1
        ) if out["seconds"] + export_s > 0 else 0.0
        out["explain_share"] = round(
            (t4 - t3) / (out["seconds"] + export_s), 4
        ) if out["seconds"] + export_s > 0 else 0.0
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="OBS_TAX_r20.json")
    ap.add_argument("--runs", type=int, default=2,
                    help="A/B pairs (interleaved on/off)")
    args = ap.parse_args()
    from kubernetes_tpu.utils import require_device

    device = require_device()
    print(f"obs_tax: platform: {device['platform']} "
          f"({device['device_kind']} x{device['n_devices']})", flush=True)
    on_runs: list[float] = []
    off_runs: list[float] = []
    exports: list[dict] = []
    explain_shares: list[float] = []
    for i in range(args.runs):
        # Interleave: on, off, on, off — drift hits both legs alike.
        r_on = run_once(True)
        v_on = r_on["pods_per_sec_all_in"]
        exports.append(r_on["export"])
        explain_shares.append(r_on["explain_share"])
        print(f"obs_tax: run {i}: observability ON  {v_on} pods/s all-in "
              f"(raw {r_on['pods_per_sec']}, export "
              f"{r_on['export']['trace_s'] + r_on['export']['derive_s']:.4f}s)",
              flush=True)
        r_off = run_once(False)
        v_off = r_off["pods_per_sec"]
        print(f"obs_tax: run {i}: observability OFF {v_off} pods/s",
              flush=True)
        on_runs.append(v_on)
        off_runs.append(v_off)
    best_on, best_off = max(on_runs), max(off_runs)
    # Best-of compares the runs' ceilings — the tax is a systematic
    # cost, noise is not.
    tax = (best_off - best_on) / best_off if best_off else 0.0
    doc = {
        "metric": "observability_tax_headline",
        "workload": "density_5kn_30kpods_default",
        "runs": args.runs,
        "pods_per_sec_on": on_runs,
        "pods_per_sec_off": off_runs,
        "export": exports,
        "best_on": best_on,
        "best_off": best_off,
        "tax": round(tax, 4),
        "gate": GATE,
        "within_gate": tax <= GATE,
        "explain_armed": True,
        # The WARM explain readout's worst per-run share of the ON
        # leg's all-in wall time — the recurring per-explain cost the
        # bench sentinel's explain_tax guard holds under the same 2%
        # gate (the one-time attribution-pass compile is charged to
        # the all-in rate above, i.e. to the headline tax).
        "explain_tax": round(max(explain_shares), 4) if explain_shares else 0.0,
        "environment": {
            "backend": device["platform"],
            "device_kind": device["device_kind"],
            "python": platform.python_version(),
            "machine": platform.machine(),
        },
    }
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(
        f"obs_tax: wrote {args.out} — ON {best_on} vs OFF {best_off} "
        f"pods/s, tax {tax * 100:.2f}% (gate {GATE * 100:.0f}%, "
        f"within={doc['within_gate']})",
        flush=True,
    )
    return 0 if doc["within_gate"] else 1


if __name__ == "__main__":
    sys.exit(main())
