#!/usr/bin/env python3
"""perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on.
The last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and the numbers compared with their limits under ``compared``, last).
The line before it is the run's timeline summary.  Without the chips the
cell asks for it prints no result and exits non-zero; ``--rehearsal``
(with JAX_PLATFORMS=cpu, at toy sizes) runs the same code on the CPU for
tests, says so, and is never a measurement.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, toy sizes, for tests: not a measurement")
    ap.add_argument("--rate", type=float, default=None,
                    help="study only: offer this many pods/s instead of the mix's rate")
    ap.add_argument("--out", default=None,
                    help="tests only: where the run's files go (default <checkout>/.perfbench_out)")
    args = ap.parse_args(argv)

    from perfbench import report, spec

    try:
        import kubernetes_tpu  # noqa: F401  the system under test
    except ImportError as exc:
        print(f"perfbench: the program is not in this checkout: {exc}", file=sys.stderr)
        return 3
    bench = spec.load(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, mix = spec.cell(bench, args.workload)
    asked = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if asked == "cpu" and not args.rehearsal:
        print("perfbench: JAX_PLATFORMS=cpu sends the program to the CPU; a cell "
              "runs on the chip (--rehearsal rehearses on the CPU)", file=sys.stderr)
        return 2
    if args.rehearsal:
        spec.shrink(config, mix)
    from perfbench import cell as cell_mod

    try:
        raw = cell_mod.run(bench["root"], cell, config, mix, args.seed, args.seconds,
                           bool(args.trace), args.rehearsal, T_START, rate=args.rate,
                           out_root=args.out,
                           log=lambda m: print(m, file=sys.stderr))
    except cell_mod.NoChip as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    from kubernetes_tpu.utils import backend_initialized

    if backend_initialized():
        print("perfbench: the client initialised a JAX backend", file=sys.stderr)
        return 4
    result, summary = report.build(bench, raw, bool(args.trace), args.rehearsal, args.rate)
    with open(os.path.join(raw["out"], "timeline.json"), "w", encoding="utf-8") as f:
        json.dump(summary, f)
        f.write("\n")
    for line in report.earlier_lines(summary):
        print(line)
    print(json.dumps({"timeline": report.brief(summary)}))
    compared = result["compared"]
    for name, v in compared.items():
        print(f"perfbench: compared {name} = {v['value']} (limit {v['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
