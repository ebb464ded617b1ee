#!/usr/bin/env python3
"""perfbench/control.py --workload <cell> --seed <n> [--pods N] [--stale S]

The control of the comparison that decides ``correct``: the cell's plain
reference put in the program's place, with one stated guarantee broken.
The configuration promises decisions that are at most one chunk
(``chunk_size`` pods) behind the cluster's state; the control answers from
a view refreshed only every ``--stale`` decisions (default 4,096: a whole
batch, the step that would tempt a later PR — one scoring sweep a batch,
or answers from a kept score table).  Its answers go through the same
``correct.compare`` as a run's, at the cell's own sizes, and have to come
out as not correct.  ``--stale`` equal to the chunk size shows what the
comparison reads of a sound stand-in.  No server and no device: it prints
the numbers beside their limits and a last line in a run's form.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def answers(*args, **how):
    """``stand_in`` without the companions: what a configuration that has
    none needs for ``correct.compare``."""
    return stand_in(*args, **how)[:6]


def stand_in(config: dict, mix: dict, seed: int, window_pods: int, stale: int,
             drop_affinity: bool = False, wander: float = 0.0, wander_to: str = "random"):
    """(node jsons, node names, {uid: json}, commit order, asked, measured,
    companions) of the reference standing in for the program over the set-up's pods and
    ``window_pods`` more."""
    from perfbench import cell, correct, objects

    ref = correct.load_reference(config["reference"])
    nodes = objects.Nodes(config, seed)
    plan = cell.pods_needed(config, mix, 0.0, 0)
    setup = plan["initial"] + plan["warm"]
    pods = objects.Pods(config, seed, setup + window_pods, plan["initial"])
    companions = objects.Companions(config, nodes, pods, plan["initial"])
    cluster, facts = correct.stand_up(ref, nodes.jsons, nodes.names, companions)
    stream = [(uid, facts(uid, raw)) for uid, raw in zip(pods.uids, pods.jsons)]
    # the set-up is placed soundly: the control breaks the window
    chunk = int(config["serve"]["chunk_size"])
    order = ref.place(cluster, stream[:setup], chunk, seed)
    order += ref.place(cluster, stream[setup:], stale, seed + 1, drop_affinity=drop_affinity,
                       wander=wander, wander_to=wander_to)
    asked = dict(order)
    measured = set(pods.uids[setup:])
    return nodes.jsons, nodes.names, dict(zip(pods.uids, pods.jsons)), order, asked, measured, companions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pods", type=int, default=40000, help="decisions of the window")
    ap.add_argument("--stale", type=int, default=4096)
    ap.add_argument("--wander", type=float, default=0.0,
                    help="study only: this share of the decisions goes to a feasible node whatever its score")
    ap.add_argument("--wander-to", choices=("random", "worst"), default="random")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    from perfbench import correct, spec

    bench = spec.load(args.bench)
    _, config, mix = spec.cell(bench, args.workload)
    node_jsons, names, by_uid, order, asked, measured, companions = stand_in(
        config, mix, args.seed, args.pods, args.stale, wander=args.wander, wander_to=args.wander_to)
    # the control's journal is its own answers: durability is not what it breaks
    res = correct.compare(config, node_jsons, names, by_uid, order, asked, measured, dict(asked), companions)
    for name, v in res["numbers"].items():
        print(f"control: compared {name} = {v['value']} (limit {v['limit']})", file=sys.stderr)
    print(json.dumps({"control": args.workload, "seed": args.seed, "stale": args.stale,
                      "correct": correct.verdict(res["numbers"]), "info": res["info"],
                      "compared": res["numbers"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
