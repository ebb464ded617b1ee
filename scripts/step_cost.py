"""What one step of a served pass program costs, by the TPU compiler's own
estimate, without a chip.

Builds a `TPUScheduler` as `serve` does for a benchmark configuration
(`perfbench/configs/<name>.json`: its nodes, its `serve.batch_size` and
`serve.chunk_size`, the default profile), featurizes one full batch of the
configuration's measured pods, picks the program the dispatch would (the
conflict-aware packer's width where the batch's plugins need it), and
compiles it ahead of time for TPU v5e
(`jax.experimental.topologies`, `v5e:2x2`, one chip).  Then, in the
compiled HLO, it finds the step loop (the `while` with the largest body)
and sums the compiler's `estimated_cycles` of the body's ops by named
scope (`pass/eval/<plugin>`, `pass/conflict`, `pass/commit`, ...) and by
primitive (the last element of an op's `op_name`, with the `jit(...)`
around it where there is one, e.g. `floor_divide/rem`).  This is XLA's
cost model, not a measurement: a chip run's trace is what times a step.

    JAX_PLATFORMS=cpu python scripts/step_cost.py podaffinity_5kn
    JAX_PLATFORMS=cpu python scripts/step_cost.py basic_5kn --top 20

Prints one JSON object.  Needs the TPU compiler (libtpu) installed, not a
chip; the process holds libtpu's lock while it runs.
"""

import argparse
import json
import os
import re
import sys
import time
from collections import defaultdict

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from kubernetes_tpu.api import serialize  # noqa: E402
from kubernetes_tpu.engine.features import build_pod_batch  # noqa: E402
from kubernetes_tpu.engine.packing import pack_batch  # noqa: E402
from kubernetes_tpu.scheduler import TPUScheduler  # noqa: E402
from perfbench import objects  # noqa: E402

PACKED = {"PodTopologySpread", "InterPodAffinity", "NodePorts"}  # as _dispatch_pass
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = .*?\s([a-z][a-z0-9-]*)\(")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|to_apply|calls)=%([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SCOPE = re.compile(r"pass/(?:eval(?:/[A-Z][A-Za-z0-9]+)?|conflict|commit|tail)")


def parse_hlo(text: str) -> dict:
    """Computation name → its instructions, each (name, opcode, line)."""
    comps: dict = {}
    cur = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            m = _INSTR.match(line)
            if m:
                cur.append((m.group(1), m.group(2), line))
    return comps


def _callees(opcode: str, line: str) -> list:
    """Computations an op runs as control flow (not a fusion's body, whose
    cost is the fusion's own estimate)."""
    if opcode == "fusion":
        return []
    out = _CALLED.findall(line)
    m = _BRANCHES.search(line)
    if m:
        out += [b.strip().lstrip("%") for b in m.group(1).split(",")]
    return out


def costed_ops(comps: dict, root: str, seen=None) -> list:
    """Every op under computation ``root`` (through control flow, every
    branch of a conditional summed, each computation once) that carries an
    estimate: (op_name, cycles)."""
    seen = set() if seen is None else seen
    if root in seen or root not in comps:
        return []
    seen.add(root)
    out = []
    for _name, opcode, line in comps[root]:
        m = _CYCLES.search(line)
        if m:
            n = _OP_NAME.search(line)
            out.append((n.group(1) if n else "", int(m.group(1))))
        for callee in _callees(opcode, line):
            out += costed_ops(comps, callee, seen)
    return out


def scope_of(op_name: str) -> str:
    found = _SCOPE.findall(op_name)
    return found[-1] if found else "(no scope)"


def primitive_of(op_name: str) -> str:
    parts = op_name.split("/")
    if not parts or not parts[-1]:
        return "(no op_name)"
    if len(parts) > 1 and parts[-2].startswith("jit("):
        return parts[-2][4:-1] + "/" + parts[-1]
    return parts[-1]


def step_loops(comps: dict) -> list:
    """Every `while` of the program with its body's costed ops, the
    largest body first."""
    loops = []
    for comp, instrs in comps.items():
        for name, opcode, line in instrs:
            if opcode != "while":
                continue
            body = re.search(r"body=%([\w.-]+)", line).group(1)
            n = _OP_NAME.search(line)
            loops.append({
                "while": name, "in": comp, "op_name": n.group(1) if n else "",
                "ops": costed_ops(comps, body),
            })
    loops.sort(key=lambda w: -sum(c for _, c in w["ops"]))
    return loops


def breakdown(ops: list, key, top: int) -> list:
    total = sum(c for _, c in ops) or 1
    acc: dict = defaultdict(lambda: [0, 0])
    for name, cycles in ops:
        acc[key(name)][0] += 1
        acc[key(name)][1] += cycles
    rows = sorted(acc.items(), key=lambda kv: -kv[1][1])[:top]
    return [
        {"name": k, "ops": n, "cycles": c, "share": round(c / total, 4)}
        for k, (n, c) in rows
    ]


def build(config_name: str, seed: int):
    """The program and its arguments as `serve` would dispatch them for one
    full batch of the configuration's pods."""
    with open(os.path.join(ROOT, "perfbench", "configs", config_name + ".json")) as f:
        config = json.load(f)
    serve = config["serve"]
    k = int(serve["batch_size"])
    s = TPUScheduler(batch_size=k, chunk_size=int(serve["chunk_size"]))
    for raw in objects.Nodes(config, seed).jsons:
        s.add_node(serialize.node_from_json(raw))
    pods = [serialize.pod_from_json(raw) for raw in objects.Pods(config, seed, k).jsons]
    batch, _deltas, active = build_pod_batch(pods, s.builder, s.profile, k)
    chunk = int(serve["chunk_size"])
    if chunk > 1 and active & PACKED:
        chunk = pack_batch(batch, len(pods), chunk).width
    batch["step_offset"] = np.arange(k, dtype=np.int32)
    batch["nominated_row"] = np.full(k, -1, np.int32)
    batch["uniform_all"] = np.bool_(False)
    program = s.passes.get(
        s.profile, s.builder.schema, s.builder.res_col, active, chunk, carry_dom=True
    )
    dom = s._dom_placeholder()
    args = (
        s.builder.state(), batch, s._full_inv(), np.uint32(0), dom[0], dom[1], np.bool_(False)
    )
    return program, args, active, chunk, k, s.builder.schema.N


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a configuration under perfbench/configs/, e.g. podaffinity_5kn")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--top", type=int, default=15, help="rows of each breakdown")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    t0 = time.perf_counter()
    program, call_args, active, chunk, k, n = build(args.config, args.seed)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=one_chip),
        call_args,
    )
    t1 = time.perf_counter()
    text = program.lower(*shapes).compile().as_text()
    t2 = time.perf_counter()
    loops = step_loops(parse_hlo(text))
    step = loops[0]["ops"] if loops else []
    out = {
        "config": args.config, "device": "TPU v5e (described, not attached)",
        "batch_size": k, "nodes_padded": n, "chunk": chunk, "active": sorted(active),
        "build_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "loops": [
            {"while": w["while"], "op_name": w["op_name"], "ops": len(w["ops"]),
             "cycles": sum(c for _, c in w["ops"])}
            for w in loops
        ],
        "step_ops": len(step),
        "step_cycles": sum(c for _, c in step),
        "by_scope": breakdown(step, scope_of, args.top),
        "by_primitive": breakdown(step, primitive_of, args.top),
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
