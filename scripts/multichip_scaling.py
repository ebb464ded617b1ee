"""Multichip scaling evidence: the sharded device pass across mesh sizes.

Runs the SAME batch pass over a virtual device mesh at 1/2/4/8 shards
(node axis sharded, XLA inserts the ICI collectives) on a large node axis
and reports relative step times — the scaling-curve evidence VERDICT r1
asked for, runnable without multi-chip hardware via
--xla_force_host_platform_device_count.  Absolute CPU times are not TPU
times; the curve shape (how work divides across shards and what the
collectives cost) is the signal.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python scripts/multichip_scaling.py [nodes] [pods]
Prints one JSON line with a per-mesh-size table.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# CPU-only, on purpose: the mesh is 8 VIRTUAL host devices — a
# partitioning-correctness and curve-shape tool, never a place to read time.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from kubernetes_tpu.api.wrappers import make_node, make_pod  # noqa: E402
from kubernetes_tpu.engine.features import build_pod_batch  # noqa: E402
from kubernetes_tpu.engine.pass_ import build_pass  # noqa: E402
from kubernetes_tpu.parallel.mesh import (  # noqa: E402
    _spec_for,
    make_mesh,
    shard_cluster_state,
    shard_pod_batch,
)
from kubernetes_tpu.snapshot import _NODE_AXIS  # noqa: E402
from kubernetes_tpu.scheduler import TPUScheduler  # noqa: E402


def main(n_nodes: int = 16384, n_pods: int = 256) -> dict:
    s = TPUScheduler(batch_size=n_pods, chunk_size=64)
    for i in range(n_nodes):
        s.add_node(
            make_node(f"n{i:05d}")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
            .zone(f"zone-{i % 8}")
            .obj()
        )
    pods = [
        make_pod(f"p{i}").req({"cpu": "500m", "memory": "1Gi"})
        .label("app", f"a{i % 8}").obj()
        for i in range(n_pods)
    ]
    infos = [p for p in pods]
    batch, _, active = build_pod_batch(infos, s.builder, s.profile, n_pods)
    batch["nominated_row"] = np.full(n_pods, -1, np.int32)
    inv = s._full_inv()
    state = s.builder.state()
    fn = build_pass(s.profile, s.builder.schema, s.builder.res_col, active, 64)

    table = []
    for shards in (1, 2, 4, 8):
        mesh = make_mesh(shards)
        st = shard_cluster_state(state, mesh)
        bt = shard_pod_batch(batch, mesh)
        # Compile + warm.
        out_state, out = fn(st, bt, inv, np.uint32(0))
        jax.block_until_ready(out.picks)
        t0 = time.perf_counter()
        reps = 3
        for r in range(reps):
            out_state, out = fn(st, bt, inv, np.uint32(r))
            jax.block_until_ready(out.picks)
        dt = (time.perf_counter() - t0) / reps
        table.append({"shards": shards, "pass_s": round(dt, 4)})
    base = table[0]["pass_s"]
    for row in table:
        row["speedup_vs_1"] = round(base / row["pass_s"], 2)
    result = {
        "nodes": n_nodes,
        "pods_per_batch": n_pods,
        "chunk": 64,
        "backend": jax.devices()[0].platform,
        "table": table,
    }
    print(json.dumps(result))
    return result


def beyond_hbm(n_nodes_big: int = 4_194_304, n_pods: int = 192) -> dict:
    """Beyond-HBM evidence (VERDICT r2 next-8): the capacity claim behind
    node-axis sharding, measured — per-device memory of the COMPILED full
    batch pass at a node count whose working set exceeds one chip's HBM.

    XLA's compiled memory analysis is exact per-device accounting
    (arguments + temps + outputs of the SPMD program each device runs),
    so the number is real without materializing terabytes on this host:
    the 1-shard program cannot fit a 16 GiB v5e; the same pass sharded
    8-ways fits with room.  Shapes-only lowering (ShapeDtypeStruct) —
    no tensor of this size is ever allocated."""
    import dataclasses as dc

    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubernetes_tpu.snapshot import ClusterState

    HBM = 16 * 1024**3  # v5e HBM bytes

    # Small REAL cluster: its featurized batch/state provide the exact
    # dtypes + vocab dims; only the node axis is scaled up abstractly.
    s = TPUScheduler(batch_size=n_pods, chunk_size=64)
    for i in range(300):
        s.add_node(
            make_node(f"n{i:05d}")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
            .zone(f"zone-{i % 8}")
            .obj()
        )
    pods = [
        make_pod(f"p{i}").req({"cpu": "500m", "memory": "1Gi"})
        .label("app", f"a{i % 8}").obj()
        for i in range(n_pods)
    ]
    batch, _, active = build_pod_batch(pods, s.builder, s.profile, n_pods)
    batch["nominated_row"] = np.full(n_pods, -1, np.int32)
    inv = s._full_inv()
    state = s.builder.state()
    n_small = s.builder.schema.N
    assert n_nodes_big % 8 == 0
    schema_big = dc.replace(s.builder.schema, N=n_nodes_big)
    fn = build_pass(s.profile, schema_big, s.builder.res_col, active, 64)

    def lower_for(shards: int):
        mesh = make_mesh(shards) if shards > 1 else None

        def state_abs():
            fields = {}
            for f in dc.fields(ClusterState):
                arr = getattr(state, f.name)
                ax = _NODE_AXIS[f.name]
                shape = list(arr.shape)
                assert shape[ax] == n_small, (f.name, arr.shape)
                shape[ax] = n_nodes_big
                sh = NamedSharding(mesh, _spec_for(f.name)) if mesh else None
                fields[f.name] = jax.ShapeDtypeStruct(
                    tuple(shape), arr.dtype, sharding=sh
                )
            return ClusterState(**fields)

        def other_abs(d):
            out = {}
            for k, v in d.items():
                v = np.asarray(v)
                shape = tuple(
                    n_nodes_big if dim == n_small else dim for dim in v.shape
                )
                spec = P(
                    *["nodes" if dim == n_nodes_big else None for dim in shape]
                )
                sh = NamedSharding(mesh, spec) if mesh else None
                out[k] = jax.ShapeDtypeStruct(shape, v.dtype, sharding=sh)
            return out

        lo = fn.lower(
            state_abs(), other_abs(batch), other_abs(inv), np.uint32(0)
        )
        ma = lo.compile().memory_analysis()
        per_dev = (
            ma.argument_size_in_bytes
            + ma.temp_size_in_bytes
            + ma.output_size_in_bytes
        )
        return {
            "shards": shards,
            "argument_gib": round(ma.argument_size_in_bytes / 1024**3, 2),
            "temp_gib": round(ma.temp_size_in_bytes / 1024**3, 2),
            "output_gib": round(ma.output_size_in_bytes / 1024**3, 2),
            "per_device_gib": round(per_dev / 1024**3, 2),
            "fits_v5e_hbm": per_dev < HBM,
        }

    table = [lower_for(1), lower_for(8)]
    result = {
        "mode": "beyond-hbm",
        "nodes": n_nodes_big,
        "pods_per_batch": n_pods,
        "chunk": 64,
        "hbm_gib": 16,
        "table": table,
    }
    print(json.dumps(result))
    assert not table[0]["fits_v5e_hbm"], "pick a larger node count"
    assert table[1]["fits_v5e_hbm"], "8-shard should fit"
    return result


def north_star(
    n_devices: int = 8,
    n_nodes: int = 5000,
    scale: int = 115,
    batch_size: int = 256,
    chunk_size: int = 32,
) -> dict:
    """The ROADMAP's multichip-evidence leg at north-star scale: the full
    default profile + gang + preemption mix (``__graft_entry__
    .build_scale_scheduler``) at 5k nodes / ~30k pods, node axis sharded
    over the mesh, asserted BIT-IDENTICAL (placements, preemption counts,
    final device state) against an unsharded run of the same workload —
    dryrun_multichip's oracle at 100× its default pod count."""
    from __graft_entry__ import compare_scale_runs
    from kubernetes_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_devices)
    t0 = time.perf_counter()
    sh, sh_place, n_pods = compare_scale_runs(
        mesh,
        n_nodes=n_nodes,
        scale=scale,
        batch_size=batch_size,
        chunk_size=chunk_size,
    )
    wall_s = round(time.perf_counter() - t0, 1)
    placed = sum(1 for v in sh_place.values() if v)
    vips = sum(1 for k, v in sh_place.items() if k.startswith("vip") and v)
    result = {
        "mode": "north-star-dryrun",
        "n_devices": n_devices,
        "mesh": dict(mesh.shape),
        "nodes": n_nodes,
        "pods": n_pods + 4,  # + the VIP preemptors
        "scale": scale,
        "batch_size": batch_size,
        "chunk_size": chunk_size,
        "placed": placed,
        "gang_members_placed": sum(
            1 for k, v in sh_place.items() if k.startswith("g") and v
        ),
        "preemptions": sh.metrics.preemptions,
        "vips_placed": vips,
        "bit_identical_to_unsharded": True,  # compare_scale_runs asserted
        "wall_s_both_runs": wall_s,
        "backend": jax.devices()[0].platform,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    if "--beyond-hbm" in sys.argv:
        rest = [int(a) for a in sys.argv[1:] if not a.startswith("-")]
        beyond_hbm(*rest)
    elif "--north-star" in sys.argv:
        rest = [int(a) for a in sys.argv[1:] if not a.startswith("-")]
        north_star(*rest)
    elif "--r07" in sys.argv:
        # The committed-artifact mode (MULTICHIP_r07.json): the
        # 1/2/4/8-device scaling table over the large node axis, plus the
        # north-star dryrun — 5k nodes / ~30k pods, full default profile
        # with gang + preemption, sharded-vs-unsharded bit-identical.
        doc = {
            "scaling_table": main(16384, 256),
            "north_star_dryrun": north_star(),
        }
        out = sys.argv[sys.argv.index("--r07") + 1]
        with open(out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {out}")
    else:
        args = [int(a) for a in sys.argv[1:3]]
        main(*args)
