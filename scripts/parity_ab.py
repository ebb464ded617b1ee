"""A/B parity harness: upstream-semantics oracle vs the TPU engine OVER THE
SIDECAR WIRE, same fixture, fixed seeds — diff the bindings.

The in-repo analog of the integration pattern SURVEY §4 prescribes
(test/integration/util/util.go:579: boot two schedulers against one
apiserver, diff bindings).  The "upstream" side is the scalar sequential
scheduler implementing the reference's truncation/rotation/interleave/
tie-break semantics (tests/test_parity.py OracleScheduler); the TPU side
runs in parity mode (percentage_of_nodes_to_score=None, chunk_size=1)
behind the framed-socket sidecar, so the comparison crosses the real
process boundary a Go host would use.

Usage:
  python scripts/parity_ab.py [nodes] [pods]             # fit-only profile
  python scripts/parity_ab.py --default [nodes] [pods]   # FULL default
      profile with preemption ON: bindings + nominations + victim sets
      diffed against tests/oracle_full.FullOracleScheduler.
Prints one JSON line: {"parity": true/false, "mismatches": N, ...,
"platform": ..., "device_kind": ..., "n_devices": N} — the device is asked
for first (kubernetes_tpu.utils.require_device): no accelerator is an
error unless JAX_PLATFORMS names cpu.
"""

import json
import os
import sys
import tempfile
from dataclasses import replace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

from kubernetes_tpu.framework.config import DEFAULT_PROFILE, fit_only_profile  # noqa: E402
from kubernetes_tpu.ops.common import registered_subset  # noqa: E402
from kubernetes_tpu.scheduler import TPUScheduler  # noqa: E402
from kubernetes_tpu.sidecar import SidecarClient, SidecarServer  # noqa: E402
from kubernetes_tpu.utils import require_device  # noqa: E402
from test_parity import OracleScheduler, _nodes, _pod  # noqa: E402


def _explain_first_mismatch(sched, mismatches: dict) -> dict | None:
    """Decision-record localization for the first mismatched pod (lowest
    uid): re-run its Filter+Score through the engine's attribution pass
    and report each contested node's verdict, rejecting plugin, and
    per-op score column — so an A/B FAIL names the (pod, op, node)
    responsible instead of a bare uid→(got, want) pair.  Post-hoc by
    construction (the store has moved past the decision); best-effort,
    never raises."""
    if not mismatches:
        return None
    uid = sorted(mismatches)[0]
    got, want = mismatches[uid]
    try:
        rec = sched.explain_pod(uid)
    except Exception as exc:  # localization must never mask the FAIL
        return {"uid": uid, "error": f"{type(exc).__name__}: {exc}"}
    if "error" in rec:
        return {"uid": uid, "got": got, "want": want, "error": rec["error"]}
    doc = {
        "uid": uid,
        "mode": rec.get("mode"),
        "picked_node": rec.get("picked_node"),
        "select": rec.get("select"),
        "note": rec.get("note"),
    }
    nodes = rec.get("nodes") or []
    for tag, node in (("got", got), ("want", want)):
        if not node:
            doc[tag] = None
            continue
        if node not in nodes:
            doc[tag] = {"node": node, "error": "node not in store"}
            continue
        r = nodes.index(node)
        doc[tag] = {
            "node": node,
            "feasible": rec["feasible"][r],
            "first_reject": (rec.get("first_reject") or {}).get(node),
            "total": rec["total"][r],
            "score_cols": {
                op: cols[r] for op, cols in rec["score_cols"].items()
            },
        }
    return doc


def main_default(n_nodes: int = 1000, n_pending: int = 1200) -> dict:
    """Default-profile A/B over the wire, preemption ON: engine (parity
    mode, behind the framed-socket sidecar) vs the full scalar oracle
    (tests/oracle_full.py) — bindings, nominations, and victim sets must
    match decision for decision (VERDICT r3 next-2)."""
    import copy

    from oracle_full import FullOracleScheduler, build_fixture

    device = require_device()
    nodes, bound, pending, pdbs, objs = build_fixture(n_nodes, n_pending, volumes=True)
    prof = replace(
        registered_subset(DEFAULT_PROFILE), percentage_of_nodes_to_score=None
    )
    sched = TPUScheduler(profile=prof, batch_size=128, chunk_size=1)
    # One deterministic requeue alignment for the A/B: volume/DRA-active
    # batches gate prefetch off anyway (see oracle_full.run docstring).
    sched._prefetch_enabled = False
    path = tempfile.mktemp(suffix=".sock")
    srv = SidecarServer(path, scheduler=sched)
    srv.serve_background()
    client = SidecarClient(path)
    try:
        for n in nodes:
            client.add("Node", n)
        # The full host-state surface crosses the WIRE too: storage
        # classes, PVs, PVCs, CSINode limits, DRA slices/claims.
        for sc in objs["classes"]:
            client.add("StorageClass", sc)
        for pv in objs["pvs"]:
            client.add("PersistentVolume", pv)
        for pvc in objs["pvcs"]:
            client.add("PersistentVolumeClaim", pvc)
        for cn in objs["csinodes"]:
            client.add("CSINode", cn)
        for sl in objs["slices"]:
            client.add("ResourceSlice", sl)
        for cl in objs["dclaims"]:
            client.add("ResourceClaim", cl)
        for p in bound:
            client.add("Pod", p)
        for pdb in pdbs:
            client.add("PodDisruptionBudget", pdb)
        # Pre-grow vocabularies (featurize without committing) so mid-run
        # schema growth doesn't shift preemption by one batch vs the oracle.
        from kubernetes_tpu.engine.features import build_pod_batch

        build_pod_batch(
            [copy.deepcopy(p) for p in pending], sched.builder, sched.profile,
            len(pending),
        )
        results = client.schedule([copy.deepcopy(p) for p in pending])
        got_bind = {r.pod_uid: r.node_name for r in results if r.node_name}
        got_nom = {r.pod_uid: r.nominated_node for r in results if r.nominated_node}
        got_vic = {
            r.pod_uid: tuple(sorted(r.victim_uids)) for r in results if r.victim_uids
        }
    finally:
        client.close()
        srv.close()

    from reference_impl import RefClaims, RefVolumes

    oracle = FullOracleScheduler(
        nodes, pct=None, seed=prof.tie_break_seed,
        hard_pod_affinity_weight=prof.hard_pod_affinity_weight,
        batch_size=128, pdbs=[copy.deepcopy(p) for p in pdbs],
        vols=RefVolumes(
            pvs=copy.deepcopy(objs["pvs"]),
            pvcs=copy.deepcopy(objs["pvcs"]),
            classes=copy.deepcopy(objs["classes"]),
            csinodes=copy.deepcopy(objs["csinodes"]),
        ),
        claims=RefClaims(
            claims=copy.deepcopy(objs["dclaims"]),
            slices=copy.deepcopy(objs["slices"]),
        ),
    )
    for p in bound:
        oracle.add_bound(copy.deepcopy(p))
    want = oracle.run([copy.deepcopy(p) for p in pending], prefetch=False)
    want_bind = {d.pod.uid: d.node for d in want if d.node}
    want_nom = {d.pod.uid: d.nominated for d in want if d.nominated}
    want_vic = {d.pod.uid: tuple(sorted(d.victims)) for d in want if d.victims}

    mm_bind = {
        k: (got_bind.get(k), want_bind.get(k))
        for k in set(got_bind) | set(want_bind)
        if got_bind.get(k) != want_bind.get(k)
    }
    out = {
        "parity": not mm_bind and got_nom == want_nom and got_vic == want_vic,
        "profile": "default+preemption",
        "nodes": len(nodes),
        "pods": len(pending),
        "bound": len(got_bind),
        "nominations": len(got_nom),
        "victims": sum(len(v) for v in got_vic.values()),
        "mismatches": len(mm_bind),
        "sample": dict(list(sorted(mm_bind.items()))[:3]),
        "nom_ok": got_nom == want_nom,
        "vic_ok": got_vic == want_vic,
        **device,
    }
    if mm_bind:
        out["first_divergence"] = _explain_first_mismatch(sched, mm_bind)
    print(json.dumps(out))
    return out


def main(n_nodes: int = 304, n_pods: int = 200) -> dict:
    device = require_device()
    nodes = _nodes(n_nodes)
    prof = replace(fit_only_profile(), percentage_of_nodes_to_score=None)

    path = tempfile.mktemp(suffix=".sock")
    sched = TPUScheduler(
        profile=prof, batch_size=32, chunk_size=1, enable_preemption=False
    )
    srv = SidecarServer(path, scheduler=sched)
    srv.serve_background()
    client = SidecarClient(path)
    try:
        for n in nodes:
            client.add("Node", n)
        results = client.schedule([_pod(i) for i in range(n_pods)])
        tpu = {r.pod_uid: r.node_name or None for r in results}
    finally:
        client.close()
        srv.close()

    oracle = OracleScheduler(nodes, pct=None, seed=prof.tie_break_seed)
    want = {_pod(i).uid: oracle.schedule(_pod(i)) for i in range(n_pods)}

    mismatches = {k: (tpu.get(k), want[k]) for k in want if tpu.get(k) != want[k]}
    out = {
        "parity": not mismatches,
        "pods": n_pods,
        "nodes": n_nodes,
        "mismatches": len(mismatches),
        "sample": dict(list(mismatches.items())[:3]),
        **device,
    }
    if mismatches:
        out["first_divergence"] = _explain_first_mismatch(sched, mismatches)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    argv = sys.argv[1:]
    if argv and argv[0] == "--default":
        args = [int(a) for a in argv[1:3]]
        result = main_default(*args)
    else:
        args = [int(a) for a in argv[:2]]
        result = main(*args)
    sys.exit(0 if result["parity"] else 1)
