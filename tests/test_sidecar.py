"""Out-of-process sidecar protocol: framed protobuf over a unix socket.

Validates (a) the wire protocol round-trips cluster objects and batch
results, (b) decisions through the socket are IDENTICAL to the in-process
scheduler on the same fixture (the A/B property the Go-side integration
needs), and (c) the native C++ client (native/sidecar_client.cc) drives
the server end-to-end."""

import os
import subprocess
import tempfile

import pytest

from kubernetes_tpu.api import types as t
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.framework.config import fit_only_profile
from kubernetes_tpu.scheduler import TPUScheduler
from kubernetes_tpu.sidecar import SidecarClient, SidecarServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def server():
    path = tempfile.mktemp(suffix=".sock")
    srv = SidecarServer(path, scheduler=TPUScheduler(batch_size=16))
    srv.serve_background()
    yield srv
    srv.close()


def nodes(n=4):
    return [
        make_node(f"node-{i}")
        .capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
        .zone(f"zone-{i % 3}")
        .obj()
        for i in range(n)
    ]


def pods(n=8):
    return [
        make_pod(f"pod-{i}").req({"cpu": "500m", "memory": "1Gi"}).obj()
        for i in range(n)
    ]


def test_protocol_matches_in_process(server):
    client = SidecarClient(server.path)
    for node in nodes():
        client.add("Node", node)
    results = client.schedule(pods())
    via_wire = {r.pod_uid: r.node_name for r in results}

    ref = TPUScheduler(batch_size=16)
    for node in nodes():
        ref.add_node(node)
    for p in pods():
        ref.add_pod(p)
    in_proc = {o.pod.uid: o.node_name or "" for o in ref.schedule_all_pending()}
    assert via_wire == in_proc
    client.close()


def test_snapshot_delta_and_diagnosis(server):
    client = SidecarClient(server.path)
    client.add(
        "Node",
        make_node("n1").capacity({"cpu": "2", "pods": 110})
        .taint("team", "ml", t.EFFECT_NO_SCHEDULE).obj(),
    )
    res = client.schedule([make_pod("p").req({"cpu": "1"}).obj()])
    assert res[0].node_name == ""
    assert list(res[0].unschedulable_plugins) == ["TaintToleration"]
    # Delta: the taint comes off → the parked pod wakes and binds.
    client.add("Node", make_node("n1").capacity({"cpu": "2", "pods": 110}).obj())
    import time

    time.sleep(1.1)  # backoff
    res2 = client.schedule([])
    assert [r.node_name for r in res2] == ["n1"]
    # Remove the node; its pod vanishes from scheduling state.
    client.remove("Node", "n1")
    assert server.scheduler.cache.node_count() == 0
    client.close()


def test_gang_and_claims_over_the_wire(server):
    client = SidecarClient(server.path)
    for node in nodes(2):
        client.add("Node", node)
    client.add("PodGroup", t.PodGroup(name="g", min_member=2))
    client.add("ResourceSlice", t.ResourceSlice("node-0", "gpu", 2))
    client.add("ResourceClaim", t.ResourceClaim("c0", "gpu"))
    client.add("ResourceClaim", t.ResourceClaim("c1", "gpu"))
    members = [
        make_pod(f"m{i}").req({"cpu": "1"}).pod_group("g")
        .resource_claim(f"c{i}").obj()
        for i in range(2)
    ]
    res = client.schedule(members)
    assert sorted(r.node_name for r in res) == ["node-0", "node-0"]
    client.close()


def test_native_cpp_client(server):
    binary = os.path.join(REPO, "native", "build", "sidecar_client")
    # Always through make (a no-op when current): native/build/ is
    # git-ignored, so a binary found there proves nothing about this tree.
    build = subprocess.run(
        ["make", "-C", os.path.join(REPO, "native")],
        capture_output=True, text=True,
    )
    if build.returncode != 0:
        pytest.skip(f"native build unavailable: {build.stderr[-300:]}")
    out = subprocess.run(
        [binary, server.path, "4", "8"], capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stderr
    lines = [ln for ln in out.stdout.splitlines() if " -> " in ln]
    assert len(lines) == 8
    assert all("<unschedulable>" not in ln for ln in lines)
    # C++-created pods landed via the same engine: state is consistent.
    assert server.scheduler.metrics.scheduled == 8
    assert server.scheduler.builder.host_mirror_equal()
