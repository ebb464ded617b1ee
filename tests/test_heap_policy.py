"""What `serve`'s frozen heap rests on (framework/tracing.py: the heap is
frozen at every batch boundary that bound a pod, and swept by a full
collection only at the checkpoint): the store makes no cycle through a pod
or a node, so reference counting alone frees what is deleted; under churn
the frozen set stays the size of the live heap; and a real `serve` child
arms the policy while a scheduler built in process never does (the unit
tests of the policy itself are beside the collector's counters in
tests/test_spans.py)."""

import gc
import re
import signal
import subprocess
import sys
import weakref

import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.framework.config import DEFAULT_PROFILE
from kubernetes_tpu.journal import Journal
from kubernetes_tpu.scheduler import TPUScheduler
from kubernetes_tpu.sidecar import SidecarClient, SidecarServer

def _node(name):
    return make_node(name).capacity({"cpu": "8", "memory": "16Gi", "pods": 110}).obj()


def _pod(name):
    return make_pod(name).req({"cpu": "100m", "memory": "64Mi"}).obj()


@pytest.fixture
def no_collector():
    """Reference counting alone: no cycle collection until the test ends."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def _gone(refs: dict) -> dict:
    return {name: ref() is None for name, ref in refs.items()}


# -- the store makes no cycles ------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_the_store_makes_no_cycle_through_a_pod_or_a_node(tmp_path, no_collector, depth):
    s = TPUScheduler(profile=DEFAULT_PROFILE, batch_size=8, pipeline_depth=depth)
    s.attach_journal(Journal(str(tmp_path), epoch=1), snapshot_every_batches=64)
    s.add_node(_node("n1"))
    s.add_pod(_pod("p0"))
    (out,) = s.schedule_batch()
    assert out.node_name == "n1" and s.journal.appends > 0
    uid = out.pod.uid
    refs = {
        "pod": weakref.ref(s.cache.pods[uid].pod),
        "pod record": weakref.ref(s.cache.pods[uid]),
        "node": weakref.ref(s.cache.nodes["n1"].node),
        "node record": weakref.ref(s.cache.nodes["n1"]),
    }
    del out
    s.delete_pod(uid)
    s.schedule_batch()  # a boundary: whatever a ticket in flight held is settled
    gone = _gone(refs)
    assert gone["pod"] and gone["pod record"], gone
    s.remove_node("n1")
    s.schedule_batch()
    assert all(_gone(refs).values()), _gone(refs)


def test_the_served_path_makes_no_cycle_through_a_pod_or_a_node(tmp_path, no_collector):
    sock = str(tmp_path / "s.sock")
    srv = SidecarServer(
        sock, batch_size=8, pipeline_depth=2, speculate=True,
        journal=Journal(str(tmp_path / "j"), epoch=1), snapshot_every_batches=64,
    )
    srv.serve_background()
    client = SidecarClient(sock)
    try:
        s = srv.scheduler
        client.add("Node", _node("n0"))
        pods = [_pod(f"p{i}") for i in range(3)]
        client.add_pending_batch(pods)  # the hint frame: decoded, queued, speculated
        answers = [client.schedule([p])[0].node_name for p in pods]
        assert answers == ["n0"] * 3
        refs = {"node": weakref.ref(s.cache.nodes["n0"].node),
                "node record": weakref.ref(s.cache.nodes["n0"])}
        for p in pods:
            refs[p.name] = weakref.ref(s.cache.pods[p.uid].pod)
            refs[p.name + " record"] = weakref.ref(s.cache.pods[p.uid])
        for p in pods:  # the host's bind comes back, then the pod goes
            p.spec.node_name = "n0"
            client.add("Pod", p)
        for p in pods:
            client.remove("Pod", p.uid)
        client.remove("Node", "n0")
        client.add("Node", _node("n1"))
        last = _pod("last")
        client.add_pending_batch([last])
        assert client.schedule([last])[0].node_name == "n1"
        assert all(_gone(refs).values()), _gone(refs)
    finally:
        client.close()
        srv.close()


# -- memory under churn -------------------------------------------------------


def test_frozen_heap_stays_the_size_of_the_live_heap_under_churn(tmp_path, armed_heap):
    s = TPUScheduler(profile=DEFAULT_PROFILE, batch_size=8, pipeline_depth=2)
    s.attach_journal(Journal(str(tmp_path), epoch=1), snapshot_every_batches=64)
    s.snapshot_every_records = 80  # a checkpoint every eight batches or so
    for i in range(4):
        s.add_node(_node(f"n{i}"))
    live: list[str] = []
    at_checkpoint: list[int] = []
    for b in range(200):
        for i in range(8):
            s.add_pod(_pod(f"p{b}-{i}"))
        seen = s.journal.snapshots
        live += [o.pod.uid for o in s.schedule_batch() if o.node_name]
        while len(live) > 16:  # a steady live count: every pod bound is deleted later
            s.delete_pod(live.pop(0))
        if s.journal.snapshots > seen:
            at_checkpoint.append(gc.get_freeze_count())
    assert len(at_checkpoint) >= 20 and s.metrics.scheduled == 1600
    # Every pod the run bound was frozen at some boundary, and the frozen set
    # after a checkpoint is the live heap: reference counts freed the deleted
    # pods, the sweep whatever was cyclic.  What still grows is a ring filling
    # (a flight record a batch: four tracked objects); one pod leaked a batch
    # with its records would be some sixty.
    grown = max(at_checkpoint) - at_checkpoint[0]
    assert grown < 10 * 200, (grown, at_checkpoint)


# -- who runs the policy ------------------------------------------------------


def _sample(text: str, name: str) -> float:
    return float(re.search(rf"^{re.escape(name)} (\S+)$", text, re.M).group(1))


def test_a_serve_process_freezes_at_its_boundaries_and_its_sweeps_find_nothing(tmp_path):
    """The deployment: a real `serve` child, hints, one call a pod, the
    host's bind echoed back, pods deleted at a steady live count, a
    checkpoint every second batch.  The policy engages (a library
    scheduler's never does: tests/test_spans.py), and what the served path
    leaves behind a batch holds no cyclic garbage: every sweep finds 0."""
    from kubernetes_tpu.loadgen.soak import _launch_serve

    sock = str(tmp_path / "s.sock")
    proc = _launch_serve(
        [sys.executable, "-m", "kubernetes_tpu", "serve", "--socket", sock,
         "--journal-dir", str(tmp_path / "j"), "--batch-size", "8", "--snapshot-every", "4",
         "--speculate", "--pipeline-depth", "2"],
        str(tmp_path), sock, "serve", deadline_s=180.0,
    )
    try:
        client = SidecarClient(sock, deadline_s=120.0)
        client.add("Node", _node("n0"))
        assert _sample(client.metrics(), "scheduler_gc_freezes_total") == 0
        live, frozen = [], []
        for b in range(24):
            pods = [_pod(f"p{b}-{i}") for i in range(8)]
            client.add_pending_batch(pods)
            for p in pods:
                p.spec.node_name = client.schedule([p])[0].node_name
                assert p.spec.node_name == "n0"
                client.add("Pod", p)
                live.append(p.uid)
            while len(live) > 16:
                client.remove("Pod", live.pop(0))
            if b % 8 == 7:
                frozen.append(_sample(client.metrics(), "scheduler_gc_frozen_objects"))
        text = client.metrics()
        client.close()
        sweeps = _sample(text, 'scheduler_gc_collections_total{generation="2"}')
        assert sweeps >= 8  # a checkpoint every second batch or so, a full collection in each
        assert _sample(text, "scheduler_gc_freezes_total") >= 24 + 8  # every boundary and every checkpoint
        assert _sample(text, "scheduler_gc_sweep_reclaimed_total") == 0
        assert frozen[-1] - frozen[0] < 16 * 40, frozen  # rings filling; a leaked pod a bind would be thousands
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
