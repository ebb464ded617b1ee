"""Crash-safe scheduler state (PR 3): the write-ahead binding journal —
record framing/CRC, torn-tail repair, snapshot barriers, lease-epoch
fencing (append-side and replay-side), full scheduler snapshot+replay
recovery, quarantine persistence, the LIST reconcile rules, the durable
host replay store, and a fast subset of the SIGKILL crash matrix
(scripts/run_fault_matrix.py --kill sweeps the full grid)."""

import json
import os
import struct
import subprocess
import sys
import zlib

import pytest

from kubernetes_tpu.api import serialize
from kubernetes_tpu.api import types as t
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.faults import FaultPlan
from kubernetes_tpu.framework.config import fit_only_profile
from kubernetes_tpu.framework.leaderelection import FileLease, read_epoch
from kubernetes_tpu.informers import (
    FakeSource,
    Reflector,
    reconcile_after_recovery,
)
from kubernetes_tpu.journal import (
    Journal,
    StaleEpochError,
    recover,
    scheduler_state,
)
from kubernetes_tpu.queue import SchedulingQueue
from kubernetes_tpu.scheduler import TPUScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_sched(**kw):
    return TPUScheduler(profile=fit_only_profile(), batch_size=8, chunk_size=1, **kw)


def bindings_of(sched):
    return {
        uid: pr.node_name
        for uid, pr in sched.cache.pods.items()
        if pr.bound
    }


def node(name, cpu="4"):
    return make_node(name).capacity({"cpu": cpu, "memory": "16Gi", "pods": 16}).obj()


def pod(name, cpu="1", **kw):
    b = make_pod(name).req({"cpu": cpu})
    if kw.get("node"):
        b = b.node(kw["node"])
    if kw.get("priority"):
        b = b.priority(kw["priority"])
    return b.obj()


# -- record format ----------------------------------------------------------


def test_append_replay_roundtrip(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    j.append("delete", {"uid": "b"})
    snap, recs, stats = j.replay()
    assert snap is None
    assert [(r["t"], r["q"]) for r in recs] == [("bind", 1), ("delete", 2)]
    assert stats["fenced"] == 0
    # A reopened journal continues the sequence.
    j.close()
    j2 = Journal(str(tmp_path), epoch=1)
    assert j2.seq == 2
    j2.append("bind", {"uid": "c", "node": "n2"})
    _, recs, _ = j2.replay()
    assert [r["q"] for r in recs] == [1, 2, 3]


def test_torn_tail_truncated_at_open(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    j.close()
    wal = os.path.join(str(tmp_path), Journal.WAL)
    good = os.path.getsize(wal)
    with open(wal, "ab") as f:
        f.write(b"\x00\x00\x01\x00" + b"half-a-record")  # length 256, 13 bytes
    j2 = Journal(str(tmp_path), epoch=1)
    assert j2.torn_bytes == 4 + 13
    assert os.path.getsize(wal) == good  # repaired in place
    _, recs, _ = j2.replay()
    assert [r["d"]["uid"] for r in recs] == ["a"]


def test_corrupt_record_stops_replay(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    j.append("bind", {"uid": "b", "node": "n2"})
    j.close()
    wal = os.path.join(str(tmp_path), Journal.WAL)
    blob = bytearray(open(wal, "rb").read())
    # Flip a byte inside the FIRST record's payload: framing can't be
    # trusted past a CRC failure, so replay must stop before it.
    blob[12] ^= 0xFF
    with open(wal, "wb") as f:
        f.write(blob)
    j2 = Journal(str(tmp_path), epoch=1)
    _, recs, _ = j2.replay()
    assert recs == []


def test_snapshot_barrier_skips_covered_records(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    j.snapshot({"marker": 1})
    j.append("bind", {"uid": "b", "node": "n2"})
    snap, recs, _ = j.replay()
    assert snap["state"] == {"marker": 1}
    assert [r["d"]["uid"] for r in recs] == ["b"]
    # The truncation actually happened (log holds only post-barrier data).
    j.close()
    j2 = Journal(str(tmp_path), epoch=1)
    snap, recs, _ = j2.replay()
    assert snap["seq"] == 1 and [r["q"] for r in recs] == [2]


def test_snapshot_seq_filter_survives_missing_truncate(tmp_path):
    """The mid-truncate crash window: snapshot replaced, log NOT yet
    truncated — every surviving record is <= the barrier and must be
    skipped, not replayed on top of the snapshot."""
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    j.append("bind", {"uid": "b", "node": "n2"})
    # Write the snapshot document by hand (what snapshot() makes durable
    # before the truncate), leaving the wal untouched.
    with open(os.path.join(str(tmp_path), Journal.SNAP), "wb") as f:
        f.write(json.dumps({"epoch": 1, "seq": 2, "state": {"x": 1}}).encode())
    j.close()
    j2 = Journal(str(tmp_path), epoch=1)
    snap, recs, _ = j2.replay()
    assert snap["state"] == {"x": 1}
    assert recs == []


def test_torn_snapshot_tmp_discarded(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    j.snapshot({"good": True})
    # A crash mid-snapshot leaves a torn temp; the replace never ran, so
    # the previous snapshot must still win.
    with open(os.path.join(str(tmp_path), Journal.SNAP + ".tmp"), "wb") as f:
        f.write(b'{"epoch": 9, "seq": 99, "state"')
    j.close()
    j2 = Journal(str(tmp_path), epoch=1)
    snap, _, _ = j2.replay()
    assert snap["state"] == {"good": True}
    assert not os.path.exists(os.path.join(str(tmp_path), Journal.SNAP + ".tmp"))


# -- epoch fencing ----------------------------------------------------------


def test_stale_epoch_append_rejected(tmp_path):
    j1 = Journal(str(tmp_path), epoch=1)
    j1.append("bind", {"uid": "a", "node": "n1"})
    j2 = Journal(str(tmp_path), epoch=2)
    j2.append("bind", {"uid": "b", "node": "n2"})
    # The deposed writer's next append trips the self-fencing tripwire
    # (the log grew under it) even without a fence callable.
    with pytest.raises(StaleEpochError):
        j1.append("bind", {"uid": "c", "node": "nX"})
    assert j1.fenced == 1
    _, recs, _ = Journal(str(tmp_path), epoch=3).replay()
    assert [r["d"]["uid"] for r in recs] == ["a", "b"]


def test_stale_epoch_record_ignored_at_replay(tmp_path):
    """Belt and braces: even a stale record that RACED onto disk is
    dropped by the replay-side running-maximum fence."""
    j = Journal(str(tmp_path), epoch=2)
    j.append("bind", {"uid": "new", "node": "n1"})
    j.close()
    # Forge a stale-epoch record after the epoch-2 one.
    payload = json.dumps(
        {"e": 1, "q": 99, "t": "bind", "d": {"uid": "stale", "node": "nX"}}
    ).encode()
    with open(os.path.join(str(tmp_path), Journal.WAL), "ab") as f:
        f.write(struct.pack(">II", len(payload), zlib.crc32(payload)) + payload)
    j2 = Journal(str(tmp_path), epoch=3)
    _, recs, stats = j2.replay()
    assert [r["d"]["uid"] for r in recs] == ["new"]
    assert stats["fenced"] == 1


def test_leader_failover_mid_append_no_double_bind(tmp_path):
    """Satellite: the standby acquires the flock while the old leader is
    mid-commit.  The old leader's in-flight append is fenced (dropped,
    not written), the new leader's decision stands alone — recovery sees
    exactly one binding for the pod."""
    lease_path = str(tmp_path / "lease")
    jdir = str(tmp_path / "journal")
    old = FileLease(lease_path, identity="old")
    assert old.acquire(block=False)
    j_old = Journal(
        jdir, epoch=old.epoch, fence=lambda: read_epoch(lease_path)
    )
    p = pod("contended")
    j_old.append(
        "bind", {"uid": p.uid, "node": "n0", "pod": serialize.to_dict(p)}
    )
    # The old leader's HOST dies mid-flight (flock freed by the kernel,
    # no clean release); the standby takes over and re-decides the pod.
    os.close(old._fd)
    old._fd = None
    new = FileLease(lease_path, identity="new")
    assert new.acquire(block=False)
    assert new.epoch == old.epoch + 1
    j_new = Journal(
        jdir, epoch=new.epoch, fence=lambda: read_epoch(lease_path)
    )
    j_new.append(
        "bind", {"uid": p.uid, "node": "n1", "pod": serialize.to_dict(p)}
    )
    # The lingering old leader finishes its in-flight commit: fenced.
    with pytest.raises(StaleEpochError):
        j_old.append(
            "bind", {"uid": p.uid, "node": "n0", "pod": serialize.to_dict(p)}
        )
    # Recovery: one binding, the new leader's.
    sched = small_sched()
    sched.add_node(node("n0"))
    sched.add_node(node("n1"))
    recover(sched, Journal(jdir, epoch=new.epoch + 1))
    assert bindings_of(sched) == {p.uid: "n1"}
    new.release()


def test_epoch_monotonicity_feeds_journal(tmp_path):
    """test_leader_election's epoch-monotonicity case, journal-side: each
    tenure's records carry its epoch and order correctly at replay."""
    lease_path = str(tmp_path / "lease")
    jdir = str(tmp_path / "journal")
    for i, who in enumerate(("a", "b", "c"), start=1):
        lease = FileLease(lease_path, identity=who)
        assert lease.acquire(block=False)
        assert lease.epoch == i
        j = Journal(jdir, epoch=lease.epoch)
        j.append("bind", {"uid": f"p{i}", "node": f"n{i}"})
        j.close()
        lease.release()
    _, recs, stats = Journal(jdir, epoch=99).replay()
    assert [r["e"] for r in recs] == [1, 2, 3]
    assert stats["fenced"] == 0


# -- scheduler snapshot + recovery ------------------------------------------


def scenario_sched(journal=None):
    s = small_sched()
    if journal is not None:
        # One full batch of records (8, the scheduler's batch size).
        s.attach_journal(journal, snapshot_every_batches=1)
    for i in range(3):
        s.add_node(node(f"n{i}"))
    s.add_pod(pod("resident", cpu="3", node="n0"))
    return s


def test_recovery_from_journal_only(tmp_path):
    """A crash before the first snapshot: bindings rebuild from the raw
    journal (the post-append/pre-apply window end to end)."""
    j = Journal(str(tmp_path), epoch=1)
    s1 = scenario_sched()
    s1.journal = j  # journal appends without snapshot cadence
    s1.queue.journal = j
    s1.add_pod(pod("w1"))
    s1.add_pod(pod("w2"))
    s1.schedule_all_pending()
    want = bindings_of(s1)
    assert {"default/w1", "default/w2"} <= set(want)
    s2 = scenario_sched()
    j2 = Journal(str(tmp_path), epoch=2)
    stats = recover(s2, j2)
    assert stats["records"] >= 2 and not stats["snapshot"]
    assert bindings_of(s2) == want


def test_recovery_from_snapshot_and_journal(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    s1 = scenario_sched(journal=j)
    for i in range(8):
        s1.add_pod(pod(f"w1-{i}"))
    s1.schedule_all_pending()  # one full batch of binds → checkpointed
    assert j.snapshots >= 1
    s1.add_pod(pod("w2"))
    s1.journal = None  # crash window: w2's bind never journals...
    s1.queue.journal = None
    want_pre = bindings_of(s1)
    s2 = small_sched()
    stats = recover(s2, Journal(str(tmp_path), epoch=2))
    assert stats["snapshot"]
    # w1's binding survives via the snapshot; w2 was never scheduled in
    # the journaled world and is simply absent (it would re-arrive via
    # the LIST reconcile as pending).
    got = bindings_of(s2)
    assert got == want_pre
    # Queue state (depths) survives too.
    assert s2.queue.pending_count() == s1.queue.pending_count() - 1  # w2


def test_queue_backoff_and_attempts_survive_restart():
    clock = [100.0]
    q1 = SchedulingQueue(clock=lambda: clock[0])
    p1 = pod("backing-off")
    q1.add(p1)
    qp = q1.pop_batch(1)[0]
    qp.attempts = 3
    q1.add_backoff(qp)
    q1._info[p1.uid] = qp
    state = q1.durable_state()
    [e] = state["entries"]
    assert e["pool"] == "backoff" and e["attempts"] == 3
    assert 0 < e["backoff_remaining_s"] <= q1.backoff_duration(3)
    # Restore into a fresh queue on a DIFFERENT clock base: the remaining
    # backoff carries over relative, not absolute.
    clock2 = [5000.0]
    q2 = SchedulingQueue(clock=lambda: clock2[0])
    assert q2.restore_state(state) == 1
    assert q2.pop_batch(1) == []  # still backing off
    clock2[0] += e["backoff_remaining_s"] + 0.01
    out = q2.pop_batch(1)
    assert [x.pod.uid for x in out] == [p1.uid]
    assert out[0].attempts == 4  # 3 restored + this pop


def test_quarantine_survives_restart(tmp_path):
    """Satellite: quarantined pods (PR 2) survive a host restart with
    their backoff state intact and still release via release_quarantine."""
    j = Journal(str(tmp_path), epoch=1)
    s1 = scenario_sched()
    s1.journal = j
    s1.queue.journal = j
    plan = FaultPlan().add_rule("engine", pod="default/poison")
    plan.install_engine(s1)
    s1.add_pod(pod("poison"))
    s1.add_pod(pod("healthy"))
    s1.schedule_all_pending()
    assert s1.queue.quarantined() == ["default/poison"]
    attempts = s1.queue._quarantine["default/poison"].attempts
    assert "default/healthy" in bindings_of(s1)
    # Restart: fresh scheduler, no fault plan (the poison was transient).
    s2 = scenario_sched()
    recover(s2, Journal(str(tmp_path), epoch=2))
    assert s2.queue.quarantined() == ["default/poison"]
    assert s2.queue._quarantine["default/poison"].attempts == attempts
    assert bindings_of(s2)["default/healthy"] == bindings_of(s1)["default/healthy"]
    # Release flows through backoff and schedules.
    assert s2.queue.release_quarantine("default/poison") == 1
    s2.schedule_all_pending(wait_backoff=True)
    assert "default/poison" in bindings_of(s2)
    assert s2.queue.quarantined() == []


def test_quarantine_release_is_journaled(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    s1 = scenario_sched()
    s1.journal = j
    s1.queue.journal = j
    plan = FaultPlan().add_rule("engine", pod="default/poison")
    plan.install_engine(s1)
    s1.add_pod(pod("poison"))
    s1.schedule_all_pending()
    s1.fault_injector = None
    assert s1.queue.release_quarantine() == 1
    s1.schedule_all_pending(wait_backoff=True)
    # Restart must NOT resurrect the pod into quarantine: the release —
    # and the subsequent bind — are both in the log.
    s2 = scenario_sched()
    recover(s2, Journal(str(tmp_path), epoch=2))
    assert s2.queue.quarantined() == []
    assert "default/poison" in bindings_of(s2)


# -- LIST reconcile ---------------------------------------------------------


def test_reconcile_rules(tmp_path):
    """The three recovery-ordering rules: journal bindings absent from
    the relist are re-applied; relist bindings win as host truth; objects
    absent from the relist are deleted."""
    j = Journal(str(tmp_path), epoch=1)
    px, py, pz = pod("x"), pod("y"), pod("z")
    for p, n in ((px, "n0"), (py, "n1"), (pz, "n2")):
        j.append(
            "bind", {"uid": p.uid, "node": n, "pod": serialize.to_dict(p)}
        )
    s = small_sched()
    for i in range(3):
        s.add_node(node(f"n{i}"))
    recover(s, j)
    assert bindings_of(s) == {px.uid: "n0", py.uid: "n1", pz.uid: "n2"}
    # Host truth: x unbound (the bind never reached the relist), y bound
    # ELSEWHERE (n2), z gone entirely.
    src_n, src_p = FakeSource(), FakeSource()
    for i in range(3):
        src_n.add(f"n{i}", node(f"n{i}"))
    src_p.add(px.uid, pod("x"))
    src_p.add(py.uid, pod("y", node="n2"))
    reconcile_after_recovery(
        s,
        Reflector(s, "Node", src_n.lister, src_n.watcher),
        Reflector(s, "Pod", src_p.lister, src_p.watcher),
    )
    got = bindings_of(s)
    assert got[px.uid] == "n0"  # journal binding re-applied
    assert got[py.uid] == "n2"  # relist won as host truth
    assert pz.uid not in got  # LIST-as-replace delete


def test_reconcile_applies_late_binding_when_node_relists(tmp_path):
    """A journal bind whose node the snapshot never held parks on
    _recovered_bindings and lands once the LIST delivers the node."""
    j = Journal(str(tmp_path), epoch=1)
    p = pod("late")
    j.append(
        "bind",
        {"uid": p.uid, "node": "n-new", "pod": serialize.to_dict(p)},
    )
    s = small_sched()  # no nodes at all pre-recovery
    stats = recover(s, j)
    assert stats["pending_bindings"] == 1
    assert bindings_of(s) == {}
    src_n, src_p = FakeSource(), FakeSource()
    src_n.add("n-new", node("n-new"))
    src_p.add(p.uid, pod("late"))
    rstats = reconcile_after_recovery(
        s,
        Reflector(s, "Node", src_n.lister, src_n.watcher),
        Reflector(s, "Pod", src_p.lister, src_p.watcher),
    )
    assert rstats["late_bindings_applied"] == 1
    assert bindings_of(s) == {p.uid: "n-new"}


# -- durable host replay store (sidecar/host.py) ----------------------------


def test_resyncing_client_store_rebuilt_from_journal(tmp_path):
    """The host's replay store survives a host kill: a fresh
    ResyncingClient(journal=...) rebuilds the mirror from durable state
    and re-ships it — including learned bindings — to the sidecar."""
    import tempfile

    from kubernetes_tpu.sidecar.host import ResyncingClient
    from kubernetes_tpu.sidecar.server import SidecarServer

    jdir = str(tmp_path / "hostj")
    with tempfile.TemporaryDirectory() as td:
        sock = os.path.join(td, "s.sock")
        srv = SidecarServer(sock, scheduler=small_sched())
        srv.serve_background()
        c1 = ResyncingClient(sock, journal=Journal(jdir, epoch=1))
        c1.add("Node", node("n0"))
        c1.add("Node", node("n1"))
        c1.add("Node", node("gone"))
        c1.add("Pod", pod("bound", cpu="1", node="n0"))
        c1.add("Pod", pod("doomed", cpu="1", node="gone"))
        results = c1.schedule(pods=[pod("w")], drain=True)
        learned = {r.pod_uid: r.node_name for r in results if r.node_name}
        assert learned
        c1.remove("Node", "gone")  # its pods vanish from the store too
        c1.close()  # host "dies" (journal already durable)
        srv.close()
        # A fresh sidecar + a fresh host process: the durable store must
        # replay the bound world (not just live-mirror memory).
        srv2 = SidecarServer(sock, scheduler=small_sched())
        srv2.serve_background()
        c2 = ResyncingClient(sock, journal=Journal(jdir, epoch=2))
        try:
            dump = c2.dump()
            assert set(dump["nodes"]) == {"n0", "n1"}  # the remove held
            assert "default/doomed" not in dump["pods"]  # died with its node
            for uid, node_name in learned.items():
                assert dump["pods"][uid]["node"] == node_name
            assert dump["pods"]["default/bound"]["node"] == "n0"
        finally:
            c2.close()
            srv2.close()


def test_host_checkpoint_covers_latest_mutation(tmp_path):
    """Checkpoint-ordering regression: a checkpoint whose seq covers the
    just-appended record must also CONTAIN its mutation — snapshotting
    before the store applied it would truncate the record into nothing
    durable.  Cadence 1 makes every mutation a checkpoint boundary."""
    import tempfile

    from kubernetes_tpu.sidecar.host import ResyncingClient
    from kubernetes_tpu.sidecar.server import SidecarServer

    jdir = str(tmp_path / "hostj")
    with tempfile.TemporaryDirectory() as td:
        sock = os.path.join(td, "s.sock")
        srv = SidecarServer(sock, scheduler=small_sched())
        srv.serve_background()
        c1 = ResyncingClient(
            sock, journal=Journal(jdir, epoch=1), journal_snapshot_every=1
        )
        c1.add("Node", node("n0"))
        results = c1.schedule(pods=[pod("w")], drain=True)
        learned = {r.pod_uid: r.node_name for r in results if r.node_name}
        assert learned == {"default/w": "n0"}
        c1.close()
        srv.close()
        # Every record was immediately checkpointed+truncated; the
        # snapshot alone must reproduce the bound store.
        j2 = Journal(jdir, epoch=2)
        snap, recs, _ = j2.replay()
        assert recs == []  # all barriers held
        pods = {p["metadata"]["name"]: p for p in snap["state"]["store"]["Pod"]}
        assert pods["w"]["spec"]["node_name"] == "n0"


# -- online compaction (bounded WAL over unbounded streams) -----------------


def _append_stream(sched, cycles: int = 12, per_cycle: int = 3):
    """An unbounded-stream stand-in: each cycle binds fresh pods and
    retires the ones bound two cycles ago (the soak driver's live-pod
    cap), so the journal sees a perpetual bind+delete append stream."""
    wal = os.path.join(sched.journal.dir, Journal.WAL)
    sizes = []
    bound_cycles: list[list[str]] = []
    for c in range(cycles):
        batch = []
        for j in range(per_cycle):
            p = pod(f"st-{c}-{j}")
            batch.append(p.uid)
            sched.add_pod(p)
        sched.schedule_all_pending()
        bound_cycles.append(batch)
        if len(bound_cycles) > 2:
            for uid in bound_cycles.pop(0):
                sched.delete_pod(uid)
        sizes.append(os.path.getsize(wal))
    return sizes


def test_wal_bounded_under_unbounded_append_stream(tmp_path):
    """Compaction guard: over a long bind+delete stream, the snapshot
    cadence keeps journal.wal bounded (truncations observed repeatedly,
    high-water mark well under the cadence-free growth) and recovery
    from the compacted state is still bit-identical."""
    # Cadence-free reference: the WAL grows monotonically.
    j_free = Journal(str(tmp_path / "free"), epoch=1)
    s_free = scenario_sched()
    s_free.attach_journal(j_free)  # no snapshot cadence
    free_sizes = _append_stream(s_free)
    assert free_sizes == sorted(free_sizes)

    # Compacted run: same stream, snapshot every 2 batches.
    j = Journal(str(tmp_path / "compact"), epoch=1)
    s1 = scenario_sched()
    s1.attach_journal(j, snapshot_every_batches=2)
    sizes = _append_stream(s1)
    assert j.truncations >= 2, "compaction must cycle during the stream"
    assert max(sizes) < 0.6 * free_sizes[-1], (
        f"WAL high-water {max(sizes)} not bounded vs cadence-free "
        f"{free_sizes[-1]}"
    )
    # The compacted journal still recovers the exact final world.
    want = bindings_of(s1)
    s2 = scenario_sched()
    recover(s2, Journal(str(tmp_path / "compact"), epoch=2))
    assert bindings_of(s2) == want


# -- the cadence is counted in records (ISSUE 32) ----------------------------


def cadence_sched(journal=None):
    """Batches of 8 rows, a checkpoint every two full batches of records
    (16), on nodes with room for every pod of these tests."""
    s = small_sched()
    if journal is not None:
        s.attach_journal(journal, snapshot_every_batches=2)
    for i in range(4):
        s.add_node(node(f"n{i}", cpu="64"))
    return s


def _bind_batch(sched, tag: str, n: int) -> None:
    for i in range(n):
        sched.add_pod(pod(f"{tag}-{i}"))
    assert all(o.node_name for o in sched.schedule_all_pending())


def test_short_batches_checkpoint_by_the_records_they_hold(tmp_path):
    """Five batches of three pods are 15 records: under 2 x 8, so no
    checkpoint however many batches they were; one record more takes one."""
    j = Journal(str(tmp_path), epoch=1)
    s = cadence_sched(j)
    for b in range(5):
        _bind_batch(s, f"short{b}", 3)
    assert s.metrics.batches == 5
    assert (j.seq, j.snapshot_seq, j.snapshots) == (15, 0, 0)
    _bind_batch(s, "one-more", 1)
    assert (j.seq, j.snapshot_seq, j.snapshots) == (16, 16, 1)


def test_full_batches_checkpoint_exactly_where_they_did(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    s = cadence_sched(j)
    barriers = []
    for b in range(6):
        _bind_batch(s, f"full{b}", 8)
        barriers.append(j.snapshot_seq)
    assert barriers == [0, 16, 16, 32, 32, 48] and j.snapshots == 3


def test_short_batches_without_a_checkpoint_recover_every_binding(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    s1 = cadence_sched(j)
    for b in range(5):
        _bind_batch(s1, f"short{b}", 3)
    assert j.snapshots == 0
    want = bindings_of(s1)
    assert len(want) == 15
    j.close()
    s2 = cadence_sched()
    stats = recover(s2, Journal(str(tmp_path), epoch=2))
    assert not stats["snapshot"] and stats["records"] == 15
    assert bindings_of(s2) == want  # node for node


def test_idle_scheduler_never_rewrites_its_snapshot(tmp_path):
    j = Journal(str(tmp_path), epoch=1)
    s = cadence_sched(j)
    _bind_batch(s, "full0", 8)
    _bind_batch(s, "full1", 8)
    assert j.snapshots == 1
    snap = os.path.join(j.dir, Journal.SNAP)
    before = (os.stat(snap).st_mtime_ns, os.stat(snap).st_ino)
    for _ in range(40):  # idle polls, each a batch boundary
        assert s.schedule_all_pending() == []
        assert not s.maybe_snapshot()
    assert j.snapshots == 1
    assert (os.stat(snap).st_mtime_ns, os.stat(snap).st_ino) == before


@pytest.mark.faults
@pytest.mark.parametrize("point", ["pre-snapshot", "post-truncate"])
def test_mid_compaction_sigkill_recovers_bit_identical(point):
    """The compaction cycle's own crash windows (the KILL_POINTS this PR
    added around snapshot+truncate): SIGKILL just before the checkpoint
    begins and just after the truncate lands, and assert recovery is
    bit-identical to an uninterrupted run."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import tempfile

    from run_fault_matrix import _read_bindings, _spawn

    with tempfile.TemporaryDirectory() as td:
        base = os.path.join(td, "base")
        os.makedirs(base)
        assert _spawn("--kill-child", base) == 0
        baseline = _read_bindings(base)
        assert baseline
        case = os.path.join(td, "case")
        os.makedirs(case)
        rc = _spawn("--kill-child", case, kill=f"{point}:1")
        assert rc == -9, f"child survived the {point} SIGKILL (rc={rc})"
        assert _spawn("--recover-child", case) == 0
        assert _read_bindings(case) == baseline


def test_quarantine_release_history_is_trimmed():
    """The release history is a bounded ring: an unbounded release
    stream keeps only the trailing RELEASE_HISTORY_MAX entries, the
    window survives a durable_state round trip, and an over-long stored
    list trims on restore."""
    from kubernetes_tpu.queue import RELEASE_HISTORY_MAX, QueuedPodInfo

    clock = [100.0]
    q = SchedulingQueue(clock=lambda: clock[0])
    n = RELEASE_HISTORY_MAX + 44
    for i in range(n):
        p = pod(f"q-{i}")
        qp = QueuedPodInfo(
            pod=p, timestamp=clock[0], initial_attempt_timestamp=clock[0],
            attempts=i % 5,
        )
        q.quarantine(qp)
        assert q.release_quarantine(p.uid) == 1
        q.delete(p.uid)  # released pods leave; only the history remains
        clock[0] += 1.0
    assert len(q.release_history) == RELEASE_HISTORY_MAX
    uids = [e["uid"] for e in q.release_history]
    assert uids[0] == "default/q-44"  # the oldest 44 were trimmed
    assert uids[-1] == f"default/q-{n - 1}"
    # The window rides durable_state (stamps stored as ages — raw
    # monotonic clocks are meaningless in the next process) and
    # restores trimmed, rebased onto the restoring clock.
    state = q.durable_state()
    assert len(state["release_history"]) == RELEASE_HISTORY_MAX
    assert all(
        "age_s" in e and "ts" not in e for e in state["release_history"]
    )
    clock[0] += 50.0
    q2 = SchedulingQueue(clock=lambda: clock[0])
    q2.restore_state(state)
    assert [e["uid"] for e in q2.release_history] == uids
    assert all(
        abs((b["ts"] - a["ts"]) - 50.0) < 1e-6
        for a, b in zip(q.release_history, q2.release_history)
    )
    # An over-long stored list (a snapshot from a future, larger bound)
    # trims to this process's window instead of growing unboundedly.
    state["release_history"] = [
        {"uid": f"x-{i}", "attempts": 0, "ts": 0.0}
        for i in range(RELEASE_HISTORY_MAX + 100)
    ]
    q3 = SchedulingQueue(clock=lambda: clock[0])
    q3.restore_state(state)
    assert len(q3.release_history) == RELEASE_HISTORY_MAX
    assert q3.release_history[-1]["uid"] == f"x-{RELEASE_HISTORY_MAX + 99}"


# -- group commit (ISSUE 15) ------------------------------------------------


def test_group_commit_one_fsync_per_group(tmp_path):
    """Appends inside ``journal.group()`` defer their fsync to ONE
    barrier at group exit; nested groups ride the outermost barrier."""
    j = Journal(str(tmp_path), epoch=1)
    f0 = j.fsyncs
    with j.group():
        j.append("bind", {"uid": "a", "node": "n1"})
        with j.group():  # nested: no inner barrier
            j.append("bind", {"uid": "b", "node": "n2"})
        j.append("bind", {"uid": "c", "node": "n1"})
        assert j.fsyncs == f0  # nothing durable yet
    assert j.fsyncs == f0 + 1
    assert j.group_commits == 1
    assert j.group_appends == 3
    assert j.last_group_size == 3 and j.max_group_size == 3
    # An empty group costs nothing.
    with j.group():
        pass
    assert j.fsyncs == f0 + 1 and j.group_commits == 1
    # Outside a group, appends fsync immediately as before.
    j.append("delete", {"uid": "a"})
    assert j.fsyncs == f0 + 2
    # Every record is on the log (the group deferred durability only).
    _snap, records, _stats = j.replay()
    assert [r["t"] for r in records] == ["bind", "bind", "bind", "delete"]


def test_group_commit_no_apply_before_group_fsync(tmp_path):
    """The commit drain's ordering contract: every staged bind's record
    is appended, then the group's SINGLE fsync barrier returns, and only
    then does any bind apply (finish_binding) — instrumented end to end
    through a real schedule_batch."""
    events = []
    sched = small_sched(enable_preemption=False)
    journal = Journal(str(tmp_path), epoch=1)

    orig_append = journal.append

    def rec_append(rtype, data):
        events.append(("append", rtype))
        return orig_append(rtype, data)

    journal.append = rec_append
    orig_commit = journal._group_commit

    def rec_commit():
        was_outermost = journal._group_depth == 1
        had_pending = bool(journal._group_buf)
        orig_commit()
        if was_outermost and had_pending:
            events.append(("group-fsync",))

    journal._group_commit = rec_commit
    sched.attach_journal(journal)
    orig_fb = sched.cache.finish_binding

    def rec_fb(uid):
        events.append(("apply", uid))
        orig_fb(uid)

    sched.cache.finish_binding = rec_fb
    for i in range(4):
        sched.add_node(node(f"gc-n{i}"))
    for i in range(6):
        sched.add_pod(pod(f"gc-p{i}"))
    out = sched.schedule_batch()
    assert sum(1 for o in out if o.node_name) == 6
    kinds = [e[0] for e in events]
    assert kinds == ["append"] * 6 + ["group-fsync"] + ["apply"] * 6, kinds
    # And the applies ran in stage order = the batch's outcome order.
    applied = [e[1] for e in events if e[0] == "apply"]
    assert applied == [o.pod.uid for o in out if o.node_name]


# -- the group is written once, all or nothing (ISSUE 26) -------------------


def wal_bytes(directory):
    with open(os.path.join(str(directory), Journal.WAL), "rb") as f:
        return f.read()


def test_group_one_write_one_fence_call(tmp_path):
    """A group costs one ``write`` on the log and two fence checks (at
    entry, before anything is buffered, and before the write), whatever
    its size: ``appends / writes`` reads the group size."""
    calls = []

    def fence():
        calls.append(1)
        return 1

    j = Journal(str(tmp_path), epoch=1, fence=fence)
    with j.group():
        for i in range(5):
            j.append("bind", {"uid": f"p{i}", "node": "n1"})
        assert j.writes == 0 and j.appends == 0  # buffered, no syscall
        assert wal_bytes(tmp_path) == b""
    assert (j.writes, j.appends, j.fence_checks, len(calls)) == (1, 5, 2, 2)
    stats = j.stats()
    assert stats["writes"] == 1 and stats["fence_checks"] == 2
    assert j.append_latency.n == 1  # one observation a write
    # A larger group costs the same.
    with j.group():
        for i in range(50):
            j.append("bind", {"uid": f"q{i}", "node": "n1"})
    assert (j.writes, j.appends, j.fence_checks) == (2, 55, 4)


def test_single_append_is_a_group_of_one(tmp_path, monkeypatch):
    """Outside ``group()`` an append goes through the same write path as
    a group of one: one fence check, one write, and the fsync has
    returned before ``append`` does."""
    import kubernetes_tpu.journal as journal_mod

    j = Journal(str(tmp_path), epoch=1, fence=lambda: 1)
    synced = []
    real_fsync = os.fsync

    def rec_fsync(fd):
        real_fsync(fd)
        synced.append(os.fstat(fd).st_size)

    monkeypatch.setattr(journal_mod.os, "fsync", rec_fsync)
    assert j.append("bind", {"uid": "a", "node": "n1"}) == 1
    assert synced == [len(wal_bytes(tmp_path))] and synced[0] > 0
    assert (j.writes, j.appends, j.fence_checks, j.fsyncs) == (1, 1, 1, 1)
    assert j.group_commits == 0 and j.group_appends == 0


def test_stale_epoch_at_group_entry_leaves_log_and_seq(tmp_path):
    """A deposed holder is stopped at the group's entry, before anything
    is buffered: the block never runs, the file and ``seq`` stay."""
    j1 = Journal(str(tmp_path), epoch=1)
    j1.append("bind", {"uid": "a", "node": "n1"})
    j2 = Journal(str(tmp_path), epoch=2)
    j2.append("bind", {"uid": "b", "node": "n2"})
    before, seq = wal_bytes(tmp_path), j1.seq
    ran = []
    with pytest.raises(StaleEpochError):
        with j1.group():
            ran.append(1)
            j1.append("bind", {"uid": "c", "node": "nX"})
    assert not ran and j1.fenced == 1
    assert wal_bytes(tmp_path) == before and j1.seq == seq
    assert j1.writes == 1 and j1._group_depth == 0


def test_stale_epoch_at_group_write_drops_the_group(tmp_path):
    """A successor that arrives while the group is being buffered is
    seen by the second fence check, before the group's first byte is
    written: nothing of the group reaches the file."""
    j1 = Journal(str(tmp_path), epoch=1)
    seq = j1.seq
    with pytest.raises(StaleEpochError):
        with j1.group():
            j1.append("bind", {"uid": "a", "node": "n1"})
            Journal(str(tmp_path), epoch=2).append(
                "bind", {"uid": "b", "node": "n2"}
            )
    assert j1.seq == seq and j1.writes == 0
    _, recs, _ = Journal(str(tmp_path), epoch=3).replay()
    assert [r["d"]["uid"] for r in recs] == ["b"]


def test_exception_inside_group_writes_nothing_and_rewinds_seq(tmp_path):
    """An exception out of the block leaves no partial group in the
    file; the seqs it took are given back, so the retry's records carry
    them and no seq appears twice."""
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    before = wal_bytes(tmp_path)
    with pytest.raises(TypeError):
        with j.group():
            j.append("bind", {"uid": "b", "node": "n1"})
            j.append("bind", {"uid": "c", "node": object()})  # no JSON form
    assert wal_bytes(tmp_path) == before
    assert (j.seq, j.appends, j.writes, j.fsyncs) == (1, 1, 1, 1)
    with j.group():
        j.append("bind", {"uid": "b", "node": "n1"})
        j.append("bind", {"uid": "c", "node": "n2"})
    _, recs, _ = j.replay()
    assert [(r["q"], r["d"]["uid"]) for r in recs] == [
        (1, "a"), (2, "b"), (3, "c"),
    ]


class HalfWriteThenRaise:
    """The log's file object, whose first ``write`` puts half of its
    bytes into the file and raises (a full disk, mid-group)."""

    def __init__(self, f):
        self._f = f
        self.raised = False

    def write(self, data):
        if self.raised:
            return self._f.write(data)
        self.raised = True
        self._f.write(data[: len(data) // 2])
        self._f.flush()
        raise OSError("no space left on device")

    def __getattr__(self, name):
        return getattr(self._f, name)


def test_failed_group_write_leaves_no_partial_group(tmp_path):
    """An OSError from the group's one write: the file is cut back to
    where the group began, ``seq`` rewinds, and the retry writes every
    record exactly once."""
    j = Journal(str(tmp_path), epoch=1)
    j.append("bind", {"uid": "a", "node": "n1"})
    before = wal_bytes(tmp_path)
    j._f = HalfWriteThenRaise(j._f)
    with pytest.raises(OSError):
        with j.group():
            for u in "bcd":
                j.append("bind", {"uid": u, "node": "n1"})
    assert wal_bytes(tmp_path) == before
    assert (j.seq, j.appends, j.writes) == (1, 1, 1)
    with j.group():
        for u in "bcd":
            j.append("bind", {"uid": u, "node": "n1"})
    j2 = Journal(str(tmp_path), epoch=2)
    assert j2.torn_bytes == 0
    _, recs, _ = j2.replay()
    assert [(r["q"], r["d"]["uid"]) for r in recs] == [
        (1, "a"), (2, "b"), (3, "c"), (4, "d"),
    ]


def test_group_bytes_identical_to_per_record_appends(tmp_path):
    """The record format did not move: a group's bytes are what the same
    appends made one at a time produce — same framing, same seq order."""
    datas = [
        {"uid": f"p{i}", "node": f"n{i % 3}", "pod": serialize.to_dict(pod(f"p{i}"))}
        for i in range(7)
    ]
    one = Journal(str(tmp_path / "one"), epoch=4)
    seqs_one = [one.append("bind", d) for d in datas]
    grouped = Journal(str(tmp_path / "grouped"), epoch=4)
    with grouped.group():
        seqs_grouped = [grouped.append("bind", d) for d in datas[:3]]
        with grouped.group():
            seqs_grouped += [grouped.append("bind", d) for d in datas[3:]]
    assert seqs_grouped == seqs_one == list(range(1, 8))
    assert wal_bytes(tmp_path / "grouped") == wal_bytes(tmp_path / "one")
    assert (one.writes, grouped.writes) == (7, 1)


# -- a record's pod: the generated dumper, byte for byte the reflective walk --

_CONFIGS = os.path.join(REPO, "perfbench", "configs")


def _config(config: str) -> dict:
    with open(os.path.join(_CONFIGS, f"{config}.json")) as f:
        return json.load(f)


_TEMPLATE_CASES = [
    f"{config}.{which}"
    for config in sorted(n[: -len(".json")] for n in os.listdir(_CONFIGS) if n.endswith(".json"))
    for which in ("template", "initial_template")
    if which in _config(config)["pod"]
]


def _template_pod(config: str, which: str):
    """The configuration's pod template filled as the benchmark fills it
    (``perfbench.objects.Pods``: name, namespace, cycles), parsed as the
    sidecar parses a hint."""
    from perfbench import objects

    pods = objects.Pods(_config(config), seed=3900000011, count=2, initial=1)
    return serialize.pod_from_json(pods.jsons[0 if which == "initial_template" else 1])


def _rich_pods():
    """Pods of the builders that reach every kind of field a record holds."""
    affinity = (
        make_pod("aff", "team-a").label("color", "blue").req({"cpu": "1", "memory": "1Gi"})
        .node_affinity_in("zone", ["z1", "z2"]).preferred_node_affinity_in("disk", ["ssd"], 3)
        .pod_affinity_in("color", ["blue"], "zone")
        .pod_anti_affinity_in("app", ["db"], "kubernetes.io/hostname")
        .preferred_pod_affinity_in("tier", ["web"], "zone", weight=5, anti=True)
        .ns_selector_pod_affinity_in("color", ["red"], "zone", "env", ["prod"])
        .obj()
    )
    spread = (
        make_pod("spread").label("color", "blue").req({"cpu": "100m"})
        .spread_constraint(5, "zone", t.DO_NOT_SCHEDULE, "color", ["blue"], min_domains=2,
                           match_label_keys=("pod-template-hash",))
        .spread_constraint(1, "kubernetes.io/hostname", t.SCHEDULE_ANYWAY, "color", ["blue"])
        .obj()
    )
    tolerations = (
        make_pod("tol").req({"cpu": "1"}).toleration("dedicated", t.TOLERATION_OP_EQUAL, "gpu", "NoSchedule")
        .toleration("node.kubernetes.io/unreachable", t.TOLERATION_OP_EXISTS, "", "NoExecute", seconds=30.0)
        .host_port(8080).container_image("busybox:1.36", "pause:3.9").init_req({"cpu": "2"})
        .overhead({"memory": "64Mi"}).node_selector({"pool": "a"}).scheduling_gate("gate")
        .obj()
    )
    volumes = (
        make_pod("vol", "ns-2").req({"cpu": "1"}).pvc_volume("claim-vol").device_volume("ebs-1", read_only=True)
        .resource_claim("gpu-claim").resource_claim("nic-claim").pod_group("gang-a").priority(7)
        .obj()
    )
    nominated = make_pod("nominated").req({"cpu": "1"}).priority(100).obj()
    nominated.status.nominated_node_name = "n-3"
    bound = make_pod("bound").req({"cpu": "1"}).node("n-1").start_time(12.5).obj()
    return {"affinity": affinity, "spread": spread, "tolerations": tolerations,
            "volumes": volumes, "nominated": nominated, "bound": bound}


def _bind_record(encode, p) -> bytes:
    """One bind record's payload as ``Journal.append`` encodes it, the
    pod's data made by ``encode``."""
    data = {"uid": p.uid, "node": "n-1", "pod": encode(p), "tie": 3}
    return json.dumps({"e": 1, "q": 1, "t": "bind", "d": data}, separators=(",", ":")).encode()


@pytest.mark.parametrize(
    "case",
    _TEMPLATE_CASES + sorted(_rich_pods()),
)
def test_bind_record_bytes_identical_to_reflective_encoding(case):
    """A write-ahead record's pod goes through ``serialize.pod_to_data``
    (the generated dumper); its bytes are what ``serialize.to_dict`` gave
    for the same pod, so recovery and every reader of the log see no
    change."""
    if "." in case:
        p = _template_pod(*case.split("."))
    else:
        p = _rich_pods()[case]
    assert _bind_record(serialize.pod_to_data, p) == _bind_record(serialize.to_dict, p)


def test_records_encoded_by_the_dumper_replay_to_the_same_pods(tmp_path):
    """A group of bind records written through ``Journal`` with the
    dumper's pods replays, through ``serialize.pod_from_data``, to pods
    equal to the ones written."""
    originals = list(_rich_pods().values())
    j = Journal(str(tmp_path), epoch=1)
    with j.group():
        for p in originals:
            j.append("bind", {"uid": p.uid, "node": "n-1", "pod": serialize.pod_to_data(p)})
    _, recs, _ = Journal(str(tmp_path), epoch=2).replay()
    back = [serialize.pod_from_data(r["d"]["pod"]) for r in recs]
    assert [r["d"]["uid"] for r in recs] == [p.uid for p in originals]
    assert back == originals


_CRASH_CHILD = """
import sys
from kubernetes_tpu.faults import KillSwitch
from kubernetes_tpu.journal import Journal
KillSwitch(sys.argv[2], int(sys.argv[3])).arm()
j = Journal(sys.argv[1], epoch=1)
with j.group():
    for i in range(5):
        j.append("bind", {"uid": "p%d" % i, "node": "n1", "pad": "x" * 64})
"""


@pytest.mark.faults
@pytest.mark.parametrize(
    "point,nth,whole,torn",
    [
        ("pre-append", 3, 2, False),
        ("torn-append", 3, 2, True),
        ("torn-group-tail", 3, 2, True),
        ("post-append", 3, 3, False),
        ("mid-group-fsync", 1, 5, False),
        ("post-group-fsync", 1, 5, False),
    ],
)
def test_crash_point_shapes_inside_one_group_write(tmp_path, point, nth, whole, torn):
    """Each crash point keeps its name, its per-record hit count and its
    on-disk shape though the group is written once: a SIGKILL at the
    Nth record leaves the N-1 before it whole and the Nth absent, torn
    (open-time repair truncates it) or whole."""
    rc = subprocess.run(
        [sys.executable, "-c", _CRASH_CHILD, str(tmp_path), point, str(nth)],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    ).returncode
    assert rc == -9, f"child survived the SIGKILL point (rc={rc})"
    j = Journal(str(tmp_path), epoch=2)
    assert (j.torn_bytes > 0) == torn
    _, recs, _ = j.replay()
    assert [r["d"]["uid"] for r in recs] == [f"p{i}" for i in range(whole)]
    assert [r["q"] for r in recs] == list(range(1, whole + 1))


@pytest.mark.faults
def test_mid_pipeline_sigkill_recovers_bit_identical():
    """One pipeline crash cell end to end through the real harness: a
    SIGKILL between the group's buffered appends and the fsync barrier
    (mid-group-fsync — records written, NONE applied) must recover to
    bindings bit-identical to an uninterrupted pipelined run.
    scripts/run_fault_matrix.py --pipeline-kill sweeps all six cells."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import tempfile

    from run_fault_matrix import _read_bindings, _spawn

    with tempfile.TemporaryDirectory() as td:
        base = os.path.join(td, "base")
        os.makedirs(base)
        assert _spawn("--pipeline-kill-child", base) == 0
        baseline = _read_bindings(base)
        assert baseline
        case = os.path.join(td, "case")
        os.makedirs(case)
        rc = _spawn("--pipeline-kill-child", case, kill="mid-group-fsync:1")
        assert rc == -9, f"child survived the SIGKILL point (rc={rc})"
        assert _spawn("--pipeline-recover-child", case) == 0
        assert _read_bindings(case) == baseline


# -- the crash matrix (fast subset; --kill sweeps the grid) -----------------


@pytest.mark.faults
def test_kill_matrix_fast_subset():
    """One SIGKILL case end to end through the real harness: torn-append
    (the nastiest window — half a record durable on disk) must recover
    to bit-identical bindings.  scripts/run_fault_matrix.py --kill runs
    all ten cells."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import tempfile

    from run_fault_matrix import _read_bindings, _spawn

    with tempfile.TemporaryDirectory() as td:
        base = os.path.join(td, "base")
        os.makedirs(base)
        assert _spawn("--kill-child", base) == 0
        baseline = _read_bindings(base)
        assert baseline
        case = os.path.join(td, "case")
        os.makedirs(case)
        rc = _spawn("--kill-child", case, kill="torn-append:1")
        assert rc == -9, f"child survived the SIGKILL point (rc={rc})"
        assert _spawn("--recover-child", case) == 0
        assert _read_bindings(case) == baseline


def test_recover_cli_reports_bindings(tmp_path):
    """The `recover` subcommand: offline triage of a journal directory."""
    jdir = str(tmp_path / "j")
    j = Journal(jdir, epoch=1)
    s1 = scenario_sched(journal=j)  # snapshot cadence: nodes checkpointed
    for i in range(8):
        s1.add_pod(pod(f"w1-{i}"))
    s1.schedule_all_pending()  # one full batch of binds → checkpointed
    assert j.snapshots >= 1
    want = bindings_of(s1)
    j.close()
    proc = subprocess.run(
        [
            sys.executable, "-m", "kubernetes_tpu", "recover",
            "--journal-dir", jdir, "--batch-size", "8",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout[proc.stdout.index("{"):])
    # The offline recovery can't re-seat pods whose nodes only the LIST
    # would deliver; here the journal carries everything.
    assert report["bindings"] == want
    assert report["recovery"]["snapshot"] is True
    assert report["journal"]["epoch"] >= 1  # the journal lease's tenure


# -- speculative decision-cache epoch (the PR 3 roadmap gap) ----------------


def test_spec_epoch_journaled_and_recovered(tmp_path):
    """The speculative frontend's epoch is journaled on every invalidation
    and restored by recovery: a restarted frontend resumes the monotonic
    sequence (subscribers hold epoch-stamped decisions — a cold start at 0
    would violate the Push stream's monotonic-epoch contract)."""
    from kubernetes_tpu.sidecar.speculate import SpeculativeFrontend

    j = Journal(str(tmp_path), epoch=1)
    s1 = small_sched()
    s1.add_node(node("n1"))
    s1.attach_journal(j)
    f1 = SpeculativeFrontend(s1)
    assert f1.epoch == 0
    # Miss with a hinted co-pod: the hint is speculated and cached.
    f1.add_hint(pod("extra"))
    out = f1._serve_one("default/p1", lambda: pod("p1"))
    assert out.node_name == "n1"
    assert f1.cached, "the hinted pod should hold a cached decision"
    f1.invalidate()  # full rollback → epoch 1, write-ahead spec_epoch record
    f1.invalidate({"default/never-cached"})  # scoped no-op: no bump
    assert f1.epoch == 1
    j.close()

    # An in-process frontend swap (no crash) must also resume, not reset:
    # subscribers hold epoch-stamped decisions from the old frontend.
    f1b = SpeculativeFrontend(s1)
    assert f1b.epoch == 1, "re-created frontend must not re-emit epoch 0"

    # Records-only recovery (no snapshot covered the epoch record).
    j2 = Journal(str(tmp_path), epoch=2)
    s2 = small_sched()
    recover(s2, j2)
    f2 = SpeculativeFrontend(s2)
    assert f2.epoch == 1, "recovered frontend must resume the epoch"

    # Snapshot path: checkpoint with the live frontend attached, truncate
    # the log, recover again — the epoch rides the snapshot document.
    s2.add_node(node("n1"))
    s2.attach_journal(j2)
    f2.add_hint(pod("extra2"))
    f2._serve_one("default/p2", lambda: pod("p2"))
    f2.invalidate()  # epoch 2, journaled
    j2.snapshot(scheduler_state(s2))
    j2.close()
    j3 = Journal(str(tmp_path), epoch=3)
    s3 = small_sched()
    recover(s3, j3)
    assert s3._recovered_spec_epoch == 2
    f3 = SpeculativeFrontend(s3)
    assert f3.epoch == 2
    j3.close()
