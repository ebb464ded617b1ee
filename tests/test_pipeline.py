"""The pipelined serving hot path (ISSUE 15): depth-2 overlapped
featurize/device/commit must bind BIT-IDENTICAL to the depth-1 serial
loop (the parity oracle) on both golden sessions and on multi-batch
workloads where the predispatch double buffer genuinely engages; the
commit drain's group fsync must precede every staged apply; and a host
mutation between predispatch and pickup must invalidate the early pass
instead of completing it against stale truth."""

import os
import sys
import tempfile

import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.journal import Journal
from kubernetes_tpu.scheduler import TPUScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
from gen_golden_transcripts import (  # noqa: E402
    scenario_objects,
    session_schedulers,
    wait_for_backoffs,
)
from test_journal import HalfWriteThenRaise, wal_bytes  # noqa: E402

# The two recorded golden sessions (basic = fit-only, default = the full
# default plugin profile) — the same factories the wire-transcript
# replay pins, so the parity claim covers both configurations.
GOLDEN_STEMS = ("basic_session", "default_session")


def bindings_of(sched) -> dict:
    return {
        uid: pr.node_name
        for uid, pr in sched.cache.pods.items()
        if pr.bound
    }


def run_golden_session(stem: str, depth: int, journal_dir: str):
    """The golden scenario end to end (schedule, a delete that triggers
    requeue, the post-backoff drain) at the given pipeline depth, with
    the write-ahead journal armed so the drain exercises group commit."""
    sched = session_schedulers()[stem]()
    sched.pipeline_depth = depth
    sched.attach_journal(Journal(journal_dir, epoch=1))
    # A checkpoint behind every batch that journalled anything (a cadence
    # of one record: the session's batches are short), so the snapshot
    # falls between pipelined batches too.
    sched.snapshot_every_records = 1
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        sched.add_node(n)
    for p in bound:
        sched.add_pod(p)
    for p in pending:
        sched.add_pod(p)
    sched.schedule_all_pending(wait_backoff=True)
    sched.delete_pod("default/bound-2")
    wait_for_backoffs(sched.queue)
    sched.schedule_all_pending(wait_backoff=True)
    return bindings_of(sched), sched


@pytest.mark.parametrize("stem", GOLDEN_STEMS)
def test_pipelined_binds_bit_identical_on_golden_sessions(stem):
    """Depth 2 (overlapped drain + predispatch) must reproduce the
    depth-1 serial loop's bindings byte for byte on both golden
    sessions — including the preemption + requeue tail."""
    with tempfile.TemporaryDirectory() as td1, \
            tempfile.TemporaryDirectory() as td2:
        serial, _s1 = run_golden_session(stem, 1, td1)
        piped, s2 = run_golden_session(stem, 2, td2)
    assert serial, "golden scenario bound nothing"
    assert piped == serial, {
        k: (serial.get(k), piped.get(k))
        for k in set(serial) | set(piped)
        if serial.get(k) != piped.get(k)
    }
    # Group commit actually ran: the drain journals each batch's binds
    # under one barrier instead of one fsync per record.
    assert s2.journal.group_commits >= 1
    assert s2.journal.group_appends >= len(
        [v for v in piped.values() if v]
    ) - len(scenario_objects()[1])


def _grid(depth: int, n_nodes=24, n_pods=96, batch=16):
    """A multi-batch workload (6 batches) with score spread and affinity
    labels, so the predispatch double buffer and the overlapped drain
    engage for real."""
    s = TPUScheduler(batch_size=batch, chunk_size=4, pipeline_depth=depth)
    for i in range(n_nodes):
        s.add_node(
            make_node(f"n{i:03d}")
            .capacity(
                {"cpu": "8" if i % 3 else "16", "memory": "16Gi", "pods": 64}
            )
            .zone(f"z{i % 4}")
            .obj()
        )
    for i in range(n_pods):
        s.add_pod(
            make_pod(f"p{i:03d}")
            .req({"cpu": "500m", "memory": "1Gi"})
            .label("app", f"a{i % 5}")
            .obj()
        )
    out = s.schedule_all_pending()
    return {o.pod.name: o.node_name for o in out}, s


def test_pipeline_multibatch_parity_and_engagement():
    serial, _ = _grid(1)
    piped, s2 = _grid(2)
    assert piped == serial
    assert sum(1 for v in piped.values() if v) == 96
    # The double buffer genuinely ran: most batches were predispatched
    # and their drains overlapped the next in-flight pass.
    hits = s2._pipeline_predispatch_counter.get(result="hit")
    assert hits >= 3, f"predispatch never engaged (hits={hits})"
    assert s2._pipeline_drain_counter.get(kind="overlapped") >= 3
    # No cross-call state leaked out of the last batch.
    assert s2._pending_ticket is None or s2._pending_ticket.drained
    assert s2._predispatched is None


def test_predispatch_invalidated_by_host_mutation():
    """A host mutation landing between predispatch and pickup must
    discard the early pass (mutation epoch moved) and re-dispatch
    against current truth — decisions equal to a serial run that saw
    the same interleaving."""
    def build(depth):
        s = TPUScheduler(batch_size=8, chunk_size=1, pipeline_depth=depth,
                         enable_preemption=False)
        for i in range(8):
            s.add_node(
                make_node(f"m{i}")
                .capacity({"cpu": "4", "memory": "8Gi", "pods": 16})
                .zone(f"z{i % 2}")
                .obj()
            )
        for i in range(24):
            s.add_pod(make_pod(f"q{i:02d}").req({"cpu": "500m"}).obj())
        return s

    def drive(s):
        outs = []
        batch_i = 0
        while True:
            out = s.schedule_batch()
            if not out and not len(s.queue) and not s.has_inflight_work:
                break
            outs.extend(out)
            if batch_i == 0:
                # Mutation between calls: a fresh node — featurization
                # and the predispatched pass (if any) both predate it.
                s.add_node(
                    make_node("late-node")
                    .capacity({"cpu": "64", "memory": "64Gi", "pods": 64})
                    .zone("z0")
                    .obj()
                )
            batch_i += 1
        return {o.pod.name: o.node_name for o in outs}

    serial = drive(build(1))
    s2 = build(2)
    piped = drive(s2)
    assert piped == serial
    # The mutation invalidated at least one predispatched pass.
    assert s2._pipeline_predispatch_counter.get(result="invalidated") >= 1


def test_delete_dissolves_predispatched_batch():
    """Deleting a pod held in a PREDISPATCHED batch must discard the
    early pass (an unbound pod's deletion moves no validity token) and
    requeue the surviving members — the dead pod never binds."""
    s = TPUScheduler(batch_size=8, chunk_size=1, pipeline_depth=2,
                     enable_preemption=False)
    for i in range(8):
        s.add_node(
            make_node(f"d{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": 32})
            .zone(f"z{i % 2}")
            .obj()
        )
    for i in range(24):
        s.add_pod(make_pod(f"del{i:02d}").req({"cpu": "250m"}).obj())
    out1 = s.schedule_batch()  # batch 1 completes; batch 2 predispatched
    assert s._predispatched is not None
    victim = s._predispatched.infos[0].pod.uid
    s.delete_pod(victim)
    assert s._predispatched is None, "predispatch survived the delete"
    rest = s.schedule_all_pending()
    bound = {o.pod.uid for o in list(out1) + rest if o.node_name}
    assert victim not in bound
    assert len(bound) == 23
    assert victim not in s.cache.pods


def test_pipeline_overlap_recorded_in_flight():
    """Depth-2 batch records carry the overlap block (stage serial sum,
    wall saved, coverage) and the drain/predispatch stage segments."""
    _, s = _grid(2)
    batches = [
        r for r in s.flight.records() if r.get("kind") == "batch"
    ]
    assert batches
    assert all("overlap" in r for r in batches)
    phases = set()
    for r in batches:
        phases |= set(r.get("phases", {}))
    assert "drain" in phases
    assert "predispatch" in phases
    # Serial stage sums are recorded; saved_s is clamped non-negative.
    for r in batches:
        ov = r["overlap"]
        assert ov["serial_s"] >= 0 and ov["saved_s"] >= 0
        assert 0.0 <= ov["coverage"] <= 1.0


def test_depth1_records_no_overlap_block():
    _, s = _grid(1)
    batches = [r for r in s.flight.records() if r.get("kind") == "batch"]
    assert batches
    assert all("overlap" not in r for r in batches)


def test_mid_drain_exception_resumes_without_losing_or_duplicating():
    """An in-process exception mid-drain (a transient append failure)
    must leave the ticket resumable: the recovery drain journals only
    the un-journaled suffix and applies every staged bind — nothing
    lost (a bind reported without its record), nothing double-journaled
    (the durable prefix appended twice)."""
    with tempfile.TemporaryDirectory() as td:
        journal = Journal(td, epoch=1)
        s = TPUScheduler(batch_size=8, chunk_size=1, pipeline_depth=1,
                         enable_preemption=False)
        s.attach_journal(journal, snapshot_every_batches=100)
        for i in range(4):
            s.add_node(
                make_node(f"r{i}")
                .capacity({"cpu": "8", "memory": "16Gi", "pods": 32})
                .obj()
            )
        for i in range(8):
            s.add_pod(make_pod(f"rp{i}").req({"cpu": "500m"}).obj())
        real_append = journal.append
        state = {"calls": 0}

        def poisoned(kind, payload):
            if kind == "bind":
                state["calls"] += 1
                if state["calls"] == 3:
                    raise OSError("transient append failure")
            return real_append(kind, payload)

        journal.append = poisoned
        out = s.schedule_all_pending()
        journal.append = real_append
        # Recovery (engine-fault path) resumed the drain: every pod is
        # applied-bound, not just cache-assumed.
        bound = [o for o in out if o.node_name]
        assert len(bound) == 8
        for o in bound:
            assert o.pod.spec.node_name == o.node_name
        assert s._pending_ticket is None
        # The log holds exactly one bind record per pod — the durable
        # prefix was not re-journaled by the resumed drain.
        _snap, records, _ = Journal(td, epoch=2).replay()
        uids = [r["d"]["uid"] for r in records if r["t"] == "bind"]
        assert sorted(uids) == sorted(o.pod.uid for o in bound)


def test_failed_group_fsync_retries_barrier_before_apply(monkeypatch):
    """When every append succeeded but the group's OWN fsync raised, the
    resumed drain must re-run the durability barrier — not skip it (the
    group has zero pending appends on re-entry) and acknowledge binds
    that were never made durable."""
    import kubernetes_tpu.journal as journal_mod

    with tempfile.TemporaryDirectory() as td:
        journal = Journal(td, epoch=1)
        s = TPUScheduler(batch_size=8, chunk_size=1, pipeline_depth=1,
                         enable_preemption=False)
        s.attach_journal(journal, snapshot_every_batches=100)
        for i in range(4):
            s.add_node(
                make_node(f"b{i}")
                .capacity({"cpu": "8", "memory": "16Gi", "pods": 32})
                .obj()
            )
        for i in range(8):
            s.add_pod(make_pod(f"bp{i}").req({"cpu": "500m"}).obj())
        real_fsync = journal_mod.os.fsync
        state = {"fail_next": True}

        def flaky_fsync(fd):
            if state["fail_next"]:
                state["fail_next"] = False
                raise OSError("barrier fsync failed")
            return real_fsync(fd)

        monkeypatch.setattr(journal_mod.os, "fsync", flaky_fsync)
        out = s.schedule_all_pending()
        bound = [o for o in out if o.node_name]
        assert len(bound) == 8
        # The barrier genuinely re-ran: the group fsynced despite the
        # first attempt failing, and no bind was acknowledged without it.
        assert journal.fsyncs >= 1
        assert journal.group_commits >= 1
        for o in bound:
            assert o.pod.spec.node_name == o.node_name


# -- the drain's group is all-or-nothing in the file (ISSUE 26) -------------


def _journaled_sched(td, pods=8, **journal_kw):
    journal = Journal(td, epoch=1, **journal_kw)
    s = TPUScheduler(batch_size=8, chunk_size=1, pipeline_depth=1,
                     enable_preemption=False)
    s.attach_journal(journal, snapshot_every_batches=100)
    for i in range(4):
        s.add_node(
            make_node(f"g{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": 32})
            .obj()
        )
    for i in range(pods):
        s.add_pod(make_pod(f"gp{i}").req({"cpu": "500m"}).obj())
    return s, journal


@pytest.mark.parametrize("fault", ["pod_to_data", "write"])
def test_failed_group_leaves_no_partial_group_and_resumes_once(fault, monkeypatch):
    """An exception inside the group's block (a pod that will not
    serialise) or an OSError from the group's one write leaves NO record
    of the group in the file and the ticket counting none; the resumed
    drain journals each record exactly once and applies only after the
    group's fsync."""
    from kubernetes_tpu.api import serialize
    from kubernetes_tpu.engine import pipeline

    with tempfile.TemporaryDirectory() as td:
        s, journal = _journaled_sched(td)
        events = []
        seen = {}
        if fault == "pod_to_data":
            real_pod_to_data = serialize.pod_to_data
            state = {"calls": 0}

            def poisoned(pod):
                state["calls"] += 1
                if state["calls"] == 3:
                    raise ValueError("pod will not serialise")
                return real_pod_to_data(pod)

            monkeypatch.setattr(serialize, "pod_to_data", poisoned)
        else:
            journal._f = HalfWriteThenRaise(journal._f)
        real_drain = pipeline.drain_commit

        def rec_drain(sched, ticket):
            try:
                return real_drain(sched, ticket)
            except (ValueError, OSError):
                # What the failed attempt left behind, before the resume.
                seen.update(
                    wal=wal_bytes(td), seq=journal.seq, journaled=ticket.journaled,
                    barriered=ticket.barriered, applied=ticket.applied,
                )
                raise

        monkeypatch.setattr(pipeline, "drain_commit", rec_drain)
        real_barrier = journal._barrier_fsync
        journal._barrier_fsync = lambda: (real_barrier(), events.append("fsync"))
        real_fb = s.cache.finish_binding
        s.cache.finish_binding = lambda uid: (events.append("apply"), real_fb(uid))
        out = s.schedule_all_pending()
        bound = [o for o in out if o.node_name]
        assert len(bound) == 8
        # The failed attempt: nothing in the file, nothing counted, nothing live.
        assert seen == {
            "wal": b"", "seq": 0, "journaled": 0, "barriered": False, "applied": 0,
        }
        # The resume: one write of the whole group, fsync, then the applies.
        assert events == ["fsync"] + ["apply"] * 8
        assert journal.writes == 1 and journal.appends == 8
        _snap, records, _ = Journal(td, epoch=2).replay()
        assert [r["q"] for r in records] == list(range(1, 9))
        assert sorted(r["d"]["uid"] for r in records) == sorted(
            o.pod.uid for o in bound
        )


def test_stale_epoch_at_drain_entry_leaves_ticket_and_log():
    """A deposed holder's drain raises StaleEpochError at the group's
    entry: the file, ``seq`` and ``ticket.journaled`` stay as they were
    and no bind is applied."""
    from kubernetes_tpu.journal import StaleEpochError

    with tempfile.TemporaryDirectory() as td:
        s, journal = _journaled_sched(td)
        Journal(td, epoch=2).append("bind", {"uid": "x", "node": "g0"})
        before, seq = wal_bytes(td), journal.seq
        with pytest.raises(StaleEpochError):
            s.schedule_all_pending()
        ticket = s._pending_ticket
        assert ticket is not None and len(ticket) == 8
        assert (ticket.journaled, ticket.barriered, ticket.applied) == (0, False, 0)
        assert wal_bytes(td) == before and journal.seq == seq
        assert journal.writes == 0 and journal.fenced >= 1
        assert not bindings_of(s)


def test_drain_counts_one_write_and_two_fence_checks_a_group():
    """The mechanism engages on the served path: one batch's drain is
    one write on the log whatever its pods, so ``appends / writes``
    reads the group size, and the counters are exported."""
    with tempfile.TemporaryDirectory() as td:
        s, journal = _journaled_sched(td, fence=lambda: 1)
        out = s.schedule_all_pending()
        assert sum(1 for o in out if o.node_name) == 8
        assert (journal.appends, journal.writes, journal.fence_checks) == (8, 1, 2)
        assert journal.fsyncs == 1 and journal.group_commits == 1
        text = s.metrics.registry.render_text()
        assert "scheduler_journal_writes_total 1" in text
        assert "scheduler_journal_fence_checks_total 2" in text
        rec = [r for r in s.flight.records() if r.get("journal")][-1]
        assert rec["journal"]["appends"] == 8 and rec["journal"]["fsyncs"] == 1
        assert rec["journal"]["writes"] == 1 and rec["journal"]["fence_checks"] == 2
