"""The span primitive of the served path (framework/tracing.SpanSink): one
interval feeds the flight record's ``spans`` and ``phases``, the phase
histogram, the ScheduleBatch step log and the profiler's trace; the
queue-wait, compile and collector counters beside it; and the named
scopes of the device pass."""

import gc
import glob
import json
import os
import re
import tempfile
import threading
import weakref
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.framework.config import DEFAULT_PROFILE, fit_only_profile
from kubernetes_tpu.framework.metrics import MetricsRegistry
from kubernetes_tpu.framework.tracing import NULL_SINK, PROCESS, SpanSink, Trace
from kubernetes_tpu.journal import Journal
from kubernetes_tpu.ops.common import registered_subset
from kubernetes_tpu.scheduler import TPUScheduler
from kubernetes_tpu.sidecar import SidecarClient, SidecarServer

ZONE = "topology.kubernetes.io/zone"

# The names PERF.md documents, by when a batch emits them.
EVERY_BATCH = (  # once each; batch/pop also pops the batch to prefetch
    "batch/featurize", "pass/dispatch", "pass/fetch_wait",
    "pass/fetch_copy", "commit/stage", "commit/failed", "pipeline/drain",
    "drain/journal_append", "drain/journal_fsync", "drain/apply",
)
SERVED = (
    "wire/lock_wait", "wire/dispatch", "wire/write", "hints/decode",
    "hints/admit", "spec/publish", "spec/cache_outcomes",
    "spec/push_decisions",
)
PIPELINED = ("batch/prefetch", "pipeline/predispatch")
CHECKPOINT = (  # behind the cadence gate
    "pipeline/snapshot", "snapshot/collect", "snapshot/encode", "snapshot/write",
)


def _node(name, cpu="8"):
    return make_node(name).capacity({"cpu": cpu, "memory": "16Gi", "pods": 110}).obj()


def _pod(name, cpu="100m"):
    return make_pod(name).req({"cpu": cpu, "memory": "64Mi"}).obj()


def _sched(tmp_path=None, **kw):
    kw.setdefault("profile", fit_only_profile())
    kw.setdefault("batch_size", 8)
    s = TPUScheduler(**kw)
    if tmp_path is not None:
        s.attach_journal(Journal(str(tmp_path), epoch=1), snapshot_every_batches=1)
    return s


def _hist():
    return MetricsRegistry().histogram("t_phase_seconds", "test")


def _by_name(rec):
    out = {}
    for sp in rec["spans"]:
        out.setdefault(sp[0], []).append(sp)
    return out


def _us(rec, *names):
    return sum(sp[2] for sp in rec["spans"] if sp[0] in names)


# -- the primitive alone ------------------------------------------------------


def test_nesting_parent_start_and_duration():
    hist = _hist()
    sink = SpanSink(hist)
    acc = {"phases": {}}
    sink.open(acc)
    with sink.span("a", phase="commit") as a:
        with sink.span("a/b") as b:
            with sink.span("a/b/c"):
                pass
        with sink.span("a/d", phase="commit") as d:
            d.set("sub_us", 7)
    with sink.span("e"):
        pass
    wall = sink.close()
    spans = acc["spans"]
    assert [sp[0] for sp in spans] == ["a", "a/b", "a/b/c", "a/d", "e"]  # start order
    assert [sp[3] for sp in spans] == [-1, 0, 1, 0, -1]  # parent index
    assert spans[3][4] == {"sub_us": 7} and len(spans[0]) == 4
    # starts are relative to the batch's start and children lie inside
    assert 0 <= spans[0][1] <= spans[1][1] <= spans[2][1]
    assert spans[1][1] + spans[1][2] <= spans[0][1] + spans[0][2] + 1
    assert abs(spans[0][2] - a.dur_s * 1e6) <= 1 and a.dur_s >= b.dur_s > 0
    assert spans[4][1] + spans[4][2] <= wall * 1e6 + 1
    # (b) a span that is one of today's phases adds its seconds to it
    assert acc["phases"] == {"commit": pytest.approx(a.dur_s + d.dur_s)}
    assert acc["t0_ns"] > 0 and acc["bid"] == sink.bid == 1
    # (c) a span observed the histogram under its own name, but one that
    # feeds a phase leaves that to the phase's batch sum: one interval,
    # one label
    assert hist.cell(phase="a/b").n == 1 and hist.sum(phase="a/b") == pytest.approx(b.dur_s)
    assert hist.cell(phase="a") is None and hist.cell(phase="a/d") is None


def test_span_outside_a_batch_touches_no_record():
    hist = _hist()
    sink = SpanSink(hist)
    with sink.span("wire/dispatch", kind="add"):
        pass
    with sink.span("wire/write", label=""):  # ends outside the lock: annotation only
        pass
    with sink.span("hints/decode", label="hint_decode", kind="parse"):
        pass
    assert sink._rec is None and hist.cell(phase="wire/dispatch").n == 1
    assert hist.cell(phase="wire/write") is None
    # hint_decode keeps the label it has: the accepted reader reads it
    assert hist.cell(phase="hint_decode").n == 1 and hist.cell(phase="hints/decode") is None
    # and after a batch has closed
    acc = {"phases": {}}
    sink.open(acc)
    sink.close()
    with sink.span("spec/publish"):
        pass
    assert acc["spans"] == []
    with NULL_SINK.span("snapshot/encode"):  # a bare journal: the annotation only
        pass


def test_span_on_another_thread_touches_no_record():
    sink = SpanSink(_hist())
    acc = {"phases": {}}
    sink.open(acc)

    def other():
        with sink.span("wire/lock_wait"):
            pass

    th = threading.Thread(target=other)
    th.start()
    th.join()
    with sink.span("batch/pop"):
        pass
    sink.close()
    assert [sp[0] for sp in acc["spans"]] == ["batch/pop"]


def test_spans_step_the_batch_trace_with_their_own_clock_reading():
    sink = SpanSink(_hist())
    sink.open({"phases": {}})
    tr = sink.trace = Trace("ScheduleBatch", 0.0)
    with sink.span("pass/dispatch") as sp:
        pass
    sink.close()
    assert tr._steps == [("pass/dispatch", sp.t1)] and sink.trace is None


def test_an_exception_leaves_no_hole_and_keeps_indexes():
    sink = SpanSink(_hist())
    acc = {"phases": {}}
    sink.open(acc)
    outer = sink.span("a")
    outer.__enter__()  # never exited: the batch closes under it
    with pytest.raises(ValueError):
        with sink.span("a/b"):
            raise ValueError("x")
    sink.close()
    assert acc["spans"][0] == ("?", 0, -1, -1) and acc["spans"][1][0] == "a/b"
    assert acc["spans"][1][3] == 0


# -- the scheduler's records --------------------------------------------------


def test_depth1_phases_are_the_spans_that_feed_them_and_tile_the_wall(tmp_path):
    s = _sched(tmp_path)
    for i in range(3):
        s.add_node(_node(f"n{i}"))
    for i in range(8):  # a full batch of records: the checkpoint falls due
        s.add_pod(_pod(f"p{i}"))
    s.trace_threshold_s = 0.0
    assert sum(1 for o in s.schedule_batch() if o.node_name) == 8
    (rec,) = s.flight.records()
    names = _by_name(rec)
    for name in EVERY_BATCH + CHECKPOINT:
        assert len(names[name]) == 1, name
    assert 1 <= len(names["batch/pop"]) <= 2
    ph = rec["phases"]
    us = 1e-6
    assert ph["commit"] == pytest.approx(_us(rec, "commit/stage", "commit/failed") * us, abs=3e-6)
    assert ph["drain"] == pytest.approx(_us(rec, "pipeline/drain") * us, abs=3e-6)
    assert ph["snapshot"] == pytest.approx(_us(rec, "pipeline/snapshot") * us, abs=3e-6)
    assert ph["featurize"] == pytest.approx(_us(rec, "batch/featurize") * us, abs=3e-6)
    # `device` keeps its meaning, dispatch to fetched: the pass in flight
    # from pass/dispatch's start to commit/stage's, less the packer
    (inflight,) = names["pass/inflight"]
    assert ph["device"] == pytest.approx(inflight[2] * us, abs=3e-6)
    assert inflight[1] == names["pass/dispatch"][0][1]
    assert inflight[1] + inflight[2] == pytest.approx(names["commit/stage"][0][1], abs=2)
    assert abs(sum(ph.values()) - rec["wall_s"]) < 5e-3  # the depth-1 tiling
    # the drain's children tile it, and the journal's fsync is the span
    inside = _us(rec, "drain/journal_append", "drain/journal_fsync", "drain/apply")
    assert 0.5 * _us(rec, "pipeline/drain") < inside <= _us(rec, "pipeline/drain")
    assert rec["journal"]["fsync_s"] == pytest.approx(
        _us(rec, "drain/journal_fsync") * us, abs=3e-6)
    (append,) = names["drain/journal_append"]
    assert 0 < append[4]["serialize_us"] <= append[2]
    parent = names["drain/apply"][0][3]
    assert rec["spans"][parent][0] == "pipeline/drain"
    # every key a record had stays, beside the new ones
    for key in ("pods", "scheduled", "unschedulable", "deferred", "dispatch", "wall_s",
                "phases", "journal", "drained", "group_fsyncs", "trace_id", "span_id",
                "kind", "seq", "ts", "spans", "t0_ns", "bid", "queue_wait"):
        assert key in rec, key
    assert abs(rec["t0_ns"] * 1e-9 + rec["wall_s"] - rec["ts"]) < 0.05
    # the step log of the batch's Trace came from the same spans
    steps = [m for m, _ in s.last_batch_span._steps]
    assert "pass/dispatch" in steps and "drain/apply" in steps
    # a histogram cell a span name, and the batch sums under today's labels
    text = s.metrics.registry.render_text()
    assert 'scheduler_phase_duration_seconds_count{phase="drain/apply"} 1' in text
    assert 'scheduler_phase_duration_seconds_count{phase="drain"} 1' in text
    # no interval under two labels
    for twice in ("pipeline/drain", "pipeline/snapshot", "batch/featurize",
                  "batch/pack", "commit/stage", "drain/journal_fsync"):
        assert f'phase="{twice}"' not in text, twice
    assert 'scheduler_phase_duration_seconds_count{phase="snapshot"} 1' in text


def test_depth2_a_predispatched_pass_starts_before_its_record(tmp_path):
    s = _sched(tmp_path, pipeline_depth=2)
    for i in range(4):
        s.add_node(_node(f"n{i}", cpu="64"))
    for i in range(30):
        s.add_pod(_pod(f"p{i}"))
    s.schedule_all_pending()
    recs = [r for r in s.flight.records() if r["kind"] == "batch"]
    assert len(recs) >= 3
    first, second = recs[0], recs[1]
    names = _by_name(first)
    for name in PIPELINED:
        assert len(names[name]) == 1, name
    assert first["phases"]["predispatch"] == pytest.approx(
        _us(first, "pipeline/predispatch") * 1e-6, abs=3e-6)
    # batch k+1's pass was dispatched inside batch k's pipeline/predispatch
    nested = [sp for sp in first["spans"] if sp[0] == "pass/dispatch" and sp[3] >= 0]
    assert nested and first["spans"][nested[0][3]][0] == "pipeline/predispatch"
    (inflight,) = _by_name(second)["pass/inflight"]
    assert inflight[1] < 0  # before the record's own start
    assert "pass/dispatch" not in {sp[0] for sp in second["spans"] if sp[3] < 0}
    assert second["phases"]["device"] == pytest.approx(inflight[2] * 1e-6, abs=3e-6)
    assert "overlap" in second and second["overlap"]["saved_s"] > 0
    assert [r["bid"] for r in recs] == sorted(r["bid"] for r in recs)


def test_packer_and_strict_tail_have_spans():
    colors = [0, 0, 0] + list(range(1, 14))
    s = TPUScheduler(profile=registered_subset(DEFAULT_PROFILE), batch_size=16,
                     chunk_size=8, enable_preemption=False)
    for i in range(24):
        s.add_node(make_node(f"n{i}").capacity({"cpu": "4", "memory": "16Gi", "pods": 8})
                   .zone(f"z{i % 4}").obj())
    for i, color in enumerate(colors):
        s.add_pod(make_pod(f"p{i}").req({"cpu": "100m"}).label("color", f"c{color}")
                  .pod_anti_affinity_in("color", [f"c{color}"], ZONE).obj())
    s.schedule_all_pending()
    assert s.metrics.deferred >= 1
    rec = s.flight.records()[0]
    names = _by_name(rec)
    (pack,) = names["batch/pack"]
    assert rec["spans"][pack[3]][0] == "pass/dispatch"
    assert rec["phases"]["packing"] == pytest.approx(pack[2] * 1e-6, abs=3e-6)
    (inflight,) = names["pass/inflight"]
    assert rec["phases"]["device"] == pytest.approx((inflight[2] - pack[2]) * 1e-6, abs=3e-6)
    (tail,) = names["pass/tail"]
    assert tail[1] >= names["pass/fetch_copy"][0][1] and tail[2] > 0


def test_queue_wait_counts_from_when_the_server_first_held_the_pod():
    now = [100.0]
    s = _sched()
    s.queue._clock = lambda: now[0]
    for i in range(3):
        s.add_node(_node(f"n{i}"))
    # two of them came as hints the server held 0.5 s before the queue did
    for i in range(4):
        s.add_pod(_pod(f"p{i}"), held_at=99.5 if i < 2 else 0.0)
    now[0] = 100.25
    s.schedule_batch()
    (rec,) = s.flight.records()
    assert rec["queue_wait"] == {"pods": 4, "sum_ms": pytest.approx(2000.0), "max_ms": pytest.approx(750.0)}


def test_hinted_pods_wait_from_their_frames_arrival():
    path = tempfile.mktemp(suffix=".sock")
    now = [10.0]
    sched = _sched()
    sched.queue._clock = lambda: now[0]
    srv = SidecarServer(path, scheduler=sched, speculate=True)
    srv.serve_background()
    client = SidecarClient(path)
    try:
        for i in range(3):
            client.add("Node", _node(f"n{i}"))
        pods = [_pod(f"p{i}") for i in range(4)]
        client.add_pending_batch(pods)  # held from here
        now[0] = 12.0
        (r,) = client.schedule([pods[0]], drain=False)
        assert r.node_name
        (rec,) = [x for x in sched.flight.records() if x["kind"] == "batch"]
        # all four were in the frame, the requested one too: held 2 s each
        assert rec["queue_wait"]["pods"] == 4
        assert rec["queue_wait"]["max_ms"] == pytest.approx(2000.0)
        assert rec["queue_wait"]["sum_ms"] == pytest.approx(8000.0)
        assert not srv.frontend.hints and not srv.frontend.raw_blobs
    finally:
        client.close()
        srv.close()


def test_a_hint_carries_its_arrival_in_its_own_entry():
    from kubernetes_tpu.api import serialize
    from kubernetes_tpu.sidecar.speculate import SpeculativeFrontend

    now = [5.0]
    s = _sched()
    s.queue._clock = lambda: now[0]
    f = SpeculativeFrontend(s)
    s.add_node(_node("n0"))
    pods = [_pod(f"p{i}") for i in range(3)]
    f.add_hint_blob(json.dumps([serialize.to_dict(p) for p in pods]).encode())
    now[0] = 6.0
    f._parse_blobs()
    f._build_hints(8)  # dict -> Pod keeps the frame's arrival
    assert {held for _, held in f.hints.values()} == {5.0}
    # a hint dropped before admission leaves nothing behind: the pool entry
    # is the only place its arrival lives
    f.note_remove("Pod", pods[2].uid)
    assert set(f.hints) == {pods[0].uid, pods[1].uid}
    now[0] = 7.5
    f._admit_hints(8)
    assert not f.hints and not f.raw_blobs and f._blob_cursor is None
    assert {qp.held_at for qp in s.queue._info.values()} == {5.0}
    s.schedule_batch()
    (rec,) = s.flight.records()
    assert rec["queue_wait"] == {"pods": 2, "sum_ms": pytest.approx(5000.0), "max_ms": pytest.approx(2500.0)}


def test_a_prefetched_batchs_wait_is_on_the_record_of_the_call_that_popped_it():
    now = [0.0]
    s = _sched(batch_size=4)
    s.queue._clock = lambda: now[0]
    s.add_node(_node("n0"))
    for i in range(6):
        s.add_pod(_pod(f"p{i}"))
    now[0] = 1.0
    s.schedule_batch()  # pops 4, and the 2 it prefetches
    s.schedule_batch()  # pops nothing
    first, second = s.flight.records()
    assert (first["pods"], second["pods"]) == (4, 2)
    assert first["queue_wait"] == {"pods": 6, "sum_ms": pytest.approx(6000.0), "max_ms": pytest.approx(1000.0)}
    assert "queue_wait" not in second


def test_compile_counters_count_every_program():
    s = _sched()
    before = PROCESS.compiles, PROCESS.compile_s
    jax.jit(lambda x: x * 3 + before[0])(jnp.ones(5)).block_until_ready()  # a program no pass owns
    assert PROCESS.compiles > before[0] and PROCESS.compile_s > before[1]
    text = s.metrics.registry.render_text()
    assert f"scheduler_jax_compiles_total {PROCESS.compiles}" in text
    assert "scheduler_jax_compile_seconds_total " in text


def test_gc_counters_move_with_a_collection():
    s = _sched()
    PROCESS.hook_gc()
    PROCESS.hook_gc()  # once
    assert gc.callbacks.count(PROCESS._on_gc) == 1
    n2, pause = PROCESS.gc_collections[2], PROCESS.gc_pause_s
    gc.collect()
    assert PROCESS.gc_collections[2] == n2 + 1 and PROCESS.gc_pause_s > pause
    text = s.metrics.registry.render_text()
    assert f'scheduler_gc_collections_total{{generation="2"}} {PROCESS.gc_collections[2]}' in text
    assert "scheduler_gc_pause_seconds_total " in text


# -- the frozen heap: `serve`'s policy (framework/tracing.py; the `armed_heap`
# fixture is conftest's) --------------------------------------------------------


class _Knot:
    """Garbage only the cycle collector can free."""

    def __init__(self):
        self.me = self


def test_a_scheduler_built_in_process_never_freezes_the_heap(tmp_path):
    frozen = gc.get_freeze_count()
    s = _sched(tmp_path)
    s.snapshot_every_records = 1  # a checkpoint behind every batch
    s.add_node(_node("n0"))
    s.add_pod(_pod("p0"))
    (out,) = s.schedule_all_pending()
    assert out.node_name == "n0" and s.journal.snapshots == 1
    assert gc.get_freeze_count() == frozen and not PROCESS.heap_armed
    names = {sp[0] for r in s.flight.records() if r["kind"] == "batch" for sp in r["spans"]}
    assert not names & {"pipeline/heap_settle", "snapshot/heap_sweep"}
    assert "scheduler_gc_frozen_objects " not in s.metrics.registry.render_text()


def test_armed_boundary_freezes_after_a_bind_and_not_after_an_empty_poll(armed_heap):
    s = _sched()
    s.add_node(_node("n0"))
    s.add_pod(_pod("p0"))
    n0 = armed_heap.gc_freezes
    (out,) = s.schedule_batch()
    assert out.node_name == "n0"
    assert armed_heap.gc_freezes == n0 + 1
    frozen = gc.get_freeze_count()
    assert frozen > 0
    rec = s.flight.records()[-1]
    (sp,) = _by_name(rec)["pipeline/heap_settle"]
    assert sp[3] == -1  # top level: idle seconds are booked to it by name
    assert s.schedule_batch() == []  # an empty poll
    s.add_pod(_pod("too-big", cpu="64"))  # a batch that binds nothing
    (out,) = s.schedule_batch()
    assert not out.node_name
    assert armed_heap.gc_freezes == n0 + 1 and gc.get_freeze_count() <= frozen


def test_a_cycle_made_and_dropped_inside_a_batch_is_dead_at_the_boundary(armed_heap, monkeypatch):
    s = _sched()
    s.add_node(_node("n0"))
    s.add_pod(_pod("p0"))
    inner, knots = s._schedule_batch_inner, []

    def knotted():
        knots.append(weakref.ref(_Knot()))
        return inner()

    monkeypatch.setattr(s, "_schedule_batch_inner", knotted)
    gc.disable()  # only the boundary's own young collection can do it
    s.schedule_batch()
    assert gc.get_freeze_count() > 0 and knots[0]() is None


def test_a_checkpoint_falling_due_sweeps_the_frozen_heap_and_counts_it(armed_heap, tmp_path):
    s = _sched(tmp_path)
    s.snapshot_every_records = 1  # a checkpoint behind every batch
    s.add_node(_node("n0"))
    knot = weakref.ref(_Knot())
    gc.freeze()  # garbage that was frozen before it died: no collection sees it
    gc.collect()
    assert knot() is not None
    s.add_pod(_pod("p0"))
    r0, f0 = armed_heap.gc_sweep_reclaimed, armed_heap.gc_freezes
    s.schedule_batch()
    assert s.journal.snapshots == 1
    assert knot() is None and armed_heap.gc_sweep_reclaimed > r0
    assert armed_heap.gc_freezes == f0 + 2  # the sweep's, then the boundary's
    assert gc.get_freeze_count() > 0  # the live heap, frozen again
    spans = s.flight.records()[-1]["spans"]
    (sweep,) = [sp for sp in spans if sp[0] == "snapshot/heap_sweep"]
    assert spans[sweep[3]][0] == "pipeline/snapshot"
    text = s.metrics.registry.render_text()
    assert f"scheduler_gc_freezes_total {armed_heap.gc_freezes}" in text
    assert f"scheduler_gc_sweep_reclaimed_total {armed_heap.gc_sweep_reclaimed}" in text
    frozen = float(re.search(r"^scheduler_gc_frozen_objects (\S+)$", text, re.M).group(1))
    assert frozen > 0


def test_full_ring_of_span_records_stays_far_under_the_frame_limit(tmp_path):
    import json

    s = _sched(tmp_path, pipeline_depth=2)
    for i in range(4):
        s.add_node(_node(f"n{i}", cpu="64"))
    for i in range(24):
        s.add_pod(_pod(f"p{i}"))
    s.schedule_all_pending()
    recs = [r for r in s.flight.records() if r["kind"] == "batch"]
    assert all(isinstance(sp, tuple) for r in recs for sp in r["spans"])
    assert max(len(r["spans"]) for r in recs) < 64  # a few dozen, whatever the pods
    worst = max(len(json.dumps(r)) for r in recs)
    assert worst * s.flight.capacity < 64 * 2**20 / 4


# -- the profiler's clock -----------------------------------------------------


def _xplane_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spans, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sched/"):
                    spans.append((e.name[6:], e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
                elif line.name.startswith("tf_XLAPjRtCpuClient") and e.duration_ns > 0:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns))
    return spans, ops


def test_a_profiler_session_holds_every_span_of_a_served_batch(tmp_path):
    path = tempfile.mktemp(suffix=".sock")
    sched = _sched(pipeline_depth=2)
    srv = SidecarServer(path, scheduler=sched, speculate=True,
                        journal=Journal(str(tmp_path / "j"), epoch=1), snapshot_every_batches=1)
    srv.serve_background()
    client = SidecarClient(path)
    sub = SidecarClient(path)
    trace_dir = str(tmp_path / "trace")
    try:
        for i in range(4):
            client.add("Node", _node(f"n{i}", cpu="64"))
        warm = [_pod(f"w{i}") for i in range(20)]
        client.add_pending_batch(warm)
        client.schedule([warm[0]], drain=False)  # compiles, outside the session
        sub.subscribe()  # a sink, so decisions are pushed
        pods = [_pod(f"p{i}") for i in range(20)]
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            client.add_pending_batch(pods)
            (r,) = client.schedule([pods[0]], drain=False)
        finally:
            jax.profiler.stop_trace()
        assert r.node_name
    finally:
        client.close()
        sub.close()
        srv.close()
    spans, ops = _xplane_events(trace_dir)
    by_batch = {}
    for name, _, _, stats in spans:
        assert "batch" in stats, name  # spans of one batch share an identifier
        by_batch.setdefault(stats["batch"], []).append(name)
    recs = {r["bid"]: r for r in sched.flight.records() if r["kind"] == "batch"}
    traced = [b for b in by_batch if b in recs and "pass/fetch_wait" in by_batch[b]]
    assert traced, by_batch
    for bid in traced:
        names = by_batch[bid]
        for name in EVERY_BATCH + CHECKPOINT:
            assert names.count(name) == 1, (name, names)
        assert "batch/pop" in names
        # the trace holds what the record holds, name for name
        # (what ran between batches carries the id of the batch before it)
        in_rec = Counter(sp[0] for sp in recs[bid]["spans"] if sp[0] != "pass/inflight")
        assert not in_rec - Counter(names)
    seen = {name for name, _, _, _ in spans}
    for name in SERVED + PIPELINED:
        assert name in seen, name
    kinds = {st.get("kind") for n, _, _, st in spans if n == "wire/dispatch"}
    assert {"add", "schedule"} <= kinds
    # pass/fetch_wait ends no earlier than its pass's last op, on that clock
    dispatch = sorted((s, e) for n, s, e, _ in spans if n == "pass/dispatch")
    waits = sorted((s, e) for n, s, e, _ in spans if n == "pass/fetch_wait")
    assert ops and waits
    checked = 0
    for w0, w1 in waits:
        before = [s for s, _ in dispatch if s <= w0]
        if not before:
            continue  # a pass predispatched before the session began
        mine = [e for s, e in ops if max(before) <= s < w1]
        if mine:
            checked += 1
            assert w1 >= max(mine) - 1_000, (w1, max(mine))
    assert checked


def test_named_scopes_are_in_the_lowered_pass():
    s = TPUScheduler(profile=registered_subset(DEFAULT_PROFILE), batch_size=16, chunk_size=8)
    lowered = {}
    get = s.passes.get

    def capture(*a, **k):
        fn = get(*a, **k)

        def run(*args):
            if not lowered:
                lowered["plain"] = fn.lower(*args).as_text()
                lowered["debug"] = fn.lower(*args).as_text(debug_info=True)
            return fn(*args)

        return run

    s.passes.get = capture
    for i in range(6):
        s.add_node(make_node(f"n{i}").capacity({"cpu": "4", "memory": "16Gi", "pods": 8})
                   .zone(f"z{i % 2}").obj())
    for i in range(6):
        s.add_pod(make_pod(f"p{i}").req({"cpu": "100m"}).obj())
    s.schedule_all_pending()
    scopes = set(re.findall(r"pass/(?:eval(?:/[A-Z][A-Za-z0-9]+)?|conflict|commit|tail)",
                            lowered["debug"]))
    assert {"pass/eval", "pass/conflict", "pass/commit", "pass/eval/NodeResourcesFit"} <= scopes
    assert "pass/eval" not in lowered["plain"]  # metadata only: the program is the same text


# -- PR 38: what the served path does with the device idle --------------------


def _hinted(n, prefix="h", priority=lambda i: 0):
    from kubernetes_tpu.api import serialize

    pods = []
    for i in range(n):
        p = _pod(f"{prefix}{i}")
        p.spec.priority = priority(i)
        pods.append(p)
    return pods, json.dumps([serialize.to_dict(p) for p in pods]).encode()


@pytest.fixture(scope="module")
def admitted_record(tmp_path_factory):
    """A batch whose dispatch admitted hints (`_on_dispatched`) and whose
    drain wrote a journal group."""
    from kubernetes_tpu.sidecar.speculate import SpeculativeFrontend

    s = _sched(str(tmp_path_factory.mktemp("j")))
    for i in range(3):
        s.add_node(_node(f"n{i}"))
    f = SpeculativeFrontend(s)
    pods, blob = _hinted(24)
    f.add_hint_blob(blob)
    f._run_batch(pods[0])
    recs = [r for r in s.flight.records() if r["kind"] == "batch"]
    return next(r for r in recs if any(sp[0] == "admit/build" for sp in r["spans"]))


@pytest.mark.parametrize("name, parent", [
    ("admit/sort", "hints/admit"), ("admit/build", "hints/admit"),
    ("admit/enqueue", "hints/admit"), ("drain/journal_write", "pipeline/drain"),
])
def test_each_new_span_sits_under_its_parent_in_a_batch_record(admitted_record, name, parent):
    rec = admitted_record
    (sp,) = _by_name(rec)[name]
    assert rec["spans"][sp[3]][0] == parent
    if parent == "hints/admit":
        # one interval an admission, in the order the admission runs
        kids = [s[0] for s in rec["spans"] if s[3] == sp[3]]
        assert kids == ["admit/sort", "admit/build", "admit/enqueue"]
        if name == "admit/build":
            # under a pass `hints/decode` built them first; only what it
            # did not reach is built here (the miss's own admission)
            assert sp[4] == {"pods": 0}
    else:
        # the group's one write + flush, between the append and the fsync
        (append,) = _by_name(rec)["drain/journal_append"]
        (fsync,) = _by_name(rec)["drain/journal_fsync"]
        assert append[3] == sp[3] == fsync[3]
        assert append[1] + append[2] <= sp[1] + 1 and sp[1] + sp[2] <= fsync[1] + 1
        assert rec["journal"]["append_s"] == pytest.approx(sp[2] * 1e-6, abs=3e-6)


def test_the_top_up_parse_runs_before_admission_opens():
    from kubernetes_tpu.sidecar.speculate import SpeculativeFrontend

    s = _sched()
    f = SpeculativeFrontend(s)
    _, blob = _hinted(6)
    f.add_hint_blob(blob)
    acc = {"phases": {}}
    s.spans.open(acc)
    f._admit_hints(4)  # the pool is empty: the top-up parses the blob
    s.spans.close()
    names = [sp[0] for sp in acc["spans"]]
    assert names == ["hints/decode", "hints/admit", "admit/sort", "admit/build", "admit/enqueue"]
    admit = names.index("hints/admit")
    assert acc["spans"][0][3] == -1  # a sibling before admission, not its child
    assert not [sp for sp in acc["spans"] if sp[3] == admit and sp[0] == "hints/decode"]
    text = s.metrics.registry.render_text()
    assert 'scheduler_phase_duration_seconds_count{phase="hints/admit"} 1' in text
    assert 'scheduler_phase_duration_seconds_count{phase="hint_decode"} 1' in text
    assert 'phase="admit/' not in text  # the children are annotations and record entries only


def _admit_as_the_parent_did(f, budget):
    """PR 37's `_admit_hints_timed`, one loop that filters, builds and
    enqueues each pod in turn: the order the two-pass admission must keep."""
    if len(f.hints) < budget:
        f._parse_blobs(budget - len(f.hints))
    in_flight = f._prefetched_uids() | f.sched._inflight_uids
    order = sorted(f.hints.items(), key=lambda kv: -f._hint_priority(kv[1][0]))[:budget]
    for uid, (obj, held) in order:
        f.hints.pop(uid, None)
        if uid in f.sched.cache.pods or uid in f.cached or uid in f.delivered or uid in in_flight:
            continue
        f.sched.add_pod(f._hint_pod(obj), held_at=held)


def test_the_two_pass_admission_queues_what_the_one_loop_did_in_its_order():
    from kubernetes_tpu.sidecar.speculate import SpeculativeFrontend

    def frontend():
        now = [1.0]
        s = _sched(batch_size=64)
        s.queue._clock = lambda: now[0]
        s.add_node(_node("n0", cpu="64"))
        f = SpeculativeFrontend(s)
        pods, blob = _hinted(30, priority=lambda i: i % 3)
        f.add_hint_blob(blob)  # held from 1.0
        now[0] = 2.0
        f._parse_blobs(20)
        f._build_hints(7)  # some built, some still dicts
        f.add_hint(_pod("late"))  # held from 2.0
        # stale hints of each kind: bound in the mirror, cached, delivered
        bound = _pod("h3")
        bound.spec.node_name = "n0"
        s.add_pod(bound)
        f.hints[pods[3].uid] = (pods[3], 1.0)
        f.cached[pods[4].uid] = None
        f.delivered[pods[5].uid] = "n0"
        return s, f

    got, want = frontend(), frontend()
    for budget in (9, 40):
        got[1]._admit_hints(budget)
        _admit_as_the_parent_did(want[1], budget)
        assert list(got[1].hints) == list(want[1].hints)
    queued = [[(qp.pod.uid, qp.held_at) for qp in s.queue.pop_batch(64)] for s, _ in (got, want)]
    assert queued[0] == queued[1] and len(queued[0]) == 28  # 30 + late - the three stale


class _Recorder:
    """A stand-in for jax.profiler.TraceAnnotation: who opened what."""

    seen: list = []

    def __init__(self, name, **kw):
        self.entry = (name[len("sched/"):], threading.get_ident(), kw)

    def __enter__(self):
        _Recorder.seen.append(self.entry)

    def __exit__(self, *exc):
        return False


def test_the_wait_for_the_client_is_timed_on_served_connections_only(monkeypatch):
    from kubernetes_tpu.framework import tracing

    monkeypatch.setattr(tracing, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(tracing, "_TRACING", lambda: True)
    _Recorder.seen = []
    path = tempfile.mktemp(suffix=".sock")
    sched = _sched()
    srv = SidecarServer(path, scheduler=sched, speculate=True)
    srv.serve_background()
    served, sub, scrape = SidecarClient(path), SidecarClient(path), SidecarClient(path)
    try:
        sub.subscribe()
        for i in range(2):
            served.add("Node", _node(f"n{i}"))
        pods = [_pod(f"p{i}") for i in range(3)]
        served.add_pending_batch(pods)
        assert served.schedule([pods[0]], drain=False)[0].node_name
        scrape.metrics()
        scrape.flight()
        text = served.metrics()
    finally:
        for c in (served, sub, scrape):
            c.close()
        srv.close()
    kinds, timed = {}, {}
    for name, tid, kw in list(_Recorder.seen):
        if name == "wire/dispatch":
            kinds.setdefault(tid, set()).add(kw["kind"])
        elif name in ("wire/await", "wire/read"):
            timed.setdefault(tid, set()).add(name)
    by_role = {frozenset(k): tid for tid, k in kinds.items()}
    assert set(by_role) == {frozenset({"subscribe"}), frozenset({"metrics", "flight"}),
                            frozenset({"add", "schedule", "metrics"})}
    # the first frame tells a served connection apart; every read after it is timed
    assert timed == {by_role[frozenset({"add", "schedule", "metrics"})]: {"wire/await", "wire/read"}}
    waited = float(re.search(r"^scheduler_wire_await_seconds_total (\S+)$", text, re.M).group(1))
    assert waited > 0


def _scrape(client):
    out = {}
    for line in client.metrics().splitlines():
        if line.startswith(("scheduler_object", "scheduler_objects_")):
            key, _, val = line.rpartition(" ")
            out[key] = float(val)
    return out


def test_each_object_added_counts_once_with_its_three_stages_and_a_hint_never():
    from kubernetes_tpu.api.wrappers import make_pv, make_pvc

    path = tempfile.mktemp(suffix=".sock")
    sched = _sched()
    srv = SidecarServer(path, scheduler=sched, speculate=True)
    srv.serve_background()
    client = SidecarClient(path)
    stages = [f'scheduler_object_add_seconds_total{{stage="{s}"}}' for s in ("decode", "scope", "apply")]
    added = 'scheduler_objects_added_total{kind="%s"}'
    try:
        before = _scrape(client)
        assert not [k for k in before if k.startswith("scheduler_objects_added_total")]
        steps = [
            ("Node", lambda: client.add("Node", _node("n0"))),
            ("PersistentVolumeClaim", lambda: client.add("PersistentVolumeClaim", make_pvc("c0"))),
            ("PersistentVolume", lambda: client.add("PersistentVolume", make_pv("v0"))),
            ("NamespaceLabels", lambda: client.set_namespace_labels("ns", {"a": "b"})),
            ("Node", lambda: client.add("Node", _node("n1"))),
            (None, lambda: client.add_pending_batch([_pod("h0"), _pod("h1")])),
            (None, lambda: client.add("PendingPod", _pod("h2"))),
        ]
        for kind, send in steps:
            send()
            after = _scrape(client)
            moved = {k: after.get(k, 0.0) - before.get(k, 0.0)
                     for k in set(after) | set(before) if after.get(k, 0.0) != before.get(k, 0.0)}
            if kind is None:  # hints are not objects
                assert not moved, moved
            else:
                assert {k: v for k, v in moved.items() if "added" in k} == {added % kind: 1.0}
                assert all(moved.get(k, 0.0) > 0 for k in stages), moved
            before = after
        client.remove("Node", "n1")
        assert _scrape(client) == before  # a remove is not an add
        with pytest.raises(RuntimeError):
            client.remove("Nonsense", "x")
        assert not [k for k in _scrape(client) if "Nonsense" in k]
    finally:
        client.close()
        srv.close()
