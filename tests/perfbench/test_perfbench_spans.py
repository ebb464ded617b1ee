"""The readers of the span-sourced per-layer metrics, on a canned record
list and scrape pair with hand-computed values, and perfbench/spans.py on
synthetic intervals and on a small trace recorded here."""

import os
import types

import pytest

import _pb
from perfbench import report, spanread, spans, spec, trace

HOME = os.path.join(_pb.ROOT, "perfbench")

# Two batches of 4,000 and 1,000 pods.  [name, start_us, dur_us, parent, {sub-times}?]
RECORDS = [
    {"pods": 4000, "queue_wait": {"pods": 4000, "sum_ms": 8000.0, "max_ms": 90.0},
     "spans": [["batch/pop", 0, 100, -1], ["batch/pack", 120, 70000, -1], ["pass/dispatch", 200, 6000, -1],
               ["pass/fetch_wait", 7000, 50000, -1], ["pass/fetch_copy", 57000, 200, -1],
               ["pipeline/drain", 60000, 2100000, -1],
               ["drain/journal_append", 60010, 1600000, 5, {"serialize_us": 600000}],
               ["drain/journal_fsync", 1660100, 2000, 5], ["drain/apply", 1662200, 400000, 5],
               ["pipeline/snapshot", 2160100, 1000000, -1], ["snapshot/collect", 2160110, 600000, 9],
               ["snapshot/encode", 2760200, 300000, 9], ["snapshot/write", 3060300, 100000, 9]]},
    {"pods": 1000, "queue_wait": {"pods": 1000, "sum_ms": 1000.0, "max_ms": 40.0},
     "spans": [["batch/pack", 50, 5000, -1], ["pass/dispatch", 100, 4000, -1], ["pass/fetch_wait", 5000, 10000, -1],
               ["pipeline/drain", 20000, 520000, -1],
               ["drain/journal_append", 20010, 400000, 3, {"serialize_us": 150000}],
               ["drain/journal_fsync", 420100, 2000, 3], ["drain/apply", 422200, 100000, 3]]},
]
BEFORE = {'scheduler_phase_duration_seconds_sum{phase="spec/publish"}': 1.0,
          "scheduler_gc_pause_seconds_total": 0.25, "scheduler_jax_compile_seconds_total": 4.5}
AFTER = {'scheduler_phase_duration_seconds_sum{phase="spec/publish"}': 1.2,
         "scheduler_gc_pause_seconds_total": 0.75, "scheduler_jax_compile_seconds_total": 4.5,
         "scheduler_chunk_pack_width": 1.0}
# the traced slice held 2,500 pods' worth of passes in 0.85 s of device time
TRACE = {"busy_s": 0.85, "pods_in_slice": 2500.0, "pass_device_s": 0.85, "window_s": 1.0, "device_plane": True}

EXPECTED = {
    "pass_fetch_wait_ms_per_batch": (50000 + 10000) / 2 / 1e3,
    "pass_fetch_wait_ms_per_batch.arrivals": 30.0,
    "pass_dispatch_ms_per_batch.arrivals": (6000 + 4000) / 2 / 1e3,
    "journal_append_us_per_pod": (1600000 + 400000) / 5000,
    "journal_serialize_us_per_pod": (600000 + 150000) / 5000,
    "drain_apply_us_per_pod": (400000 + 100000) / 5000,
    "publish_us_per_pod": 0.2 / 5000 * 1e6,
    "queue_wait_ms_mean.arrivals": 9000.0 / 5000,
    "snapshot_cpu_share.arrivals": 100.0 * 900000 / 1000000,
    "server_gc_pause_ms": 500.0,
    "setup_compile_s": 4.5,
    "pack_us_per_pod": (70000 + 5000) / 5000,
    "pack_width": 1.0,
    "pass_device_us_per_pod": 0.85 / 2500 * 1e6,
}


def ctx(records, before, after, trace=None):
    c = types.SimpleNamespace(records=records, before=before, after=after, trace=trace)
    c.delta = lambda key: after.get(key, 0.0) - before.get(key, 0.0)
    c.pods = c.window_pods = lambda: sum(int(r.get("pods", 0)) for r in records)
    return c


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_computed_value(name):
    value = report.load_reader(HOME, name).read(ctx(RECORDS, BEFORE, AFTER, TRACE))
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_nothing_from_a_program_without_the_primitive(name):
    """The parent's records carry no spans and its scrapes none of the
    counters: nothing, never 0, and nothing raised."""
    old = [{"pods": 4000, "phases": {"drain": 2.1}}, {"pods": 1000, "phases": {"drain": 0.5}}]
    reader = report.load_reader(HOME, name)
    assert reader.read(ctx(old, {}, {})) is None
    assert reader.read(ctx([], {}, {})) is None


# out of ``per_layer`` since PR 35: no checkpoint falls due in any window, so no
# run could report it; the reader file and its arithmetic above stay for the
# window in which one does (PERF.md section 8)
WITHOUT_AN_ENTRY = {"snapshot_cpu_share.arrivals"}
# the order in which the accepted entries stand in ``per_layer``, wherever
# later entries stand among or after them
ACCEPTED_ORDER = [
    "pass_fetch_wait_ms_per_batch", "pass_fetch_wait_ms_per_batch.arrivals", "pass_dispatch_ms_per_batch.arrivals",
    "journal_append_us_per_pod", "journal_serialize_us_per_pod", "drain_apply_us_per_pod", "publish_us_per_pod",
    "queue_wait_ms_mean.arrivals", "server_gc_pause_ms", "setup_compile_s", "pass_device_us_per_pod",
    "pack_us_per_pod", "pack_width",
]


def test_every_new_metric_is_declared_with_its_reader_and_an_accepted_layer():
    bench = _pb.bench()
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in EXPECTED}
    backlogs = ["basic_5kn.backlog", "podaffinity_5kn.backlog"]
    assert set(ACCEPTED_ORDER) == set(EXPECTED) - WITHOUT_AN_ENTRY and not WITHOUT_AN_ENTRY & set(declared)
    for name in EXPECTED:
        assert os.path.exists(os.path.join(HOME, "metrics", name + ".py"))
        if name in WITHOUT_AN_ENTRY:
            continue
        m = declared[name]
        assert m["layer"] in layers and m["source"] in ("program_span", "program_counter", "device_trace")
        cells = m.get("workloads")
        if name == "setup_compile_s":
            assert cells is None and m["moves"] == "setup_s"
        else:
            assert m in spec.metrics_for(bench, "per_layer", cells[0])
            want = "decision_p50_ms" if name.endswith(".arrivals") else "pods_per_s"
            assert m["moves"] == want
            # the cells a list began with, in their order; later cells follow
            if name.endswith(".arrivals"):
                assert cells[:1] == ["basic_5kn.arrivals"]
            elif name in ("pack_us_per_pod", "pack_width"):
                # only the batches of an ordered configuration are packed
                assert cells[:1] == backlogs[1:] and backlogs[0] not in cells
            else:
                assert cells[:2] == backlogs
    # the accepted entries stand in the order they had
    assert [m["name"] for m in bench["per_layer"] if m["name"] in EXPECTED] == ACCEPTED_ORDER


def test_a_silent_checkpoint_share_in_a_window_without_one():
    reader = report.load_reader(HOME, "snapshot_cpu_share.arrivals")
    assert reader.read(ctx(RECORDS[1:], BEFORE, AFTER)) is None
    assert spanread.seconds(RECORDS[1:], "snapshot/write") is None
    assert spanread.seconds(RECORDS, "snapshot/write") == pytest.approx(0.1)


# -- spans.py -----------------------------------------------------------------


def test_idle_goes_to_the_innermost_span_and_sums_to_the_idle_time():
    gaps = [(0.0, 1.0), (2.0, 6.0)]
    sp = [("wire/dispatch", 0.5, 5.5, 1), ("pipeline/drain", 2.5, 4.5, 1),
          ("drain/apply", 3.0, 4.0, 1), ("wire/lock_wait", 3.2, 3.4, 1)]
    idle = trace.innermost(gaps, sp)
    assert idle["drain/apply"] == pytest.approx(0.8)  # 3.0-3.2 and 3.4-4.0
    assert idle["wire/lock_wait"] == pytest.approx(0.2)  # another thread's, started last
    assert idle["pipeline/drain"] == pytest.approx(1.0)  # 2.5-3.0 and 4.0-4.5
    assert idle["wire/dispatch"] == pytest.approx(0.5 + 0.5 + 1.0)  # 0.5-1, 2-2.5, 4.5-5.5
    assert idle[trace.NO_SPAN] == pytest.approx(0.5 + 0.5)  # 0-0.5, 5.5-6
    assert sum(idle.values()) == pytest.approx(5.0)


def test_stage_of_reads_the_scope_out_of_an_ops_metadata():
    assert spans.stage_of("jit(_run)/while/body/vmap(pass/eval)/pass/eval/NodeResourcesFit/add") \
        == "pass/eval/NodeResourcesFit"
    assert spans.stage_of("jit(_run)/while/body/vmap(pass/eval)/and") == "pass/eval"
    assert spans.stage_of("jit(_run)/pass/tail/while/body/pass/commit/scatter-add") == "pass/tail"
    assert spans.stage_of("jit(_run)/while/body/pass/conflict/reduce_or") == "pass/conflict"
    assert spans.stage_of("reduce-window.21") == "(no scope)"


def _msg(*fields):
    """A protobuf message from (number, bytes | int) pairs, by hand."""
    def varint(v):
        out = bytearray()
        while True:
            out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
            v >>= 7
            if not v:
                return bytes(out)
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def test_stages_come_from_the_hlo_the_profiler_keeps_with_each_program():
    def inst(name, op_name):
        return _msg((1, name.encode()), (2, b"fusion"), (7, _msg((1, b"add"), (2, op_name.encode()))))

    comp = _msg((1, b"body"), (2, inst("fusion.7", "jit(_run)/while/body/vmap(pass/eval)/pass/eval/NodeResourcesFit/add")),
               (2, inst("fusion.8", "jit(_run)/while/body/pass/commit/scatter-add")),
               (2, inst("copy.1", "jit(_run)/copy")), (2, _msg((1, b"constant.3"))))
    hlo = _msg((1, _msg((1, b"jit__run"), (3, comp))))
    assert spans.hlo_op_names(hlo) == {
        "fusion.7": "jit(_run)/while/body/vmap(pass/eval)/pass/eval/NodeResourcesFit/add",
        "fusion.8": "jit(_run)/while/body/pass/commit/scatter-add", "copy.1": "jit(_run)/copy"}
    meta_plane = _msg(
        (2, b"/host:metadata"),
        (5, _msg((1, 9), (2, _msg((1, 9), (2, b"Hlo Proto"))))),  # stat_metadata[9]
        (4, _msg((1, 1), (2, _msg((1, 1), (2, b"jit__run(77)"), (5, _msg((1, 9), (6, hlo))))))),
        (4, _msg((1, 2), (2, _msg((1, 2), (2, b"jit_other(78)"), (5, _msg((1, 9), (3, 5))))))),
    )
    stages = spans.module_stages(memoryview(_msg((1, _msg((2, b"/device:TPU:0"))), (1, meta_plane))))
    assert stages == {"jit__run(77)": {"fusion.7": "pass/eval/NodeResourcesFit",
                                       "fusion.8": "pass/commit", "copy.1": spans.NO_SCOPE}}


def test_overlap_of_two_interval_lists():
    assert spans.overlap_s([(0.0, 2.0), (3.0, 5.0)], [(1.0, 3.5), (4.0, 9.0)]) == pytest.approx(1.0 + 0.5 + 1.0)
    assert spans.overlap_s([(0.0, 1.0)], []) == 0.0


def test_spans_on_a_trace_recorded_here(tmp_path):
    """The test's own fixture: a CPU session around a few annotated
    matmuls.  Idle by span sums to the idle time; the drain that ran the
    matmul overlaps device work and the one that slept does not."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    out = tmp_path / "run"
    x = (jnp.ones((256, 256)) @ jnp.ones((256, 256))).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out / "trace"), profiler_options=opts)
    try:
        with TraceAnnotation("sched/wire/dispatch", batch=7, kind="schedule"):
            with TraceAnnotation("sched/pipeline/drain", batch=7):
                with TraceAnnotation("sched/drain/apply", batch=7):
                    for _ in range(3):
                        x = (x @ x / 256.0).block_until_ready()
            with TraceAnnotation("sched/pipeline/snapshot", batch=7):
                t0 = time.perf_counter()
                time.sleep(0.02)
                slept = time.perf_counter() - t0
        time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    r = spans.analyse(str(out), rehearsal=True)
    assert r["span_events"] == 4 and r["batches_in_slice"] == 1 and r["device_ops"] > 0
    assert r["idle_s"] + r["busy_s"] == pytest.approx(r["window_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    # the sleep as it was timed here (20 ms, or what a loaded host made of it):
    # no more is booked to the span, and no less
    assert slept >= 0.02 and r["idle_by_span"]["pipeline/snapshot"] == pytest.approx(slept, abs=0.01)
    assert 0.0 < r["idle_named_share"] <= 100.0
    assert 0.0 < r["drain_overlapped_share"] <= 100.0
    assert r["spans_in_slice"] == {"drain/apply": 1, "pipeline/drain": 1,
                                   "pipeline/snapshot": 1, "wire/dispatch": 1}
    assert set(r["device_s_by_stage"]) == {"(no scope)"}
    assert "on a named span" in spans.render(r)
    # without the rehearsal's stand-in there is no device op to read here
    proper = spans.analyse(str(out), rehearsal=False)
    assert proper["device_ops"] == 0 and proper["busy_s"] == 0.0
    with pytest.raises(SystemExit):
        spans.analyse(str(tmp_path / "nothing"))
