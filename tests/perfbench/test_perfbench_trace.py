"""The reduction from a trace to numbers, on synthetic event lists and on
a small trace recorded here."""

import glob
import json
import os

import pytest

import _pb
from perfbench import peaks, trace


def test_busy_is_the_union_of_op_intervals():
    ops = [("a", 1.0, 2.0), ("b", 2.5, 1.0), ("c", 5.0, 1.0), ("while.1", 0.5, 3.2)]
    busy_s, busy = trace.busy_seconds(ops, 0.0, 10.0)
    assert busy == [(0.5, 3.7), (5.0, 6.0)]
    assert busy_s == pytest.approx(4.2)
    # clipped to the traced window
    busy_s, _ = trace.busy_seconds(ops, 1.0, 5.5)
    assert busy_s == pytest.approx(2.7 + 0.5)


def test_gaps_are_what_the_busy_intervals_leave_of_the_slice():
    busy = [(1.0, 2.0), (4.0, 5.0)]
    g = trace.gaps(busy, 0.0, 8.0)
    assert g == [(0.0, 1.0), (2.0, 4.0), (5.0, 8.0)]
    assert trace.gaps([], 0.0, 2.0) == [(0.0, 2.0)]
    assert trace.gaps([(0.0, 2.0)], 0.0, 2.0) == []
    # spans are cut to the slice before the idle seconds are laid against them
    assert trace.clip([("a", -1.0, 0.5, 1), ("b", 3.0, 9.0, 2), ("c", 9.0, 10.0, 3)], 0.0, 8.0) == \
        [("a", 0.0, 0.5, 1), ("b", 3.0, 8.0, 2)]


# -- pods_in_slice ------------------------------------------------------------
# spans: (name, start_s, end_s, batch).  A pass's interval runs from its
# pass/dispatch's start to its pass/fetch_wait's end.


def _pass(d0, f0, f1, batch):
    return [("pass/dispatch", d0, d0 + 0.01, batch), ("pass/fetch_wait", f0, f1, batch)]


def test_pods_in_slice_counts_a_whole_pass_whole_and_pro_rates_a_cut_one():
    # two whole passes of 4,096 pods, 1.0 s and 1.2 s long, then one cut by
    # the slice's end 0.55 s after its dispatch: whole passes of as many
    # pods took 1.1 s (their median), so half of it lies inside
    spans = _pass(1.0, 1.5, 2.0, 7) + _pass(2.5, 3.0, 3.7, 8) + [("pass/dispatch", 4.0, 4.01, 9)]
    pods = {1.0: 4096, 2.5: 4096, 4.0: 4096}
    got = trace.pods_in_slice(spans, pods, 0.5, 4.55)
    assert got["whole"] == 2 and got["cut"] == 1
    assert got["pods"] == pytest.approx(2 * 4096 + 4096 * 0.55 / 1.1)
    assert got["passes"] == pytest.approx(2.5)
    # a slice that holds only whole passes counts each whole
    whole = trace.pods_in_slice(spans[:4], pods, 0.5, 3.9)
    assert whole == {"pods": 8192.0, "passes": 2.0, "whole": 2, "cut": 0}
    # nothing dispatched or fetched inside: nothing to say
    assert trace.pods_in_slice(spans, pods, 10.0, 11.0) is None


def _record(seq, bid, pods, t0_s, spans):
    return {"seq": seq, "bid": bid, "pods": pods, "t0_ns": int(t0_s * 1e9), "ts": t0_s + 9.0,
            "spans": [[n, int(s * 1e6), int(d * 1e6), -1] for n, s, d in spans]}


# The flight records of a served backlog at depth 2: the full pass is
# dispatched and fetched in one call (1.40 s), the short one is dispatched
# in that call and fetched in the next (0.30 s from dispatch to fetched).
RECORDS = [
    _record(1, 11, 4095, 100.0, [("pass/dispatch", 0.05, 0.01), ("pass/fetch_wait", 0.06, 1.39),
                                 ("pass/dispatch", 1.50, 0.01)]),
    _record(2, 12, 905, 102.0, [("pass/fetch_wait", -0.30, 0.10)]),
    _record(3, 13, 4095, 103.0, [("pass/dispatch", 0.05, 0.01), ("pass/fetch_wait", 0.06, 1.41),
                                 ("pass/dispatch", 1.50, 0.01)]),
    _record(4, 14, 905, 105.0, [("pass/fetch_wait", -0.20, 0.10)]),
]


def test_a_whole_pass_is_as_long_as_the_records_of_as_many_pods_say():
    # 100.05 -> 101.45 and 103.05 -> 104.47; 101.50 -> 101.80 and 104.50 -> 104.90
    got = trace.whole_pass_seconds(RECORDS)
    assert got == {4095: pytest.approx(1.41), 905: pytest.approx(0.35)}
    assert trace.whole_pass_seconds([{"pods": 5, "phases": {}}]) == {}


def test_a_slice_that_holds_no_whole_pass_takes_its_length_from_the_records():
    # the ordered cell: the slice starts before the full pass's dispatch and
    # ends 0.55 s into it; no pass ends inside
    spans = [("batch/pack", 0.02, 0.04, 21), ("pass/dispatch", 0.05, 0.06, 21)]
    got = trace.pods_in_slice(spans, {0.05: 4095}, 0.0, 0.6, RECORDS)
    assert got["whole"] == 0 and got["cut"] == 1
    assert got["pods"] == pytest.approx(4095 * 0.55 / 1.41)
    # without a whole pass of as many pods anywhere there is no measure: nothing, not a guess
    assert trace.pods_in_slice(spans, {0.05: 4095}, 0.0, 0.6, RECORDS[1:2]) is None
    assert trace.pods_in_slice(spans, {0.05: 4095}, 0.0, 0.6) is None
    # a pass in flight when the slice began: its pods are its record's, by the span's batch
    flying = [("pass/fetch_wait", -0.2, 0.141, 13)]
    got = trace.pods_in_slice(flying, {}, 0.0, 0.6, RECORDS)
    assert got["cut"] == 1 and got["pods"] == pytest.approx(4095 * 0.141 / 1.41)


def _ctx(tr, records, config):
    import types

    c = types.SimpleNamespace(trace=tr, records=records, config=config, peaks=peaks,
                              device={"kind": "TPU v5 lite"})
    c.pods = lambda: sum(r["pods"] for r in records)
    return c


def test_pass_roofline_cut_form_equals_the_whole_form_on_whole_passes():
    from perfbench import report

    home = os.path.join(_pb.ROOT, "perfbench")
    config = {"cluster": {"nodes": 5000}, "pod": {"template": {"spec": {"affinity": None}}, "cycles": {}}}
    records = [{"pods": 4096}] * 3
    # three whole passes of the mean size, 80 ms of device time each
    tr = {"pods_in_slice": 3 * 4096.0, "pass_device_s": 0.24, "busy_s": 0.2, "device_plane": True}
    whole_form = 100.0 * (3 * peaks.pass_bytes(config, 4096) / 819e9) / 0.24
    reader = report.load_reader(home, "pass_roofline")
    assert reader.read(_ctx(tr, records, config)) == pytest.approx(whole_form)
    # half a pass in half the time reads the same share
    half = dict(tr, pods_in_slice=2048.0, pass_device_s=0.04)
    assert reader.read(_ctx(half, records, config)) == pytest.approx(whole_form)
    # and a slice whose pods are not known, or a rehearsal's CPU threads, nothing
    assert reader.read(_ctx(dict(tr, pods_in_slice=None), records, config)) is None
    assert reader.read(_ctx(dict(tr, device_plane=False), records, config)) is None
    # the device time a pod: the programs' device seconds over the slice's pods
    per_pod = report.load_reader(home, "pass_device_us_per_pod")
    assert per_pod.read(_ctx(tr, records, config)) == pytest.approx(0.24 / (3 * 4096) * 1e6)
    assert per_pod.read(_ctx(dict(tr, pods_in_slice=None), records, config)) is None
    assert per_pod.read(_ctx(None, records, config)) is None


def test_top_ops_leaves_out_the_ops_that_enclose_others():
    ops = [("fusion.1", 0, 1.0), ("fusion.1", 2, 1.0), ("while.3", 0, 9.0), ("copy.2", 3, 0.5)]
    assert trace.top_ops(ops) == [["fusion.1", 2.0], ["copy.2", 0.5]]


def test_reduce_reads_a_recorded_trace_on_its_own_clock(tmp_path):
    """A trace recorded here, on the CPU, through the launcher's own
    session: the slice lies between its two marks, the idle seconds go to
    the innermost span and sum to the idle time, a pass dispatched and
    fetched inside counts whole.  No device plane, so only a rehearsal
    reads ops from it, and a run proper finds none."""
    import time

    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from perfbench import launcher

    x = (jnp.ones((256, 256)) @ jnp.ones((256, 256))).block_until_ready()
    sl = launcher.Slice(str(tmp_path))
    sl.start(30.0)
    with TraceAnnotation("sched/wire/dispatch", batch=7, kind="schedule"):
        with TraceAnnotation("sched/pass/dispatch", batch=7, pods=64):
            for _ in range(3):
                x = x @ x / 256.0
        with TraceAnnotation("sched/pass/fetch_wait", batch=7):
            x.block_until_ready()
        with TraceAnnotation("sched/pipeline/drain", batch=7):
            time.sleep(0.03)
    time.sleep(0.01)
    t0, t1 = sl.stop()
    assert sl.stop() == (t0, t1) and t1 > t0  # one stop, whoever asks again
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    red = trace.reduce(str(tmp_path), [], rehearsal=True)
    assert 0.04 <= red["window_s"] < 5.0
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] + red["idle_s"] == pytest.approx(red["window_s"])
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(red["idle_s"])
    assert idle["pipeline/drain"] == pytest.approx(0.03, abs=0.01)
    assert trace.NO_SPAN in idle and "device" not in idle and "between_batches" not in idle
    assert 0.0 < red["idle_named_share"] < 100.0
    assert red["pods_in_slice"] == 64.0 and red["passes_in_slice"]["whole"] == 1
    assert red["span_events"] == 4 and not red["device_plane"]
    proper = trace.reduce(str(tmp_path), [], rehearsal=False)
    assert proper["busy_s"] == 0.0 and proper["device_ops"] == []
    assert trace.reduce(str(tmp_path / "nothing"), []) is None


def test_a_trace_without_the_slices_marks_is_not_reduced(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jax.profiler.stop_trace()
    assert trace.find_xplane(str(tmp_path)) is not None
    assert trace.reduce(str(tmp_path), [], rehearsal=True) is None


def test_pass_bytes_from_shapes():
    config = {"cluster": {"nodes": 5000}, "serve": {"chunk_size": 64},
              "pod": {"template": {"spec": {"affinity": None}}, "cycles": {}}}
    row = peaks.node_row_bytes(config)
    assert row == 6 * 8
    # the node table in once and out once whatever the steps, and 28 bytes a pod
    assert peaks.pass_bytes(config, 4096) == 2 * 5000 * row + 4096 * 28
    assert peaks.pass_bytes(config, 1) == 2 * 5000 * row + 28
    zone, host = "topology.kubernetes.io/zone", "kubernetes.io/hostname"
    term = {"topology_key": zone,
            "label_selector": {"match_labels": [["color", "blue"]],
                               "match_expressions": [{"key": "tier", "operator": "In", "values": ["{colour}", "x"]}]}}
    spec = {"affinity": {"pod_affinity": {"required": [term]}, "pod_anti_affinity": None}}
    aff = {"cluster": {"nodes": 5000}, "serve": {"chunk_size": 64},
           "pod": {"cycles": {"colour": {"prefix": "c", "count": 50}}, "template": {"spec": spec}}}
    # a domain id, and a count for blue, for each of the 50 colours and for x
    assert peaks.node_row_bytes(aff) == row + 4 + 4 * (1 + 50 + 1)
    # a cycle that names its values is as long as its list
    aff["pod"]["cycles"] = {"colour": {"values": ["red", "green", "blue"]}}
    assert peaks.node_row_bytes(aff) == row + 4 + 4 * (1 + 3 + 1)
    # spread constraints: a count for each value selected; a domain id for
    # each distinct topology key, whoever names it (the zone key is the term's too)
    blue = {"match_labels": [["color", "blue"]], "match_expressions": []}
    spec["topology_spread_constraints"] = [{"topology_key": zone, "label_selector": blue}]
    assert peaks.node_row_bytes(aff) == row + 4 + 4 * (1 + 3 + 1) + 4
    spec["topology_spread_constraints"].append({"topology_key": host, "label_selector": blue})
    assert peaks.node_row_bytes(aff) == row + 2 * 4 + 4 * (1 + 3 + 1) + 2 * 4
    spec["affinity"] = None
    assert peaks.node_row_bytes(aff) == row + 2 * 4 + 2 * 4


@pytest.mark.parametrize("name, row", [("basic_5kn", 48), ("podaffinity_5kn", 56)])
def test_the_accepted_configurations_node_rows(name, row):
    """What ``pass_roofline`` has divided by since PR 24 and PR 27."""
    with open(os.path.join(_pb.ROOT, "perfbench", "configs", name + ".json")) as f:
        assert peaks.node_row_bytes(json.load(f)) == row


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="no peak"):
        peaks.peak("TPU v9", "hbm_bytes_per_s")
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")
