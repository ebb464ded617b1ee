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


def test_gaps_and_their_attribution_to_host_spans():
    busy = [(1.0, 2.0), (4.0, 5.0)]
    g = trace.gaps(busy, 0.0, 8.0)
    assert g == [(0.0, 1.0), (2.0, 4.0), (5.0, 8.0)]
    spans = [("featurize", 0.2, 1.0), ("drain", 2.0, 3.5), ("snapshot", 5.0, 5.5)]
    idle = trace.attribute(g, spans)
    assert idle["featurize"] == pytest.approx(0.8)
    assert idle["drain"] == pytest.approx(1.5)
    assert idle["snapshot"] == pytest.approx(0.5)
    assert idle["between_batches"] == pytest.approx(0.2 + 0.5 + 2.5)
    assert sum(idle.values()) == pytest.approx(sum(b - a for a, b in g))


def test_flight_records_become_spans_inside_their_call():
    rec = {"ts": 1000.5, "wall_s": 0.4,
           "phases": {"device": 0.1, "featurize": 0.1, "drain": 0.3, "other": 0.0}}
    spans = trace.flight_spans([rec], lambda wall: wall - 1000.0)
    names = [s[0] for s in spans]
    assert names == ["featurize", "device", "drain"]  # the batch loop's order
    assert spans[0][1] == pytest.approx(0.1) and spans[-1][2] == pytest.approx(0.5)
    # 0.5 s of phases in a 0.4 s call: shrunk alike, never outside the call
    assert sum(b - a for _, a, b in spans) == pytest.approx(0.4)


def test_top_ops_leaves_out_the_ops_that_enclose_others():
    ops = [("fusion.1", 0, 1.0), ("fusion.1", 2, 1.0), ("while.3", 0, 9.0), ("copy.2", 3, 0.5)]
    assert trace.top_ops(ops) == [["fusion.1", 2.0], ["copy.2", 0.5]]


def test_reduce_reads_a_recorded_trace(tmp_path):
    """A trace recorded here, on the CPU: no device plane, so only a
    rehearsal reads ops from it, and a run proper finds none."""
    import time

    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.time_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    t1 = time.time_ns()
    x = jnp.ones((256, 256))
    for _ in range(3):
        x = (x @ x / 256.0).block_until_ready()
    t2 = time.time_ns()
    jax.profiler.stop_trace()
    marks = {"start": (t0, t1), "stop": (t2, time.time_ns())}
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    red = trace.reduce(str(tmp_path), marks, [], rehearsal=True)
    assert red["window_s"] == pytest.approx((t2 - t1) * 1e-9)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    assert red["idle_gaps"][0][0] == "between_batches"
    proper = trace.reduce(str(tmp_path), marks, [], rehearsal=False)
    assert proper["busy_s"] == 0.0 and proper["device_ops"] == []
    assert trace.reduce(str(tmp_path / "nothing"), marks, []) is None


def test_pass_bytes_from_shapes():
    config = {"cluster": {"nodes": 5000}, "serve": {"chunk_size": 64},
              "pod": {"template": {"spec": {"affinity": None}}, "cycles": {}}}
    row = peaks.node_row_bytes(config)
    assert row == 6 * 8
    # the node table in once and out once whatever the steps, and 28 bytes a pod
    assert peaks.pass_bytes(config, 4096) == 2 * 5000 * row + 4096 * 28
    assert peaks.pass_bytes(config, 1) == 2 * 5000 * row + 28
    term = {"label_selector": {"match_labels": [["color", "blue"]],
                               "match_expressions": [{"key": "tier", "operator": "In", "values": ["{colour}", "x"]}]}}
    aff = {"cluster": {"nodes": 5000}, "serve": {"chunk_size": 64},
           "pod": {"cycles": {"colour": {"prefix": "c", "count": 50}},
                   "template": {"spec": {"affinity": {"pod_affinity": {"required": [term]},
                                                      "pod_anti_affinity": None}}}}}
    # a domain id, and a count for blue, for each of the 50 colours and for x
    assert peaks.node_row_bytes(aff) == row + 4 + 4 * (1 + 50 + 1)
    with open(os.path.join(_pb.ROOT, "perfbench", "configs", "podaffinity_5kn.json")) as f:
        assert peaks.node_row_bytes(json.load(f)) == row + 4 + 4


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="no peak"):
        peaks.peak("TPU v9", "hbm_bytes_per_s")
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")
