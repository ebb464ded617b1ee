"""Each cell rehearsed on the CPU at toy sizes, as the driver would run it:
the same command, a child of its own.  A rehearsal says it is not a chip
run, and without --rehearsal the same command refuses the CPU."""

import json

import pytest

import _pb

CELLS = [w["name"] for w in _pb.bench()["workloads"]]


def _metric_names(group, cell):
    b = _pb.bench()
    return {m["name"] for m in b[group] if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell(cell, tmp_path):
    trace = 1 if cell == CELLS[0] else 0
    rc, out, err = _pb.run_cell(cell, str(tmp_path), seconds=1.5, trace=trace)
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    keys = set(res)
    assert _pb.RESULT_KEYS <= keys
    assert keys - _pb.RESULT_KEYS <= {"breakdown", "rehearsal", "compared", "companion_objects", "companion_s"}
    # an accepted configuration has no companion objects and sends none
    assert (res["companion_objects"], res["companion_s"]) == (0, 0.0)
    assert list(res)[-1] == "compared"  # the numbers compared come last
    assert "not a chip run" in res["rehearsal"]
    assert res["device"]["platform"] == "cpu"
    assert any("CPU REHEARSAL" in line for line in out[:-1])
    assert res["attempted"] > 0 and res["failed"] == 0
    # every number compared stands beside its limit, on stderr too
    for name, v in res["compared"].items():
        assert f"compared {name} = " in err and set(v) == {"value", "limit"}
    exact = {k: v for k, v in res["compared"].items() if k != "score_gap_mean"}
    assert all(v["value"] == 0 and v["limit"] == 0 for v in exact.values()), exact
    timeline = json.loads(out[-2])["timeline"]
    assert timeline["batches"] > 0
    assert timeline["compiled_in_window"] == 0, {k: timeline[k] for k in (
        "jax_compiles_in_window", "compiled_programs", "cache_entries")}
    assert timeline["companions"] == {"of_nodes": 0, "setup": 0, "setup_s": 0.0, "setup_echoes": 0, "window": 0,
                                      "window_s": 0.0, "window_echoes": 0, "window_echo_s": 0.0}
    assert timeline["os_cpu_count"] and timeline["compare_info"]["journal_bindings"] >= res["attempted"]
    group = "per_layer" if trace else "end_to_end"
    assert set(res["metrics"]) <= _metric_names(group, cell)
    if trace:
        assert "pack_width" not in res["metrics"]  # no batch of the basic cell is packed
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in res["breakdown"].values())
    else:
        assert set(res["metrics"]) == _metric_names("end_to_end", cell)
        assert all(m["value"] > 0 for m in res["metrics"].values())


def test_the_ordered_cell_traced_packs_at_width_one_and_names_its_idle_seconds(tmp_path):
    """One colour in one zone: the packer finds one class as large as the
    batch and falls back to the strictly ordered scan, at toy size too.
    The slice has the configuration's own length and ends inside the
    window; the idle seconds go to program spans."""
    cell = "podaffinity_5kn.backlog"
    assert cell in CELLS
    rc, out, err = _pb.run_cell(cell, str(tmp_path), seconds=2.5, trace=1)
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] and res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["pack_width"] == 1 and m["pack_us_per_pod"] > 0
    assert m["pass_device_us_per_pod"] > 0 and m["pass_fetch_wait_ms_per_batch"] > 0
    assert "pass_roofline" not in m  # a share of a roofline comes from a chip
    with open(_pb.ROOT + "/perfbench/configs/podaffinity_5kn.json") as f:
        assert json.load(f)["trace"]["seconds"] < 7.0  # its own bound, under the mix's
    assert res["device"]["window_s"] == pytest.approx(0.5, abs=0.2)  # spec.shrink's, at toy size
    gaps = dict(res["breakdown"]["idle_gaps"])
    assert gaps and not {"device", "between_batches"} & set(gaps)
    assert any("/" in name for name in gaps)  # program spans: pass/fetch_wait, hints/decode ...
    timeline = json.loads(out[-2])["timeline"]
    stop = timeline["trace_stop"]
    assert stop["stop_began_at_s"] < timeline["window_s"] and stop["records_left_out"] >= 1
    assert timeline["trace"]["pods_in_slice"] > 0
    assert isinstance(timeline["server_gc_gen2_collections"], int)


def test_without_rehearsal_the_cpu_is_refused(tmp_path):
    rc, out, err = _pb.run_cell(CELLS[0], str(tmp_path), rehearsal=False)
    assert rc != 0 and not [line for line in out if line.startswith("{")]
    assert "rehearsal" in err
    # and with the variable unset the serving process itself refuses the CPU
    rc, out, err = _pb.run_cell(CELLS[0], str(tmp_path), rehearsal=False,
                                env_extra={"JAX_PLATFORMS": ""}, timeout=300)
    assert rc != 0 and not [line for line in out if line.startswith("{")]
