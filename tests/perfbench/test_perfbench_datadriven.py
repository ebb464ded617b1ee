"""The harness is driven by data: a configuration, a traffic mix, a cell and
a per-layer metric are each added as new files and entries, with no edit to
a file that is there.  And a run whose timed path is broken underneath
(an answer altered where the wire hands it over) comes out as not correct."""

import copy
import json
import os
import shutil
import time

import _pb
import _upstream


def test_a_new_configuration_mix_cell_and_metric_are_files_and_entries(tmp_path):
    root = tmp_path / "checkout"
    home = root / "perfbench"
    shutil.copytree(os.path.join(_pb.ROOT, "perfbench"), home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(p) for p in map(str, home.rglob("*")) if os.path.isfile(p)}
    bench = _pb.bench()
    # a configuration of its own, in the shape of upstream's TopologySpreading
    # row: three zones by a cycle that names them, initial pods of a template
    # without labels, measured pods with a DoNotSchedule constraint; with its
    # excerpt, its measured pods' template and a reference file of its own
    config = _upstream.spreading_row(str(home / "configs"))
    config["reference"] = "throwaway_spreading"
    (home / "configs" / "throwaway_spreading.json").write_text(json.dumps(config))
    shutil.copy(_upstream.SPREADING_STAND_IN, home / "references" / "throwaway_spreading.py")
    _upstream.hold(config, str(home / "configs"))
    # a mix of its own: two rates in one window
    mix = {"name": "steps", "loop": "open", "rate_pods_per_s": 50, "hint_flush_delay_s": 0.002,
           "segments": [{"share": 0.5, "rate_pods_per_s": 30}, {"share": 0.5, "rate_pods_per_s": 70}],  # piecewise
           "warmup": {"initial_pods": "initial_pods", "full_batches": 1, "short_pods": 10, "arrivals_s": 0.5},
           "trace": {"seconds": 0.5}}
    (home / "traffic" / "steps.json").write_text(json.dumps(mix))
    # a per-layer metric of its own
    (home / "metrics" / "wire_ms_per_miss.py").write_text(
        '"""layer: wire. source: host_clock."""\n\n\ndef read(ctx):\n'
        "    w = ctx.window\n    return w.wire_s / w.misses * 1e3 if w.misses else None\n")
    bench["configs"].append({"name": "throwaway_spreading", "source": "a test", "reduced": [],
                             "file": "perfbench/configs/throwaway_spreading.json", "why": "a test"})
    bench["workloads"].append({"name": "throwaway_spreading.steps", "config": "throwaway_spreading",
                               "traffic": "steps", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"].startswith("decision_"):
            m["workloads"].append("throwaway_spreading.steps")
    bench["per_layer"].append({"name": "wire_ms_per_miss", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "wire and hints",
                               "moves": "decision_p50_ms", "workloads": ["throwaway_spreading.steps"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the throw-away checkout runs the real program
    os.symlink(os.path.join(_pb.ROOT, "kubernetes_tpu"), root / "kubernetes_tpu")
    for trace in (0, 1):
        rc, out, err = _pb.run_cell("throwaway_spreading.steps", str(tmp_path / "out"), seconds=1.5,
                                    trace=trace, root=str(root))
        assert rc == 0, err[-3000:]
        res = json.loads(out[-1])
        assert res["attempted"] == 90 and res["failed"] == 0  # the toy rates 36/s and 84/s, 0.75 s each
        # resources, the journal and the constraint (the stand-in counts a
        # pod bound past max_skew as infeasible) hold on every answer
        exact = {k: v["value"] for k, v in res["compared"].items() if k != "score_gap_mean"}
        assert not any(exact.values()), exact
        info = json.loads(out[-2])["timeline"]["compare_info"]
        assert info["replayed"] == 40 + 84 + 60 + 90  # initial, warm-up (64 + 20), warm arrivals, the window
        (skew,) = info["infeasible_examples"]  # the stand-in's one line, and no pod past the constraint
        assert skew.startswith("largest skew among the measured pods") and int(skew.split()[-1]) <= 5, skew
        if trace:
            assert res["metrics"]["wire_ms_per_miss"]["value"] > 0
        else:
            assert {"decision_p50_ms", "setup_s"} <= set(res["metrics"])
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before  # nothing that was there was touched


def test_an_altered_answer_comes_out_as_not_correct(tmp_path, monkeypatch):
    """The rest of a run, driven past the look for a chip, with the wire
    handing over another node than the sidecar committed for a few pods."""
    from perfbench import cell, correct, spec, wire

    bench = spec.load(os.path.join(_pb.ROOT, "BENCHMARK.json"))
    c, config, mix = spec.cell(bench, "basic_5kn.backlog")
    config, mix = copy.deepcopy(config), copy.deepcopy(mix)
    spec.shrink(config, mix)
    real = wire.Conn.schedule_raw
    calls = {"n": 0}

    def altered(self, data):
        node = real(self, data)
        calls["n"] += 1
        return "node-0" if calls["n"] % 7 == 0 and node != "node-0" else node

    monkeypatch.setattr(wire.Conn, "schedule_raw", altered)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    raw = cell.run(_pb.ROOT, c, config, mix, 2400000778, 1.0, False, True,
                   time.monotonic(), out_root=str(tmp_path))
    numbers = raw["verdict"]["numbers"]
    assert calls["n"] >= 7
    assert not correct.verdict(numbers)
    assert numbers["journal_lost"]["value"] > 0  # the journal holds what was committed
    assert numbers["unanswered"]["value"] == 0
