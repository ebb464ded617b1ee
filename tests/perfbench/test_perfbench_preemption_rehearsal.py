"""A row in the shape of upstream's PreemptionAsync enters by new files and
one added entry of BENCHMARK.json each, and is rehearsed on the CPU through
``perfbench/run.py``, the benchmark's command: the throw-away row of
``preemption_stand_in.py`` (100 nodes, 400 low-priority pods, backlogs of
100, a churn pod every 100 ms, thirty in all, recycled room) in a
throw-away checkout.  Nothing that was there is edited; the committed
``BENCHMARK.json`` gains no configuration and no cell.

The row serves with ``--chunk-size 1``: at any larger chunk the program
lets chunk-mates commit onto a node whose room a nomination holds, past
that room, and the stand-in rightly counts them infeasible (PERF.md,
section 8: the next configuration's first repair).  A file of its own: its
two children take a quarter of a minute each, and a file's tests share one
worker."""

import json
import os
import shutil

import pytest

import _pb
import _upstream
import preemption_stand_in as stand_in

ROW = {"nodes": 100, "initial": 400, "measure": 100, "interval_ms": 100, "number": 30, "chunk_size": 1}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """(root, out): a copy of ``perfbench/`` with the row's files beside the
    accepted ones, its reference twice (as it is, and reprieving the pod
    bound first), and a BENCHMARK.json that is the committed one with an
    entry added to ``configs`` and one to ``workloads`` for each."""
    root = tmp_path_factory.mktemp("checkout")
    home = root / "perfbench"
    shutil.copytree(os.path.join(_pb.ROOT, "perfbench"), home, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(p) for p in map(str, home.rglob("*")) if os.path.isfile(p)}
    committed = _pb.bench()
    bench = json.loads(json.dumps(committed))
    with open(stand_in.__file__) as f:
        text = f.read()
    assert text.count('REPRIEVE = "bound_last"') == 1
    for name, reprieve in (("throwaway_preemption", "bound_last"), ("throwaway_preemption_other_victims", "bound_first")):
        config = stand_in.row(str(home / "configs"), name=name, **ROW)
        _upstream.hold(config, str(home / "configs"))
        (home / "configs" / (name + ".json")).write_text(json.dumps(config))
        (home / "references" / (name + ".py")).write_text(
            text.replace('REPRIEVE = "bound_last"', f'REPRIEVE = "{reprieve}"'))
        bench["configs"].append({"name": name, "source": config["source"][:200], "reduced": [], "why": "a test",
                                 "file": f"perfbench/configs/{name}.json"})
        bench["workloads"].append({"name": name + ".backlog", "config": name, "traffic": "backlog",
                                   "chips": 1, "why": "a test"})
    # only added entries: every other line of the file is the committed one
    assert {k: v for k, v in bench.items() if k not in ("configs", "workloads")} == \
        {k: v for k, v in committed.items() if k not in ("configs", "workloads")}
    for key in ("configs", "workloads"):
        assert bench[key][:len(committed[key])] == committed[key] and len(bench[key]) == len(committed[key]) + 2
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(_pb.ROOT, "kubernetes_tpu"), root / "kubernetes_tpu")
    yield str(root), str(tmp_path_factory.mktemp("out"))
    assert {p: os.path.getmtime(p) for p in before} == before  # nothing that was there was touched


def _run(checkout, cell):
    root, out = checkout
    rc, lines, err = _pb.run_cell(cell, out, seconds=3.0, root=root)
    assert rc == 0, err[-3000:]
    return json.loads(lines[-1]), json.loads(lines[-2])["timeline"]


def test_the_row_runs_through_run_py_with_its_nominations_acted_on_and_its_room_recycled(checkout):
    res, timeline = _run(checkout, "throwaway_preemption.backlog")
    assert res["correct"] and res["failed"] == 0 and list(res)[-1] == "compared"
    exact = {k: v["value"] for k, v in res["compared"].items() if k != "score_gap_mean"}
    assert "preemption_mismatch" in exact and not any(exact.values()), exact
    # the cell reports what the entries it was given name: set-up alone, no other metric lists it
    assert set(res["metrics"]) == {"setup_s"}
    # the churn inside the window: every pod nominated, its victims removed, asked again, bound
    n = res["churn_pods"]
    assert n > 0 and res["nominations"] >= n and res["preempt_retries"] >= n
    assert res["victims_removed"] >= res["nominations"]
    assert len(res["churn_due_to_bound_s"]) == n and all(x is not None and x > 0 for x in res["churn_due_to_bound_s"])
    churn = timeline["churn"]
    assert churn["churn_pods"] > 0 and churn["asked"] == churn["churn_pods"] + n  # it started in the warm-up
    assert churn["asked"] <= ROW["number"] and not churn["exhausted"]
    # the room: the warm-up's pods in the set-up, each backlog's before the next
    assert res["recycle_s"] > 0 and 0 < res["recycle_objects"] <= res["attempted"] - ROW["measure"]
    assert timeline["recycle"]["setup"] == timeline["plan"]["warm"]
    assert timeline["window_s"] >= 3.0  # the window's length less the recycle's seconds
    assert timeline["compiled_in_window"] == 0
    assert timeline["compare_info"]["removed"] >= res["victims_removed"] + res["recycle_objects"]


def test_a_reference_that_chooses_other_victims_is_not_correct(checkout):
    res, timeline = _run(checkout, "throwaway_preemption_other_victims.backlog")
    numbers = {k: v["value"] for k, v in res["compared"].items()}
    assert not res["correct"] and numbers["preemption_mismatch"] > 0
    assert any("the reference" in x for x in timeline["compare_info"]["infeasible_examples"])
