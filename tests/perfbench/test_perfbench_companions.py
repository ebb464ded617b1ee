"""A row with companion objects, rehearsed on the CPU through
``perfbench/run.py``'s own path: the throw-away row of ``csi_stand_in.py``
(a CSINode a node with an attach limit of 3, a claim and a volume a
measured pod) enters a throw-away checkout as files and entries, and a
child runs its backlog cell there as the driver would.  The committed
``BENCHMARK.json`` gains no configuration and no cell.

A file of its own: its two children take half a minute each, and a file's
tests share one worker."""

import json
import os
import shutil

import pytest

import _pb
import _upstream
import csi_stand_in
from perfbench import correct


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """(root, out): a copy of ``perfbench/`` with the row's files beside the
    accepted ones, its reference twice (as it is, and with the attach limit
    read as 2), and a BENCHMARK.json that names both."""
    root = tmp_path_factory.mktemp("checkout")
    home = root / "perfbench"
    shutil.copytree(os.path.join(_pb.ROOT, "perfbench"), home, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(p) for p in map(str, home.rglob("*")) if os.path.isfile(p)}
    config = csi_stand_in.row(str(home / "configs"))
    _upstream.hold(config, str(home / "configs"))
    bench = _pb.bench()
    with open(csi_stand_in.__file__) as f:
        text = f.read()
    for name, reads in (("throwaway_csi", "None"), ("throwaway_csi_limit_read_as_2", "2")):
        (home / "configs" / (name + ".json")).write_text(json.dumps(dict(config, name=name, reference=name)))
        assert text.count("LIMIT_READ_AS = None") == 1
        (home / "references" / (name + ".py")).write_text(text.replace("LIMIT_READ_AS = None", "LIMIT_READ_AS = " + reads))
        bench["configs"].append({"name": name, "source": "a test", "reduced": [], "why": "a test",
                                 "file": f"perfbench/configs/{name}.json"})
        bench["workloads"].append({"name": name + ".backlog", "config": name, "traffic": "backlog",
                                   "chips": 1, "why": "a test"})
        for m in bench["end_to_end"]:
            if m["name"] == "pods_per_s":
                m["workloads"].append(name + ".backlog")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(_pb.ROOT, "kubernetes_tpu"), root / "kubernetes_tpu")
    yield str(root), str(tmp_path_factory.mktemp("out"))
    assert {p: os.path.getmtime(p) for p in before} == before  # nothing that was there was touched


def _run(checkout, cell):
    root, out = checkout
    # long enough that every prebuilt pod is sent: 10 backlogs of 150 on 600
    # nodes of 3 volumes each (the window closes early, and says so)
    rc, lines, err = _pb.run_cell(cell, out, seconds=120.0, root=root)
    assert rc == 0, err[-3000:]
    return json.loads(lines[-1]), json.loads(lines[-2])["timeline"]


def test_the_stand_in_row_runs_through_run_py_with_its_companions_inside_the_window(checkout):
    res, timeline = _run(checkout, "throwaway_csi.backlog")
    assert res["correct"] and res["failed"] == 0 and list(res)[-1] == "compared"
    exact = {k: v["value"] for k, v in res["compared"].items() if k != "score_gap_mean"}
    assert not any(exact.values()), exact  # no claim unresolved, no node over 3 volumes, none lost
    sent = timeline["companions"]
    initial, warm = timeline["plan"]["initial"], timeline["plan"]["warm"]
    assert (initial, warm, sent["of_nodes"]) == (40, 148, 600)
    # a CSINode a node; a claim and a volume for every pod of the measured
    # template, the warm-up's in the set-up and the window's inside the window
    assert sent["setup"] == 2 * warm and sent["window"] == 2 * res["attempted"] == 2 * timeline["plan"]["window"]
    assert res["companion_objects"] == timeline["companion_objects"] == 600 + 2 * (warm + res["attempted"])
    assert res["companion_s"] == sent["window_s"] > 0
    assert timeline["hint_frames"] == res["attempted"] // 150  # one hint frame a backlog, its companions before it
    # every answered pod went back bound, so no later claim or volume rolled a decision back
    assert sent["setup_echoes"] == initial + warm and sent["window_echoes"] == res["attempted"]
    assert timeline["push"]["invalidations"] == 0
    assert timeline["push"]["decided"] == timeline["compare_info"]["replayed"] == initial + warm + res["attempted"]
    # the attach limit bites: the fullest node stands at it, none over it
    assert timeline["compare_info"]["infeasible_examples"] == ["most distinct volumes of one driver on a node: 3"]
    assert "early" in res["window_short"]  # every prebuilt pod was sent: the plan stops at the cluster's room


def test_a_reference_that_reads_the_limit_as_two_finds_nodes_over_it_and_nothing_else(checkout):
    res, timeline = _run(checkout, "throwaway_csi_limit_read_as_2.backlog")
    numbers = {k: v["value"] for k, v in res["compared"].items()}
    assert not res["correct"] and numbers["over_capacity_nodes"] > 0
    assert not any(v for k, v in numbers.items() if k not in ("over_capacity_nodes", "score_gap_mean"))
    assert numbers["score_gap_mean"] <= res["compared"]["score_gap_mean"]["limit"]


class _WithKinds:
    COMPANION_KINDS = ("CSINode", "PersistentVolume")

    def __init__(self):
        self.seen = []

    def Cluster(self, node_jsons, names, companions):  # noqa: N802
        self.seen.append(("cluster", companions))
        return "cluster"

    def pod_facts(self, raw, companions):
        return raw, companions


class _Without:
    def Cluster(self, node_jsons, names):  # noqa: N802
        return ("cluster", len(node_jsons))

    def pod_facts(self, raw):
        return raw


class _Companions:
    of_nodes = [("CSINode", [b"c0", b"c1"]), ("Lease", [b"l0", b"l1"])]

    def of_uid(self, uid):
        return {"PersistentVolumeClaim": [b"pvc-" + uid.encode()], "PersistentVolume": [b"pv-" + uid.encode()]}


def test_a_reference_gets_the_companions_it_asks_for_and_one_that_asks_for_none_is_called_as_it_was():
    ref = _WithKinds()
    cluster, facts = correct.stand_up(ref, [b"n0", b"n1"], ["n0", "n1"], _Companions())
    assert cluster == "cluster" and ref.seen == [("cluster", {"CSINode": [b"c0", b"c1"], "PersistentVolume": []})]
    assert facts("ns/p", b"raw") == (b"raw", {"CSINode": [], "PersistentVolume": [b"pv-ns/p"]})
    # a configuration without companions under such a reference: empty lists, not an error
    cluster, facts = correct.stand_up(ref, [b"n0"], ["n0"], None)
    assert facts("ns/p", b"raw") == (b"raw", {"CSINode": [], "PersistentVolume": []})
    cluster, facts = correct.stand_up(_Without(), [b"n0", b"n1"], ["n0", "n1"], _Companions())
    assert cluster == ("cluster", 2) and facts("ns/p", b"raw") == b"raw"
    for name in ("default_profile", "topology_spreading"):
        assert not hasattr(correct.load_reference(name), "COMPANION_KINDS")
