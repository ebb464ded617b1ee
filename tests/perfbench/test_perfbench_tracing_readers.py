"""PR 38's six readers: what the served path does with the device idle.

Each gives the issue's arithmetic on hand-made material and nothing where
the program has no such span or counter (the parent's tree): the wait for
the client (``wire_await_us_per_pod``), admission alone
(``hint_admit_us_per_pod``), an object's three stages (``object_*``) and
the journal group's write (``journal_write_ms_per_batch``).  The entries
stand after the accepted ones, and the cell with objects in its window,
rehearsed on the CPU, reports all six."""

import json
import os
import types

import pytest

import _pb
from perfbench import report, spec

HOME = os.path.join(_pb.ROOT, "perfbench")
CSI = "csi_pvs_5kn.backlog"
BACKLOGS = ["basic_5kn.backlog", "podaffinity_5kn.backlog", "topology_spreading_5kn.backlog", CSI]
NEW = {  # name: (unit, source, layer, workloads), in the issue's order
    "hint_admit_us_per_pod": ("us/pod", "program_span", "batch forming", BACKLOGS),
    "wire_await_us_per_pod": ("us/pod", "program_counter", "plugin emulation", BACKLOGS),
    "object_decode_us_per_object": ("us/object", "program_counter", "wire and hints", [CSI]),
    "object_scope_us_per_object": ("us/object", "program_counter", "wire and hints", [CSI]),
    "object_apply_us_per_object": ("us/object", "program_counter", "wire and hints", [CSI]),
    "journal_write_ms_per_batch": ("ms", "program_span", "journal", BACKLOGS),
}
LAST_ACCEPTED = "echo_us_per_pod"  # the benchmark's last entry when PR 38 began


def _ctx(records=(), before=None, after=None):
    before, after = before or {}, after or {}
    c = types.SimpleNamespace(records=list(records), window_records=list(records), before=before,
                              after=after, window=types.SimpleNamespace(), trace=None)
    c.delta = lambda key: after.get(key, 0.0) - before.get(key, 0.0)
    c.pods = c.window_pods = lambda: sum(int(r.get("pods", 0)) for r in c.records)
    return c


ADMIT = 'scheduler_phase_duration_seconds_sum{phase="hints/admit"}'
AWAIT = "scheduler_wire_await_seconds_total"
STAGE = 'scheduler_object_add_seconds_total{stage="%s"}'
ADDED = 'scheduler_objects_added_total{kind="%s"}'
RECORDS = [  # a backlog of 5,000 pods in two batches, as the program leaves them since PR 38
    {"pods": 4095, "spans": [["hints/admit", 0, 90000, -1], ["admit/sort", 10, 5000, 0],
                             ["admit/build", 5020, 70000, 0], ["admit/enqueue", 75030, 14000, 0],
                             ["pipeline/drain", 100000, 9000, -1], ["drain/journal_write", 102000, 2500, 4]]},
    {"pods": 905, "spans": [["pipeline/drain", 10, 2000, -1], ["drain/journal_write", 500, 700, 0]]},
]
BEFORE = {ADMIT: 4.0, AWAIT: 10.0, STAGE % "decode": 1.0, STAGE % "scope": 0.5, STAGE % "apply": 2.0,
          ADDED % "PersistentVolumeClaim": 100.0, ADDED % "Pod": 40.0}
AFTER = {ADMIT: 4.125, AWAIT: 10.6, STAGE % "decode": 1.45, STAGE % "scope": 0.53, STAGE % "apply": 2.3,
         ADDED % "PersistentVolumeClaim": 5100.0, ADDED % "PersistentVolume": 5000.0, ADDED % "Pod": 5040.0}
OBJECTS = 5000 + 5000 + 5000  # claims, volumes and bind echoes added inside the window
READINGS = {
    "hint_admit_us_per_pod": 0.125 / 5000 * 1e6,
    "wire_await_us_per_pod": 0.6 / 5000 * 1e6,
    "object_decode_us_per_object": 0.45 / OBJECTS * 1e6,
    "object_scope_us_per_object": 0.03 / OBJECTS * 1e6,
    "object_apply_us_per_object": 0.3 / OBJECTS * 1e6,
    "journal_write_ms_per_batch": (2500 + 700) * 1e-3 / 2,
}


@pytest.mark.parametrize("name", list(NEW))
def test_each_reader_gives_the_arithmetic_and_nothing_on_a_parent_tree(name):
    reader = report.load_reader(HOME, name)
    assert reader.read(_ctx(RECORDS, BEFORE, AFTER)) == pytest.approx(READINGS[name])
    # the parent: `hints/admit` without children (its histogram held the
    # top-up parse), no wait counter, no stage counters, no write span
    parent = [{"pods": 4095, "spans": [["hints/admit", 0, 90000, -1], ["pipeline/drain", 100000, 9000, -1]]},
              {"pods": 905, "spans": [["pipeline/drain", 10, 2000, -1]]}]
    assert reader.read(_ctx(parent, {ADMIT: 4.0}, {ADMIT: 4.2})) is None
    assert reader.read(_ctx([], {}, {})) is None


def test_a_window_that_added_no_object_reports_no_stage():
    """The backlog cells without companions: the counters are there and
    did not move, so there is no object to divide by."""
    quiet = dict(BEFORE)
    for name in ("object_decode_us_per_object", "object_scope_us_per_object", "object_apply_us_per_object"):
        assert report.load_reader(HOME, name).read(_ctx(RECORDS, BEFORE, quiet)) is None


def test_the_six_entries_stand_after_the_accepted_ones_as_the_issue_gives_them():
    bench = _pb.bench()
    names = [m["name"] for m in bench["per_layer"]]
    accepted = names[:names.index(LAST_ACCEPTED) + 1]
    assert names[len(accepted):len(accepted) + len(NEW)] == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] in accepted}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            unit, source, layer, workloads = NEW[m["name"]]
            assert m == {"name": m["name"], "unit": unit, "better": "lower", "source": source,
                         "layer": layer, "moves": "pods_per_s", "workloads": workloads}
            assert layer in layers  # an existing layer, letter for letter
            assert os.path.exists(os.path.join(HOME, "metrics", m["name"] + ".py"))


def test_the_cell_with_objects_in_its_window_reports_all_six_on_the_cpu(tmp_path):
    """Rehearsed, traced, as the driver would run it: the three object
    stages (claims, volumes and bind echoes inside the window), the wait,
    admission and the journal's write; and the idle seconds name the new
    spans."""
    rc, out, err = _pb.run_cell(CSI, str(tmp_path), seconds=1.5, trace=1)
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] and res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m) <= {x["name"] for x in spec.metrics_for(_pb.bench(), "per_layer", CSI)}
    assert all(m[name] > 0 for name in NEW), {name: m[name] for name in NEW}
    gaps = {name for name, _ in res["breakdown"]["idle_gaps"]}
    assert {"objects/add", "wire/await", "admit/build"} & gaps, gaps
