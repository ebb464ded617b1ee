"""BENCHMARK.json against the contract's letter, and each file it names."""

import json
import os
import re

import _pb
from perfbench import report, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
B = _pb.bench()


def _line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(_pb.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert 1 <= len(B["paths"]) <= 16 and all(PATH.match(p) and ".." not in p for p in B["paths"])
    assert 1 <= len(B["command"]) <= 32 and all(_line_ok(w) for w in B["command"])
    assert B["command"][1].startswith(B["paths"][0] + "/")
    # the budget of a full check with all 24 cells
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs_cells_and_their_files():
    under = tuple(p + "/" for p in B["paths"])
    names = [c["name"] for c in B["configs"]]
    assert len(set(names)) == len(names) and len(set(c["file"] for c in B["configs"])) == len(names)
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line_ok(c["source"]) and _line_ok(c["why"])
        assert c["file"].startswith(under) and len(c["reduced"]) <= 16
        with open(os.path.join(_pb.ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
        for key in ("source", "assumed", "guarantees", "serve", "capacity"):
            assert doc[key]
        s = doc["serve"]
        assert (s["journal_fsync"], s["speculate"], s["pipeline_depth"], s["batch_size"], s["chunk_size"]) == \
            ("always", True, 2, 4096, 64)
    cells = [w["name"] for w in B["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in B["workloads"]}) == len(cells)
    assert {w["config"] for w in B["workloads"]} == set(names)
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert _line_ok(w["why"])
        cell, config, mix = spec.cell(spec.load(os.path.join(_pb.ROOT, "BENCHMARK.json")), w["name"])
        assert mix["loop"] in ("closed", "open") and mix["name"] == w["traffic"]
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, len(cells) // 2)


def _quantity(q) -> int:
    """A Kubernetes quantity in the wire's canonical units: millicores for
    cpu-like values (``4``, ``100m``), bytes for ``Mi``/``Gi``."""
    q = str(q)
    for suffix, mult in (("Gi", 1 << 30), ("Mi", 1 << 20)):
        if q.endswith(suffix):
            return int(q[:-2]) * mult
    return int(q[:-1]) if q.endswith("m") else int(q) * 1000


def test_each_configuration_is_its_upstream_templates_in_wire_form():
    """The templates a run sends say what the vendored upstream YAML says:
    sizes, labels, the affinity term, the row's counts and namespaces."""
    import yaml

    home = os.path.join(_pb.ROOT, "perfbench", "configs")
    with open(os.path.join(home, "upstream", "performance-config.excerpt.yaml")) as f:
        cases = {c["name"]: c for c in yaml.safe_load(f)}
    named = {c["name"]: c for c in B["configs"]}
    files = sorted(f for f in os.listdir(home) if f.endswith(".json"))
    assert len(files) == len(cases) and set(named) <= {f[:-5] for f in files}
    for name in files:
        with open(os.path.join(home, name)) as f:
            doc = json.load(f)
        # what BENCHMARK.json says of a configuration is what its file says
        c = named.get(doc["name"], {"reduced": [], "source": doc["source"][:200]})
        assert doc["source"].startswith(c["source"]) and doc["reduced"] == c["reduced"] == []
        assert c["source"].startswith("https://github.com/kubernetes/kubernetes/")
        up = doc["upstream"]
        case = cases[up["test_case"]]
        assert up["test_case"] + "/" + up["workload"] in c["source"]
        (row,) = [w for w in case["workloads"] if w["name"] == up["workload"]]
        assert row["params"] == up["params"]
        assert (doc["cluster"]["nodes"], doc["initial_pods"], doc["measure_pods"]) == \
            (row["params"]["initNodes"], row["params"]["initPods"], row["params"]["measurePods"])
        assert os.path.basename(up["pod_template"]) == os.path.basename(case["defaultPodTemplatePath"])
        ops = case["workloadTemplate"]
        # nodes: upstream's node-default.yaml, with the labels the row's createNodes op adds
        with open(os.path.join(home, up["node_template"])) as f:
            node = yaml.safe_load(f)
        wire_node = doc["cluster"]["node_template"]
        cap = {k: (int(v) if k == "pods" else _quantity(v)) for k, v in node["status"]["capacity"].items()}
        assert wire_node["status"]["capacity"] == cap == wire_node["status"]["allocatable"]
        strategy = ops[0].get("labelNodePrepareStrategy")
        labels = {strategy["labelKey"]: strategy["labelValues"][0]} if strategy else {}
        assert len((strategy or {}).get("labelValues", [0])) == 1
        assert wire_node["metadata"]["labels"] == labels == up.get("node_labels", {})
        assert not doc["cluster"]["cycles"] and not doc["pod"]["cycles"]
        # pods: the row's template; the two createPods ops' namespaces
        with open(os.path.join(home, up["pod_template"])) as f:
            pod = yaml.safe_load(f)
        wire_pod = doc["pod"]["template"]
        assert wire_pod["metadata"]["labels"] == pod["metadata"].get("labels", {})
        (cont,), (wcont,) = pod["spec"]["containers"], wire_pod["spec"]["containers"]
        for side in ("requests", "limits"):
            assert wcont[side] == {k: _quantity(v) for k, v in cont["resources"][side].items()}
        assert wcont["images"] == [cont["image"]] and wcont["name"] == cont["name"]
        assert [p["container_port"] for p in wcont["ports"]] == [p["containerPort"] for p in cont["ports"]]
        assert not any(p["host_port"] for p in wcont["ports"])
        creates = [op for op in ops if op["opcode"] == "createPods"]
        spaces = [op.get("namespace", f"namespace-{ops.index(op)}") for op in creates]
        assert [doc["pod"]["namespaces"]["initial"], doc["pod"]["namespaces"]["measured"]] == spaces
        assert wire_pod["metadata"]["namespace"] == "{namespace}"
        aff = pod["spec"].get("affinity")
        if aff is None:
            assert wire_pod["spec"]["affinity"] is None
            continue
        (term,) = aff["podAffinity"]["requiredDuringSchedulingIgnoredDuringExecution"]
        wire_aff = wire_pod["spec"]["affinity"]
        assert wire_aff["node_affinity"] is None and wire_aff["pod_anti_affinity"] is None
        assert wire_aff["pod_affinity"]["preferred"] == []
        (wterm,) = wire_aff["pod_affinity"]["required"]
        assert wterm["topology_key"] == term["topologyKey"] and wterm["namespaces"] == term["namespaces"]
        assert wterm["namespace_selector"] is None
        assert wterm["label_selector"] == {
            "match_labels": [list(kv) for kv in term["labelSelector"]["matchLabels"].items()],
            "match_expressions": []}
        assert set(spaces) <= set(term["namespaces"])


def test_metrics_names_units_and_readers():
    cells = {w["name"] for w in B["workloads"]}
    seen = set()
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(B["end_to_end"]) <= 16 and 1 <= len(B["per_layer"]) <= 128
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _line_ok(m["layer"])
        layers.add(m["layer"])
        # each listed cell reports the end-to-end metric this one moves
        target = next(x for x in B["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(target.get("workloads", cells))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and m["name"] not in seen
        seen.add(m["name"])
        assert set(m.get("workloads", cells)) <= cells
        reader = report.load_reader(os.path.join(_pb.ROOT, B["paths"][0]), m["name"])
        assert callable(reader.read) and reader.__doc__
    # the layers PERF.md lists, letter for letter
    with open(os.path.join(_pb.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"**{layer}**" in perf, layer
    for w in B["workloads"]:
        assert len(spec.metrics_for(B, "end_to_end", w["name"])) >= 2
        assert len(spec.metrics_for(B, "per_layer", w["name"])) >= 1


def test_every_file_under_paths_is_named_from_permitted_characters():
    for p in B["paths"]:
        for base, dirs, files in os.walk(os.path.join(_pb.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), _pb.ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_reader_that_finds_nothing_returns_nothing():
    """No records, no trace, no arrivals: every reader stays silent, and
    none answers 0 for a share."""
    from perfbench import loops

    raw = {"window": loops.Window(), "records": [], "scrape0": {}, "scrape1": {},
           "config": {}, "mix": {}, "device": {"kind": "TPU v5 lite"}, "setup_s": 1.0}
    ctx = report.Ctx(raw, None)
    home = os.path.join(_pb.ROOT, B["paths"][0])
    for m in B["per_layer"] + [x for x in B["end_to_end"] if x["name"] != "setup_s"]:
        assert report.load_reader(home, m["name"]).read(ctx) is None, m["name"]
