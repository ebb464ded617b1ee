"""BENCHMARK.json against the contract's letter, and each file it names."""

import json
import os
import re
import shutil

import _pb
import _upstream
import pytest
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


CONFIG_HOME = os.path.join(_pb.ROOT, "perfbench", "configs")
CONFIG_FILES = sorted(f for f in os.listdir(CONFIG_HOME) if f.endswith(".json"))


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_each_configuration_is_its_upstream_templates_in_wire_form(name):
    """The templates a run sends say what the vendored upstream YAML says:
    sizes, labels, the affinity term, the spread constraints, the row's
    counts, label strategy and namespaces, a template for each createPods
    op.  One case a configuration file, each read through its own
    ``upstream`` block (``_upstream.hold``); none is named here."""
    named = {c["name"]: c for c in B["configs"]}
    assert set(named) <= {f[:-5] for f in CONFIG_FILES}
    with open(os.path.join(CONFIG_HOME, name)) as f:
        doc = json.load(f)
    assert doc["name"] == name[:-5]
    _upstream.hold(doc, CONFIG_HOME, named.get(doc["name"]))


@pytest.fixture
def spreading(tmp_path):
    """(configuration, home): a row in TopologySpreading's shape, entered
    into a copy of ``perfbench/configs`` as new files."""
    home = str(tmp_path / "configs")
    shutil.copytree(CONFIG_HOME, home)
    return _upstream.spreading_row(home), home


def test_a_row_in_topology_spreadings_shape_enters_beside_the_accepted_ones(spreading):
    """Two pod templates, a label strategy of three values, a spread
    constraint, an excerpt of its own."""
    doc, home = spreading
    _upstream.hold(doc, home)
    # the accepted excerpt was not needed, and still serves its own
    for name in CONFIG_FILES:
        with open(os.path.join(home, name)) as f:
            _upstream.hold(json.load(f), home)


def _constraint(doc):
    return doc["pod"]["template"]["spec"]["topology_spread_constraints"][0]


def _node_labels(doc):
    return doc["cluster"]["node_template"]["metadata"]["labels"]


ALTERATIONS = {
    "max_skew": lambda doc: _constraint(doc).update(max_skew=1),
    "zone_order": lambda doc: doc["cluster"]["cycles"]["zone"]["values"].reverse(),
    "initial_labels": lambda doc: doc["pod"]["initial_template"]["metadata"].update(labels={"color": "blue"}),
    "initial_template_left_out": lambda doc: doc["pod"].pop("initial_template"),
    "literal_zone": lambda doc: _node_labels(doc).update({_upstream.ZONE: "moon-1"}),
    "a_field_upstream_does_not_have": lambda doc: doc["pod"]["template"]["spec"].update(node_selector={"disk": "ssd"}),
    "a_constraint_default": lambda doc: _constraint(doc).update(node_taints_policy="Honor"),
}


@pytest.mark.parametrize("how", sorted(ALTERATIONS))
def test_an_altered_wire_form_fails_the_comparison(spreading, how):
    doc, home = spreading
    ALTERATIONS[how](doc)
    with pytest.raises(AssertionError):
        _upstream.hold(doc, home)


def test_an_upstream_field_the_comparison_does_not_know_fails_it(spreading):
    import yaml

    doc, home = spreading
    path = os.path.join(home, doc["upstream"]["pod_template"])
    with open(path) as f:
        pod = yaml.safe_load(f)
    pod["spec"]["priorityClassName"] = "high"
    with open(path, "w") as f:
        yaml.safe_dump(pod, f)
    with pytest.raises(AssertionError, match="priorityClassName"):
        _upstream.hold(doc, home)


@pytest.mark.parametrize("name, nodes, pods, uids", [
    ("basic_5kn", "441716391a32b8ae", "51040e5ea2cd8aa9", "b506a807ed02b990"),
    ("podaffinity_5kn", "c9176c2467a23449", "1eae8b7d3c6829a2", "9e0507630f73928a"),
])
def test_the_accepted_configurations_objects_are_the_parents(name, nodes, pods, uids):
    """Digests taken on PR 27's tree at seed 2800000001 (the initial pods
    and 700 more): what an accepted cell sends is byte for byte what it
    sent before a configuration could carry a second template."""
    from perfbench import objects

    with open(os.path.join(CONFIG_HOME, name + ".json")) as f:
        config = json.load(f)
    n = objects.Nodes(config, 2800000001)
    p = objects.Pods(config, 2800000001, config["initial_pods"] + 700, config["initial_pods"])
    assert (_digest(n.jsons), _digest(p.jsons), _digest(p.uids)) == (nodes, pods, uids)


def _digest(items):
    import hashlib

    h = hashlib.sha256()
    for x in items:
        h.update(x if isinstance(x, bytes) else x.encode())
        h.update(b"\n")
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name, seed, want", [
    ("basic_5kn", 3500000011, "8017ea597b7330cb 27f4019adca47a51 781e0f271f6ecac9 d29c47a68487456d cde301ed4c6dd944 0a9a4242c525a597"),
    ("basic_5kn", 2147483659, "b06fcb47915f6a30 0dfd4d65e384177a 9ee895653860cf8b 885bea4246c28994 8165d1c7d20fcc41 4bb7b0ecfadcaa54"),
    ("podaffinity_5kn", 3500000011, "8017ea597b7330cb 6942a9bf01065c7c 781e0f271f6ecac9 5a3921bc978fdae3 7b6aa0a1e5a6ffb6 d58c5893e26d0b88"),
    ("podaffinity_5kn", 2147483659, "b06fcb47915f6a30 35a59c5593eaf8b0 9ee895653860cf8b f4b1aec36da043d0 128b985931131609 eb03af04dec008b5"),
    ("topology_spreading_5kn", 3500000011, "8017ea597b7330cb 34b05a4f9470f699 781e0f271f6ecac9 d29c47a68487456d a36bcf574e74dce6 21be86dd89727c91"),
    ("topology_spreading_5kn", 2147483659, "b06fcb47915f6a30 e5331fc3028f1409 9ee895653860cf8b 885bea4246c28994 704f032a445c7754 2a02e5cf01c7b655"),
])
def test_the_accepted_configurations_traffic_did_not_move_when_companions_came(name, seed, want):
    """Digests taken on PR 34's tree (e6bce10), before ``objects.py`` learnt
    companions: the node names and JSON, the pod names, uids and JSON of a
    whole run's plan at ``spec.shrink`` sizes, and the first measured hint
    frame.  An accepted configuration has no companions, sends none, echoes
    no bind, and draws from the seed what it drew."""
    from perfbench import cell, objects, traffic, wire

    with open(os.path.join(CONFIG_HOME, name + ".json")) as f:
        config = json.load(f)
    mix = traffic.load(os.path.join(_pb.ROOT, "perfbench", "traffic", "backlog.json"))
    spec.shrink(config, mix)
    plan = cell.pods_needed(config, mix, 1.5, 0)
    n = objects.Nodes(config, seed)
    p = objects.Pods(config, seed, plan["initial"] + plan["warm"] + plan["window"], plan["initial"])
    first = plan["initial"] + plan["warm"]
    frame = wire.pending_pods_frame(p.jsons[first: first + plan["backlog"]])
    got = [_digest(x) for x in (n.names, n.jsons, p.names, p.uids, p.jsons, [frame])]
    assert " ".join(got) == want
    c = objects.Companions(config, n, p, plan["initial"])
    assert (c.of_nodes, c.per_pod, c.node_objects, c.frames(0, len(p)), c.of_pod(first)) == ([], False, 0, (b"", 0), {})


@pytest.fixture
def csi(tmp_path):
    """(configuration, home): the throw-away row with companion objects,
    entered into a copy of ``perfbench/configs`` as new files."""
    import csi_stand_in

    home = str(tmp_path / "configs")
    shutil.copytree(CONFIG_HOME, home)
    return csi_stand_in.row(home), home


def test_a_row_with_companion_objects_enters_beside_the_accepted_ones(csi):
    """A nodeAllocatableStrategy, a persistentVolumeTemplatePath and a
    persistentVolumeClaimTemplatePath, an excerpt and two templates of its own."""
    doc, home = csi
    _upstream.hold(doc, home)
    for name in CONFIG_FILES:
        with open(os.path.join(home, name)) as f:
            _upstream.hold(json.load(f), home)


def _companion(doc, kind):
    (entry,) = [c for c in doc["pod"]["companions"] + doc["cluster"]["companions"] if c["kind"] == kind]
    return entry["template"]


def _excerpt(old, new):
    def alter(doc, home):
        path = os.path.join(home, doc["upstream"]["row"])
        with open(path) as f:
            text = f.read()
        assert old in text
        with open(path, "w") as f:
            f.write(text.replace(old, new))
    return alter


COMPANION_ALTERATIONS = {
    "the_drivers_count": (lambda doc, home: _companion(doc, "CSINode")["driver_limits"].update(
        {"throwaway.csi.example": 39}), "csiNodeAllocatable"),
    "the_nodes_own_allocatable": (lambda doc, home: doc["cluster"]["node_template"]["status"]["allocatable"].pop(
        "attachable-volumes-csi-throwaway.csi.example"), "nodeAllocatable"),
    "the_pvs_driver": (lambda doc, home: _companion(doc, "PersistentVolume").update(csi_driver="ebs.csi.aws.com"),
                       "csi.driver"),
    "the_claims_request": (lambda doc, home: _companion(doc, "PersistentVolumeClaim").update(request=1 << 29),
                           "resources.requests.storage"),
    "the_binding_volume_to_claim": (lambda doc, home: _companion(doc, "PersistentVolume").update(
        claim_ref="{namespace}/pvc-0"), "claim_ref"),
    "the_binding_claim_to_volume": (lambda doc, home: _companion(doc, "PersistentVolumeClaim").update(
        volume_name=""), "volume_name"),
    "one_claim_for_all_pods": (lambda doc, home: (
        _companion(doc, "PersistentVolumeClaim").update(name="pvc"),
        _companion(doc, "PersistentVolume").update(claim_ref="{namespace}/pvc")), "one for each pod"),
    "the_pods_volume_names_another_claim": (lambda doc, home: doc["pod"]["template"]["spec"]["volumes"][0].update(
        pvc="pvc-other"), None),
    "the_initial_pods_carry_the_volume_too": (lambda doc, home: doc["pod"].pop("initial_template"), "initial_template"),
    "companions_for_the_initial_pods": (lambda doc, home: doc["pod"]["companions"][0].update(of="all"), "of the measured pods"),
    "a_field_the_wire_lacks_left_unsaid": (lambda doc, home: doc["assumed"]["no_wire_field"].pop("migratedPlugins"),
                                           "no_wire_field"),
    "an_opcode_it_does_not_know": (_excerpt("  - opcode: createPods\n    countParam: $initPods\n",
                                            "  - opcode: churn\n  - opcode: createPods\n    countParam: $initPods\n"),
                                   "opcode churn"),
    "an_ops_key_it_does_not_know": (_excerpt("    collectMetrics: true\n", "    collectMetrics: true\n    skipWaitToCompletion: true\n"),
                                    "skipWaitToCompletion"),
    "a_strategy_it_does_not_know": (_excerpt("    nodeAllocatableStrategy:", "    uniqueNodeLabelStrategy:\n      labelKey: x\n    nodeAllocatableStrategy:"),
                                    "uniqueNodeLabelStrategy"),
    "a_key_of_the_strategy_it_does_not_know": (_excerpt("      migratedPlugins:", "      somethingNew: 1\n      migratedPlugins:"),
                                               "somethingNew"),
}


@pytest.mark.parametrize("how", sorted(COMPANION_ALTERATIONS))
def test_an_altered_companion_fails_the_comparison_by_name(csi, how):
    doc, home = csi
    alter, named = COMPANION_ALTERATIONS[how]
    alter(doc, home)
    with pytest.raises(AssertionError, match=named and named.replace(".", r"\.")):
        _upstream.hold(doc, home)


def test_companions_are_one_a_node_and_one_a_selected_pod_in_the_files_order(csi):
    from perfbench import cell, objects, wire

    config, _ = csi
    config["cluster"]["nodes"] = 5
    config["cluster"]["cycles"] = {"zone": {"values": ["a", "b"]}}
    config["cluster"]["companions"][0]["template"]["driver_limits"] = {"d-{zone}": 3}
    nodes = objects.Nodes(config, 7)
    pods = objects.Pods(config, 7, 6, initial=2)
    c = objects.Companions(config, nodes, pods, 2)
    ((kind, csinodes),) = c.of_nodes
    # one a node, in the nodes' shuffled order, filled as the node is
    assert kind == "CSINode" and [json.loads(j)["name"] for j in csinodes] == nodes.names
    assert {json.loads(j)["name"]: list(json.loads(j)["driver_limits"]) for j in csinodes} == \
        {f"node-{i}": ["d-" + "ab"[i % 2]] for i in range(5)}
    assert c.node_objects == 5 and c.per_pod
    # of "measured": none for the initial pods; kind by kind in the file's order
    assert c.frames(0, 2) == (b"", 0) and c.of_pod(1) == {}
    data, n = c.frames(1, 5)
    claims = [c.of_pod(k)["PersistentVolumeClaim"][0] for k in (2, 3, 4)]
    volumes = [c.of_pod(k)["PersistentVolume"][0] for k in (2, 3, 4)]
    assert n == 6 and data == b"".join([wire.add_frame("PersistentVolumeClaim", j) for j in claims]
                                       + [wire.add_frame("PersistentVolume", j) for j in volumes])
    for k in (2, 3, 4):
        pod, claim, volume = json.loads(pods.jsons[k]), json.loads(claims[k - 2]), json.loads(volumes[k - 2])
        assert pod["spec"]["volumes"][0]["pvc"] == claim["name"] == "pvc-" + pods.names[k]
        assert claim["namespace"] == pod["metadata"]["namespace"] == "namespace-2"
        assert claim["volume_name"] == volume["name"] and volume["claim_ref"] == f"namespace-2/{claim['name']}"
        assert c.of_uid(pods.uids[k]) == c.of_pod(k)
    config["pod"]["companions"][1]["of"] = "initial"
    config["pod"]["companions"][0]["of"] = "all"
    c = objects.Companions(config, nodes, pods, 2)
    assert [len(v) for v in c.of_pod(0).values()] == [1, 1] and list(c.of_pod(4)) == ["PersistentVolumeClaim"]
    assert c.frames(0, 6)[1] == 6 + 2
    config["pod"]["companions"][0]["of"] = "some"
    with pytest.raises(ValueError, match="some"):
        objects.Companions(config, nodes, pods, 2)
    # the pods' names and JSON are drawn as without companions
    bare = dict(config, pod={k: v for k, v in config["pod"].items() if k not in ("companions", "bind_echo")})
    assert objects.Pods(bare, 7, 6, initial=2).jsons == pods.jsons
    # a bind echo is the pod as it was sent, with its node; a pod that was
    # not sent as pending cannot be echoed, and says so
    echoed = json.loads(pods.jsons[3])
    echoed["spec"]["node_name"] = "node-4"
    assert pods.bound_frame(3, "node-4") == wire.add_frame("Pod", json.dumps(echoed, sort_keys=True).encode())
    pods.jsons[4] = pods.jsons[4].replace(b'"node_name": ""', b'"node_name": "node-9"')
    with pytest.raises(ValueError, match="cannot be echoed"):
        pods.bound_frame(4, "node-4")
    # the echo is the configuration's to state, in the one shape there is
    assert not cell.bind_echo(bare) and cell.bind_echo(config)
    with pytest.raises(SystemExit, match="bind_echo.*'answered'.*'each_backlog'"):
        cell.bind_echo(dict(config, pod=dict(config["pod"], bind_echo="each_backlog")))
    # the room: a further cap a node
    assert cell.cluster_pod_capacity(config) == 5 * 3
    del config["capacity"]["pods_per_node_max"]
    assert cell.cluster_pod_capacity(config) == 5 * 40


def test_a_template_for_each_createpods_op_and_cycles_that_name_their_values(spreading):
    from perfbench import cell, objects

    config, _ = spreading
    config["cluster"]["nodes"] = 7
    nodes = objects.Nodes(config, 5)
    zone = {json.loads(j)["metadata"]["name"]: json.loads(j)["metadata"]["labels"][_upstream.ZONE]
            for j in nodes.jsons}
    # dealt round-robin by the index before the seed's shuffle
    assert zone == {f"node-{i}": f"moon-{i % 3 + 1}" for i in range(7)}
    config["cluster"]["cycles"] = {"zone": {"prefix": "z", "count": 2}}  # the older form: a prefix and a count
    assert {json.loads(j)["metadata"]["labels"][_upstream.ZONE] for j in objects.Nodes(config, 5).jsons} == {"z0", "z1"}
    pods = [json.loads(j) for j in objects.Pods(config, 5, 6, initial=4).jsons]
    assert [p["metadata"]["labels"] for p in pods] == [{}] * 4 + [{"color": "blue"}] * 2
    assert [len(p["spec"]["topology_spread_constraints"]) for p in pods] == [0] * 4 + [1] * 2
    assert [p["metadata"]["namespace"] for p in pods] == ["namespace-1"] * 4 + ["namespace-2"] * 2
    # the room is reckoned with the larger request of the two templates
    assert cell.cluster_pod_capacity(config) == 7 * 40
    config["pod"]["initial_template"]["spec"]["containers"][0]["requests"]["cpu"] = 1000
    assert cell.cluster_pod_capacity(config) == 7 * 4
    config["pod"]["template"]["spec"]["containers"][0]["requests"]["memory"] = 16 << 30
    assert cell.cluster_pod_capacity(config) == 7 * 2


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
