"""A configuration file held to the ``scheduler_perf`` row it names.

``hold`` reads the configuration's own ``upstream`` block: the excerpt of
``performance-config.yaml`` that ``upstream.row`` names (several
configurations may share one file), upstream's node template, and one pod
template for each ``createPods`` op of the row.  It then compares the
wire's canonical JSON with them field by field.  A field of an upstream
template, or of the wire's pod, that it does not know how to compare
fails it, so a later row cannot slip one past.

``spreading_row`` writes a configuration in the shape of upstream's
TopologySpreading row (two pod templates, a label strategy of three
values, a spread constraint) as new files into a copy of
``perfbench/configs``: what the tests enter by files alone.  Its reference
is ``SPREADING_STAND_IN``, a file a test copies into the throw-away
checkout's ``references/``.
"""

import copy
import json
import os

import yaml

ZONE = "topology.kubernetes.io/zone"
SPREADING_STAND_IN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "spreading_stand_in.py")

# what the wire writes out for a pod field that the YAML leaves unsaid
POD_SPEC_DEFAULTS = {
    "init_containers": [], "node_name": "", "node_selector": {}, "overhead": {}, "pod_group": "",
    "preemption_policy": "PreemptLowerPriority", "priority": 0, "resource_claims": [],
    "scheduler_name": "default-scheduler", "scheduling_gates": [], "tolerations": [], "volumes": [],
}
POD_STATUS = {"nominated_node_name": "", "phase": "Pending", "start_time": 0.0}


def quantity(q) -> int:
    """A Kubernetes quantity in the wire's canonical units: millicores for
    cpu-like values (``4``, ``100m``), bytes for ``Mi``/``Gi``."""
    q = str(q)
    for suffix, mult in (("Gi", 1 << 30), ("Mi", 1 << 20)):
        if q.endswith(suffix):
            return int(q[:-2]) * mult
    return int(q[:-1]) if q.endswith("m") else int(q) * 1000


def _yaml(home: str, rel: str):
    with open(os.path.join(home, rel)) as f:
        return yaml.safe_load(f)


def _selector(sel: dict) -> dict:
    """A YAML labelSelector in the wire's form."""
    assert set(sel) <= {"matchLabels", "matchExpressions"}, sorted(sel)
    return {"match_labels": [list(kv) for kv in sel.get("matchLabels", {}).items()],
            "match_expressions": [{"key": e["key"], "operator": e["operator"], "values": list(e.get("values", []))}
                                  for e in sel.get("matchExpressions", [])]}


def _hold_affinity(aff: dict, wire_aff: dict) -> None:
    assert set(aff) <= {"podAffinity", "podAntiAffinity"}, sorted(aff)
    assert set(wire_aff) == {"node_affinity", "pod_affinity", "pod_anti_affinity"}
    assert wire_aff["node_affinity"] is None
    for side, wire_side in (("podAffinity", "pod_affinity"), ("podAntiAffinity", "pod_anti_affinity")):
        if side not in aff:
            assert wire_aff[wire_side] is None
            continue
        assert set(aff[side]) == {"requiredDuringSchedulingIgnoredDuringExecution"}, sorted(aff[side])
        terms = aff[side]["requiredDuringSchedulingIgnoredDuringExecution"]
        assert wire_aff[wire_side]["preferred"] == [] and set(wire_aff[wire_side]) == {"preferred", "required"}
        assert len(wire_aff[wire_side]["required"]) == len(terms)
        for term, wterm in zip(terms, wire_aff[wire_side]["required"]):
            assert set(term) <= {"labelSelector", "topologyKey", "namespaces"}, sorted(term)
            assert wterm == {"topology_key": term["topologyKey"], "namespaces": term.get("namespaces", []),
                             "namespace_selector": None, "label_selector": _selector(term["labelSelector"])}


def _hold_spread(constraints: list, wire_constraints: list) -> None:
    assert len(wire_constraints) == len(constraints)
    for c, w in zip(constraints, wire_constraints):
        assert set(c) <= {"maxSkew", "topologyKey", "whenUnsatisfiable", "labelSelector", "minDomains",
                          "nodeAffinityPolicy", "nodeTaintsPolicy", "matchLabelKeys"}, sorted(c)
        assert w == {
            "max_skew": c["maxSkew"], "topology_key": c["topologyKey"],
            "when_unsatisfiable": c["whenUnsatisfiable"],
            "label_selector": _selector(c["labelSelector"]) if "labelSelector" in c else None,
            # the defaults the wire writes out
            "min_domains": c.get("minDomains"), "node_affinity_policy": c.get("nodeAffinityPolicy", "Honor"),
            "node_taints_policy": c.get("nodeTaintsPolicy", "Ignore"),
            "match_label_keys": list(c.get("matchLabelKeys", [])),
        }


def hold_pod(pod: dict, wire_pod: dict, spaces) -> None:
    """One upstream pod template against one wire template."""
    assert set(pod) == {"apiVersion", "kind", "metadata", "spec"} and pod["kind"] == "Pod"
    assert set(pod["metadata"]) <= {"generateName", "labels"}, sorted(pod["metadata"])
    assert wire_pod["metadata"] == {"annotations": {}, "labels": pod["metadata"].get("labels", {}),
                                    "name": "{name}", "namespace": "{namespace}", "uid": ""}
    assert set(wire_pod) == {"metadata", "spec", "status"} and wire_pod["status"] == POD_STATUS
    spec, wire_spec = pod["spec"], wire_pod["spec"]
    assert set(spec) <= {"containers", "affinity", "topologySpreadConstraints"}, sorted(spec)
    compared = {"containers", "affinity", "topology_spread_constraints"}
    assert {k: v for k, v in wire_spec.items() if k not in compared} == POD_SPEC_DEFAULTS
    assert compared <= set(wire_spec)
    (cont,), (wcont,) = spec["containers"], wire_spec["containers"]
    assert set(cont) == {"image", "name", "ports", "resources"}, sorted(cont)
    assert set(wcont) == {"images", "limits", "name", "ports", "requests", "restart_policy"}
    assert set(cont["resources"]) == {"requests", "limits"}
    for side in ("requests", "limits"):
        assert wcont[side] == {k: quantity(v) for k, v in cont["resources"][side].items()}
    assert wcont["images"] == [cont["image"]] and wcont["name"] == cont["name"] and wcont["restart_policy"] is None
    assert wcont["ports"] == [{"container_port": p["containerPort"], "host_ip": "", "host_port": 0, "protocol": "TCP"}
                              for p in cont["ports"]]
    assert all(set(p) == {"containerPort"} for p in cont["ports"])
    if "affinity" in spec:
        _hold_affinity(spec["affinity"], wire_spec["affinity"])
        for side in spec["affinity"].values():
            for term in side["requiredDuringSchedulingIgnoredDuringExecution"]:
                assert set(spaces) <= set(term.get("namespaces", spaces))
    else:
        assert wire_spec["affinity"] is None
    _hold_spread(spec.get("topologySpreadConstraints", []), wire_spec["topology_spread_constraints"])


def _hold_nodes(doc: dict, home: str, op: dict) -> None:
    """Upstream's node template, with the labels the row's createNodes op adds."""
    up = doc["upstream"]
    assert os.path.basename(op.get("nodeTemplatePath", "config/node-default.yaml")) == \
        os.path.basename(up["node_template"])
    node = _yaml(home, up["node_template"])
    wire_node = doc["cluster"]["node_template"]
    cap = {k: (int(v) if k == "pods" else quantity(v)) for k, v in node["status"]["capacity"].items()}
    assert wire_node["status"]["capacity"] == cap == wire_node["status"]["allocatable"]
    strategy = op.get("labelNodePrepareStrategy")
    labels, cycles = {}, {}
    if strategy and len(strategy["labelValues"]) == 1:
        labels = {strategy["labelKey"]: strategy["labelValues"][0]}
    elif strategy:
        # several values are dealt round-robin in creation order: a cycle
        # that names them, in upstream's order, under a variable of the file's choosing
        label = wire_node["metadata"]["labels"].get(strategy["labelKey"], "")
        assert label.startswith("{") and label.endswith("}"), label
        labels = {strategy["labelKey"]: label}
        cycles = {label[1:-1]: {"values": list(strategy["labelValues"])}}
    assert wire_node["metadata"]["labels"] == labels == up.get("node_labels", {})
    assert doc["cluster"]["cycles"] == cycles


def hold(doc: dict, home: str, entry: dict | None = None) -> None:
    """``doc``: a configuration file's contents; ``home``: the directory
    its ``upstream`` paths are relative to; ``entry``: what BENCHMARK.json
    says of it, where it names it."""
    c = entry or {"reduced": [], "source": doc["source"][:200]}
    # what BENCHMARK.json says of a configuration is what its file says
    assert doc["source"].startswith(c["source"]) and doc["reduced"] == c["reduced"] == []
    assert c["source"].startswith("https://github.com/kubernetes/kubernetes/")
    up = doc["upstream"]
    (case,) = [x for x in _yaml(home, up["row"]) if x["name"] == up["test_case"]]
    assert up["test_case"] + "/" + up["workload"] in c["source"]
    (row,) = [w for w in case["workloads"] if w["name"] == up["workload"]]
    assert row["params"] == up["params"]
    assert (doc["cluster"]["nodes"], doc["initial_pods"], doc["measure_pods"]) == \
        (row["params"]["initNodes"], row["params"]["initPods"], row["params"]["measurePods"])
    ops = case["workloadTemplate"]
    (nodes_op,) = [op for op in ops if op["opcode"] == "createNodes"]
    _hold_nodes(doc, home, nodes_op)
    # pods: a template for each createPods op, the op's own or the case's
    # default; the op that collects metrics is the measured one, the one
    # before it the initial pods'
    creates = [op for op in ops if op["opcode"] == "createPods"]
    (measured,) = [op for op in creates if op.get("collectMetrics")]
    assert len(creates) == 2 and creates[1] is measured
    paths = [os.path.basename(op.get("podTemplatePath") or case["defaultPodTemplatePath"]) for op in creates]
    spaces = [op.get("namespace", f"namespace-{ops.index(op)}") for op in creates]
    pod = doc["pod"]
    assert [pod["namespaces"]["initial"], pod["namespaces"]["measured"]] == spaces
    assert not pod["cycles"]
    assert os.path.basename(up["pod_template"]) == paths[1]
    hold_pod(_yaml(home, up["pod_template"]), pod["template"], spaces)
    if paths[0] == paths[1]:
        assert "initial_template" not in pod and "initial_pod_template" not in up
    else:
        assert "initial_template" in pod and os.path.basename(up["initial_pod_template"]) == paths[0]
        hold_pod(_yaml(home, up["initial_pod_template"]), pod["initial_template"], spaces)


SPREADING_EXCERPT = """\
# a throw-away row in the shape of upstream's TopologySpreading (a test's)
- name: ThrowawaySpreading
  workloadTemplate:
  - opcode: createNodes
    countParam: $initNodes
    nodeTemplatePath: config/node-default.yaml
    labelNodePrepareStrategy:
      labelKey: "topology.kubernetes.io/zone"
      labelValues: ["moon-1", "moon-2", "moon-3"]
  - opcode: createPods
    countParam: $initPods
    podTemplatePath: config/pod-default.yaml
  - opcode: createPods
    countParam: $measurePods
    podTemplatePath: config/pod-with-throwaway-spreading.yaml
    collectMetrics: true
  workloads:
  - name: 2000Nodes_3000Pods
    params:
      initNodes: 2000
      initPods: 1500
      measurePods: 3000
"""


def spreading_row(home: str) -> dict:
    """Writes ``upstream/throwaway-spreading.excerpt.yaml`` and the row's
    measured pod template under ``home`` (a copy of ``perfbench/configs``),
    and returns the configuration that stands on them: three zones by a
    ``values`` cycle, initial pods of ``pod-default.yaml`` without labels,
    measured pods with one DoNotSchedule constraint."""
    with open(os.path.join(home, "basic_5kn.json")) as f:
        config = json.load(f)
    with open(os.path.join(home, "upstream", "throwaway-spreading.excerpt.yaml"), "w") as f:
        f.write(SPREADING_EXCERPT)
    pod = _yaml(home, "upstream/pod-default.yaml")
    pod["metadata"] = {"generateName": "spreading-pod-", "labels": {"color": "blue"}}
    pod["spec"]["topologySpreadConstraints"] = [{
        "maxSkew": 5, "topologyKey": ZONE, "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"color": "blue"}}}]
    with open(os.path.join(home, "upstream", "pod-with-throwaway-spreading.yaml"), "w") as f:
        yaml.safe_dump(pod, f)
    config["name"] = "throwaway_spreading"
    config["source"] = ("https://github.com/kubernetes/kubernetes/ a test's row in the shape of "
                        "TopologySpreading: ThrowawaySpreading/2000Nodes_3000Pods")
    config["upstream"] = {
        "test_case": "ThrowawaySpreading", "workload": "2000Nodes_3000Pods",
        "row": "upstream/throwaway-spreading.excerpt.yaml",
        "node_template": "upstream/node-default.yaml",
        "pod_template": "upstream/pod-with-throwaway-spreading.yaml",
        "initial_pod_template": "upstream/pod-default.yaml",
        "node_labels": {ZONE: "{zone}"},
        "params": {"initNodes": 2000, "initPods": 1500, "measurePods": 3000},
    }
    config["cluster"]["nodes"] = 2000
    config["initial_pods"], config["measure_pods"] = 1500, 3000
    config["cluster"]["cycles"] = {"zone": {"values": ["moon-1", "moon-2", "moon-3"]}}
    config["cluster"]["node_template"]["metadata"]["labels"] = {ZONE: "{zone}"}
    config["pod"]["initial_template"] = copy.deepcopy(config["pod"]["template"])
    measured = config["pod"]["template"]
    measured["metadata"]["labels"] = {"color": "blue"}
    measured["spec"]["topology_spread_constraints"] = [{
        "max_skew": 5, "topology_key": ZONE, "when_unsatisfiable": "DoNotSchedule",
        "label_selector": {"match_labels": [["color", "blue"]], "match_expressions": []},
        "min_domains": None, "node_affinity_policy": "Honor", "node_taints_policy": "Ignore",
        "match_label_keys": []}]
    return config

