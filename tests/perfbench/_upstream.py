"""A configuration file held to the ``scheduler_perf`` row it names.

``hold`` reads the configuration's own ``upstream`` block: the excerpt of
``performance-config.yaml`` that ``upstream.row`` names (several
configurations may share one file), upstream's node template, and one pod
template for each ``createPods`` op of the row.  It then compares the
wire's canonical JSON with them field by field.  A field of an upstream
template, or of the wire's pod, that it does not know how to compare
fails it, so a later row cannot slip one past; so does an opcode or an
op's key it does not know, by name.  Beside nodes and pods it knows the
row's *companion objects*: a ``nodeAllocatableStrategy`` on the createNodes
op (counts added to the node's allocatable, and a CSINode a node:
``cluster.companions``), and a ``persistentVolumeTemplatePath`` with a
``persistentVolumeClaimTemplatePath`` on the measured createPods op (a
claim and a volume a pod, ``pod.companions``: access modes, capacity and
request in bytes, the CSI driver, the binding both ways, the pod's volume
naming its claim).  What upstream writes and the wire has no field for has
to stand in the configuration's ``assumed.no_wire_field``, with the reason.

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


def hold_pod(pod: dict, wire_pod: dict, spaces, volumes=()) -> None:
    """One upstream pod template against one wire template.  ``volumes``:
    what the wire's pod has to carry beyond the template, where the op
    that creates it adds a volume to every pod."""
    assert set(pod) == {"apiVersion", "kind", "metadata", "spec"} and pod["kind"] == "Pod"
    assert set(pod["metadata"]) <= {"generateName", "labels"}, sorted(pod["metadata"])
    assert wire_pod["metadata"] == {"annotations": {}, "labels": pod["metadata"].get("labels", {}),
                                    "name": "{name}", "namespace": "{namespace}", "uid": ""}
    assert set(wire_pod) == {"metadata", "spec", "status"} and wire_pod["status"] == POD_STATUS
    spec, wire_spec = pod["spec"], wire_pod["spec"]
    assert set(spec) <= {"containers", "affinity", "topologySpreadConstraints"}, sorted(spec)
    compared = {"containers", "affinity", "topology_spread_constraints"}
    assert {k: v for k, v in wire_spec.items() if k not in compared} == dict(POD_SPEC_DEFAULTS, volumes=list(volumes))
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


def _no_wire_field(doc: dict, found: set) -> None:
    """Fields upstream writes for which the wire's object has no field: the
    configuration's ``assumed.no_wire_field`` names each, and says why."""
    said = doc["assumed"].get("no_wire_field", {})
    assert set(said) == found and all(said.values()), f"assumed.no_wire_field: {sorted(said)} != {sorted(found)}"


def _hold_allocatable(doc: dict, strategy: dict | None, cap: dict) -> set:
    """The createNodes op's nodeAllocatableStrategy: ``nodeAllocatable`` (counts
    added to the node's allocatable) and ``csiNodeAllocatable`` (a CSINode a
    node: ``cluster.companions``' template).  Returns the fields met for
    which the wire has none."""
    up, wire_node = doc["upstream"], doc["cluster"]["node_template"]
    csinodes = [c for c in doc["cluster"].get("companions", ()) if c["kind"] == "CSINode"]
    assert len(csinodes) == len(doc["cluster"].get("companions", ())), "cluster.companions: a kind no strategy gives"
    if not strategy:
        assert wire_node["status"]["allocatable"] == cap and not csinodes and "node_allocatable" not in up
        return set()
    unknown = set(strategy) - {"nodeAllocatable", "csiNodeAllocatable", "migratedPlugins"}
    assert not unknown, f"nodeAllocatableStrategy: {sorted(unknown)}"
    assert up["node_allocatable"] == strategy, "upstream.node_allocatable"
    extra = {k: int(v) for k, v in strategy.get("nodeAllocatable", {}).items()}
    assert wire_node["status"]["allocatable"] == dict(cap, **extra), f"nodeAllocatable: {extra}"
    limits = {}
    for driver, alloc in strategy.get("csiNodeAllocatable", {}).items():
        assert set(alloc) == {"count"}, f"csiNodeAllocatable.{driver}: {sorted(alloc)}"
        limits[driver] = int(alloc["count"])
    if limits:
        (csinode,) = csinodes
        assert csinode["template"] == {"name": "{name}", "driver_limits": limits}, \
            f"csiNodeAllocatable: {limits} != {csinode['template']}"
    else:
        assert not csinodes
    return {"migratedPlugins"} & set(strategy)


def _hold_volumes(doc: dict, home: str, op: dict, spaces) -> tuple[list, set]:
    """The measured createPods op's persistentVolumeTemplatePath and
    persistentVolumeClaimTemplatePath against ``pod.companions``: a claim
    and a volume for every measured pod, bound to each other before the pod
    exists, and the pod's one volume naming the claim.  Returns the
    volumes the wire's pod has to carry and the fields met for which the
    wire has none."""
    up, pod = doc["upstream"], doc["pod"]
    paths = {k: op.get(k) for k in ("persistentVolumeTemplatePath", "persistentVolumeClaimTemplatePath")}
    companions = pod.get("companions", [])
    if not any(paths.values()):
        assert not companions and not {"pv_template", "pvc_template"} & set(up)
        return [], set()
    assert all(paths.values()), f"one of {sorted(paths)} without the other"
    assert os.path.basename(up["pv_template"]) == os.path.basename(paths["persistentVolumeTemplatePath"])
    assert os.path.basename(up["pvc_template"]) == os.path.basename(paths["persistentVolumeClaimTemplatePath"])
    assert sorted(c["kind"] for c in companions) == ["PersistentVolume", "PersistentVolumeClaim"]
    assert all(c["of"] == "measured" and set(c) == {"kind", "template", "of"} for c in companions), \
        "pod.companions: of the measured pods, whose op names the templates"
    wire = {c["kind"]: c["template"] for c in companions}
    wire_pv, wire_pvc = wire["PersistentVolume"], wire["PersistentVolumeClaim"]
    pv, pvc = _yaml(home, up["pv_template"]), _yaml(home, up["pvc_template"])
    missing = set()
    # the volume
    assert set(pv) <= {"apiVersion", "kind", "metadata", "spec"} and pv["kind"] == "PersistentVolume"
    assert set(pv.get("metadata", {})) <= {"name"}, sorted(pv["metadata"])
    unknown = set(pv["spec"]) - {"accessModes", "capacity", "csi", "persistentVolumeReclaimPolicy", "storageClassName"}
    assert not unknown, f"PersistentVolume.spec: {sorted(unknown)}"
    missing |= {"persistentVolumeReclaimPolicy"} & set(pv["spec"])
    assert set(pv["spec"]["capacity"]) == {"storage"} and set(pv["spec"]["csi"]) == {"driver"}
    assert wire_pv["csi_driver"] == pv["spec"]["csi"]["driver"], f"csi.driver: {wire_pv['csi_driver']}"
    assert wire_pv["capacity"] == quantity(pv["spec"]["capacity"]["storage"]), "capacity.storage"
    assert wire_pv["access_modes"] == pv["spec"]["accessModes"], "PersistentVolume accessModes"
    # the claim
    assert set(pvc) <= {"apiVersion", "kind", "metadata", "spec"} and pvc["kind"] == "PersistentVolumeClaim"
    assert set(pvc.get("metadata", {})) <= {"name", "annotations"}, sorted(pvc["metadata"])
    missing |= {"annotations"} & set(pvc.get("metadata", {}))
    unknown = set(pvc["spec"]) - {"accessModes", "resources", "storageClassName"}
    assert not unknown, f"PersistentVolumeClaim.spec: {sorted(unknown)}"
    assert pvc["spec"]["resources"] == {"requests": {"storage": pvc["spec"]["resources"]["requests"]["storage"]}}
    assert wire_pvc["request"] == quantity(pvc["spec"]["resources"]["requests"]["storage"]), \
        f"resources.requests.storage: {wire_pvc['request']}"
    assert wire_pvc["access_modes"] == pvc["spec"]["accessModes"], "PersistentVolumeClaim accessModes"
    assert wire_pvc["request"] <= wire_pv["capacity"] and set(wire_pvc["access_modes"]) <= set(wire_pv["access_modes"])
    # names of the pod's own, and the binding both ways
    for which in (wire_pv, wire_pvc):
        assert "{name}" in which["name"], f"{which['name']}: one for each pod"
    assert wire_pvc["namespace"] == "{namespace}"
    assert wire_pv["claim_ref"] == "{namespace}/" + wire_pvc["name"], f"claim_ref: {wire_pv['claim_ref']}"
    assert wire_pvc["volume_name"] == wire_pv["name"], f"volume_name: {wire_pvc['volume_name']}"
    assert wire_pv == {"name": wire_pv["name"], "capacity": wire_pv["capacity"], "access_modes": wire_pv["access_modes"],
                       "storage_class": pv["spec"].get("storageClassName", ""), "node_affinity": None, "labels": {},
                       "claim_ref": wire_pv["claim_ref"], "csi_driver": wire_pv["csi_driver"]}
    assert wire_pvc == {"name": wire_pvc["name"], "namespace": "{namespace}", "request": wire_pvc["request"],
                        "storage_class": pvc["spec"].get("storageClassName", ""),
                        "access_modes": wire_pvc["access_modes"], "volume_name": wire_pv["name"]}
    # what CreatePodWithPersistentVolume gives every pod: one volume, naming its claim
    return [{"name": "vol", "pvc": wire_pvc["name"], "device_id": "", "read_only": False}], missing


OP_KEYS = {
    "createNodes": {"opcode", "countParam", "nodeTemplatePath", "labelNodePrepareStrategy", "nodeAllocatableStrategy"},
    "createNamespaces": {"opcode", "prefix", "count"},
    "createPods": {"opcode", "countParam", "podTemplatePath", "namespace", "collectMetrics",
                   "persistentVolumeTemplatePath", "persistentVolumeClaimTemplatePath"},
}


def _hold_nodes(doc: dict, home: str, op: dict) -> set:
    """Upstream's node template, with the labels the row's createNodes op adds."""
    up = doc["upstream"]
    assert os.path.basename(op.get("nodeTemplatePath", "config/node-default.yaml")) == \
        os.path.basename(up["node_template"])
    node = _yaml(home, up["node_template"])
    wire_node = doc["cluster"]["node_template"]
    cap = {k: (int(v) if k == "pods" else quantity(v)) for k, v in node["status"]["capacity"].items()}
    assert wire_node["status"]["capacity"] == cap
    missing = _hold_allocatable(doc, op.get("nodeAllocatableStrategy"), cap)
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
    return missing


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
    for op in ops:
        assert op["opcode"] in OP_KEYS, f"opcode {op['opcode']}"
        unknown = set(op) - OP_KEYS[op["opcode"]]
        assert not unknown, f"{op['opcode']}: {sorted(unknown)}"
    (nodes_op,) = [op for op in ops if op["opcode"] == "createNodes"]
    missing = _hold_nodes(doc, home, nodes_op)
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
    assert not {"persistentVolumeTemplatePath", "persistentVolumeClaimTemplatePath"} & set(creates[0]), \
        "persistentVolumeTemplatePath on the initial pods' op"
    volumes, more = _hold_volumes(doc, home, measured, spaces)
    _no_wire_field(doc, missing | more)
    hold_pod(_yaml(home, up["pod_template"]), pod["template"], spaces, volumes)
    if paths[0] == paths[1] and volumes:
        # one template upstream, two on the wire: the initial pods' is the
        # measured pods' without the volume the op adds
        bare = dict(pod["template"], spec=dict(pod["template"]["spec"], volumes=[]))
        assert pod.get("initial_template") == bare, "pod.initial_template: the initial pods carry no volume"
        assert "initial_pod_template" not in up
    elif paths[0] == paths[1]:
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

