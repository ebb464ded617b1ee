"""A throw-away row with companion objects, and its reference: a stand-in,
not a configuration of the benchmark and not a row's plain reference.

The row has the shape of upstream's SchedulingCSIPVs: a
``nodeAllocatableStrategy`` on the createNodes op (a CSINode a node, with an
attach limit of ``LIMIT`` volumes of a driver nobody ships), initial pods of
``pod-default.yaml``, and a measured createPods op with a
``persistentVolumeTemplatePath`` and a ``persistentVolumeClaimTemplatePath``:
every measured pod gets a claim and a volume of its own, bound to each
other before the pod is created, and a volume that names the claim.
``row(home)`` writes the excerpt and the two templates as new files into a
copy of ``perfbench/configs`` and returns the configuration that stands on
them; a test copies this file into the throw-away checkout's
``references/`` under the name the configuration gives.

The reference half: resources and scores are ``default_profile``'s (which
refuses a pod with volumes, so they are taken off first).  It asks for the
three kinds by ``COMPANION_KINDS`` and holds the answers to what the row's
guarantees state, from the objects it was given and nothing else: a pod
whose claim is missing, unbound, or bound to a volume that does not exist
or does not point back counts as ``infeasible``, and a node that ends with
more distinct attached volumes of a driver than its CSINode allows counts
into ``over_capacity``.  ``LIMIT_READ_AS`` (None: as the CSINodes say) is what a
test lowers to plant a fault in the reference alone.
"""

import copy
import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_base = os.path.join(HERE, "default_profile.py")
if not os.path.exists(_base):  # imported by a test from tests/perfbench, for ``row``
    _base = os.path.join(HERE, os.pardir, os.pardir, "perfbench", "references", "default_profile.py")
_sp = importlib.util.spec_from_file_location("default_profile_under_the_csi_stand_in", _base)
base = importlib.util.module_from_spec(_sp)
_sp.loader.exec_module(base)

DRIVER = "throwaway.csi.example"
LIMIT = 3
LIMIT_READ_AS = None
COMPANION_KINDS = ("CSINode", "PersistentVolumeClaim", "PersistentVolume")

EXCERPT = f"""\
# a throw-away row in the shape of upstream's SchedulingCSIPVs (a test's)
- name: ThrowawayCSIPVs
  defaultPodTemplatePath: config/pod-default.yaml
  workloadTemplate:
  - opcode: createNodes
    countParam: $initNodes
    nodeTemplatePath: config/node-default.yaml
    nodeAllocatableStrategy:
      nodeAllocatable:
        attachable-volumes-csi-{DRIVER}: "{LIMIT}"
      csiNodeAllocatable:
        {DRIVER}:
          count: {LIMIT}
      migratedPlugins:
      - "kubernetes.io/throwaway"
  - opcode: createPods
    countParam: $initPods
  - opcode: createPods
    countParam: $measurePods
    persistentVolumeTemplatePath: config/pv-throwaway-csi.yaml
    persistentVolumeClaimTemplatePath: config/pvc-throwaway.yaml
    collectMetrics: true
  workloads:
  - name: 2000Nodes_3000Pods
    params:
      initNodes: 2000
      initPods: 1500
      measurePods: 3000
"""
PV_TEMPLATE = f"""\
apiVersion: v1
kind: PersistentVolume
spec:
  accessModes:
  - ReadOnlyMany
  capacity:
    storage: 1Gi
  csi:
    driver: {DRIVER}
  persistentVolumeReclaimPolicy: Retain
"""
PVC_TEMPLATE = """\
apiVersion: v1
kind: PersistentVolumeClaim
metadata:
  annotations:
    pv.kubernetes.io/bind-completed: "true"
spec:
  accessModes:
  - ReadOnlyMany
  resources:
    requests:
      storage: 1Gi
"""


def row(home: str) -> dict:
    """Writes ``upstream/throwaway-csi.excerpt.yaml`` and the row's two
    templates under ``home`` (a copy of ``perfbench/configs``), and returns
    the configuration that stands on them."""
    with open(os.path.join(home, "basic_5kn.json")) as f:
        config = json.load(f)
    for name, text in (("throwaway-csi.excerpt.yaml", EXCERPT), ("pv-throwaway-csi.yaml", PV_TEMPLATE),
                       ("pvc-throwaway.yaml", PVC_TEMPLATE)):
        with open(os.path.join(home, "upstream", name), "w") as f:
            f.write(text)
    config["name"] = "throwaway_csi"
    config["source"] = ("https://github.com/kubernetes/kubernetes/ a test's row in the shape of "
                        "SchedulingCSIPVs: ThrowawayCSIPVs/2000Nodes_3000Pods")
    strategy = {"nodeAllocatable": {f"attachable-volumes-csi-{DRIVER}": str(LIMIT)},
                "csiNodeAllocatable": {DRIVER: {"count": LIMIT}},
                "migratedPlugins": ["kubernetes.io/throwaway"]}
    config["upstream"] = {
        "test_case": "ThrowawayCSIPVs", "workload": "2000Nodes_3000Pods",
        "row": "upstream/throwaway-csi.excerpt.yaml",
        "node_template": "upstream/node-default.yaml",
        "pod_template": "upstream/pod-default.yaml",
        "node_allocatable": strategy,
        "pv_template": "upstream/pv-throwaway-csi.yaml",
        "pvc_template": "upstream/pvc-throwaway.yaml",
        "params": {"initNodes": 2000, "initPods": 1500, "measurePods": 3000},
    }
    config["reference"] = "throwaway_csi"
    config["cluster"]["nodes"] = 2000
    config["initial_pods"], config["measure_pods"] = 1500, 3000
    config["cluster"]["node_template"]["status"]["allocatable"][f"attachable-volumes-csi-{DRIVER}"] = LIMIT
    config["cluster"]["companions"] = [
        {"kind": "CSINode", "template": {"name": "{name}", "driver_limits": {DRIVER: LIMIT}}}]
    # the measured pods carry the volume, the initial pods (the row's
    # default template, as it stands) do not
    config["pod"]["initial_template"] = copy.deepcopy(config["pod"]["template"])
    config["pod"]["template"]["spec"]["volumes"] = [
        {"name": "vol", "pvc": "pvc-{name}", "device_id": "", "read_only": False}]
    config["pod"]["companions"] = [
        # the order in which upstream's CreatePodWithPersistentVolume creates
        # them, as the builder knows it: the claim, then the volume
        {"kind": "PersistentVolumeClaim", "of": "measured", "template": {
            "name": "pvc-{name}", "namespace": "{namespace}", "storage_class": "",
            "access_modes": ["ReadOnlyMany"], "request": 1 << 30, "volume_name": "pv-{name}"}},
        {"kind": "PersistentVolume", "of": "measured", "template": {
            "name": "pv-{name}", "capacity": 1 << 30, "access_modes": ["ReadOnlyMany"], "storage_class": "",
            "node_affinity": None, "labels": {}, "claim_ref": "{namespace}/pvc-{name}", "csi_driver": DRIVER}},
    ]
    # every pod goes back bound when it is answered, as a deployment's does
    config["pod"]["bind_echo"] = "answered"
    config["assumed"]["bind_echo"] = (
        "go/tpubatchscore/plugin.go upsertPod: the plugin forwards the informer's update of a pod the host "
        "scheduler has bound as one AddObject(Pod) a pod on its request connection; without it the sidecar "
        "rolls back every decision it does not know to be bound when the next claim or volume arrives")
    config["capacity"]["pods_per_node_max"] = LIMIT
    config["assumed"]["no_wire_field"] = {
        "migratedPlugins": "the wire's CSINode carries the drivers' limits only; the row's volumes are CSI volumes",
        "persistentVolumeReclaimPolicy": "nothing the scheduler reads",
        "annotations": "pv.kubernetes.io/bind-completed: the wire's claim says the same by naming its volume",
    }
    return config


# -- the reference half ------------------------------------------------------------


def pod_facts(raw: bytes, companions: dict):
    """(default_profile's facts, the (driver, claim uid) of each volume the
    pod attaches, how many of its claims do not resolve)."""
    d = json.loads(raw)
    ns = d["metadata"].get("namespace") or "default"
    claims = {(c["namespace"], c["name"]): c for c in map(json.loads, companions["PersistentVolumeClaim"])}
    volumes = {v["name"]: v for v in map(json.loads, companions["PersistentVolume"])}
    attached, unresolved = [], 0
    for vol in d["spec"]["volumes"]:
        if vol["device_id"] or not vol["pvc"]:
            raise base.Unsupported("only volumes that name a claim are in this stand-in")
        claim = claims.get((ns, vol["pvc"]))
        pv = volumes.get(claim["volume_name"]) if claim else None
        if pv is None or pv["claim_ref"] != f"{ns}/{vol['pvc']}":
            unresolved += 1
        elif pv["csi_driver"]:
            attached.append((pv["csi_driver"], f"{ns}/{vol['pvc']}"))
    d["spec"]["volumes"] = []
    return base.pod_facts(json.dumps(d).encode()), tuple(attached), unresolved


class Cluster(base.Cluster):
    def __init__(self, node_jsons, names, companions):
        # the node's own attachable-volumes-* allocatable is the in-tree
        # plugins' count; the limit held here is the CSINode's
        super().__init__(node_jsons, names)
        self.limit: dict[str, np.ndarray] = {}  # driver -> attach limit a node
        for raw in companions["CSINode"]:
            d = json.loads(raw)
            for driver, count in d["driver_limits"].items():
                per_node = self.limit.setdefault(driver, np.full(len(self.names), np.iinfo(np.int64).max))
                per_node[self.row[d["name"]]] = count if LIMIT_READ_AS is None else LIMIT_READ_AS
        self.attached: dict[str, list] = {}  # driver -> the distinct volumes a node holds
        self.fullest = 0

    def attach(self, row: int, attached) -> None:
        for driver, vol in attached:
            if driver not in self.attached:
                self.attached[driver] = [set() for _ in self.names]
            held = self.attached[driver][row]
            held.add(vol)
            self.fullest = max(self.fullest, len(held))

    def over_capacity(self) -> int:
        over = ((self.used_cpu > self.alloc_cpu) | (self.used_mem > self.alloc_mem)
                | (self.used_pods > self.alloc_pods))
        for driver, per_node in self.attached.items():
            if driver in self.limit:
                over |= np.array([len(held) for held in per_node]) > self.limit[driver]
        return int(over.sum())


class Replay(base.Replay):
    """``examples[0]`` says how full the fullest node stands, which is what
    a run's timeline carries."""

    def __init__(self, cluster):
        super().__init__(cluster)
        self.examples = [""]
        self._say()

    def _say(self):
        self.examples[0] = f"most distinct volumes of one driver on a node: {self.cluster.fullest}"

    def step(self, uid, node, facts, measure):
        facts, attached, unresolved = facts
        row = self.cluster.row.get(node)
        if row is None:
            return super().step(uid, node, facts, measure)
        if measure and unresolved:
            self.infeasible += 1
            if len(self.examples) < 6:
                self.examples.append(f"{uid}->{node}: {unresolved} claim(s) unresolved")
        super().step(uid, node, facts, measure)
        self.cluster.attach(row, attached)
        self._say()
