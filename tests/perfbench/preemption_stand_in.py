"""A throw-away row with a priority churn, and its reference: a stand-in,
not a configuration of the benchmark and not a row's plain reference.

The row has the shape of upstream's PreemptionAsync: nodes of
``node-default.yaml``, initial pods of a low-priority template (900m, so
four fill a 4-CPU node to 3.6 CPU), a ``churn`` op that creates one pod of a
high-priority template (priority 10, 3 CPU) every interval, and a measured
createPods op of ``pod-default.yaml``.  ``row(home, ...)`` writes the excerpt
and the two templates as new files into a copy of ``perfbench/configs`` and
returns the configuration that stands on them, sizes as asked; a test
copies this file into the throw-away checkout's ``references/`` under the
name the configuration gives.  The configuration recycles its measured
pods between backlogs (``pod.recycle``) and echoes every bind.

The reference half preempts plainly for a fit-only profile: resources and
scores are ``default_profile``'s; it keeps each node's pods in the order
they were bound and, told of a nomination, chooses its own: every
lower-priority pod of a node removed, then reprieved most important first
(higher priority, then earlier start, then the pod bound later: the
program's order among pods that tie), the node kept where the preemptor
then fits with at least one victim, and the node picked by upstream's
pickOneNodeForPreemption (fewest victims' highest priority, smallest sum of
priorities, fewest victims, latest earliest start among the
highest-priority victims; no PodDisruptionBudget exists here, so none is
violated), ties to the node that joined the cluster first.  A nomination
whose node or victim set differ counts as a mismatch.  ``REPRIEVE`` is what
a test turns to ``"bound_first"`` to plant a fault in the reference alone:
the pod bound earlier reprieved first among ties, so it chooses other victims.
"""

import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_base = os.path.join(HERE, "default_profile.py")
if not os.path.exists(_base):  # imported by a test from tests/perfbench, for ``row``
    _base = os.path.join(HERE, os.pardir, os.pardir, "perfbench", "references", "default_profile.py")
_sp = importlib.util.spec_from_file_location("default_profile_under_the_preemption_stand_in", _base)
base = importlib.util.module_from_spec(_sp)
_sp.loader.exec_module(base)

PREEMPTION = True
REPRIEVE = "bound_last"

LOW = "pod-throwaway-low-priority.yaml"
HIGH = "pod-throwaway-high-priority.yaml"
LOW_TEMPLATE = """\
apiVersion: v1
kind: Pod
metadata:
  generateName: pod-
spec:
  terminationGracePeriodSeconds: 0
  containers:
  - image: registry.k8s.io/pause:3.10
    name: pause
    ports:
    - containerPort: 80
    resources:
      limits:
        cpu: 900m
        memory: 500Mi
      requests:
        cpu: 900m
        memory: 500Mi
"""
HIGH_TEMPLATE = LOW_TEMPLATE.replace("  terminationGracePeriodSeconds: 0\n", "  priority: 10\n").replace("900m", "3000m")


def excerpt(nodes: int, initial: int, measure: int, interval_ms: int, number: int) -> str:
    workload = f"{nodes}Nodes"
    return f"""\
# a throw-away row in the shape of upstream's PreemptionAsync (a test's)
- name: ThrowawayPreemption
  defaultPodTemplatePath: config/templates/pod-default.yaml
  workloadTemplate:
  - opcode: createNodes
    countParam: $initNodes
  - opcode: createPods
    countParam: $initPods
    podTemplatePath: config/templates/{LOW}
  - opcode: churn
    mode: create
    number: {number}
    templatePaths:
    - config/templates/{HIGH}
    intervalMilliseconds: {interval_ms}
  - opcode: createPods
    countParam: $measurePods
    podTemplatePath: config/templates/pod-default.yaml
    collectMetrics: true
  workloads:
  - name: {workload}
    params:
      initNodes: {nodes}
      initPods: {initial}
      measurePods: {measure}
"""


def row(home: str, nodes: int = 100, initial: int = 400, measure: int = 100, interval_ms: int = 200,
        number: int = 0, name: str = "throwaway_preemption", chunk_size: int = 64) -> dict:
    """Writes ``upstream/throwaway-preemption.excerpt.yaml`` and the two
    templates under ``home`` (a copy of ``perfbench/configs``), and returns
    the configuration that stands on them."""
    with open(os.path.join(home, "basic_5kn.json")) as f:
        config = json.load(f)
    for fname, text in (("throwaway-preemption.excerpt.yaml", excerpt(nodes, initial, measure, interval_ms, number)),
                        (LOW, LOW_TEMPLATE), (HIGH, HIGH_TEMPLATE)):
        with open(os.path.join(home, "upstream", fname), "w") as f:
            f.write(text)
    workload = f"{nodes}Nodes"
    config["name"] = name
    config["source"] = ("https://github.com/kubernetes/kubernetes/ a test's row in the shape of "
                        f"PreemptionAsync: ThrowawayPreemption/{workload}")
    config["upstream"] = {
        "test_case": "ThrowawayPreemption", "workload": workload,
        "row": "upstream/throwaway-preemption.excerpt.yaml",
        "node_template": "upstream/node-default.yaml",
        "pod_template": "upstream/pod-default.yaml",
        "initial_pod_template": "upstream/" + LOW,
        "churn_template": "upstream/" + HIGH,
        "params": {"initNodes": nodes, "initPods": initial, "measurePods": measure},
    }
    config["reference"] = name
    config["cluster"]["nodes"] = nodes
    config["initial_pods"], config["measure_pods"] = initial, measure
    pod = config["pod"]
    # the ops' own namespaces: namespace-<index of the op>
    pod["namespaces"] = {"initial": "namespace-1", "measured": "namespace-3"}
    low = json.loads(json.dumps(pod["template"]))
    for side in ("requests", "limits"):
        low["spec"]["containers"][0][side]["cpu"] = 900
    high = json.loads(json.dumps(low))
    for side in ("requests", "limits"):
        high["spec"]["containers"][0][side]["cpu"] = 3000
    high["spec"]["priority"] = 10
    for t in (low, high):
        t["spec"]["containers"][0]["images"] = ["registry.k8s.io/pause:3.10"]
    pod["initial_template"] = low
    pod["churn"] = {"template": high, "interval_ms": interval_ms, "number": number, "namespace": "namespace-2"}
    pod["recycle"] = "measured"
    pod["bind_echo"] = "answered"
    config["assumed"]["no_wire_field"] = {
        "terminationGracePeriodSeconds": "the wire's pod has no grace period: a deleted victim leaves the "
                                         "sidecar when its RemoveObject arrives"}
    config["assumed"]["recycle"] = (
        "the harness's: before each backlog after the first the previous backlog's bound measured pods are "
        "removed, so that backlogs fit beside the initial pods; the seconds are out of the window")
    config["assumed"]["bind_echo"] = "go/tpubatchscore/plugin.go upsertPod"
    config["capacity"] = {"note": "cell.cluster_pod_capacity: one backlog beside the initial pods and the churn"}
    config["serve"]["chunk_size"] = chunk_size
    return config


# -- the reference half ------------------------------------------------------------


def pod_facts(raw: bytes):
    """(default_profile's facts, priority, start time).  Fit only: a pod
    with labels or a term is refused by name."""
    d = json.loads(raw)
    facts = base.pod_facts(raw)
    if facts[3] or facts[4]:
        raise base.Unsupported("labels and pod-affinity terms are not in this stand-in: it removes pods by resources only")
    return facts, int(d["spec"].get("priority") or 0), float(d["status"].get("start_time") or 0.0)


class Cluster(base.Cluster):
    """Beside ``default_profile``'s: each node's pods in the order bound,
    and the room a nominated pod holds on its node until it binds (upstream
    runs Filter with the nominated pods of at least the incoming pod's
    priority added to their nodes; Score without them)."""

    def __init__(self, node_jsons, names):
        super().__init__(node_jsons, names)
        self.held: list[dict] = [{} for _ in self.names]  # uid -> (cpu, mem, priority, start, bound as k-th)
        self.row_of: dict[str, int] = {}
        self.bound = 0
        self.claims: dict[str, tuple] = {}  # nominated uid -> (row, cpu, mem, priority)
        self.asking = ("", 0)  # the pod whose filter is being run, and its priority

    def claimed(self, uid: str, prio: int):
        """(cpu, mem, pods) per node that the other nominated pods of at
        least ``prio`` hold."""
        cpu, mem, pods = (np.zeros(len(self.names), np.int64) for _ in range(3))
        for nominee, (row, c, m, p) in self.claims.items():
            if nominee != uid and p >= prio:
                cpu[row] += c
                mem[row] += m
                pods[row] += 1
        return cpu, mem, pods

    def fit_mask(self, cpu, mem):
        ccpu, cmem, cpods = self.claimed(*self.asking)
        return ((self.used_cpu + ccpu + cpu <= self.alloc_cpu)
                & (self.used_mem + cmem + mem <= self.alloc_mem)
                & (self.used_pods + cpods + 1 <= self.alloc_pods))


class Replay(base.Replay):
    """``examples`` also names the first mismatched nominations."""

    def step(self, uid, node, facts, measure):
        facts, prio, start = facts
        cl = self.cluster
        cl.asking = (uid, prio)
        row = cl.row.get(node)
        if row is not None and not measure and not cl.fit_mask(facts[0], facts[1])[row]:
            self.infeasible += 1  # a pod outside the window, the preemptor among them, is held to fit too
            if len(self.examples) < 5:
                self.examples.append(f"{uid}->{node}")
        super().step(uid, node, facts, measure)
        cl.claims.pop(uid, None)  # a nominated pod that binds gives its room back
        if row is not None:
            cl.held[row][uid] = (facts[0], facts[1], prio, start, cl.bound)
            cl.row_of[uid] = row
            cl.bound += 1

    def remove(self, uid):
        cl = self.cluster
        cl.claims.pop(uid, None)
        row = cl.row_of.pop(uid, None)
        if row is None:
            return
        cpu, mem, _prio, _start, _k = cl.held[row].pop(uid)
        cl.used_cpu[row] -= cpu
        cl.used_mem[row] -= mem
        cl.used_pods[row] -= 1

    def choose(self, uid, facts):
        """(node name, frozenset of victims) by the row's arithmetic, or None."""
        (cpu, mem, *_), prio, _start = facts
        cl = self.cluster
        ccpu, cmem, cpods = cl.claimed(uid, prio)
        later = -1 if REPRIEVE == "bound_last" else 1
        best = None
        for row, held in enumerate(cl.held):
            lower = [(u, p) for u, p in held.items() if p[2] < prio]
            if not lower:
                continue
            free_cpu = cl.alloc_cpu[row] - cl.used_cpu[row] - ccpu[row] + sum(p[0] for _, p in lower)
            free_mem = cl.alloc_mem[row] - cl.used_mem[row] - cmem[row] + sum(p[1] for _, p in lower)
            free_pods = cl.alloc_pods[row] - cl.used_pods[row] - cpods[row] + len(lower)
            if free_cpu < cpu or free_mem < mem or free_pods < 1:
                continue
            lower.sort(key=lambda x: (-x[1][2], x[1][3], later * x[1][4]))  # most important first
            victims = []
            for u, p in lower:
                if free_cpu - p[0] >= cpu and free_mem - p[1] >= mem and free_pods - 1 >= 1:
                    free_cpu, free_mem, free_pods = free_cpu - p[0], free_mem - p[1], free_pods - 1
                else:
                    victims.append((u, p))
            if not victims:
                continue
            top = max(p[2] for _, p in victims)
            key = (top, sum(p[2] for _, p in victims), len(victims),
                   -min(p[3] for _, p in victims if p[2] == top), row)
            if best is None or key < best[0]:
                best = (key, row, frozenset(u for u, _ in victims))
        return None if best is None else (cl.names[best[1]], best[2])

    def nominate(self, uid, node, victims, facts) -> bool:
        chose = self.choose(uid, facts)
        ok = chose == (node, frozenset(victims))
        if not ok and len(self.examples) < 8:
            mine = "none" if chose is None else f"{chose[0]} {sorted(chose[1])}"
            self.examples.append(f"{uid}: the program {node} {sorted(victims)}, the reference {mine}")
        row = self.cluster.row.get(node)
        if row is not None:  # the program's nomination holds the room, whoever chose better
            (cpu, mem, *_), prio, _start = facts
            self.cluster.claims[uid] = (row, cpu, mem, prio)
        return ok
