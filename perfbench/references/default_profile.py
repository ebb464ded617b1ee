"""Plain reference for the default plugin profile, as far as the
benchmark's configurations exercise it: NodeResourcesFit (filter, and the
LeastAllocated score over cpu and memory), NodeResourcesBalancedAllocation,
and InterPodAffinity (one required term selecting one label key, by
``matchLabels`` or an ``In`` expression, in the term's namespaces: the
filter with its first-pod rule, and the hard-affinity score).
TaintToleration, NodeAffinity, ImageLocality and PodTopologySpread score
every node alike for pods that carry none of their fields, so they move
no comparison made here; a pod that does carry one is refused by name.

Straight numpy on plain numbers read from the same JSON the wire carried.
It imports nothing of the program and is given nothing the program made
except the answers it checks: which node each pod was bound to, in the
order the sidecar committed them.

``Replay`` walks those answers in order.  Before each it asks: does the
node pass the filters on the cluster as it stands (``infeasible``), and by
how much does the node's total score lie under the best feasible node's
(``gap``).  Then it commits the pod and moves on.  ``place`` is the same
arithmetic put in the program's place: it makes the decisions itself, on
a cluster view refreshed every ``stale`` decisions, and serves as the
control (``stale`` far above the configuration's chunk size) and, in the
tests, as a stand-in for the program (``stale`` equal to it).
"""

from __future__ import annotations

import json
import random

import numpy as np

MAX_NODE_SCORE = 100
W_FIT, W_BALANCED, W_IPA = 1, 1, 2
HARD_POD_AFFINITY_WEIGHT = 1


class Unsupported(ValueError):
    """The object carries a field this reference does not implement."""


def pod_facts(pod_json: bytes) -> tuple:
    """(cpu, memory, namespace, label items, affinity) of one pod.
    ``affinity`` is None or (namespaces, label key, values, topology key)
    of its one required term; a term that names no namespace means the
    pod's own."""
    d = json.loads(pod_json)
    spec = d["spec"]
    for key in ("node_selector", "tolerations", "topology_spread_constraints",
                "volumes", "resource_claims", "init_containers", "overhead",
                "scheduling_gates", "pod_group", "node_name"):
        if spec.get(key):
            raise Unsupported(f"pod field {key} is not in this reference")
    cpu = mem = 0
    for c in spec["containers"]:
        if any(p.get("host_port") for p in c.get("ports", ())):
            raise Unsupported("host ports are not in this reference")
        req = c.get("requests", {})
        extra = set(req) - {"cpu", "memory"}
        if extra:
            raise Unsupported(f"resources {sorted(extra)} are not in this reference")
        cpu += int(req.get("cpu", 0))
        mem += int(req.get("memory", 0))
    if cpu <= 0 or mem <= 0:
        raise Unsupported("a pod without cpu and memory requests (non-zero defaults) is not in this reference")
    ns = d["metadata"].get("namespace") or "default"
    labels = tuple(sorted(d["metadata"].get("labels", {}).items()))
    aff = spec.get("affinity")
    term = None
    if aff:
        if aff.get("node_affinity") or aff.get("pod_anti_affinity"):
            raise Unsupported("node affinity and anti-affinity are not in this reference")
        pa = aff.get("pod_affinity")
        if pa:
            if pa.get("preferred") or len(pa.get("required", [])) != 1:
                raise Unsupported("only one required pod-affinity term is in this reference")
            t = pa["required"][0]
            sel = t["label_selector"]
            pairs = [(k, (v,)) for k, v in sel.get("match_labels", [])]
            for e in sel.get("match_expressions", []):
                if e["operator"] != "In":
                    raise Unsupported("only matchLabels and In expressions are in this reference")
                pairs.append((e["key"], tuple(e["values"])))
            if t.get("namespace_selector") or len(pairs) != 1:
                raise Unsupported("only a term selecting one label key, without a namespace selector, is in this reference")
            term = (tuple(sorted(t.get("namespaces") or (ns,))),) + pairs[0] + (t["topology_key"],)
    return cpu, mem, ns, labels, term


class Cluster:
    """Node capacities, what is bound where, and per topology domain how
    many pods carry each label value / each required term."""

    def __init__(self, node_jsons, names):
        self.names = list(names)
        self.row = {n: i for i, n in enumerate(self.names)}
        n = len(self.names)
        self.alloc_cpu = np.zeros(n, np.int64)
        self.alloc_mem = np.zeros(n, np.int64)
        self.alloc_pods = np.zeros(n, np.int64)
        self.labels = []
        for i, raw in enumerate(node_jsons):
            d = json.loads(raw)
            if d["spec"].get("taints") or d["spec"].get("unschedulable"):
                raise Unsupported("taints and cordons are not in this reference")
            a = d["status"]["allocatable"]
            self.alloc_cpu[i] = a["cpu"]
            self.alloc_mem[i] = a["memory"]
            self.alloc_pods[i] = a["pods"]
            self.labels.append(d["metadata"]["labels"])
        self.used_cpu = np.zeros(n, np.int64)
        self.used_mem = np.zeros(n, np.int64)
        self.used_pods = np.zeros(n, np.int64)
        self._domain: dict[str, np.ndarray] = {}  # topology key -> domain id per node
        self._ndomains: dict[str, int] = {}
        # (topology key, namespace, label key, label value) -> pods per domain
        self.label_count: dict[tuple, np.ndarray] = {}
        # (term) -> pods per domain that carry this required term
        self.term_count: dict[tuple, np.ndarray] = {}

    def domain(self, key: str) -> np.ndarray:
        dom = self._domain.get(key)
        if dom is None:
            ids: dict[str, int] = {}
            dom = np.array([ids.setdefault(lab.get(key, ""), len(ids)) for lab in self.labels], np.int64)
            self._domain[key] = dom
            self._ndomains[key] = len(ids)
        return dom

    def _counter(self, table: dict, key: tuple, topo: str) -> np.ndarray:
        arr = table.get(key)
        if arr is None:
            self.domain(topo)
            arr = table[key] = np.zeros(self._ndomains[topo], np.int64)
        return arr

    # -- filters -----------------------------------------------------------

    def fit_mask(self, cpu: int, mem: int) -> np.ndarray:
        return ((self.used_cpu + cpu <= self.alloc_cpu)
                & (self.used_mem + mem <= self.alloc_mem)
                & (self.used_pods + 1 <= self.alloc_pods))

    def affinity_mask(self, ns, labels, term):
        """Required pod affinity: the node's domain holds a pod matching
        the term; or none does anywhere and the pod matches its own term
        (the first pod of a self-affine group may go anywhere)."""
        if term is None:
            return None
        spaces, key, values, topo = term
        dom = self.domain(topo)
        per_domain = sum(self._counter(self.label_count, (topo, s, key, v), topo)
                         for s in spaces for v in values)
        if per_domain.sum() == 0:
            own = ns in spaces and dict(labels).get(key) in values
            return np.ones(len(dom), bool) if own else np.zeros(len(dom), bool)
        return per_domain[dom] > 0

    # -- scores ------------------------------------------------------------

    def scores(self, cpu: int, mem: int, ns, labels, feasible: np.ndarray, ipa: bool = True) -> np.ndarray:
        """Weighted total of the scorers that tell nodes apart, int64."""
        req_cpu = self.used_cpu + cpu
        req_mem = self.used_mem + mem
        least_cpu = np.where(req_cpu > self.alloc_cpu, 0, (self.alloc_cpu - req_cpu) * MAX_NODE_SCORE // self.alloc_cpu)
        least_mem = np.where(req_mem > self.alloc_mem, 0, (self.alloc_mem - req_mem) * MAX_NODE_SCORE // self.alloc_mem)
        fit = (least_cpu + least_mem) // 2
        f_cpu = np.minimum(req_cpu / self.alloc_cpu, 1.0)
        f_mem = np.minimum(req_mem / self.alloc_mem, 1.0)
        balanced = ((1.0 - np.abs(f_cpu - f_mem) / 2.0) * MAX_NODE_SCORE).astype(np.int64)
        total = W_FIT * fit + W_BALANCED * balanced
        # InterPodAffinity: existing pods whose required term the incoming
        # pod matches pull it into their domain, hard weight each.
        raw = None
        lab = dict(labels)
        for (spaces, key, values, topo), per_domain in (self.term_count.items() if ipa else ()):
            if ns in spaces and lab.get(key) in values and per_domain.any():
                part = per_domain[self.domain(topo)] * HARD_POD_AFFINITY_WEIGHT
                raw = part if raw is None else raw + part
        if raw is not None and feasible.any():
            mx = raw[feasible].max()
            mn = raw[feasible].min()
            if mx > mn:
                total = total + W_IPA * (MAX_NODE_SCORE * (raw - mn) // (mx - mn))
        return total

    # -- commit ------------------------------------------------------------

    def commit(self, row: int, cpu: int, mem: int, ns, labels, term) -> None:
        self.used_cpu[row] += cpu
        self.used_mem[row] += mem
        self.used_pods[row] += 1
        for topo in list(self._domain):
            d = self._domain[topo][row]
            for k, v in labels:
                self._counter(self.label_count, (topo, ns, k, v), topo)[d] += 1
        if term is not None:
            self._counter(self.term_count, term, term[3])[self.domain(term[3])[row]] += 1

    def watch(self, term) -> None:
        """Make sure the counters a term reads exist before pods that could
        match it are committed."""
        if term is not None:
            spaces, key, values, topo = term
            for s in spaces:
                for v in values:
                    self._counter(self.label_count, (topo, s, key, v), topo)

    def over_capacity(self) -> int:
        return int(((self.used_cpu > self.alloc_cpu) | (self.used_mem > self.alloc_mem)
                    | (self.used_pods > self.alloc_pods)).sum())


class Replay:
    """Walk the program's answers in commit order."""

    def __init__(self, cluster: Cluster):
        self.cluster = cluster
        self.infeasible = 0
        self.unknown_node = 0
        self.gaps: list[int] = []
        self.examples: list[str] = []

    def step(self, uid: str, node: str, facts, measure: bool) -> None:
        cl = self.cluster
        cpu, mem, ns, labels, term = facts
        cl.watch(term)
        row = cl.row.get(node)
        if row is None:
            self.unknown_node += 1
            return
        if measure:
            feasible = cl.fit_mask(cpu, mem)
            aff = cl.affinity_mask(ns, labels, term)
            if aff is not None:
                feasible &= aff
            if not feasible[row]:
                self.infeasible += 1
                if len(self.examples) < 5:
                    self.examples.append(f"{uid}->{node}")
            else:
                total = cl.scores(cpu, mem, ns, labels, feasible)
                self.gaps.append(int(total[feasible].max() - total[row]))
        cl.commit(row, cpu, mem, ns, labels, term)


def place(cluster: Cluster, pods, stale: int, seed: int, drop_affinity: bool = False,
          wander: float = 0.0, wander_to: str = "random"):
    """The reference in the program's place: bind ``pods`` (uid, facts) in
    order, scoring on a view of the cluster refreshed every ``stale``
    decisions.  Within a view, capacity is still counted exactly against
    the pods bound so far (as the program's chunk does), a required term
    is judged on the view, and ties break at random from ``seed``.
    ``drop_affinity`` switches InterPodAffinity off, filter and score (a
    second way to break a guarantee, used by the tests); ``wander`` sends
    that share of the decisions to a feasible node whatever its score:
    one drawn at random, or (``wander_to`` "worst") one of the lowest
    score, as an inverted comparison would (a third).
    Returns [(uid, node name or "")]."""
    rng = random.Random(seed)
    out = []
    cache: dict = {}
    for k, (uid, facts) in enumerate(pods):
        cpu, mem, ns, labels, term = facts
        cluster.watch(term)
        if k % stale == 0:
            cache = {}
        key = (cpu, mem, ns, labels, term)
        if key not in cache:
            feasible = cluster.fit_mask(cpu, mem)
            aff = None if drop_affinity else cluster.affinity_mask(ns, labels, term)
            if aff is not None:
                feasible = feasible & aff
            cache[key] = (cluster.scores(cpu, mem, ns, labels, feasible, ipa=not drop_affinity), aff)
        total, aff = cache[key]
        feasible = cluster.fit_mask(cpu, mem)
        if aff is not None:
            feasible &= aff
        if not feasible.any():
            out.append((uid, ""))
            continue
        if wander and rng.random() < wander:
            ties = np.flatnonzero(feasible)
            if wander_to == "worst":
                ties = np.flatnonzero(feasible & (total == total[feasible].min()))
        else:
            ties = np.flatnonzero(feasible & (total == total[feasible].max()))
        row = int(ties[rng.randrange(len(ties))])
        cluster.commit(row, cpu, mem, ns, labels, term)
        out.append((uid, cluster.names[row]))
    return out
