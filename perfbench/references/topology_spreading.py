"""Plain reference for the default plugin profile with PodTopologySpread's
filter: ``default_profile.py``'s resources, scores, required-affinity rule
and commit, loaded from the file beside this one (not copied: one
arithmetic, two files that read it), plus the DoNotSchedule half of
upstream's ``plugins/podtopologyspread``.

The filter follows ``filtering.go`` (``Filter``, with the counts that
``calPreFilterState`` and ``AddPod`` keep): for each constraint of the
incoming pod the node has to carry the topology key, and

    matchNum + selfMatchNum - minMatchNum <= maxSkew

where ``matchNum`` counts the pods of the incoming pod's own namespace that
match the constraint's selector and sit in the node's domain,
``selfMatchNum`` is 1 where the pod's own labels match the selector, and
``minMatchNum`` is the least count over every domain that exists among the
nodes that carry the key, an empty domain included.

DoNotSchedule constraints add no score: upstream's ``scoring.go``
``PreScore`` keeps only the ScheduleAnyway constraints
(``filterTopologySpreadConstraints(..., v1.ScheduleAnyway)``) and returns
Skip when none is left, so the plugin's ``Score`` never runs for these pods
and the totals compared are ``default_profile``'s.  The best score is
taken over the nodes that pass fit, the required term (if any) and every
constraint, so ``score_gap_mean`` means here what it means in the other
cells.

Departures from upstream's text, each refused by name where a pod could
ask for it:
  * ``whenUnsatisfiable: ScheduleAnyway`` (the score half) is not here;
  * selectors are ``matchLabels`` only: ``matchExpressions`` and a null
    selector are refused;
  * ``minDomains`` (anything but null) and ``matchLabelKeys`` are refused;
  * ``nodeAffinityPolicy`` has to be Honor and ``nodeTaintsPolicy`` Ignore,
    the defaults; since ``default_profile`` refuses node selectors, node
    affinity and taints, every node that carries the key is eligible, as
    upstream's ``processNode`` would find;
  * a pod's constraints have to name one topology key: with a second key
    upstream counts only nodes that carry every key, which is not here;
  * pods are never deleted and nodes never change, so ``RemovePod`` and
    the minimum's two-entry ``criticalPaths`` are a plain ``min`` over a
    count a domain;
  * the system default constraints apply upstream only to pods that a
    Service, ReplicaSet or StatefulSet selects; the benchmark's pods have
    no owner, so a pod without constraints has none here either.

Straight numpy on plain numbers read from the same JSON the wire carried.
It imports nothing of the program.  ``Replay`` walks the program's answers
in commit order; ``place`` is the same arithmetic put in the program's
place, as ``default_profile.place`` is: the control, and the tests'
stand-in for the program.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random

import numpy as np

_sp = importlib.util.spec_from_file_location(
    "default_profile_under_topology_spreading",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "default_profile.py"))
base = importlib.util.module_from_spec(_sp)
_sp.loader.exec_module(base)
Unsupported = base.Unsupported


def pod_facts(pod_json: bytes) -> tuple:
    """(``default_profile``'s facts, constraints): each constraint is
    (topology key, max skew, selector's label items, selfMatchNum)."""
    d = json.loads(pod_json)
    own = d["metadata"].get("labels", {})
    spread = []
    for c in d["spec"].get("topology_spread_constraints") or ():
        if c["when_unsatisfiable"] != "DoNotSchedule":
            raise Unsupported(f"whenUnsatisfiable {c['when_unsatisfiable']} is not in this reference")
        sel = c.get("label_selector")
        if sel is None or sel.get("match_expressions"):
            raise Unsupported("a null selector and matchExpressions are not in this reference")
        if c.get("min_domains") is not None:
            raise Unsupported("minDomains is not in this reference")
        if c.get("match_label_keys"):
            raise Unsupported("matchLabelKeys is not in this reference")
        if c.get("node_affinity_policy", "Honor") != "Honor":
            raise Unsupported(f"nodeAffinityPolicy {c['node_affinity_policy']} is not in this reference")
        if c.get("node_taints_policy", "Ignore") != "Ignore":
            raise Unsupported(f"nodeTaintsPolicy {c['node_taints_policy']} is not in this reference")
        pairs = tuple(sorted((k, v) for k, v in sel.get("match_labels", ())))
        self_match = int(all(own.get(k) == v for k, v in pairs))
        spread.append((c["topology_key"], int(c["max_skew"]), pairs, self_match))
    if len({c[0] for c in spread}) > 1:
        raise Unsupported("constraints over a second topology key are not in this reference")
    d["spec"]["topology_spread_constraints"] = []
    return base.pod_facts(json.dumps(d).encode()), tuple(spread)


class Cluster(base.Cluster):
    """``default_profile``'s cluster, and per (namespace, topology key,
    selector) how many matching pods of that namespace each domain holds."""

    def __init__(self, node_jsons, names):
        super().__init__(node_jsons, names)
        self._zones: dict[str, tuple] = {}
        # (namespace, label items) -> pods per node: what a count that is
        # first asked for late is built from
        self.group_nodes: dict[tuple, np.ndarray] = {}
        # (namespace, topology key, selector's label items) -> pods per domain
        self.spread_count: dict[tuple, np.ndarray] = {}

    def zones(self, key: str) -> tuple:
        """(domain id per node, node carries the key, number of domains):
        the domains that exist are those of the nodes that carry the key."""
        z = self._zones.get(key)
        if z is None:
            ids: dict[str, int] = {}
            has = np.array([key in lab for lab in self.labels], bool)
            dom = np.array([ids.setdefault(lab[key], len(ids)) if key in lab else 0
                            for lab in self.labels], np.int64)
            z = self._zones[key] = (dom, has, len(ids))
        return z

    def matching(self, ns: str, topo: str, pairs: tuple) -> np.ndarray:
        """Pods of ``ns`` matching ``pairs``, by domain of ``topo``."""
        count = self.spread_count.get((ns, topo, pairs))
        if count is None:
            dom, has, n = self.zones(topo)
            count = np.zeros(n, np.int64)
            for (space, labels), per_node in self.group_nodes.items():
                if space == ns and set(pairs) <= set(labels):
                    np.add.at(count, dom[has], per_node[has])
            self.spread_count[(ns, topo, pairs)] = count
        return count

    def spread_mask(self, ns: str, spread) -> np.ndarray | None:
        """Nodes every constraint lets the pod onto, or None without one."""
        mask = None
        for topo, max_skew, pairs, self_match in spread:
            dom, has, n = self.zones(topo)
            if n == 0:
                return np.zeros(len(dom), bool)
            count = self.matching(ns, topo, pairs)
            ok = has & (count[dom] + self_match - count.min() <= max_skew)
            mask = ok if mask is None else mask & ok
        return mask

    def skew(self, ns: str, spread) -> int:
        """Largest (fullest domain - emptiest domain) over the constraints."""
        return max((int(c.max() - c.min()) for c in
                    (self.matching(ns, topo, pairs) for topo, _, pairs, _ in spread) if len(c)), default=0)

    def commit(self, row: int, cpu: int, mem: int, ns, labels, term) -> None:
        super().commit(row, cpu, mem, ns, labels, term)
        per_node = self.group_nodes.get((ns, labels))
        if per_node is None:
            per_node = self.group_nodes[(ns, labels)] = np.zeros(len(self.names), np.int64)
        per_node[row] += 1
        for (space, topo, pairs), count in self.spread_count.items():
            dom, has, _ = self._zones[topo]
            if space == ns and has[row] and set(pairs) <= set(labels):
                count[dom[row]] += 1


class Replay(base.Replay):
    """Walk the program's answers in commit order.  ``skew_max`` is the
    largest skew a measured pod's commit left behind; it is the first of
    ``examples``, which is what a run's timeline carries."""

    def __init__(self, cluster: Cluster):
        super().__init__(cluster)
        self.skew_max = 0
        self.examples = [""]
        self._say()

    def _say(self) -> None:
        self.examples[0] = f"largest skew among the measured pods at any commit: {self.skew_max}"

    def step(self, uid: str, node: str, facts, measure: bool) -> None:
        cl = self.cluster
        (cpu, mem, ns, labels, term), spread = facts
        cl.watch(term)
        row = cl.row.get(node)
        if row is None:
            self.unknown_node += 1
            return
        if measure:
            feasible = cl.fit_mask(cpu, mem)
            for mask in (cl.affinity_mask(ns, labels, term), cl.spread_mask(ns, spread)):
                if mask is not None:
                    feasible &= mask
            if not feasible[row]:
                self.infeasible += 1
                if len(self.examples) < 6:
                    self.examples.append(f"{uid}->{node}")
            else:
                total = cl.scores(cpu, mem, ns, labels, feasible)
                self.gaps.append(int(total[feasible].max() - total[row]))
        cl.commit(row, cpu, mem, ns, labels, term)
        if measure and spread:
            self.skew_max = max(self.skew_max, cl.skew(ns, spread))
            self._say()


def place(cluster: Cluster, pods, stale: int, seed: int, drop_spread: bool = False,
          wander: float = 0.0, wander_to: str = "random", drop_affinity: bool = False):
    """The reference in the program's place, with ``default_profile.place``'s
    contract: bind ``pods`` (uid, facts) in order, scoring on a view of the
    cluster refreshed every ``stale`` decisions.  Within a view capacity and
    the constraints are still judged exactly against the pods bound so far
    (the program's chunk defers a chunk-mate whose constraint reads what
    another wrote), a required term on the view, and ties break at random
    from ``seed``.  ``drop_spread`` switches the constraints off (a second
    way to break a guarantee); ``drop_affinity``, ``wander`` and
    ``wander_to`` are ``default_profile.place``'s.
    Returns [(uid, node name or "")]."""
    rng = random.Random(seed)
    out = []
    cache: dict = {}
    for k, (uid, facts) in enumerate(pods):
        (cpu, mem, ns, labels, term), spread = facts
        cluster.watch(term)
        if k % stale == 0:
            cache = {}
        feasible = cluster.fit_mask(cpu, mem)
        ok = None if drop_spread else cluster.spread_mask(ns, spread)
        if ok is not None:
            feasible &= ok
        key = (cpu, mem, ns, labels, term)
        if key not in cache:
            aff = None if drop_affinity else cluster.affinity_mask(ns, labels, term)
            seen = feasible if aff is None else feasible & aff
            cache[key] = (cluster.scores(cpu, mem, ns, labels, seen, ipa=not drop_affinity), aff)
        total, aff = cache[key]
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
