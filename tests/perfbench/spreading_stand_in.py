"""The throw-away spreading configuration's reference: a stand-in, not the
row's plain reference.  A test copies this file into the throw-away
checkout's ``references/``, beside ``default_profile.py``.

Resources and scores are ``default_profile``'s (which refuses a pod with a
spread constraint, so the constraints are taken off first), and each
DoNotSchedule constraint is held as the filter states it, from the answers
alone: pods of the incoming pod's namespace that match the constraint's
matchLabels are counted by domain, and a measured pod whose node's domain
would stand more than ``max_skew`` above the emptiest domain counts as
``infeasible``.  The gap is reckoned over nodes the constraint may rule
out, so no limit on it means anything here.  The largest skew among the
measured pods at any commit is the first of ``examples``.
"""

import importlib.util
import json
import os

import numpy as np

_sp = importlib.util.spec_from_file_location(
    "default_profile_under_a_stand_in",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "default_profile.py"))
base = importlib.util.module_from_spec(_sp)
_sp.loader.exec_module(base)
Cluster = base.Cluster


def pod_facts(raw: bytes):
    """(default_profile's facts, ((topology key, max skew, label items), ...))."""
    d = json.loads(raw)
    spread = []
    for c in d["spec"]["topology_spread_constraints"]:
        sel = c["label_selector"]
        if c["when_unsatisfiable"] != "DoNotSchedule" or sel["match_expressions"] or c["min_domains"]:
            raise base.Unsupported("only DoNotSchedule constraints on matchLabels are in this stand-in")
        spread.append((c["topology_key"], int(c["max_skew"]), tuple(map(tuple, sel["match_labels"]))))
    d["spec"]["topology_spread_constraints"] = []
    return base.pod_facts(json.dumps(d).encode()), tuple(spread)


class Replay(base.Replay):
    def __init__(self, cluster):
        super().__init__(cluster)
        self.counts = {}  # (namespace, topology key, label items) -> matching pods a domain
        self.skew_max = 0
        self.examples = [""]
        self._say()

    def _say(self):
        self.examples[0] = f"largest skew among the measured pods at any commit: {self.skew_max}"

    def step(self, uid, node, facts, measure):
        facts, spread = facts
        ns, labels = facts[2], set(facts[3])
        row = self.cluster.row.get(node)
        if row is None:
            return super().step(uid, node, facts, measure)
        for topo, max_skew, pairs in spread:
            dom = self.cluster.domain(topo)
            count = self.counts.setdefault((ns, topo, pairs), np.zeros(int(dom.max()) + 1, np.int64))
            skew = int(count[dom[row]] + 1 - count.min())
            if measure and skew > max_skew:
                self.infeasible += 1
                if len(self.examples) < 6:
                    self.examples.append(f"{uid}->{node}: skew {skew} > {max_skew}")
        super().step(uid, node, facts, measure)
        for (space, topo, pairs), count in self.counts.items():
            if space == ns and set(pairs) <= labels:
                count[self.cluster.domain(topo)[row]] += 1
                if measure:
                    self.skew_max = max(self.skew_max, int(count.max() - count.min()))
        self._say()
