"""Plain reference for the default plugin profile with the CSI attach limit:
``default_profile.py``'s resources, scores, required-affinity rule and
commit, loaded from the file beside this one (not copied: one arithmetic,
two files that read it), plus what upstream's ``plugins/nodevolumelimits``
(``csi.go``) and the bound half of ``plugins/volumebinding`` hold a pod
with PersistentVolumeClaims to.

The filter follows ``csi.go`` (``Filter``): the pod's volumes are resolved
claim -> volume -> CSI driver; those the node already holds attached do not
count again (``attachedVolumes``: a volume is its driver and its handle, so
a claim that two pods of one node share is ONE volume there); for every
driver with a limit in the node's CSINode

    distinct volumes of the driver on the node + the pod's new ones <= limit

The volume handle is taken to be the claim (namespace/name): a
PersistentVolume carries one ``claimRef``, so two pods reach one volume
only through one claim.

What the configuration's guarantees state, and where it is counted, from
the objects this module was given and nothing else:
  * a node that ends with more distinct attached volumes of a driver than
    its CSINode allows counts into ``over_capacity``;
  * a decision that puts a measured pod on a node whose budget for one of
    the pod's drivers was full at that commit counts into ``infeasible``;
  * a measured pod with a claim that is missing, unbound, bound to a volume
    that does not exist or to one that does not point back counts into
    ``infeasible`` (VolumeBinding's PreFilter refuses such a pod).

Departures from upstream's text, each refused by name where a pod could
ask for it:
  * volumes that do not name a claim (in-tree devices, ephemeral volumes)
    are not here; nor are migrated in-tree plugins (``migratedPlugins``):
    the row's volumes are CSI volumes;
  * a volume's node affinity and zone labels (VolumeBinding's and
    VolumeZone's topology) are refused: the row's volumes have none;
  * a CSINode is its node's name and its drivers' counts; a node without a
    CSINode, or a driver without a count, has no limit (``csi.go`` skips
    it);
  * pods are never deleted, so nothing is ever detached.

Resources and scores are ``default_profile``'s (which refuses a pod with
volumes, so they are taken off before it reads the pod).  NodeVolumeLimits
and VolumeBinding add no score for bound claims, so the totals compared are
``default_profile``'s and ``score_gap_mean`` means here what it means in
the other cells; the best score is taken over the nodes that pass fit, the
required term (if any) and the attach limit.

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
    "default_profile_under_csi_pvs",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "default_profile.py"))
base = importlib.util.module_from_spec(_sp)
_sp.loader.exec_module(base)
Unsupported = base.Unsupported

COMPANION_KINDS = ("CSINode", "PersistentVolumeClaim", "PersistentVolume")
NO_LIMIT = np.iinfo(np.int64).max
# None: the limits are what the CSINodes say.  A test that plants a fault in
# the reference alone writes a copy of this file with another number here.
LIMIT_READ_AS = None


def pod_facts(pod_json: bytes, companions: dict) -> tuple:
    """(``default_profile``'s facts, the (driver, claim) of each distinct
    volume the pod attaches, how many of its claims do not resolve)."""
    d = json.loads(pod_json)
    ns = d["metadata"].get("namespace") or "default"
    claims = {(c["namespace"], c["name"]): c for c in map(json.loads, companions["PersistentVolumeClaim"])}
    volumes = {v["name"]: v for v in map(json.loads, companions["PersistentVolume"])}
    attached, unresolved = [], 0
    for vol in d["spec"]["volumes"]:
        if vol["device_id"] or not vol["pvc"]:
            raise Unsupported("only volumes that name a claim are in this reference")
        claim = claims.get((ns, vol["pvc"]))
        pv = volumes.get(claim["volume_name"]) if claim and claim["volume_name"] else None
        if pv is None or pv["claim_ref"] != f"{ns}/{vol['pvc']}":
            unresolved += 1
            continue
        if pv.get("node_affinity") or pv.get("labels"):
            raise Unsupported("a volume's node affinity and zone labels are not in this reference")
        if pv["csi_driver"] and (pv["csi_driver"], f"{ns}/{vol['pvc']}") not in attached:
            attached.append((pv["csi_driver"], f"{ns}/{vol['pvc']}"))
    d["spec"]["volumes"] = []
    return base.pod_facts(json.dumps(d).encode()), tuple(attached), unresolved


class Cluster(base.Cluster):
    """``default_profile``'s cluster, and per driver how many distinct
    volumes each node holds attached, and where each volume is."""

    def __init__(self, node_jsons, names, companions):
        # the node's own attachable-volumes-* allocatable is the in-tree
        # plugins' count; the limit held here is the CSINode's
        super().__init__(node_jsons, names)
        self.limit: dict[str, np.ndarray] = {}  # driver -> attach limit a node
        for raw in companions["CSINode"]:
            d = json.loads(raw)
            row = self.row.get(d["name"])
            if row is None:
                continue
            for driver, count in d["driver_limits"].items():
                per_node = self.limit.setdefault(driver, np.full(len(self.names), NO_LIMIT))
                per_node[row] = count if LIMIT_READ_AS is None else LIMIT_READ_AS
        self.count: dict[str, np.ndarray] = {}  # driver -> distinct volumes a node
        # (driver, volume) -> the row that holds it, or the set of rows once
        # a second node does
        self.where: dict[tuple, int | set] = {}
        self.fullest = 0

    def _rows_of(self, vol: tuple) -> tuple:
        at = self.where.get(vol)
        if at is None:
            return ()
        return (at,) if isinstance(at, int) else tuple(at)

    def attach_mask(self, attached) -> np.ndarray | None:
        """Nodes whose CSINode still has room for the pod's new volumes of
        every driver, or None where no volume of it has a limit."""
        mask = None
        new: dict[str, np.ndarray] = {}
        for driver, vol in attached:
            if driver not in self.limit:
                continue
            per_node = new.get(driver)
            if per_node is None:
                per_node = new[driver] = np.zeros(len(self.names), np.int64)
            per_node += 1
            for row in self._rows_of((driver, vol)):
                per_node[row] -= 1  # already attached there: not new
        for driver, per_node in new.items():
            held = self.count.get(driver)
            total = per_node if held is None else held + per_node
            ok = (total <= self.limit[driver]) | (per_node == 0)
            mask = ok if mask is None else mask & ok
        return mask

    def attach(self, row: int, attached) -> None:
        for driver, vol in attached:
            at = self.where.get((driver, vol))
            if at is None:
                self.where[(driver, vol)] = row
            elif isinstance(at, int):
                if at == row:
                    continue
                self.where[(driver, vol)] = {at, row}
            elif row in at:
                continue
            else:
                at.add(row)
            held = self.count.get(driver)
            if held is None:
                held = self.count[driver] = np.zeros(len(self.names), np.int64)
            held[row] += 1
            self.fullest = max(self.fullest, int(held[row]))

    def over_capacity(self) -> int:
        over = ((self.used_cpu > self.alloc_cpu) | (self.used_mem > self.alloc_mem)
                | (self.used_pods > self.alloc_pods))
        for driver, held in self.count.items():
            if driver in self.limit:
                over |= held > self.limit[driver]
        return int(over.sum())


class Replay(base.Replay):
    """Walk the program's answers in commit order.  ``examples[0]`` says how
    full the fullest node stands, which is what a run's timeline carries."""

    def __init__(self, cluster: Cluster):
        super().__init__(cluster)
        self.examples = [""]
        self._say()

    def _say(self) -> None:
        self.examples[0] = f"most distinct volumes of one driver on a node: {self.cluster.fullest}"

    def _note(self, text: str) -> None:
        self.infeasible += 1
        if len(self.examples) < 6:
            self.examples.append(text)

    def step(self, uid: str, node: str, facts, measure: bool) -> None:
        cl = self.cluster
        (cpu, mem, ns, labels, term), attached, unresolved = facts
        cl.watch(term)
        row = cl.row.get(node)
        if row is None:
            self.unknown_node += 1
            return
        if measure:
            feasible = cl.fit_mask(cpu, mem)
            for mask in (cl.affinity_mask(ns, labels, term), cl.attach_mask(attached)):
                if mask is not None:
                    feasible &= mask
            if unresolved:
                self._note(f"{uid}->{node}: {unresolved} claim(s) unresolved")
            elif not feasible[row]:
                self._note(f"{uid}->{node}")
            else:
                total = cl.scores(cpu, mem, ns, labels, feasible)
                self.gaps.append(int(total[feasible].max() - total[row]))
        cl.commit(row, cpu, mem, ns, labels, term)
        cl.attach(row, attached)
        self._say()


def place(cluster: Cluster, pods, stale: int, seed: int, drop_limit: bool = False,
          wander: float = 0.0, wander_to: str = "random", drop_affinity: bool = False):
    """The reference in the program's place, with ``default_profile.place``'s
    contract: bind ``pods`` (uid, facts) in order, scoring on a view of the
    cluster refreshed every ``stale`` decisions.  Within a view capacity and
    the attach limit are still judged exactly against the pods bound so far
    (the program's chunk defers a chunk-mate that lands where another's
    volumes did), a required term on the view, and ties break at random from
    ``seed``.  A pod with a claim that does not resolve is bound nowhere.
    ``drop_limit`` switches the attach limit off (a second way to break a
    guarantee); ``drop_affinity``, ``wander`` and ``wander_to`` are
    ``default_profile.place``'s.  Returns [(uid, node name or "")]."""
    rng = random.Random(seed)
    out = []
    cache: dict = {}
    for k, (uid, facts) in enumerate(pods):
        (cpu, mem, ns, labels, term), attached, unresolved = facts
        cluster.watch(term)
        if k % stale == 0:
            cache = {}
        if unresolved:
            out.append((uid, ""))
            continue
        feasible = cluster.fit_mask(cpu, mem)
        ok = None if drop_limit else cluster.attach_mask(attached)
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
        cluster.attach(row, attached)
        out.append((uid, cluster.names[row]))
    return out
