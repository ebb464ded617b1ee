"""Cluster objects as the wire carries them, built from a configuration
file's literal templates.

A configuration holds one node template and one pod template for each of
the source's createPods ops (``pod.template`` for the measured pods,
``pod.initial_template`` for the initial ones where the row gives them a
template of their own) in the sidecar's canonical JSON (what the program's
own serializer emits for the same objects), with ``{name}``, ``{i}`` and
the configuration's own cyclic variables (``{zone}`` ...) as placeholders
inside string values.  For each cycle object ``i`` gets ``values[i %
len(values)]`` (upstream's labelNodePrepareStrategy: its labelValues dealt
round-robin in creation order) or, where the cycle is a ``prefix`` and a
``count``, ``prefix + str(i % count)``; a pod's ``{namespace}`` is the
configuration's ``pod.namespaces.initial`` for the first ``initial`` pods
(the source's initPods) and ``.measured`` for the rest.  Every object is
bytes before the window opens; nothing here touches the program.  (A pod's
one-pod ``Schedule`` request is those bytes in an envelope, ~11 us of
protobuf, and is made when a loop asks for it, which it does on a miss
only: some thirty of a window's 90,000 pods.  Made for every prebuilt pod
while the server starts, they cost the set-up a second; PERF.md, PR 27.)

A configuration may give nodes and pods *companions*: objects of another
kind, one for each node (``cluster.companions``: a list of {``kind``,
``template``}) or one for each pod that ``of`` selects (``pod.companions``:
a list of {``kind``, ``template``, ``of``: ``"measured"`` | ``"initial"`` |
``"all"``).  A companion's template is the sidecar's canonical JSON of its
kind with the placeholders of the object it accompanies (``{name}``,
``{i}``, ``{namespace}`` for a pod's, the same cycles), so that a pod
template can name its companion by ``{name}``.  Nothing here knows what a
kind means: upstream's SchedulingCSIPVs gives every node a CSINode and
every measured pod a claim and a volume, and says so in its file.

A configuration may also state that a pod goes back bound once it is
answered (``pod.bind_echo``: ``"answered"``; its ``assumed``
names the source): ``Pods.bound_frame`` is that echo, the pod's own bytes
with the node written in, made when the loop asks for it.
"""

from __future__ import annotations

import json
import random

from . import wire


def _fill(text: str, name: str, i: int, cycles: dict) -> str:
    out = text.replace("{name}", name).replace("{i}", str(i))
    for var, spec in cycles.items():
        out = out.replace("{" + var + "}", cycle_value(spec, i))
    return out


def cycle_value(spec: dict, i: int) -> str:
    if "values" in spec:
        return spec["values"][i % len(spec["values"])]
    return f"{spec['prefix']}{i % spec['count']}"


def cycle_length(spec: dict) -> int:
    return len(spec["values"]) if "values" in spec else int(spec["count"])


class Nodes:
    """The cluster's nodes in the order the seed shuffled them into (the
    order of arrival decides each node's device row)."""

    def __init__(self, config: dict, seed: int):
        cluster = config["cluster"]
        text = json.dumps(cluster["node_template"], sort_keys=True)
        cycles = cluster.get("cycles", {})
        order = list(range(cluster["nodes"]))
        random.Random(seed).shuffle(order)
        self.order = order
        self.names = [f"node-{i}" for i in order]
        self.jsons = [
            _fill(text, f"node-{i}", i, cycles).encode() for i in order
        ]


_UNBOUND = b'"node_name": ""'  # spec.node_name of a pending pod, as json.dumps writes it


class _Frames:
    """``frames[k]``: pod ``k``'s ``Schedule`` request, made when asked for."""

    def __init__(self, jsons):
        self._jsons = jsons

    def __getitem__(self, k: int) -> bytes:
        return wire.schedule_frame(self._jsons[k])

    def __len__(self) -> int:
        return len(self._jsons)


class Pods:
    """``count`` pods named from the seed, the first ``initial`` of them of
    the initial pods' template (the measured pods' where the configuration
    has no other) and in the initial pods' namespace, the rest of the
    measured pods' template.
    ``uids[k]`` is the uid the sidecar derives for pod ``k`` (namespace/name,
    the template leaving ``metadata.uid`` empty)."""

    def __init__(self, config: dict, seed: int, count: int, initial: int = 0, tag: str = "p"):
        pod = config["pod"]
        text = json.dumps(pod["template"], sort_keys=True)
        first = json.dumps(pod.get("initial_template", pod["template"]), sort_keys=True)
        cycles = pod.get("cycles", {})
        rng = random.Random((seed << 1) ^ 0x5EED)
        spaces = pod.get("namespaces") or {}
        own = pod["template"]["metadata"].get("namespace") or "default"
        ns = [spaces.get("initial", own)] * min(initial, count)
        ns += [spaces.get("measured", own)] * (count - len(ns))
        self.names = [
            f"{tag}-{k}-{rng.getrandbits(32):08x}" for k in range(count)
        ]
        self.namespaces = ns
        self.uids = [f"{s}/{n}" for s, n in zip(ns, self.names)]
        self.jsons = [
            _fill(first if k < initial else text, n, k, cycles)
            .replace("{namespace}", ns[k]).encode()
            for k, n in enumerate(self.names)
        ]
        self.frames = _Frames(self.jsons)

    def __len__(self) -> int:
        return len(self.uids)

    def bound_frame(self, k: int, node: str) -> bytes:
        """Pod ``k``'s bind echo: the pod as the plugin forwards it once
        the host scheduler has bound it to ``node`` (an AddObject of the
        pod with ``spec.node_name`` set), ready to send."""
        raw = self.jsons[k]
        if raw.count(_UNBOUND) != 1:
            raise ValueError(f"pod {self.uids[k]}: its template does not write spec.node_name as "
                             f"{_UNBOUND.decode()} exactly once, so it cannot be echoed as bound")
        return wire.add_frame("Pod", raw.replace(_UNBOUND, b'"node_name": "%s"' % node.encode()))


class Companions:
    """The objects that accompany a run's nodes and pods, every one of them
    bytes before the window opens.

    ``of_nodes``: [(kind, [json of node 0's, node 1's ...])] in the order of
    ``cluster.companions``, each list in the nodes' shuffled order.
    ``frames(a, z)``: the companions of pods ``[a, z)`` as AddObject frames
    ready to send, kind by kind in the order of ``pod.companions`` (the
    order in which the source creates a pod's objects), and how many they
    are.  ``of_pod(k)``: {kind: [json]} of pod ``k``'s own, for a reference
    that asks for them.  A configuration without companions has none of
    either, and draws nothing from the seed either way."""

    def __init__(self, config: dict, nodes: Nodes, pods: Pods, initial: int = 0):
        cluster, pod = config["cluster"], config["pod"]
        cycles = cluster.get("cycles", {})
        self.of_nodes = []
        for entry in cluster.get("companions", ()):
            text = json.dumps(entry["template"], sort_keys=True)
            self.of_nodes.append((entry["kind"], [
                _fill(text, name, i, cycles).encode() for name, i in zip(nodes.names, nodes.order)]))
        cycles = pod.get("cycles", {})
        count = len(pods)
        initial = min(initial, count)
        # (kind, first pod selected, one past the last, [json a selected pod])
        self._of_pods = []
        which = {"measured": (initial, count), "initial": (0, initial), "all": (0, count)}
        for entry in pod.get("companions", ()):
            if entry["of"] not in which:
                raise ValueError(f"pod.companions: of must be one of {sorted(which)}, not {entry['of']!r}")
            a, z = which[entry["of"]]
            text = json.dumps(entry["template"], sort_keys=True)
            self._of_pods.append((entry["kind"], a, z, [
                _fill(text, pods.names[k], k, cycles)
                .replace("{namespace}", pods.namespaces[k]).encode() for k in range(a, z)]))
        self.node_objects = sum(len(jsons) for _, jsons in self.of_nodes)
        self.per_pod = bool(self._of_pods)
        self._uids = pods.uids
        self._index: dict | None = None

    def frames(self, a: int, z: int) -> tuple[bytes, int]:
        out, n = [], 0
        for kind, first, last, jsons in self._of_pods:
            lo, hi = max(a, first), min(z, last)
            if lo < hi:
                out.extend(wire.add_frame(kind, j) for j in jsons[lo - first: hi - first])
                n += hi - lo
        return b"".join(out), n

    def of_pod(self, k: int) -> dict:
        out: dict = {}
        for kind, first, last, jsons in self._of_pods:
            if first <= k < last:
                out.setdefault(kind, []).append(jsons[k - first])
        return out

    def of_uid(self, uid: str) -> dict:
        if self._index is None:
            self._index = {u: k for k, u in enumerate(self._uids)}
        return self.of_pod(self._index[uid])
