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
        self.names = [f"node-{i}" for i in order]
        self.jsons = [
            _fill(text, f"node-{i}", i, cycles).encode() for i in order
        ]


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
        self.uids = [f"{s}/{n}" for s, n in zip(ns, self.names)]
        self.jsons = [
            _fill(first if k < initial else text, n, k, cycles)
            .replace("{namespace}", ns[k]).encode()
            for k, n in enumerate(self.names)
        ]
        self.frames = _Frames(self.jsons)

    def __len__(self) -> int:
        return len(self.uids)
