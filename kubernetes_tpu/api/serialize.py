"""Canonical JSON (de)serialization for the API object model.

The sidecar wire protocol ships cluster objects as JSON — the same choice
the reference's extender protocol makes for v1.Pod (extender/v1/types.go
ExtenderArgs) — so any host scheduler (Go, C++, Python) can produce them
without sharing our dataclasses.  Encoding is a direct field mapping:
dataclass → object, tuple → array, INT_SENTINEL-free primitives as-is."""

from __future__ import annotations

import dataclasses
import json
import threading
import typing
from typing import Any, get_args, get_origin, get_type_hints

from . import types as t

_HINTS_CACHE: dict[type, dict[str, Any]] = {}


def _codegen():
    # Deferred: codegen imports back into this module's _build as the
    # missing-key fallback.  Double-checked init — server threads and the
    # in-process client race the first call.
    global _GEN
    g = _GEN
    if g is None:
        with _GEN_LOCK:
            if _GEN is None:
                from . import codegen

                _GEN = codegen._Gen(_build)
            g = _GEN
    return g


_GEN = None
_GEN_LOCK = threading.Lock()


def to_dict(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {
            f.name: to_dict(getattr(obj, f.name))
            for f in dataclasses.fields(obj)
        }
    if isinstance(obj, (list, tuple)):
        return [to_dict(x) for x in obj]
    if isinstance(obj, dict):
        return {k: to_dict(v) for k, v in obj.items()}
    return obj


def to_json(obj: Any) -> bytes:
    """Canonical JSON bytes.  Dataclasses go through the generated
    per-type dumper (codegen.py — byte-identical to the reflective
    to_dict path, ~8× faster); anything else through to_dict."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        data = _codegen().dumper(type(obj))(obj)
    else:
        data = to_dict(obj)
    return json.dumps(data, sort_keys=True).encode()


def build(tp: type, data: Any):
    """Fast reconstruction via the generated per-type builder."""
    return _codegen().builder(tp)(data)


def _build(tp: Any, data: Any) -> Any:
    """Reconstruct a value of type ``tp`` from plain JSON data."""
    if data is None:
        return None
    origin = get_origin(tp)
    if origin is typing.Union:  # Optional[X] and unions
        for arg in get_args(tp):
            if arg is type(None):
                continue
            return _build(arg, data)
        return None
    if origin in (tuple,):
        args = get_args(tp)
        if len(args) == 2 and args[1] is Ellipsis:
            return tuple(_build(args[0], x) for x in data)
        return tuple(_build(a, x) for a, x in zip(args, data))
    if origin in (list,):
        (elem,) = get_args(tp) or (Any,)
        return [_build(elem, x) for x in data]
    if origin in (dict,):
        kt, vt = get_args(tp) or (Any, Any)
        return {k: _build(vt, v) for k, v in data.items()}
    if isinstance(tp, type) and dataclasses.is_dataclass(tp):
        hints = _HINTS_CACHE.get(tp)
        if hints is None:
            hints = get_type_hints(tp)
            _HINTS_CACHE[tp] = hints
        kwargs = {
            f.name: _build(hints[f.name], data[f.name])
            for f in dataclasses.fields(tp)
            if f.name in data
        }
        return tp(**kwargs)
    return data


def pod_from_json(raw: bytes | str) -> t.Pod:
    return pod_from_data(json.loads(raw))


def featsig_from_data(namespace, labels, spec_data) -> tuple:
    """THE featurization-cache key constructor — the single source for
    both entry paths (wire pods here via pod_from_data; in-process pods
    via engine/features.pod_sig), so identical templates always share
    cache entries: the key is (namespace, sort-keys labels JSON or "",
    sort-keys spec JSON) over the canonical data model, and the two
    paths produce string-identical dumps because the canonical dumper
    emits exactly the parsed wire shape.

    The NAMES of the pod's claims stay out of it: pods that differ only in
    which claim each names (a claim of its own a pod) share the signature,
    and the cache's key puts back what featurization reads of each claim
    (engine/features._claim_key)."""
    vols = spec_data.get("volumes")
    if vols and any(v.get("pvc") for v in vols):
        spec_data = dict(
            spec_data,
            volumes=[dict(v, pvc="*") if v.get("pvc") else v for v in vols],
        )
    return (
        namespace or "default",
        json.dumps(labels, sort_keys=True) if labels else "",
        json.dumps(spec_data, sort_keys=True),
    )


def pod_from_data(data: dict) -> t.Pod:
    """Pod from parsed JSON data, pre-stamping the featurization
    signature (engine/features.py `_featsig`) for unassigned, un-pinned
    pods: identical template-stamped pods share identical canonical spec
    JSON, so the sort-keys dump of the parsed subtrees IS the cache key —
    computed here at C speed."""
    pod = build(t.Pod, data)
    spec = data.get("spec")
    if spec is not None and not spec.get("node_name"):
        from ..engine.features import pin_name

        if pin_name(pod) is None:
            meta = data.get("metadata") or {}
            pod._featsig = featsig_from_data(
                meta.get("namespace"), meta.get("labels"), spec
            )
    return pod


def node_from_json(raw: bytes | str) -> t.Node:
    return build(t.Node, json.loads(raw))


# Kind name → (type, scheduler add-method name) for the sidecar's AddObject.
KINDS: dict[str, tuple[type, str]] = {
    # update_node diffs against the cached record for precise requeue
    # events and falls back to add for unknown nodes — upserts over the
    # wire must not fire NODE_ADD per heartbeat.
    "Node": (t.Node, "update_node"),
    # update_pod diffs against the cached/queued record (no-op for
    # status-only re-deliveries) and falls back to add for unknown pods —
    # re-running add_pod per watch upsert would double-apply a bound pod's
    # resource delta and gang quorum credit (ADVICE r2).
    "Pod": (t.Pod, "update_pod"),
    "PersistentVolume": (t.PersistentVolume, "add_pv"),
    "PersistentVolumeClaim": (t.PersistentVolumeClaim, "add_pvc"),
    "StorageClass": (t.StorageClass, "add_storage_class"),
    "CSINode": (t.CSINode, "add_csinode"),
    "PodGroup": (t.PodGroup, "add_pod_group"),
    "PodDisruptionBudget": (t.PodDisruptionBudget, "add_pdb"),
    "ResourceClaim": (t.ResourceClaim, "add_resource_claim"),
    "ResourceSlice": (t.ResourceSlice, "add_resource_slice"),
    # Node-heartbeat lease (coordination.k8s.io): renewals feed the
    # node-lifecycle controller's staleness clock (controllers.py).
    "Lease": (t.Lease, "renew_node_lease"),
}

# Kind name → scheduler remove-method for the kinds that support watch
# DELETED events (the Reflector's full object surface and the sidecar's
# remove frame).  Pod/Node keep their historical direct routes
# (delete_pod / remove_node); the method takes the object's uid/name.
REMOVERS: dict[str, str] = {
    "Node": "remove_node",
    "Pod": "delete_pod",
    "PersistentVolume": "remove_pv",
    "PersistentVolumeClaim": "remove_pvc",
    "StorageClass": "remove_storage_class",
    "CSINode": "remove_csinode",
    "PodDisruptionBudget": "remove_pdb",
    "ResourceClaim": "remove_resource_claim",
    "ResourceSlice": "remove_resource_slice",
}


def from_json(kind: str, raw: bytes | str):
    if kind == "Pod":
        return pod_from_data(json.loads(raw))
    tp, _ = KINDS[kind]
    return build(tp, json.loads(raw))
