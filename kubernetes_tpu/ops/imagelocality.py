"""ImageLocality, vectorized.

Reference (plugins/imagelocality/image_locality.go): a node scores the summed
sizes of the pod's container images it already holds, each scaled by the
image's spread across the cluster (``size × numNodesWithImage/totalNodes``,
:117 scaledImageScore, truncated per image), then clamped into
[23MB, 1000MB × numContainers] and mapped to [0, MaxNodeScore]
(:84 calculatePriority).  Image names are normalized to a tagged CRI form
(:128 normalizedImageName).

TPU design: node rows carry interned image-name slots (one per alias) with
sizes; a pod ships its container image ids and the device computes presence
masks, spread counts, and the clamp in one vector pass.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..api import types as t
from ..framework.config import MAX_NODE_SCORE
from ..snapshot import _bucket
from .common import FeaturizeContext, OpDef, PassContext, feature_fill, register
from .helpers import bounded_quotient

MB = 1024 * 1024
MIN_THRESHOLD = 23 * MB
MAX_CONTAINER_THRESHOLD = 1000 * MB


def normalized_image_name(name: str) -> str:
    """image_locality.go:128 — append :latest when the ref has no tag."""
    if name.rfind(":") <= name.rfind("/"):
        name = name + ":latest"
    return name


def featurize(pod: t.Pod, fctx: FeaturizeContext) -> dict:
    refs = [
        normalized_image_name(img)
        for c in list(pod.spec.init_containers) + list(pod.spec.containers)
        for img in c.images
    ]
    # Unknown images can never be on a node: leave them as -1 (scores 0).
    ids = [fctx.interns.images.get(r) for r in refs]
    dim = _bucket(max(len(ids), 1), 1)
    arr = np.full(dim, -1, np.int32)
    arr[: len(ids)] = ids
    n_containers = len(pod.spec.init_containers) + len(pod.spec.containers)
    return {"il_image_ids": arr, "il_ncontainers": np.int64(max(n_containers, 1))}


def score_fn(state, pf, ctx: PassContext, feasible):
    ids = pf["il_image_ids"]  # (CI,)
    active = ids >= 0
    # (CI, N, IM) presence of each wanted image in each node's slots.
    hit = state.image_ids[None, :, :] == ids[:, None, None]
    hit &= active[:, None, None]
    present = hit.any(-1)  # (CI, N)
    # Size of the image on the node (0 when absent); slots of one image alias
    # set never collide within a node row.
    size = jnp.where(hit, state.image_sizes[None, :, :], 0).sum(-1)  # (CI, N)
    num_nodes_with = (present & state.valid[None, :]).sum(-1)  # (CI,)
    total = jnp.maximum(state.valid.sum(), 1)
    spread = num_nodes_with.astype(jnp.float64) / total.astype(jnp.float64)
    # Per-image truncation before the sum (scaledImageScore returns int64).
    scaled = (size.astype(jnp.float64) * spread[:, None]).astype(jnp.int64)
    sum_scores = scaled.sum(0)  # (N,)

    max_threshold = MAX_CONTAINER_THRESHOLD * pf["il_ncontainers"]
    clamped = jnp.clip(sum_scores, MIN_THRESHOLD, max_threshold)
    denom = jnp.maximum(max_threshold - MIN_THRESHOLD, 1)
    # The clip holds every row's quotient in [0, MaxNodeScore].
    return bounded_quotient(
        MAX_NODE_SCORE * (clamped - MIN_THRESHOLD), denom, MAX_NODE_SCORE
    )


feature_fill("il_image_ids", -1)
feature_fill("il_ncontainers", 1)
def is_active(pod: t.Pod, fctx: FeaturizeContext) -> bool:
    # Score is 0 for every node when the pod names no images or no node
    # reports any (min-threshold clamp maps empty sums to 0).
    if len(fctx.interns.images) == 0:
        return False
    return any(
        c.images for c in list(pod.spec.init_containers) + list(pod.spec.containers)
    )


register(
    OpDef(name="ImageLocality", featurize=featurize, score=score_fn, is_active=is_active)
)
