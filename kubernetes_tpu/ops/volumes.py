"""Volume plugins, vectorized: VolumeBinding, VolumeZone,
VolumeRestrictions, NodeVolumeLimits.

Reference semantics:
  * VolumeBinding (plugins/volumebinding/volume_binding.go): bound claims
    restrict the pod to nodes matching each PV's node affinity; unbound
    claims with a WaitForFirstConsumer class need, per claim, a matching
    static PV whose affinity fits the node, or a provisioner whose
    StorageClass allowedTopologies fit; unbound Immediate claims are
    UnschedulableAndUnresolvable.  The actual binding (PreBind) happens
    host-side after the pick (volumes.VolumeCatalog.bind_pod_volumes).
  * VolumeZone (plugins/volumezone/volume_zone.go): each bound PV's
    zone/region labels (``__``-separated value sets) must match the node.
  * VolumeRestrictions (plugins/volumerestrictions/volume_restrictions.go):
    an in-tree device volume conflicts with an existing use on the node
    unless both sides are read-only; a ReadWriteOncePod claim already used
    by another pod is Unschedulable everywhere.
  * NodeVolumeLimits (plugins/nodevolumelimits/csi.go): per CSI driver,
    attached volumes + the pod's new volumes must stay within the CSINode
    allocatable count.

TPU design: all string/object work happens at featurize time against the
host VolumeCatalog.  PV affinities and zone labels compile into the same
requirement-program encoding NodeAffinity uses, with one extra *group* axis:
each claim (or bound PV) is an OR-group of terms and the node must satisfy
every group — evaluated as one broadcast + a segment-style group reduction.
Device conflicts and attach limits read per-node count tensors maintained by
the same commit deltas that move resources; the attach budget is a per-node,
per-driver count, and only a claim that several pods share holds a row of
per-claim, per-node counts beside it (snapshot.SnapshotBuilder).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..api import types as t
from ..snapshot import _bucket
from .common import FeaturizeContext, OpDef, PassContext, feature_fill, invert_filter, register
from .nodeaffinity import _Program, _eval_terms


class _GroupedProgram(_Program):
    """Requirement program whose terms belong to AND-ed OR-groups."""

    def __init__(self) -> None:
        super().__init__()
        self.groups: list[int] = []  # group id per term
        self.n_groups = 0

    def start_group(self) -> int:
        gid = self.n_groups
        self.n_groups += 1
        return gid

    def add_group_term(self, gid: int, term: t.NodeSelectorTerm, it) -> None:
        before = len(self.terms)
        self.add_term(term, it)
        if len(self.terms) > before:
            self.groups.append(gid)

    def add_group_true(self, gid: int) -> None:
        """A term that matches every node (PV without node affinity)."""
        self.terms.append([])
        self.groups.append(gid)

    def tensors(self, prefix: str) -> dict:
        # The term axis must cover every group id so _eval_grouped's
        # existence check sees term-less (unsatisfiable) groups too.
        out = super().tensors(prefix, min_terms=self.n_groups)
        gdim = out[f"{prefix}_op"].shape[0]
        groups = np.full(gdim, -1, np.int32)
        groups[: len(self.groups)] = self.groups
        out[f"{prefix}_group"] = groups
        out[f"{prefix}_ngroups"] = np.int32(self.n_groups)
        return out


def _eval_grouped(state, pf, prefix: str) -> jnp.ndarray:
    """(N,) bool: every group has ≥1 matching valid term."""
    term_match = _eval_terms(
        state, pf[f"{prefix}_op"], pf[f"{prefix}_key"],
        pf[f"{prefix}_vals"], pf[f"{prefix}_int"],
    )  # (T, N)
    term_match &= pf[f"{prefix}_term_valid"][:, None]
    groups = pf[f"{prefix}_group"]  # (T,) -1 pad
    n_groups = pf[f"{prefix}_ngroups"]
    t_dim = groups.shape[0]
    # Group satisfaction via max over the group's terms: one-hot matmul keeps
    # shapes static (group count ≤ term count).
    onehot = (groups[:, None] == jnp.arange(t_dim)[None, :]) & (groups >= 0)[:, None]
    grp_any = (onehot[:, :, None] & term_match[:, None, :]).any(0)  # (T, N)
    grp_exists = jnp.arange(t_dim)[:, None] < n_groups
    return (grp_any | ~grp_exists).all(0)


# --------------------------------------------------------------------------
# VolumeBinding
# --------------------------------------------------------------------------


def _vb_featurize(pod: t.Pod, fctx: FeaturizeContext) -> dict:
    cat = fctx.builder.volumes
    it = fctx.interns
    prog = _GroupedProgram()
    feasible = True
    for pvc in cat.pod_pvcs(pod):
        if pvc is None:
            feasible = False
            break
        kind, *rest = cat.classify(pvc)
        if kind in ("lost", "unbound_immediate"):
            feasible = False
            break
        if kind == "bound":
            pv = rest[0]
            gid = prog.start_group()
            if pv.node_affinity is None or not pv.node_affinity.terms:
                prog.add_group_true(gid)
            else:
                for term in pv.node_affinity.terms:
                    prog.add_group_term(gid, term, it)
        else:  # delayed
            candidates, sc = rest
            gid = prog.start_group()
            for pv in candidates:
                if pv.node_affinity is None or not pv.node_affinity.terms:
                    prog.add_group_true(gid)
                else:
                    for term in pv.node_affinity.terms:
                        prog.add_group_term(gid, term, it)
            from ..volumes import NO_PROVISIONER

            if sc.provisioner != NO_PROVISIONER:
                if sc.allowed_topologies is None or not sc.allowed_topologies.terms:
                    prog.add_group_true(gid)
                else:
                    for term in sc.allowed_topologies.terms:
                        prog.add_group_term(gid, term, it)
            # No candidates and no provisioner → empty group → infeasible
            # everywhere (correct: nothing can satisfy the claim yet).
    feats = prog.tensors("vb")
    feats["vb_feasible"] = np.bool_(feasible)
    return feats


def _vb_filter(state, pf, ctx: PassContext):
    return pf["vb_feasible"] & _eval_grouped(state, pf, "vb")


def _vb_hard(state, pf, ctx: PassContext):
    # Lost/unbound-immediate claims are UnschedulableAndUnresolvable; PV
    # affinity mismatches are too (deleting pods moves no volume).
    return ~_vb_filter(state, pf, ctx)


def _vb_active(pod: t.Pod, fctx: FeaturizeContext) -> bool:
    return any(v.pvc for v in pod.spec.volumes)


# --------------------------------------------------------------------------
# VolumeZone
# --------------------------------------------------------------------------


def _vz_featurize(pod: t.Pod, fctx: FeaturizeContext) -> dict:
    cat = fctx.builder.volumes
    it = fctx.interns
    prog = _GroupedProgram()
    feasible = True
    for pvc in cat.pod_pvcs(pod):
        if pvc is None:
            feasible = False
            break
        kind, *rest = cat.classify(pvc)
        if kind in ("lost", "unbound_immediate"):
            feasible = False
            break
        if kind != "bound":
            continue  # delayed claims are VolumeBinding's business
        reqs = cat.zone_requirements(rest[0])
        if reqs:
            gid = prog.start_group()
            prog.add_group_term(
                gid, t.NodeSelectorTerm(match_expressions=tuple(reqs)), it
            )
    feats = prog.tensors("vz")
    feats["vz_feasible"] = np.bool_(feasible)
    return feats


def _vz_filter(state, pf, ctx: PassContext):
    return pf["vz_feasible"] & _eval_grouped(state, pf, "vz")


def _vz_active(pod: t.Pod, fctx: FeaturizeContext) -> bool:
    return any(v.pvc for v in pod.spec.volumes)


# --------------------------------------------------------------------------
# VolumeRestrictions
# --------------------------------------------------------------------------


def _vr_featurize(pod: t.Pod, fctx: FeaturizeContext) -> dict:
    cat = fctx.builder.volumes
    # ReadWriteOncePod: any other pod already using the claim blocks
    # scheduling everywhere (volume_restrictions.go isRWOPConflict).
    rwop_ok = True
    for pvc in cat.pod_pvcs(pod):
        if pvc is not None and t.RWOP in pvc.access_modes:
            if cat.pvc_users.get(pvc.uid, 0) > 0:
                rwop_ok = False
                break
    return {"vr_rwop_ok": np.bool_(rwop_ok)}


def _vr_filter(state, pf, ctx: PassContext):
    ids = pf["vol_dev_ids"]  # (S,) engine base features
    active = ids >= 0
    safe = jnp.maximum(ids, 0)
    uses = state.dev_counts[safe]  # (S, N)
    rw_uses = state.dev_rw_counts[safe]
    ro = ~pf["vol_dev_rw"]
    # Read-only want: conflicts only with a writer; writer want: any use.
    conflict = jnp.where(ro[:, None], rw_uses > 0, uses > 0) & active[:, None]
    return pf["vr_rwop_ok"] & ~conflict.any(0)


def _vr_active(pod: t.Pod, fctx: FeaturizeContext) -> bool:
    return any(v.device_id or v.pvc for v in pod.spec.volumes)


# --------------------------------------------------------------------------
# NodeVolumeLimits
# --------------------------------------------------------------------------


def _nvl_filter(state, pf, ctx: PassContext):
    """Attach-limit check by DISTINCT volume (csi.go:219): per driver,
    csi_used + the pod's new volumes <= csi_limit.  A claim of the pod's
    own (slot id -1) is new wherever the pod goes; a SHARED claim (slot id
    = its row of csivol_counts) already attached to the node does not
    count again."""
    drv = pf["vol_csi_drv"]  # (S,) engine base features, -1 pad
    ids = pf["vol_csi_ids"]  # (S,) row of a shared claim, -1 for the pod's own
    act = drv >= 0
    present = (ids >= 0)[:, None] & (
        state.csivol_counts[jnp.maximum(ids, 0)] > 0
    )  # (S, N)
    newv = act[:, None] & ~present  # (S, N) — genuinely new attachments
    dr = state.csi_used.shape[0]
    drv_oh = (drv[:, None] == jnp.arange(dr)[None, :]) & act[:, None]
    new_cnt = (drv_oh[:, :, None] & newv[:, None, :]).sum(0)  # (DR, N)
    ok = state.csi_used + new_cnt <= state.csi_limit
    return (ok | (new_cnt == 0)).all(0)


def _nvl_active(pod: t.Pod, fctx: FeaturizeContext) -> bool:
    return any(v.pvc for v in pod.spec.volumes) and len(fctx.interns.drivers) > 0


for _k, _fill in [
    ("vb_op", -1), ("vb_key", -1), ("vb_vals", -1), ("vb_int", 0),
    ("vb_term_valid", 0), ("vb_group", -1), ("vb_ngroups", 0), ("vb_feasible", 1),
    ("vz_op", -1), ("vz_key", -1), ("vz_vals", -1), ("vz_int", 0),
    ("vz_term_valid", 0), ("vz_group", -1), ("vz_ngroups", 0), ("vz_feasible", 1),
    ("vr_rwop_ok", 1),
]:
    feature_fill(_k, _fill)

register(
    OpDef(
        name="VolumeBinding",
        featurize=_vb_featurize,
        filter=_vb_filter,
        hard_filter=_vb_hard,
        is_active=_vb_active,
    )
)
register(
    OpDef(
        name="VolumeZone",
        featurize=_vz_featurize,
        filter=_vz_filter,
        # Zone label mismatches are UnschedulableAndUnresolvable
        # (volume_zone.go ErrReasonConflict).
        hard_filter=invert_filter(_vz_filter),
        is_active=_vz_active,
    )
)
register(
    OpDef(
        name="VolumeRestrictions",
        featurize=_vr_featurize,
        filter=_vr_filter,
        is_active=_vr_active,
    )
)
register(
    OpDef(
        name="NodeVolumeLimits",
        filter=_nvl_filter,
        is_active=_nvl_active,
    )
)
