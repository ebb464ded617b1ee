"""PodTopologySpread, vectorized.

Reference (plugins/podtopologyspread/):
  * Filter (filtering.go:283): for each DoNotSchedule constraint, the
    candidate node must carry the topology key, and
    ``matchNum + selfMatch − minMatchNum ≤ maxSkew`` where matchNum counts
    selector-matching pods in the candidate's topology domain and minMatchNum
    is the global minimum over existing domains (0 if fewer than minDomains
    domains exist; MaxInt32 when no eligible domain exists —
    newCriticalPaths, filtering.go:113).
  * Score (scoring.go): per ScheduleAnyway constraint, a node is credited
    ``cnt × log(topoSize+2) + (maxSkew−1)`` (scoreForCount :318) where cnt is
    the domain's matching-pod count (per-node count for the hostname key,
    :254); nodes missing a topology key are "ignored" → score 0; the final
    normalization maps to ``100 × (max + min − s) / max`` (:276).
  * Domain counting eligibility (filtering.go:262 processNode): nodes must
    carry all constraint topology keys, and per-constraint node inclusion
    policies apply (nodeAffinityPolicy Honor → pod's nodeSelector/required
    affinity; nodeTaintsPolicy Honor → pod tolerates the node's
    hard taints; defaults Honor/Ignore).

TPU design: pods with identical (namespace, labels) share an interned *group*;
the cluster state keeps per-(group, node) pod counts.  A constraint's selector
is compiled host-side to a (G,) group bitmask, so per-node matching-pod counts
are one f32 matmul ``(C,G) × (G,N)`` on the MXU.  Domains are interned
topology-value ids; per-domain sums/minima are segment reductions into a
(DV,)-bucketed table, gathered back per node.  Node-inclusion policies reuse
the NodeAffinity and TaintToleration ops' device filters on the same pod
features.

The DoNotSchedule constraint masks (``tps_h_groups`` — the ``tps_h``
prefix is the HARD subset) are load-bearing twice: the chunked pass's
conflict deferral (engine/pass_.py ``_conflict_pairs``) AND the
conflict-aware chunk packer's class derivation (engine/packing.py
``conflict_classes``) both consume them — renaming the key must update
both, or packed batches silently lose their sequential-equivalence
guarantee.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..api import types as t
from ..framework.config import MAX_NODE_SCORE
from ..snapshot import _bucket
from .common import FeaturizeContext, OpDef, PassContext, feature_fill, register
from .helpers import bounded_quotient, domain_tables
from . import nodeaffinity, tainttoleration

from ..intern import InternTable

HOSTNAME_KEY = InternTable.HOSTNAME_KEY
MAX_INT32 = np.int64(2**31 - 1)


def groups_matching(it, g_cap: int, ns_ids: set[int] | None, selector) -> np.ndarray:
    """(G,) bitmask of pod label-groups matched by ``selector`` within the
    given namespace-id set (None = any namespace) — the host-side analog of
    countPodsMatchSelector (podtopologyspread/common.go).  Scalar reference
    implementation; the hot paths use the vectorized
    GroupIndex.match_selector (intern.py), which must stay equivalent."""
    mask = np.zeros(g_cap, np.bool_)
    for gid in range(len(it.groups)):
        ns_id, fs = it.groups.value(gid)  # type: ignore[misc]
        if ns_ids is not None and ns_id not in ns_ids:
            continue
        if t.label_selector_matches(selector, dict(fs)):
            mask[gid] = True
    return mask


def _constraint_feats(
    constraints, pod: t.Pod, fctx: FeaturizeContext, prefix: str
) -> dict:
    it = fctx.interns
    builder = fctx.builder
    cdim = _bucket(max(len(constraints), 1), 1)
    ns_id = it.namespaces.id(pod.namespace)
    slots = np.zeros(cdim, np.int32)
    skew = np.ones(cdim, np.int32)
    mindom = np.ones(cdim, np.int32)
    selfm = np.zeros(cdim, np.bool_)
    hostname = np.zeros(cdim, np.bool_)
    honor_aff = np.zeros(cdim, np.bool_)
    honor_taint = np.zeros(cdim, np.bool_)
    valid = np.zeros(cdim, np.bool_)
    masks = np.zeros((cdim, builder.schema.G), np.bool_)
    # Gates (plfeature.Features analog): inclusion policies fall back to
    # the legacy fixed policy (honor affinity, ignore taints) when
    # NodeInclusionPolicyInPodTopologySpread is off; matchLabelKeys is
    # ignored when MatchLabelKeysInPodTopologySpread is off.
    incl = fctx.gates.enabled("NodeInclusionPolicyInPodTopologySpread")
    mlk = fctx.gates.enabled("MatchLabelKeysInPodTopologySpread")
    for i, c in enumerate(constraints):
        slot = builder.ensure_topo_key(c.topology_key)
        valid[i] = True
        slots[i] = slot
        skew[i] = c.max_skew
        mindom[i] = c.min_domains or 1
        sel = (
            t.spread_effective_selector(c, pod.metadata.labels)
            if mlk
            else c.label_selector
        )
        selfm[i] = t.label_selector_matches(sel, pod.metadata.labels)
        hostname[i] = c.topology_key == HOSTNAME_KEY
        honor_aff[i] = (c.node_affinity_policy == t.POLICY_HONOR) if incl else True
        honor_taint[i] = (c.node_taints_policy == t.POLICY_HONOR) if incl else False
        m = builder.group_index.match_selector(sel, {ns_id})
        masks[i, : m.shape[0]] = m
    return {
        f"{prefix}_valid": valid,
        f"{prefix}_slot": slots,
        f"{prefix}_skew": skew,
        f"{prefix}_mindom": mindom,
        f"{prefix}_self": selfm,
        f"{prefix}_hostname": hostname,
        f"{prefix}_aff": honor_aff,
        f"{prefix}_taint": honor_taint,
        f"{prefix}_groups": masks,
    }


def _effective_constraints(pod: t.Pod, fctx: FeaturizeContext):
    """Pod constraints, or the profile's defaultConstraints for pods without
    any (PodTopologySpreadArgs List defaulting, types_pluginargs.go:72).
    The reference derives each default's selector from the pod's owning
    services/replicasets (plugins/helper.DefaultSelector); without a
    controller model the analog is the pod's own full label set, and
    label-less pods skip defaulting (like selector-less defaults do)."""
    cons = pod.spec.topology_spread_constraints
    if cons:
        return cons
    prof = fctx.profile
    if prof is None or not prof.pts_default_constraints or not pod.metadata.labels:
        return cons
    import dataclasses

    sel = t.LabelSelector(
        match_labels=tuple(sorted(pod.metadata.labels.items()))
    )
    return tuple(
        dataclasses.replace(c, label_selector=sel)
        for c in prof.pts_default_constraints
    )


def featurize(pod: t.Pod, fctx: FeaturizeContext) -> dict:
    cons = _effective_constraints(pod, fctx)
    hard = [c for c in cons if c.when_unsatisfiable == t.DO_NOT_SCHEDULE]
    soft = [c for c in cons if c.when_unsatisfiable == t.SCHEDULE_ANYWAY]
    feats = _constraint_feats(hard, pod, fctx, "tps_h")
    feats.update(_constraint_feats(soft, pod, fctx, "tps_s"))
    # Node-inclusion policies are evaluated with the NodeAffinity and
    # TaintToleration device filters — their features must exist whenever
    # spread is active, even when those ops are absent from the profile or
    # batch-inactive (skipped by their is_active predicates).  When they ARE
    # batch-active the engine's op loop produces the identical keys already.
    if fctx.active is None or "NodeAffinity" not in fctx.active:
        feats.update(nodeaffinity.featurize(pod, fctx))
    if fctx.active is None or "TaintToleration" not in fctx.active:
        feats.update(tainttoleration.featurize(pod, fctx))
    return feats


def _per_constraint(state, pf, ctx: PassContext, prefix: str):
    """Shared geometry: values, key presence, counting eligibility, counts.

    Returns (valid (C,), vals (C,N), key_present (C,N), all_keys (N,),
    elig (C,N), cnt (C,N) f32)."""
    valid = pf[f"{prefix}_valid"]  # (C,)
    slots = pf[f"{prefix}_slot"]  # (C,)
    vals = jnp.take(state.topo_vals, slots, axis=1).T  # (C, N)
    key_present = vals >= 0
    all_keys = (key_present | ~valid[:, None]).all(0)  # (N,)
    na_ok = nodeaffinity.filter_fn(state, pf, ctx)  # (N,)
    taint_ok = tainttoleration.filter_fn(state, pf, ctx)  # (N,)
    elig = (
        state.valid[None, :]
        & all_keys[None, :]
        & jnp.where(pf[f"{prefix}_aff"][:, None], na_ok[None, :], True)
        & jnp.where(pf[f"{prefix}_taint"][:, None], taint_ok[None, :], True)
    )
    # Matching-pod counts per node: (C,G) × (G,N) matmul.  Counts are small
    # integers — f32 is exact far beyond any real pod count.
    cnt_raw = jnp.einsum(
        "cg,gn->cn",
        pf[f"{prefix}_groups"].astype(jnp.float32),
        state.group_counts.astype(jnp.float32),
    )
    cnt = jnp.where(elig, cnt_raw, 0.0)
    return valid, vals, key_present, all_keys, elig, cnt, cnt_raw


def _segment_tables(state, slots, elig, cnt, dv, onehot=None):
    """Per-domain totals and presence: (C, DV) tables (MXU matmuls).

    The counting-eligibility mask is per-pod (node-inclusion policies), so
    these stay per-step einsums — but over the engine's hoisted one-hot
    (ctx.dom.onehot), never rebuilding the (N, TK, DV) tensor in the scan."""
    _v, _k, _m, tbl = domain_tables(state, slots, cnt, dv, onehot)
    _v, _k, _m, pres = domain_tables(state, slots, elig.astype(jnp.float32), dv, onehot)
    return tbl, pres > 0.5


def _segment_presence(state, slots, mask, dv, onehot=None):
    """(C, DV) bool: domains containing a True-masked node."""
    _v, _k, _m, pres = domain_tables(state, slots, mask.astype(jnp.float32), dv, onehot)
    return pres > 0.5


def _onehot(ctx: PassContext):
    return ctx.dom.onehot if ctx.dom is not None else None


def filter_fn(state, pf, ctx: PassContext):
    valid, vals, key_present, _all_keys, elig, cnt, _raw = _per_constraint(
        state, pf, ctx, "tps_h"
    )
    host = pf["tps_h_hostname"]  # (C,)
    # Generic path: per-domain tables over the (hostname-free) DV vocabulary.
    tbl, present = _segment_tables(
        state, pf["tps_h_slot"], elig, cnt, ctx.schema.DV, _onehot(ctx)
    )
    tbl = tbl.astype(jnp.int64)
    min_g = jnp.min(jnp.where(present, tbl, MAX_INT32), axis=1)  # (C,)
    dom_g = present.sum(axis=1)
    # Table read-back as a one-hot MXU contraction, not a node-axis gather
    # (gathers are the TPU slow path; invalid vals have all-zero one-hot
    # rows and are masked by key_present downstream).  Contract over the
    # shared (N, TK·DV) one-hot via the slot one-hot — a per-pod take of
    # the table would materialize (N, C, DV) per batch lane.
    oh = _onehot(ctx)
    n_, tk_, dv_ = oh.shape
    slot_oh = (
        pf["tps_h_slot"][:, None] == jnp.arange(tk_)[None, :]
    ).astype(jnp.float32)
    tbl_kd = jnp.einsum(
        "cd,ck->ckd", tbl.astype(jnp.float32), slot_oh
    ).reshape(-1, tk_ * dv_)
    match_g = (tbl_kd @ oh.reshape(n_, tk_ * dv_).T).astype(jnp.int64)
    # Hostname fast path: every domain is a single node (its vocabulary is
    # excluded from DV), so counts/minima are per-node reductions.
    cnt_i = cnt.astype(jnp.int64)
    min_h = jnp.min(jnp.where(elig, cnt_i, MAX_INT32), axis=1)
    dom_h = elig.sum(axis=1)
    # Global minimum over existing domains; MaxInt32 when none exist
    # (newCriticalPaths) — then every skew check passes, like the reference.
    min_tbl = jnp.where(host, min_h, min_g)
    domains = jnp.where(host, dom_h, dom_g)
    match_n = jnp.where(host[:, None], cnt_i, match_g)  # (C, N)
    min_match = jnp.where(domains < pf["tps_h_mindom"], 0, min_tbl)
    skew = match_n + pf["tps_h_self"][:, None].astype(jnp.int64) - min_match[:, None]
    ok = key_present & (skew <= pf["tps_h_skew"][:, None])
    return (ok | ~valid[:, None]).all(0)


def score_fn(state, pf, ctx: PassContext, feasible):
    valid, vals, key_present, all_keys, elig, cnt, cnt_raw = _per_constraint(
        state, pf, ctx, "tps_s"
    )
    any_constraint = valid.any()
    # Pod-defined constraints require all topology keys on scored nodes
    # (requireAllTopologies, scoring.go:150); nodes missing one are "ignored"
    # and end at score 0 via the final `scored` mask.
    scored = feasible & all_keys

    tbl, _present = _segment_tables(
        state, pf["tps_s_slot"], elig, cnt, ctx.schema.DV, _onehot(ctx)
    )
    # Domains/topoSize count distinct pairs among *scored candidate* nodes
    # (initPreScoreState iterates filteredNodes); hostname topoSize is the
    # number of scored nodes.
    present_cand = _segment_presence(
        state,
        pf["tps_s_slot"],
        jnp.broadcast_to(scored[None, :], vals.shape),
        ctx.schema.DV,
        _onehot(ctx),
    )
    # One-hot contraction instead of a node-axis gather (see filter_fn).
    oh_s = _onehot(ctx)
    n_, tk_, dv_ = oh_s.shape
    slot_oh_s = (
        pf["tps_s_slot"][:, None] == jnp.arange(tk_)[None, :]
    ).astype(jnp.float32)
    tbl_kd_s = jnp.einsum(
        "cd,ck->ckd", tbl.astype(jnp.float32), slot_oh_s
    ).reshape(-1, tk_ * dv_)
    pair_cnt = (tbl_kd_s @ oh_s.reshape(n_, tk_ * dv_).T).astype(tbl.dtype)  # (C, N)
    # Hostname counts the node's own pods directly, with no counting-
    # eligibility mask (scoring.go:254 uses nodeInfo.Pods).
    cnt_for_node = jnp.where(pf["tps_s_hostname"][:, None], cnt_raw, pair_cnt)
    # Hostname topoSize = len(filteredNodes) − len(IgnoredNodes)
    # (scoring.go:104) = the scored set (feasible ∧ all keys present).
    topo_size = jnp.where(
        pf["tps_s_hostname"],
        scored.sum(),
        present_cand.sum(axis=1),
    )  # (C,)
    w = jnp.log(topo_size.astype(jnp.float64) + 2.0)
    term = key_present * (
        cnt_for_node.astype(jnp.float64) * w[:, None]
        + (pf["tps_s_skew"][:, None].astype(jnp.float64) - 1.0)
    )
    raw = jnp.where(valid[:, None], term, 0.0).sum(0)  # (N,)
    # math.Round semantics (half away from zero); terms are non-negative.
    raw = jnp.floor(raw + 0.5).astype(jnp.int64)

    big = jnp.int64(2**62)
    mn = jnp.min(jnp.where(scored, raw, big))
    mn = jnp.where(scored.any(), mn, 0)
    mx = jnp.max(jnp.where(scored, raw, 0))
    # A scored row's raw lies in [mn, mx] with mn >= 0, so mx + mn - raw is
    # in [0, mx] and the quotient in [0, MaxNodeScore]; the other rows are
    # masked below.
    norm = jnp.where(
        mx == 0,
        MAX_NODE_SCORE,
        bounded_quotient(
            MAX_NODE_SCORE * (mx + mn - raw), jnp.maximum(mx, 1), MAX_NODE_SCORE
        ),
    )
    norm = jnp.where(scored, norm, 0)
    # No soft constraints → plugin is skipped (PreScore returns Skip):
    # contribute 0 everywhere.
    return jnp.where(any_constraint, norm, 0)


for _k, _fill in [
    ("tps_h_valid", 0), ("tps_h_slot", 0), ("tps_h_skew", 1), ("tps_h_mindom", 1),
    ("tps_h_self", 0), ("tps_h_hostname", 0), ("tps_h_aff", 0), ("tps_h_taint", 0),
    ("tps_h_groups", 0),
    ("tps_s_valid", 0), ("tps_s_slot", 0), ("tps_s_skew", 1), ("tps_s_mindom", 1),
    ("tps_s_self", 0), ("tps_s_hostname", 0), ("tps_s_aff", 0), ("tps_s_taint", 0),
    ("tps_s_groups", 0),
]:
    feature_fill(_k, _fill)

def hard_filter_fn(state, pf, ctx: PassContext):
    """Missing topology keys are UnschedulableAndUnresolvable
    (filtering.go:337 ErrReasonNodeLabelNotMatch); skew violations are not."""
    valid = pf["tps_h_valid"]
    slots = pf["tps_h_slot"]
    vals = jnp.take(state.topo_vals, slots, axis=1).T
    return ((vals < 0) & valid[:, None]).any(0)


def is_active(pod: t.Pod, fctx: FeaturizeContext) -> bool:
    # No constraints: both PreFilter and PreScore return Skip
    # (filtering.go:152, scoring.go:140).  Profile defaultConstraints make
    # the op active for any labelled pod of the profile (cheap check only —
    # the derived constraints are built in featurize, not here).
    if pod.spec.topology_spread_constraints:
        return True
    prof = fctx.profile
    return bool(
        prof is not None
        and prof.pts_default_constraints
        and pod.metadata.labels
    )


register(
    OpDef(
        name="PodTopologySpread",
        featurize=featurize,
        filter=filter_fn,
        score=score_fn,
        hard_filter=hard_filter_fn,
        is_active=is_active,
    )
)
