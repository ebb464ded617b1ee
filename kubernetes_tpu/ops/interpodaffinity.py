"""InterPodAffinity, vectorized.

Reference (plugins/interpodaffinity/):
  * Filter (filtering.go:354–383 satisfy*): three checks against
    topology-pair match counts —
    (1) existing pods' required anti-affinity terms matching the incoming pod
        forbid every node sharing the term's topology pair with a carrier
        (existingAntiAffinityCounts; the node fails if ANY of its topology
        pairs has a positive count, :306);
    (2) the incoming pod's required affinity terms need, per term, a node
        whose (topologyKey, value) domain hosts a pod matching ALL terms
        (affinityCounts; all topology keys must exist on the node, with the
        lonely-first-pod self-match exception, :337–351);
    (3) the incoming pod's required anti-affinity terms forbid domains
        hosting any matching pod (antiAffinityCounts, :322).
  * Score (scoring.go:80–124 processExistingPod): per existing pod E on node
    m, weights accumulate onto m's (topologyKey, value) pairs — the incoming
    pod's preferred (anti-)affinity terms matching E contribute ±weight; E's
    required affinity terms matching the pod contribute HardPodAffinityWeight;
    E's preferred (anti-)affinity terms matching the pod contribute ±weight.
    A node's raw score sums its pairs' weights (:243); NormalizeScore maps
    [min,max] over feasible nodes to [0,100] (:265).

TPU design: existing pods' terms are interned into a term vocabulary; the
cluster state carries per-(term, node) carrier counts (et_counts), updated by
the same commit delta that moves resources.  Featurization matches the
incoming pod against every interned term once (host-side string work), and
compiles the pod's own terms to group bitmasks.

The HARD-read masks this op emits (``ipa_ra_allmask``/``ipa_rs_groups``
group reads, ``ipa_et_match ∧ ipa_et_anti`` term reads vs ``ipa_own_terms``
writes) are load-bearing twice: the chunked pass's conflict deferral
(engine/pass_.py ``_conflict_pairs``) AND the conflict-aware chunk packer's
class derivation (engine/packing.py ``conflict_classes``) both consume
them — renaming a key must update both, or packed batches silently lose
their sequential-equivalence guarantee.  On device, all domain
tallies come from the engine's DomTables (engine/pass_.py): ``group_dom``
(G, TK, DV) and ``et_dom`` (ET, DV) are built once per pass with MXU matmuls
and updated incrementally as the scan commits pods, so each step only does
tiny (T,G)×(G,DV) contractions and (N, TK) gathers — replacing the
reference's O(pods × nodes) goroutine sweep (the BASELINE config #3 worst
case) with dense linear algebra whose per-pod cost is near-constant.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..api import types as t
from ..framework.config import MAX_NODE_SCORE
from ..intern import term_key
from ..snapshot import _bucket
from .common import FeaturizeContext, OpDef, PassContext, feature_fill, register
from .helpers import bounded_quotient

# Existing-term categories (intern.term_id).
CAT_REQ_AFF, CAT_REQ_ANTI, CAT_PREF_AFF, CAT_PREF_ANTI = 0, 1, 2, 3


def _term_matches_pod(term_key, pod: t.Pod, ns_labels: dict[str, dict[str, str]]) -> bool:
    """AffinityTerm.Matches (framework/types.go:479): namespace membership or
    namespaceSelector over the pod's namespace labels, AND label selector."""
    _cat, _w, _topo, ns_tuple, ns_sel, selector = term_key
    ns_ok = pod.namespace in ns_tuple or (
        ns_sel is not None
        and t.label_selector_matches(ns_sel, ns_labels.get(pod.namespace, {}))
    )
    return ns_ok and t.label_selector_matches(selector, pod.metadata.labels)


def _term_group_ns_ids(term: t.PodAffinityTerm, pod: t.Pod, fctx: FeaturizeContext):
    """Namespace-id set an incoming pod's term selects."""
    it = fctx.interns
    ns = set(term.namespaces)
    if not ns and term.namespace_selector is None:
        ns = {pod.namespace}
    ids = {it.namespaces.id(n) for n in ns}
    if term.namespace_selector is not None:
        # Evaluate the selector over every namespace any group references.
        nsl = fctx.builder.namespace_labels
        for nid in range(len(it.namespaces)):
            name = it.namespaces.value(nid)
            if t.label_selector_matches(term.namespace_selector, nsl.get(name, {})):
                ids.add(nid)
    return ids


def _own_term_feats(
    terms, pod: t.Pod, fctx: FeaturizeContext, prefix: str, weights=None
) -> dict:
    """Compile the incoming pod's terms: per-term topo slot + group bitmask."""
    builder = fctx.builder
    dim = _bucket(max(len(terms), 1), 1)
    valid = np.zeros(dim, np.bool_)
    slots = np.zeros(dim, np.int32)
    masks = np.zeros((dim, builder.schema.G), np.bool_)
    wvec = np.zeros(dim, np.int64)
    for i, term in enumerate(terms):
        valid[i] = True
        slots[i] = builder.ensure_topo_key(term.topology_key)
        ns_ids = _term_group_ns_ids(term, pod, fctx)
        m = builder.group_index.match_selector(term.label_selector, ns_ids)
        masks[i, : m.shape[0]] = m
        if weights is not None:
            wvec[i] = weights[i]
    host = np.zeros(dim, np.bool_)
    for i, term in enumerate(terms):
        host[i] = term.topology_key == fctx.interns.HOSTNAME_KEY
    out = {
        f"{prefix}_valid": valid,
        f"{prefix}_slot": slots,
        f"{prefix}_groups": masks,
        f"{prefix}_host": host,
    }
    if weights is not None:
        out[f"{prefix}_w"] = wvec
    return out


def featurize(pod: t.Pod, fctx: FeaturizeContext) -> dict:
    it = fctx.interns
    builder = fctx.builder
    aff = pod.spec.affinity
    pa = aff.pod_affinity if aff else None
    paa = aff.pod_anti_affinity if aff else None
    req_aff = list(pa.required) if pa else []
    req_anti = list(paa.required) if paa else []
    pref = [(wt.term, wt.weight) for wt in (pa.preferred if pa else ())]
    pref += [(wt.term, -wt.weight) for wt in (paa.preferred if paa else ())]

    feats = _own_term_feats(req_aff, pod, fctx, "ipa_ra")
    feats.update(_own_term_feats(req_anti, pod, fctx, "ipa_rs"))
    feats.update(
        _own_term_feats(
            [term for term, _ in pref], pod, fctx, "ipa_pf", [w for _, w in pref]
        )
    )
    # Required affinity counts pods matching ALL terms (podMatchesAllAffinityTerms)
    # — intersect the per-term group masks.
    if req_aff:
        allmask = feats["ipa_ra_groups"][: len(req_aff)].all(axis=0)
    else:
        allmask = np.zeros(builder.schema.G, np.bool_)
    feats["ipa_ra_allmask"] = allmask
    # podMatchesAllAffinityTerms(pod's own terms, pod) for the lonely-first-pod
    # exception (filtering.go:345).
    feats["ipa_ra_self"] = np.bool_(
        bool(req_aff)
        and all(
            _term_matches_pod(
                term_key(CAT_REQ_AFF, 0, term, pod.namespace), pod, builder.namespace_labels
            )
            for term in req_aff
        )
    )

    # Match the pod against every interned existing-pod term: one COLUMN of
    # the incremental term↔group matrix (intern.TermIndex) — pods with
    # identical (namespace, labels) share a group, so this replaces the
    # per-pod O(ET) Python loop that dominated featurization on the
    # affinity-heavy configs (BASELINE #3).  The terms' topology slots/host
    # flags are batch-invariant and live in the engine's DomTables.
    builder._ensure(ET=max(len(it.terms), 1))
    et = builder.schema.ET
    et_match = np.zeros(et, np.bool_)
    et_anti = np.zeros(et, np.bool_)
    et_w = np.zeros(et, np.int64)
    hard_w = fctx.profile.hard_pod_affinity_weight if fctx.profile else 1
    gid = it.group_id(pod.namespace, pod.metadata.labels)
    builder.term_index.sync(builder.ns_epoch)
    col, cats, weights = builder.term_index.column(gid)
    nt = col.shape[0]
    et_match[:nt] = col
    et_anti[:nt] = col & (cats == CAT_REQ_ANTI)
    et_w[:nt] = np.where(
        col,
        np.where(
            cats == CAT_REQ_AFF,
            hard_w,
            np.where(
                cats == CAT_PREF_AFF,
                weights,
                np.where(cats == CAT_PREF_ANTI, -weights, 0),
            ),
        ),
        0,
    )
    feats.update(ipa_et_match=et_match, ipa_et_anti=et_anti, ipa_et_w=et_w)
    return feats


def _own_term_tallies(state, dom, slots, masks, host):
    """Per-term domain tallies for the incoming pod's own terms: (T, N).

    ``masks`` (T, G) group bitmasks, ``slots`` (T,) topo-key slots.  Generic
    terms contract the engine's group_dom table — (T,G)×(G,DV) per slot, no
    node-axis work; hostname terms (single-node domains, their vocabulary is
    excluded from DV) take the per-node (T,G)×(G,N) matmul fast path.
    Returns (vals (T,N), key_present (T,N), cnt_node (T,N), at_node (T,N))
    where cnt_node is the per-node matching count and at_node the term's
    domain tally at each node (0 where the key is missing)."""
    masks = masks.astype(jnp.float32)
    vals = jnp.take(state.topo_vals, slots, axis=1).T  # (T, N)
    key_present = vals >= 0
    cnt_node = masks @ state.group_counts.astype(jnp.float32)  # (T, N)
    gd = jnp.take(dom.group_dom, slots, axis=1)  # (G, T, DV)
    tbl = jnp.einsum("tg,gtd->td", masks, gd)  # (T, DV)
    # Read tbl back per node via the hoisted one-hot — an MXU contraction,
    # not a take_along_axis: node-axis gathers are the slow path on TPU
    # (this was ~60% of the IPA-active per-pod cost).  The slot one-hot
    # keeps the contraction over the shared (N, TK·DV) table — a per-pod
    # take of dom.onehot would materialize (N, T, DV) per batch lane.
    # Invalid topo values have all-zero one-hot rows, so key_present
    # masking is preserved.
    n, tk, dv = dom.onehot.shape
    slot_oh = (slots[:, None] == jnp.arange(tk)[None, :]).astype(jnp.float32)
    # Explicit order: expand tbl over its slot (tiny), then ONE flat
    # (T, TK·DV)×(TK·DV, N) MXU matmul — a single einsum here lets XLA
    # pick a contraction order that materializes (T, N, DV) per lane.
    tbl_kd = jnp.einsum("td,tk->tkd", tbl, slot_oh).reshape(-1, tk * dv)
    gathered = tbl_kd @ dom.onehot.reshape(n, tk * dv).T  # (T, N)
    at_node = jnp.where(key_present, jnp.where(host[:, None], cnt_node, gathered), 0.0)
    return vals, key_present, cnt_node, at_node, tbl


def _affinity_ok(state, pf, ctx: PassContext):
    """Incoming required-affinity check (2) — its failures are
    UnschedulableAndUnresolvable (ErrReasonAffinityRulesNotMatch)."""
    dom = ctx.dom
    ra_valid = pf["ipa_ra_valid"]  # (RA,)
    any_ra = ra_valid.any()
    host = pf["ipa_ra_host"]
    # All required terms share one intersection mask (podMatchesAllAffinityTerms).
    allmask = jnp.broadcast_to(
        pf["ipa_ra_allmask"][None, :], (ra_valid.shape[0], pf["ipa_ra_allmask"].shape[0])
    )
    _v, key_ra, cnt_node, at_ra, tbl = _own_term_tallies(
        state, dom, pf["ipa_ra_slot"], allmask, host
    )
    keys_ok = (key_ra | ~ra_valid[:, None]).all(0)
    pods_exist = ((at_ra > 0.5) | ~ra_valid[:, None]).all(0)
    # len(affinityCounts) == 0 ⟺ no key-bearing node hosts a matching pod.
    per_term_total = jnp.where(
        host,
        (key_ra.astype(jnp.float32) * cnt_node).sum(1),
        tbl.sum(1),
    )  # (T,)
    counts_empty = jnp.sum(jnp.where(ra_valid, per_term_total, 0.0)) == 0
    return ~any_ra | (keys_ok & (pods_exist | (counts_empty & pf["ipa_ra_self"])))


def _existing_anti_fail(state, pf, ctx: PassContext):
    """(1) Existing pods' required anti-affinity: a node fails if any of its
    topology domains carries a matching term (filtering.go:306).  Reduced to a
    (TK, DV) forbidden-domain table (terms merge per slot) + an (N, TK)
    gather; hostname terms check their per-node carrier counts directly."""
    dom = ctx.dom
    tk, dv = ctx.schema.TK, ctx.schema.DV
    active_e = pf["ipa_et_match"] & pf["ipa_et_anti"]  # (ET,)
    nonhost = active_e & ~dom.et_host
    slot_oh = (dom.et_slot[:, None] == jnp.arange(tk)[None, :]).astype(jnp.float32)
    forbidden_kd = jnp.einsum(
        "tk,td->kd",
        jnp.where(nonhost[:, None], slot_oh, 0.0),
        (dom.et_dom > 0.5).astype(jnp.float32),
    )  # (TK, DV)
    # Read-back as ONE flat (TK·DV) matvec against the hoisted one-hot
    # (gather-free; invalid topo values have all-zero one-hot rows, so the
    # summed hit count only sees present keys — a node fails iff any of
    # its domains is forbidden ⟺ the sum is positive).
    n, tk2, dv2 = dom.onehot.shape
    hit_sum = dom.onehot.reshape(n, tk2 * dv2) @ forbidden_kd.reshape(tk2 * dv2)
    fail_nonhost = hit_sum > 0.5
    host_active = (active_e & dom.et_host).astype(jnp.float32)
    key_e = dom.et_vals >= 0  # (ET, N)
    fail_host = (
        host_active @ ((state.et_counts > 0) & key_e).astype(jnp.float32)
    ) > 0.5
    return fail_nonhost | fail_host


def filter_fn(state, pf, ctx: PassContext):
    # (1) Existing pods' required anti-affinity.
    fail_existing = _existing_anti_fail(state, pf, ctx)

    # (2) Incoming required affinity.
    aff_ok = _affinity_ok(state, pf, ctx)

    # (3) Incoming required anti-affinity.
    rs_valid = pf["ipa_rs_valid"]
    _v, key_rs, _cnt, at_rs, _tbl = _own_term_tallies(
        state, ctx.dom, pf["ipa_rs_slot"], pf["ipa_rs_groups"], pf["ipa_rs_host"]
    )
    fail_anti = (rs_valid[:, None] & key_rs & (at_rs > 0.5)).any(0)

    return ~fail_existing & aff_ok & ~fail_anti


def hard_filter_fn(state, pf, ctx: PassContext):
    return ~_affinity_ok(state, pf, ctx)


def score_fn(state, pf, ctx: PassContext, feasible):
    dom = ctx.dom
    tk, dv = ctx.schema.TK, ctx.schema.DV

    # Incoming pod's preferred terms: ±w × (matching pods in the node's domain).
    pf_valid = pf["ipa_pf_valid"]
    _v, key_p, _cnt, at_p, _tbl = _own_term_tallies(
        state, dom, pf["ipa_pf_slot"], pf["ipa_pf_groups"], pf["ipa_pf_host"]
    )
    raw = jnp.sum(
        jnp.where(pf_valid[:, None] & key_p, at_p, 0.0)
        * pf["ipa_pf_w"][:, None].astype(jnp.float32),
        axis=0,
    )

    # Existing pods' terms matching the incoming pod: carriers in the node's
    # domain × signed weight (hard affinity / preferred ±w).  Terms collapse
    # into a (TK, DV) weighted-domain table, read back with one (N, TK)
    # gather; hostname terms use their per-node carrier counts via a matvec.
    active_e = pf["ipa_et_match"] & (pf["ipa_et_w"] != 0)
    wts = pf["ipa_et_w"].astype(jnp.float32)
    slot_oh = (dom.et_slot[:, None] == jnp.arange(tk)[None, :]).astype(jnp.float32)
    wsum_kd = jnp.einsum(
        "t,tk,td->kd",
        jnp.where(active_e & ~dom.et_host, wts, 0.0),
        slot_oh,
        dom.et_dom,
    )  # (TK, DV)
    # One flat matvec against the hoisted one-hot (see filter; invalid
    # topo values contribute zero rows, replacing the dvals>=0 mask).
    n2, tk2, dv2 = dom.onehot.shape
    raw += dom.onehot.reshape(n2, tk2 * dv2) @ wsum_kd.reshape(tk2 * dv2)
    host_w = jnp.where(active_e & dom.et_host, wts, 0.0)
    key_e = dom.et_vals >= 0  # (ET, N)
    raw += host_w @ (state.et_counts.astype(jnp.float32) * key_e)
    raw = raw.astype(jnp.int64)

    big = jnp.int64(2**62)
    mn = jnp.min(jnp.where(feasible, raw, big))
    mx = jnp.max(jnp.where(feasible, raw, -big))
    diff = mx - mn
    # A feasible row's raw lies in [mn, mx], so its quotient is in
    # [0, MaxNodeScore]; the infeasible rows are masked below.
    norm = jnp.where(
        diff > 0,
        bounded_quotient(MAX_NODE_SCORE * (raw - mn), jnp.maximum(diff, 1), MAX_NODE_SCORE),
        0,
    )
    return jnp.where(feasible, norm, 0)


for _k, _fill in [
    ("ipa_ra_valid", 0), ("ipa_ra_slot", 0), ("ipa_ra_groups", 0),
    ("ipa_ra_allmask", 0), ("ipa_ra_self", 0), ("ipa_ra_host", 0),
    ("ipa_rs_valid", 0), ("ipa_rs_slot", 0), ("ipa_rs_groups", 0), ("ipa_rs_host", 0),
    ("ipa_pf_valid", 0), ("ipa_pf_slot", 0), ("ipa_pf_groups", 0), ("ipa_pf_w", 0),
    ("ipa_pf_host", 0),
    ("ipa_et_match", 0), ("ipa_et_anti", 0), ("ipa_et_w", 0),
]:
    feature_fill(_k, _fill)

def is_active(pod: t.Pod, fctx: FeaturizeContext) -> bool:
    # Inactive only when the pod has no pod (anti-)affinity AND no existing
    # pod carries any term (existing pods' terms score/filter incoming pods
    # regardless of the incoming spec — PreFilter Skip, filtering.go:257).
    if len(fctx.interns.terms) > 0:
        return True
    aff = pod.spec.affinity
    return bool(aff and (aff.pod_affinity or aff.pod_anti_affinity))


register(
    OpDef(
        name="InterPodAffinity",
        featurize=featurize,
        filter=filter_fn,
        score=score_fn,
        hard_filter=hard_filter_fn,
        is_active=is_active,
    )
)
