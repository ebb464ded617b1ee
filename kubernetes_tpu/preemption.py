"""Batched preemption: the PostFilter dry-run as one device pass.

Reference (framework/preemption/preemption.go + plugins/defaultpreemption/):
the evaluator clones the snapshot per candidate node, removes lower-priority
pods most-important-last (SelectVictimsOnNode sorts by MoreImportantPod and
reprieves most-important-first, :541 DryRunPreemption), and picks the winner
by five lexicographic criteria (:424 pickOneNodeForPreemption — fewest PDB
violations → lowest max victim priority → smallest victim priority sum →
fewest victims → latest earliest victim start time).

TPU design: both parallel axes of the reference map onto one dispatch — the
candidate-node axis is the device vector axis, and the queue of failed pods
becomes a `lax.scan` whose carry commits each preemption's resource release
before the next preemptor looks (mirroring the scheduling pass).  The host
packs every node's pods into (N, V) tensors once per batch (non-violating
first, least-important-first within each class — violating classified with
simulated per-PDB budget consumption most-important-first, exactly
filterPodsWithPDBViolation); each scan step masks the entries below its own
preemptor's priority and excludes nodes any unresolvable filter rejects
(the UnschedulableAndUnresolvable analog, :216).  Chosen victims are marked
consumed in the carried tensors so later preemptors in the batch cannot
double-claim them.  Unlike the reference, which dry-runs only a rotating
percentage of candidates, the full node axis is evaluated.

Candidacy and feasibility run the preemptor's FULL active filter set
against per-node what-if states (resources, pod counts, group/term/port
tensors released via scatter) — a node whose only failure is a victim's
host port or anti-affinity pair is still found (the r1 false negative).

Victim selection is the reference's GREEDY REPRIEVE (SelectVictimsOnNode):
start from every lower-priority pod removed, then walk victims in reverse
slot order — violating most-important-first, then non-violating
most-important-first, the reference's exact reprieve order — re-admitting
each one whose return keeps the preemptor feasible, yielding possibly
NON-CONTIGUOUS victim sets.  Criterion 1's violation count is thereby
minimized per candidate, as in pickOneNodeForPreemption.  On device the
reprieve is a lax.scan over victim slots whose carry is the per-node
removal mask — each step one batched what-if filter evaluation (O(V) evals
of O(N·V·R) masked sums; V buckets at 8 for realistic pods-per-node, so
the quadratic term stays small — an incremental-carry formulation is the
known optimization if dense nodes ever dominate).

Volume/DRA state IS released in the what-if (r5): victims' device-volume
uses, CSI attachments (distinct-volume crossings), and DRA claim/pool
charges join the released tensors, so reprieve runs for those classes and
a node feasible only via a volume/DRA victim is found with the reference's
minimal victim set.

Divergences (documented): later preemptors in one batch see consumed
victims' group/term/port/volume/DRA counts un-released (conservative; the
retry runs against truth).  A ReadWriteOncePod conflict (host featurize
scalar) keeps the evict-all-no-reprieve route.  Two victims on one node
SHARING an attached CSI volume or a DRA claim both register its crossing
when both are masked (the shared release double-counts — over-optimistic;
the Reserve re-check validates against truth before any commit).
PDB-violation classification simulates
budget consumption over ALL of a node's pods (preemptor-independent
packing); with mixed preemptor priorities in one batch the reference
classifies per preemptor over only its potential victims, which can
order the reprieve differently.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .api import types as t
from .framework.config import Profile
from .ops import common as opcommon
from .snapshot import Schema, _bucket
from .utils import device_fetch

I32_MAX = np.int32(2**31 - 1)

from functools import partial  # noqa: E402


# Pad fills for the what-if feature tensors' empty victim slots (id-like
# columns use the -1 sentinel; counts/flags use 0) — must match the
# np.full/np.zeros defaults pack_victims stages with.
_VFEAT_PAD = {
    "group": -1, "terms": -1, "port_triples": -1, "port_keys": -1,
    "vol_dev_ids": -1, "csi_ids": -1, "csi_drv": -1, "dra_kid": -1,
}


@partial(jax.jit, static_argnums=1)
def _unpack_victims(buf, spec):
    """Slice the single-transfer victim mega-buffer (pack_victims) back
    into per-field device arrays — one compiled program, so the seven
    logical arrays cost ONE host→device transfer instead of seven.  The
    buffer ships only the OCCUPIED victim slots (vu = pow2 ≥ vmax); the
    unpack pads each field up to the pass's floor-8 victim axis ``v`` with
    its empty-slot sentinel on device — a node usually holds 1-4 pods, so
    the floor-8 shape stability no longer costs 8× the upload bytes.
    ``spec`` = (R, n_pdbs, pdb_words, vf_cols, v) — static per layout."""
    r, n_pdbs, pdb_words, vf_cols, v = spec
    vu = buf.shape[1]

    def pad(x, fill):
        if vu == v:
            return x
        w = [(0, 0)] * x.ndim
        w[1] = (0, v - vu)
        return jnp.pad(x, w, constant_values=fill)

    prio = pad(buf[..., 0].astype(jnp.int32), I32_MAX)
    req = pad(buf[..., 1 : 1 + r], 0)
    nonzero = pad(buf[..., 1 + r : 3 + r], 0)
    start = pad(lax.bitcast_convert_type(buf[..., 3 + r], jnp.float64), jnp.inf)
    words = buf[..., 4 + r : 4 + r + pdb_words]
    idx = np.arange(n_pdbs)
    pdb = pad(
        ((words[..., idx // 64] >> jnp.asarray(idx % 64)) & 1).astype(bool),
        False,
    )
    allowed = buf[:n_pdbs, 0, -1]
    out = [prio, req, nonzero, start, pdb, allowed]
    off = 4 + r + pdb_words
    for name, width, shape in vf_cols:
        fill = _VFEAT_PAD.get(name, 0)
        if len(shape) == 2:
            out.append(pad(buf[..., off].astype(jnp.int32), fill))
        else:
            out.append(pad(buf[..., off : off + width].astype(jnp.int32), fill))
        off += width
    return tuple(out)


@jax.jit
def _scatter_buf_rows(d_buf, rows, sub):
    """Update dirty node rows of the device-resident victim mega-buffer in
    place of a full re-upload: the incremental repack ships only the
    changed rows' bytes (a preemption batch dirties a handful of nodes;
    the full buffer is ~0.65MB at 5k nodes)."""
    return d_buf.at[rows].set(sub)


@partial(jax.jit, static_argnums=0)
def _chain_speculative(fn, state, batch_d, picks, elig_sigs, inv_d, *pack_arrays):
    """Run a compiled dry-run with its valid mask derived from the main
    pass's DEVICE-resident picks (valid = eligible ∧ pick < 0 — scan
    failures AND chunk-deferrals speculate; results for pods the strict
    tail later places are simply never applied).  fn is static (the cached
    compiled pass), so this wrapper inlines into one dispatched program."""
    elig, sigs = elig_sigs
    b = dict(batch_d)
    b["valid"] = elig & (picks < 0)
    b["sig"] = sigs
    return fn(state, b, inv_d, *pack_arrays)


@dataclass
class PreemptionResult:
    node_name: str
    victims: list[t.Pod]


class PreemptStep(NamedTuple):
    picks: jax.Array  # (K,) i32 node row, -1 = no candidate
    vic_mask: jax.Array  # (K, V) bool — chosen victims at the picked node
    n_victims: jax.Array  # (K,) i32 victims in that mask


def build_preempt_pass(
    profile: Profile,
    schema: Schema,
    builder_res_col,
    active: frozenset[str] | None = None,
    n_pdbs: int = 1,
    chunk: int = 1,
):
    """Compile the scan-over-preemptors dry-run for one (profile, schema,
    active-op-set) — the active set must match the scheduling batch whose
    feature rows feed this pass.

    ``chunk`` preemptors evaluate per scan step (vmapped — on TPU the
    per-step dispatch overhead dominates these tensors, exactly like the
    scheduling pass).  Chunk-mates picking the SAME node would double-claim
    victims, so later same-node picks defer (pick = -2) to a strict
    chunk=1 re-run by the evaluator.  Within-chunk drift (documented):
    chunk-mates see chunk-start victim state, and PDB budgets are not
    shared across chunk-mates' nodes — placement stays sound because
    same-node conflicts defer."""
    filter_ops = [
        opcommon.get(n)
        for n in profile.filters
        if active is None or n in active
    ]
    static: dict = {}
    for op in {o.name: o for o in filter_ops}.values():
        if op.static is not None:
            static.update(op.static(profile, schema, builder_res_col))
    ctx = opcommon.PassContext(profile=profile, schema=schema, static=static)

    # Whether the active filter set reads the domain tables (rebuilt per
    # what-if inside full_ok when so).
    needs_dom = any(
        op.name in ("InterPodAffinity", "PodTopologySpread") for op in filter_ops
    )
    # Filters whose verdict can change when pods are removed from a node.
    # NodeResourcesFit evaluates in closed form against the masked release
    # sums; _SEARCHABLE ops get the per-mask what-if evaluation — their
    # release overlays are simulated, INCLUDING the volume device
    # conflicts, CSI attach counts (distinct-volume crossings), and DRA
    # claim/pool charges victims held (VERDICT r4 missing-6: the
    # reference's dry-run re-runs full filters with victims' RemovePod
    # extensions releasing that state, preemption.go:541,
    # interpodaffinity/filtering.go:155).  Their UNRESOLVABLE portions
    # (missing claims, allocation pins, zone conflicts) still constrain
    # candidacy via hard_filter.  Release-INdependent filters (taints,
    # node affinity, volume zones, …) run once on the live state.
    # Residual divergence: a ReadWriteOncePod conflict is a host-side
    # featurize scalar, not a released tensor — an RWOP-blocked preemptor
    # keeps the old evict-all-no-reprieve route (res_fail below).
    _RELEASE_DEPENDENT = {
        "NodeResourcesFit", "NodePorts", "InterPodAffinity",
        "PodTopologySpread", "VolumeRestrictions", "NodeVolumeLimits",
        "DynamicResources",
    }
    _SEARCHABLE = {
        "NodePorts", "InterPodAffinity", "PodTopologySpread",
        "VolumeRestrictions", "NodeVolumeLimits", "DynamicResources",
    }
    search_ops = [
        op
        for op in filter_ops
        if op.name in _SEARCHABLE and op.filter is not None
    ]
    # Unresolvable portions of searchable ops (DRA missing/pins) still
    # gate candidacy.
    search_hard_ops = [
        op
        for op in filter_ops
        if op.name in _SEARCHABLE and op.hard_filter is not None
    ]
    vr_active = any(op.name == "VolumeRestrictions" for op in filter_ops)
    invariant_ops = [
        op
        for op in filter_ops
        if op.name not in _RELEASE_DEPENDENT and op.filter is not None
    ]
    resolvable_ops = [
        op
        for op in filter_ops
        if op.name in _RELEASE_DEPENDENT - _SEARCHABLE - {"NodeResourcesFit"}
        and op.hard_filter is not None
    ]

    def eval_one(
        state, vic_prio, vic_req, vic_nonzero, vic_start, pf, dctx, vfeat,
        vic_pdb, pdb_allowed,
    ):
        """One preemptor's dry-run against the given victim state: returns
        the pick and its commit ingredients (no state mutation).

        Victim selection = the reference's SelectVictimsOnNode: remove ALL
        lower-priority pods, then reprieve most-important-first (reverse
        slot order; PDB-violating victims are packed last, so they get
        their reprieve attempt first)."""
        n, v = vic_prio.shape
        prio = pf["priority"].astype(jnp.int32)
        lower = vic_prio < prio  # (N, V) — consumed victims carry I32_MAX

        rows2 = jnp.broadcast_to(jnp.arange(n)[:, None], (n, v))

        def released(mask):
            """ClusterState with each node's masked victims removed — the
            per-node what-if the reference builds with NodeInfo.Snapshot()
            + RemovePod per candidate (DryRunPreemption, preemption.go:541).
            ``mask`` (N, V) bool."""
            rel_m = jnp.sum(jnp.where(mask[:, :, None], vic_req, 0), axis=1)
            relnz_m = jnp.sum(
                jnp.where(mask[:, :, None], vic_nonzero, 0), axis=1
            )
            new = dict(
                req=state.req - rel_m,
                nonzero_req=state.nonzero_req - relnz_m,
                num_pods=state.num_pods - mask.sum(axis=1).astype(jnp.int32),
            )
            if "group" in vfeat:
                g = vfeat["group"]  # (N, V)
                new["group_counts"] = state.group_counts.at[
                    jnp.maximum(g, 0), rows2
                ].add(-(mask & (g >= 0)).astype(jnp.int32))
            if "terms" in vfeat:
                tm = vfeat["terms"]  # (N, V, TS)
                new["et_counts"] = state.et_counts.at[
                    jnp.maximum(tm, 0), rows2[:, :, None]
                ].add(-(mask[:, :, None] & (tm >= 0)).astype(jnp.int32))
            if "port_triples" in vfeat:
                pt, pk = vfeat["port_triples"], vfeat["port_keys"]
                dec = (mask[:, :, None] & (pt >= 0)).astype(jnp.int32)
                new["port_counts"] = state.port_counts.at[
                    jnp.maximum(pt, 0), rows2[:, :, None]
                ].add(-dec)
                new["portkey_counts"] = state.portkey_counts.at[
                    jnp.maximum(pk, 0), rows2[:, :, None]
                ].add(-dec)
            rows3 = rows2[:, :, None]
            if "vol_dev_ids" in vfeat:
                # Victims' device-volume uses (VolumeRestrictions): exact
                # inverse of apply_pod_delta's devices application.
                di = vfeat["vol_dev_ids"]  # (N, V, Sd)
                dw = vfeat["vol_dev_rw"]
                dm = (mask[:, :, None] & (di >= 0)).astype(jnp.int32)
                new["dev_counts"] = state.dev_counts.at[
                    jnp.maximum(di, 0), rows3
                ].add(-dm)
                new["dev_rw_counts"] = state.dev_rw_counts.at[
                    jnp.maximum(di, 0), rows3
                ].add(-(dm * (dw > 0)))
            if "csi_ids" in vfeat:
                # CSI attach limits: a victim's own claim (slot id -1)
                # gives csi_used back with the victim; a SHARED claim's
                # csivol_counts decrement per reference, and csi_used
                # releases only where the DISTINCT volume's count crosses
                # to zero (csi.go:219 semantics — two victims sharing an
                # attached volume free it only together).
                ci = vfeat["csi_ids"]  # (N, V, Sc)
                cd = vfeat["csi_drv"]
                act = mask[:, :, None] & (cd >= 0)
                cm = (act & (ci >= 0)).astype(jnp.int32)
                ci_s = jnp.maximum(ci, 0)
                new_cv = state.csivol_counts.at[ci_s, rows3].add(-cm)
                freed = act & ((ci < 0) | (new_cv[ci_s, rows3] == 0))
                new["csivol_counts"] = new_cv
                new["csi_used"] = state.csi_used.at[
                    jnp.maximum(cd, 0), rows3
                ].add(-freed.astype(jnp.int32))
            if "dra_kid" in vfeat:
                # DRA claim references + pool charges: claim counts drop
                # per FIRST slot (the count-moving one, mirroring
                # apply_pod_delta); EVERY slot of a crossing claim
                # releases its own pool column's charge (the prev==1
                # branch applies per slot there too).
                kid = vfeat["dra_kid"]  # (N, V, Sk)
                cid = vfeat["dra_cid"]
                cnt = vfeat["dra_cnt"]
                first = vfeat["dra_first"] > 0
                act = mask[:, :, None] & (kid >= 0)
                km = (act & first).astype(jnp.int32)
                kid_s = jnp.maximum(kid, 0)
                new_kc = state.dra_claim_counts.at[kid_s, rows3].add(-km)
                crossed = act & (new_kc[kid_s, rows3] == 0)
                new["dra_claim_counts"] = new_kc
                new["dra_alloc"] = state.dra_alloc.at[
                    jnp.maximum(cid, 0), rows3
                ].add(-jnp.where(crossed, cnt, 0).astype(state.dra_alloc.dtype))
            return dataclasses.replace(state, **new)

        # Release-independent filters: one evaluation on the live state —
        # pod removal never fixes a taint/node-affinity/zone rejection, so
        # these also subsume UnschedulableAndUnresolvable candidacy.
        base_ok = state.valid
        for op in invariant_ops:
            base_ok &= op.filter(state, pf, dctx)
        # Unresolvable portions of the searchable set (DRA missing claims
        # and allocation pins): deleting pods moves no allocation.
        for op in search_hard_ops:
            base_ok &= ~op.hard_filter(state, pf, dctx)
        # Residual unsimulated-resolvable ops (none in the in-tree set —
        # volume/DRA releases are simulated since r5) keep the
        # evict-all-no-reprieve route, as does a ReadWriteOncePod-blocked
        # preemptor: the RWOP conflict is a host featurize scalar, not a
        # released tensor, so its per-node eviction is not simulated.
        res_fail = jnp.zeros(state.valid.shape, jnp.bool_)
        for op in resolvable_ops:
            base_ok &= ~op.hard_filter(state, pf, dctx)
            if op.filter is not None:
                res_fail |= ~op.filter(state, pf, dctx)
        if vr_active:
            res_fail |= jnp.broadcast_to(
                ~pf["vr_rwop_ok"], state.valid.shape
            )

        demand = pf["req"]  # (R,)

        def ok_closed(rel_m, cnt):
            """Closed-form fit of the preemptor given released resources
            ``rel_m`` (N, R) and removed-pod count ``cnt`` (N,)."""
            free = state.alloc - (state.req - rel_m)
            ok = ((demand[None, :] == 0) | (demand[None, :] <= free)).all(-1)
            ok &= state.num_pods - cnt + 1 <= state.allowed_pods
            return ok

        def ok_search(mask):
            """The release-dependent filter set against the released state
            (exact candidacy — a node whose sole failure is a victim's
            port, anti-affinity pair, device volume, CSI attachment, or
            DRA device is still found)."""
            st2 = released(mask)
            if needs_dom:
                from .engine.pass_ import build_dom

                dom0 = dctx.dom
                dom2 = build_dom(st2, dom0.et_slot, dom0.et_host, schema.DV)
                d2 = dataclasses.replace(dctx, dom=dom2)
            else:
                d2 = dctx
            pf2 = pf
            if vr_active:
                # The RWOP scalar is handled by the res_fail evict-all
                # route; inside the what-if it must not veto every node.
                pf2 = dict(pf)
                pf2["vr_rwop_ok"] = jnp.ones((), jnp.bool_)
            ok = jnp.ones(state.valid.shape, jnp.bool_)
            for op in search_ops:
                ok &= op.filter(st2, pf2, d2)
            return ok

        # Phase 1 — all lower-priority pods removed: the candidacy check
        # (SelectVictimsOnNode's initial RemovePod sweep).
        rel_lower = jnp.sum(jnp.where(lower[:, :, None], vic_req, 0), axis=1)
        cnt_lower = lower.sum(axis=1).astype(jnp.int32)
        feas_all = ok_closed(rel_lower, cnt_lower)
        if search_ops:
            feas_all &= ok_search(lower)

        # Phase 2 — greedy reprieve, most-important-first = reverse slot
        # order (slots are least-important-first, PDB-violating last, so
        # violating victims get their reprieve attempt first — exactly
        # filterPodsWithPDBViolation + the two reprieve loops).  Nodes
        # failing an unsimulated-resolvable op skip reprieve entirely.
        # The release sums ride the carry INCREMENTALLY — each step
        # adjusts (N, R) by one slot instead of re-reducing (N, V, R)
        # (the O(V) full evaluations were the preemption-async device
        # ceiling; search ops still pay their full what-if per step).
        can_reprieve = feas_all & ~res_fail

        def reprieve_step(carry, s):
            mask, rel_m, cnt = carry
            has = mask[:, s]
            t_rel = rel_m - jnp.where(has[:, None], vic_req[:, s], 0)
            t_cnt = cnt - has.astype(jnp.int32)
            ok = ok_closed(t_rel, t_cnt)
            tentative = mask & ~(jnp.arange(v)[None, :] == s)
            if search_ops:
                ok &= ok_search(tentative)
            take = can_reprieve & ok & has
            mask = jnp.where(take[:, None], tentative, mask)
            rel_m = jnp.where(take[:, None], t_rel, rel_m)
            cnt = jnp.where(take, t_cnt, cnt)
            return (mask, rel_m, cnt), None

        (vic_mask, rel_all, _cnt_final), _ = lax.scan(
            reprieve_step,
            (lower, rel_lower, cnt_lower),
            jnp.arange(v - 1, -1, -1),
        )

        n_vic = vic_mask.sum(axis=1).astype(jnp.int32)
        # At least one victim, else deletion can't be what fixes this node.
        possible = base_ok & feas_all & (n_vic >= 1) & pf["valid"]

        # Criteria over the FINAL victim set (pickOneNodeForPreemption,
        # preemption.go:424): fewest PDB violations → lowest max victim
        # priority → smallest priority sum → fewest victims → latest
        # earliest start AMONG the highest-priority victims
        # (GetEarliestPodStartTime).
        cnt_p = jnp.einsum(
            "nv,nvp->np", vic_mask.astype(jnp.float32),
            vic_pdb.astype(jnp.float32),
        ).astype(jnp.int64)  # (N, P)
        violations = jnp.maximum(cnt_p - pdb_allowed[None, :], 0).sum(axis=1)
        max_prio = jnp.max(jnp.where(vic_mask, vic_prio, -1), axis=1)
        prio_sum = jnp.sum(
            jnp.where(vic_mask, vic_prio, 0).astype(jnp.int64), axis=1
        )
        min_start = jnp.min(
            jnp.where(
                vic_mask & (vic_prio == max_prio[:, None]), vic_start, jnp.inf
            ),
            axis=1,
        )

        big = jnp.int64(2**62)

        def narrow(mask, key):
            best = jnp.min(jnp.where(mask, key, big))
            return mask & (key == best)

        # Latest earliest-start wins: minimize the negated key, in
        # microseconds so sub-second differences survive the int cast.
        start_key = jnp.where(
            jnp.isfinite(min_start), -min_start * 1e6, -jnp.float64(2**61)
        ).astype(jnp.int64)

        # rel_all rode the reprieve carry; only the nonzero companion needs
        # its (single) masked reduce.
        relnz_all = jnp.sum(
            jnp.where(vic_mask[:, :, None], vic_nonzero, 0), axis=1
        )

        if chunk == 1:
            # Exact lexicographic narrowing (parity-grade semantics).
            mask = possible
            mask = narrow(mask, violations)
            mask = narrow(mask, max_prio.astype(jnp.int64))
            mask = narrow(mask, prio_sum)
            mask = narrow(mask, n_vic.astype(jnp.int64))
            mask = narrow(mask, start_key)
            pick = jnp.argmax(mask).astype(jnp.int32)
            do = possible.any()
            pick = jnp.where(do, pick, -1)
            row = jnp.maximum(pick, 0)
            chosen = vic_mask[row] & do  # (V,)
            rel_vec = jnp.where(do, rel_all[row], 0)
            rel_nz_vec = jnp.where(do, relnz_all[row], 0)
            nvic = jnp.where(do, n_vic[row], 0)
            return (
                pick, chosen, nvic.astype(jnp.int32),
                rel_vec, rel_nz_vec,
            )

        # Chunked mode: the five criteria ride out RAW for an exact
        # lexicographic rank order in the step (jnp.lexsort) — the old
        # saturating bit-packed i64 quantized sub-granularity differences
        # away (a start_key gap under 2^50 collapsed, so the rank-0 pick —
        # the representative's own candidate — could diverge from the
        # chunk=1 narrowing; ISSUE 13's parity oracle pinned it).  The
        # step assigns same-signature chunk-mates the 1st, 2nd, … best
        # nodes in one shot — identical preemptors (the async-preemption
        # shape) otherwise all converge on one node and serialize.
        crit = (
            violations,
            max_prio.astype(jnp.int64),
            prio_sum,
            n_vic.astype(jnp.int64),
            start_key,
        )
        return crit, possible, vic_mask, n_vic, rel_all, relnz_all

    def step(carry, pf, dctx, vfeat, vic_pdb, pdb_allowed):
        state, vic_prio, vic_req, vic_nonzero, vic_start = carry
        c = pf["valid"].shape[0]
        n, v = vic_prio.shape
        if chunk == 1:
            picks, chosens, nvics, rel_vecs, relnz_vecs = jax.vmap(
                lambda p: eval_one(
                    state, vic_prio, vic_req, vic_nonzero, vic_start, p, dctx,
                    vfeat, vic_pdb, pdb_allowed,
                )
            )(pf)
            defer = jnp.zeros((c,), jnp.bool_)
            do = picks >= 0
        else:
            # ONE dry-run per chunk, evaluated for mate 0: chunk-mates with
            # mate-0's signature (priority + request — their dry-runs would
            # be identical) take the 1st, 2nd, … best nodes by the packed
            # key, emulating the sequential take-next-best without C copies
            # of the per-preemptor release tensors.  Mates with a different
            # signature defer to the strict chunk=1 re-run.
            # The representative mate is the first VALID one — under the
            # speculative chained dispatch the chunk is the ORIGINAL batch,
            # whose leading pods may have PLACED (valid False, features
            # gated off); evaluating those would turn the whole rank-split
            # into defers.  (Sync mode stacks failed pods from index 0, so
            # idx0 == 0 there — behavior unchanged.)
            idx0 = jnp.argmax(pf["valid"])
            pf0 = jax.tree_util.tree_map(lambda x: x[idx0], pf)
            crit, possible, vic_mask_all, n_vic_all, rel_all, relnz_all = eval_one(
                state, vic_prio, vic_req, vic_nonzero, vic_start, pf0, dctx,
                vfeat, vic_pdb, pdb_allowed,
            )
            # Signature = the featurize-cache identity (namespace + labels +
            # full spec), computed host-side: equal sigs ⇒ identical feature
            # rows ⇒ identical dry-runs.  Priority/req equality alone would
            # wrongly share the representative's feasibility with pods whose
            # FILTERS differ (node affinity, taints, ports — r2 review).
            samesig = pf["sig"] == pf["sig"][idx0]
            eligible = pf["valid"] & samesig
            big = jnp.int64(2**62)
            # EXACT lexicographic candidate order (pickOneNode criteria,
            # most-significant last in the lexsort key list; lexsort is
            # stable, so full ties keep snapshot row order — exactly the
            # chunk=1 narrowing's argmax-first tie-break).  Infeasible
            # nodes sort last via the sentinel on the primary criterion.
            vio_m = jnp.where(possible, crit[0], big)  # (N,)
            order = jnp.lexsort((crit[4], crit[3], crit[2], crit[1], vio_m))
            srt = vio_m[order]
            rank = jnp.cumsum(eligible.astype(jnp.int32)) - 1  # (C,)
            safe_rank = jnp.clip(rank, 0, n - 1)
            row = order[safe_rank]
            has = eligible & (srt[safe_rank] < big)
            picks = jnp.where(has, row.astype(jnp.int32), -1)
            # Heterogeneous mates retry strictly; exhausted ranks fall back
            # to the strict pass too (the sequential semantics may still
            # place them by deepening a prefix on an already-taken node).
            defer = pf["valid"] & ~has
            do = has
            rows_safe = jnp.where(do, picks, 0)
            nvics = jnp.where(do, n_vic_all[rows_safe], 0).astype(jnp.int32)
            rel_vecs = jnp.where(do[:, None], rel_all[rows_safe], 0)
            relnz_vecs = jnp.where(do[:, None], relnz_all[rows_safe], 0)
            chosens = vic_mask_all[rows_safe] & do[:, None]
        rows = jnp.where(do, picks, 0)
        state = dataclasses.replace(
            state,
            req=state.req.at[rows].add(-jnp.where(do[:, None], rel_vecs, 0)),
            nonzero_req=state.nonzero_req.at[rows].add(
                -jnp.where(do[:, None], relnz_vecs, 0)
            ),
            num_pods=state.num_pods.at[rows].add(-jnp.where(do, nvics, 0)),
        )
        # Consume chosen victims.  Consumption only ever RAISES priorities
        # to the I32_MAX sentinel, so scatter-MAX makes duplicate row
        # entries (the placeholders of non-committing chunk-mates) safe.
        upd = jnp.where(
            do[:, None] & chosens, jnp.int32(I32_MAX), jnp.int32(-(2**31))
        )
        vic_prio = vic_prio.at[rows].max(upd)
        out = PreemptStep(
            picks=jnp.where(defer, -2, picks), vic_mask=chosens, n_victims=nvics
        )
        return (state, vic_prio, vic_req, vic_nonzero, vic_start), out

    @jax.jit
    def run(
        state, batch, inv, vic_prio, vic_req, vic_nonzero, vic_start,
        vfeat, vic_pdb, pdb_allowed,
    ):
        # Domain tables for the filters.  The scan carry releases resources
        # only; the per-mask what-if rebuilds its own tables inside
        # ok_under when an affinity/spread op is active.
        from .engine.pass_ import build_dom

        # Domain tables only when an active op reads them (XLA would DCE
        # the dead matmuls anyway, but the explicit gate keeps the trace —
        # and the compile — small for the fit-only shape).
        dom = (
            build_dom(state, inv["et_slot"], inv["et_host"], schema.DV)
            if needs_dom
            else None
        )
        dctx = dataclasses.replace(ctx, dom=dom)
        k = next(iter(batch.values())).shape[0]
        assert k % chunk == 0, f"preempt batch {k} not a multiple of {chunk}"
        cbatch = jax.tree_util.tree_map(
            lambda x: x.reshape((k // chunk, chunk) + x.shape[1:]), batch
        )
        carry = (state, vic_prio, vic_req, vic_nonzero, vic_start)
        carry, out = lax.scan(
            lambda c, pf: step(c, pf, dctx, vfeat, vic_pdb, pdb_allowed),
            carry, cbatch,
        )
        out = jax.tree_util.tree_map(
            lambda x: x.reshape((k,) + x.shape[2:]), out
        )
        # Final carry feeds the evaluator's strict re-run of deferred
        # preemptors (same-node chunk conflicts).
        return out, carry[0], carry[1]

    return run


class PreemptionEvaluator:
    """Host driver: packs victim tensors once per failed batch, runs the
    scan, applies the chosen victims (prepareCandidate, preemption.go:342)."""

    def __init__(self, scheduler) -> None:
        self.sched = scheduler
        self._cache: dict = {}
        # Incremental victim-staging cache (see pack_victims): staging
        # arrays + per-node victim lists + the last uploaded device result,
        # keyed by per-node pods_gen so an unchanged cluster repacks free.
        self._stage: dict | None = None
        # Sticky hint from the driver: recent batches produced failures, so
        # the next batch prepacks victim tensors concurrently with its
        # device pass (scheduler._batch_traced).
        self.expect_failures = False

    def worth_prepacking(self, pods) -> bool:
        """Cheap eligibility precheck before a speculative pack: packing is
        pure waste when NO pod in the batch could ever have victims (the
        perma-stuck Unschedulable-workload shape, whose failures would
        otherwise keep expect_failures — and the packing walk — on every
        batch).  Mirrors preempt_batch's min-priority prune."""
        cache = self.sched.cache
        if not cache.pods:
            return False
        min_prio = min(pr.pod.spec.priority for pr in cache.pods.values())
        return any(
            p.spec.priority > min_prio
            and p.spec.preemption_policy != t.PREEMPT_NEVER
            for p in pods
        )

    def _pass(
        self, profile, active: frozenset[str] | None, n_pdbs: int, chunk: int
    ):
        b = self.sched.builder
        key = (
            profile, b.schema, tuple(sorted(b.res_col.items())),
            active, n_pdbs, chunk,
        )
        fn = self._cache.get(key)
        if fn is None:
            fn = build_preempt_pass(
                profile, b.schema, b.res_col, active, n_pdbs, chunk
            )
            self._cache[key] = fn
        return fn

    @staticmethod
    def _unpack_spec(layout: dict):
        return (
            layout["r"], layout["n_pdbs"], layout["pdb_words"],
            layout["vf_cols"], layout["v"],
        )

    def pack_victims(self, profile, active: frozenset[str] | None) -> dict:
        """Build (and ship to device) the per-node victim tensors for one
        dry-run — separable from preempt_batch so the driver can OVERLAP
        packing + transfer with the failing batch's device pass
        (_batch_traced prepacks when recent batches produced failures).
        Packed from the CURRENT cache state: prepacking therefore sees the
        pre-batch snapshot, i.e. same-batch placements are not victim
        candidates — the reference's dry-run runs on the cycle snapshot
        the same way (DryRunPreemption, preemption.go:541).

        INCREMENTAL between calls (cache.go:186 UpdateSnapshot's
        generation diff, applied to the victim tensors): each NodeRecord
        carries a pods_gen bumped on any pod-membership or pod-object
        change, so a repack rebuilds only the dirty nodes' staging rows —
        and an unchanged cluster returns the previous device arrays with
        zero staging or transfer work.  Gated off when PDBs exist (the
        violating-victim classification reads mutable budget state) or
        DynamicResources is active (claim reservation state changes
        without touching node pod membership)."""
        sched = self.sched
        cache, builder = sched.cache, sched.builder
        schema = builder.schema
        # PDBs: per-victim matched budgets.  A victim is "violating" when it
        # matches a PDB with no disruptions left; such pods sort LAST in the
        # eviction order (the reference reprieves violating victims first —
        # filterPodsWithPDBViolation + the reprieve loop), so the minimal
        # fitting prefix prefers non-violating victims.
        pdbs = list(getattr(sched, "pdbs", {}).values())
        # Spec-carrying budgets track live pod state (the disruption
        # controller's reconcile, disruption.go:732): recompute before the
        # pack classifies violating victims against disruptionsAllowed.
        dc = getattr(sched, "disruption_controller", None)
        if dc is not None and pdbs:
            dc.sync()  # sync_one no-ops for spec-less (informer-fed) budgets
        n_pdbs = _bucket(len(pdbs), 1)

        def matched_pdbs(p: t.Pod) -> list[int]:
            return [
                i
                for i, pdb in enumerate(pdbs)
                if pdb.namespace == p.namespace
                and t.label_selector_matches(pdb.selector, p.metadata.labels)
            ]

        # What-if release features, gated by what the active filters read
        # (the pass branches on the same key set at trace time).
        names = set(
            profile.filters if active is None else active
        )
        cacheable = not pdbs and "DynamicResources" not in names
        if not cacheable:
            # Drop any retained stage: a profile that turned non-cacheable
            # (gained a PDB / activated DRA) would otherwise pin the
            # multi-MB staging + device tensors for the process lifetime.
            self._stage = None
        st = self._stage if cacheable else None
        if st is not None and not (
            st["n"] == schema.N
            and st["r"] == schema.R
            and st["names"] == names
            and st["profile"] is profile
            and st["active"] == active
            # staged csi_ids are rows of the shared-claim table as it stood
            and st["csi_epoch"] == builder.csi_epoch
        ):
            st = None
        if st is not None:
            return self._pack_incremental(st)

        # Pack every node's pods: non-violating first, least-important-first
        # within each class.  "Violating" is classified with SIMULATED
        # per-PDB budget consumption, walking the node's pods
        # most-important-first (filterPodsWithPDBViolation: the most
        # important matching pods claim the remaining disruptions; the rest
        # are violating and therefore reprieved first).
        per_node: dict[int, list] = {}
        vmax = 1
        for rec in cache.nodes.values():
            viol: dict[str, bool] = {}
            if pdbs:
                remaining = [max(p.disruptions_allowed, 0) for p in pdbs]
                for p in sorted(
                    rec.pods.values(),
                    key=lambda p: (-p.spec.priority, p.status.start_time),
                ):
                    v = False
                    for pi in matched_pdbs(p):
                        if remaining[pi] > 0:
                            remaining[pi] -= 1
                        else:
                            v = True
                    viol[p.uid] = v
            vics = sorted(
                rec.pods.values(),
                key=lambda p: (
                    viol.get(p.uid, False),
                    p.spec.priority,
                    -p.status.start_time,
                ),
            )
            per_node[rec.row] = vics
            vmax = max(vmax, len(vics))
        # Floor 8: the victim axis stays one shape across the common range,
        # so a node gaining a pod mid-run (vmax 1→2) doesn't recompile the
        # pass and re-negotiate every transfer layout inside the measured
        # window.  The UPLOAD ships only the occupied slots (vu): at
        # vmax=1 the old floor-8 buffer moved 8× the bytes — ~3.6MB vs
        # 0.45MB at 5k nodes — and _unpack_victims pads back to v on
        # device.
        v = _bucket(vmax)
        vu = _bucket(vmax, 1)
        n = schema.N
        vic_prio = np.full((n, vu), I32_MAX, np.int32)
        vic_req = np.zeros((n, vu, schema.R), np.int64)
        vic_nonzero = np.zeros((n, vu, 2), np.int64)
        vic_start = np.full((n, vu), np.inf, np.float64)
        vic_pdb = np.zeros((n, vu, n_pdbs), np.bool_)
        vfeat: dict[str, np.ndarray] = {}
        if names & {"InterPodAffinity", "PodTopologySpread"}:
            ts = _bucket(  # floor 8: shape-stable like the victim axis
                max(
                    (
                        len(cache.pods[p.uid].delta["own_terms"])
                        for vics in per_node.values()
                        for p in vics
                    ),
                    default=1,
                ),
            )
            vfeat["group"] = np.full((n, vu), -1, np.int32)
            vfeat["terms"] = np.full((n, vu, ts), -1, np.int32)
        if "NodePorts" in names:
            from .snapshot import POD_PORT_SLOTS

            vfeat["port_triples"] = np.full((n, vu, POD_PORT_SLOTS), -1, np.int32)
            vfeat["port_keys"] = np.full((n, vu, POD_PORT_SLOTS), -1, np.int32)

        def _slots(key_: str) -> int:
            return _bucket(
                max(
                    (
                        len(cache.pods[p.uid].delta.get(key_, ()))
                        for vics in per_node.values()
                        for p in vics
                    ),
                    default=1,
                ),
                1,
            )

        if "VolumeRestrictions" in names:
            sd = _slots("devices")
            vfeat["vol_dev_ids"] = np.full((n, vu, sd), -1, np.int32)
            vfeat["vol_dev_rw"] = np.zeros((n, vu, sd), np.int32)
        if "NodeVolumeLimits" in names:
            sc = _slots("csivols")
            vfeat["csi_ids"] = np.full((n, vu, sc), -1, np.int32)
            vfeat["csi_drv"] = np.full((n, vu, sc), -1, np.int32)
        dra_slot_map: dict[tuple[int, int], list] = {}
        if "DynamicResources" in names:
            # Per-victim claim slots = the pod's own delta slots PLUS a
            # compensating slot per externally-charged claim the victim
            # solely reserves: the external allocation's PHANTOM charge
            # (apply_external_claim) holds the claim count at ≥1 even with
            # the victim gone, but deleting the sole reserver empties
            # status.reservedFor and the claim-release control loop
            # deallocates it — the what-if must see that crossing.
            dra_cat = builder.dra
            mx = 1
            for row, vics in per_node.items():
                node_name = cache.node_name_at_row(row)
                for j, p in enumerate(vics):
                    slots = list(cache.pods[p.uid].delta.get("dra_claims", ()))
                    for claim in dra_cat.pod_claims(p):
                        if (
                            claim is None
                            or claim.allocated_node != node_name
                            or claim.uid in dra_cat.local_reserved
                            or not set(claim.reserved_for) <= {p.uid}
                        ):
                            continue
                        kid = builder.interns.dra_claims.id(claim.uid)
                        # The phantom moved the COUNT once; the pool
                        # charges were applied exactly once between the
                        # phantom and the pod's delta (whichever came
                        # first — apply_external_claim/apply_pod_delta
                        # both gate on prev==0).  The victim's own delta
                        # slots release those charges at the crossing, so
                        # the compensator moves ONLY the count (cnt=0) —
                        # a cnt-carrying duplicate would double-release
                        # (review finding).
                        slots.append((kid, 0, 0, False, True))
                    dra_slot_map[(row, j)] = slots
                    mx = max(mx, len(slots))
            sk = _bucket(mx, 1)
            vfeat["dra_kid"] = np.full((n, vu, sk), -1, np.int32)
            vfeat["dra_cid"] = np.zeros((n, vu, sk), np.int32)
            vfeat["dra_cnt"] = np.zeros((n, vu, sk), np.int32)
            vfeat["dra_first"] = np.zeros((n, vu, sk), np.int32)
        A = dict(
            vic_prio=vic_prio, vic_req=vic_req, vic_nonzero=vic_nonzero,
            vic_start=vic_start, vic_pdb=vic_pdb, vfeat=vfeat, pdbs=pdbs,
            matched_pdbs=matched_pdbs, dra_slot_map=dra_slot_map,
        )
        self._fill_rows(A, per_node.items())
        st_new = (
            dict(
                n=n, r=schema.R, names=names, profile=profile, active=active,
                csi_epoch=builder.csi_epoch,
                vmax=vmax, vu=vu, v=v, A=A, per_node=per_node,
                gens={rec.row: rec.pods_gen for rec in cache.nodes.values()},
            )
            if cacheable
            else None
        )
        result = self._assemble(
            A, n, v, n_pdbs, pdbs, matched_pdbs, per_node, profile, active,
            st=st_new,
        )
        if st_new is not None:
            st_new["result"] = result
            st_new["buf_v"] = v
            self._stage = st_new
        return result

    def _pack_incremental(self, st: dict) -> dict:
        """Repack only the nodes whose pods_gen moved since the staged
        pack; an unchanged cluster returns the previous device arrays."""
        cache = self.sched.cache
        A, per_node, gens = st["A"], st["per_node"], st["gens"]
        dirty: list = []
        live: set[int] = set()
        for rec in cache.nodes.values():
            live.add(rec.row)
            if gens.get(rec.row) != rec.pods_gen:
                dirty.append(rec)
        gone = [row for row in gens if row not in live]
        if not dirty and not gone:
            return st["result"]
        items: list[tuple[int, list]] = []
        vmax = st["vmax"]
        for rec in dirty:
            vics = sorted(
                rec.pods.values(),
                key=lambda p: (p.spec.priority, -p.status.start_time),
            )
            items.append((rec.row, vics))
            vmax = max(vmax, len(vics))
        if vmax > st["vmax"]:
            # High-water growth only: shrinking would thrash shapes.
            st["vmax"] = vmax
            self._grow_victim_axis(st, vmax)
        widths_grew = self._grow_widths(st, items)
        self._clear_rows(A, [row for row, _ in items] + gone)
        for row in gone:
            per_node.pop(row, None)
            gens.pop(row, None)
        self._fill_rows(A, items)
        for rec, (row, vics) in zip(dirty, items):
            per_node[row] = vics
            gens[row] = rec.pods_gen
        rows = sorted({row for row, _ in items} | set(gone))
        buf = st.get("buf")
        layout_stable = (
            buf is not None
            and not widths_grew  # vfeat slot dims define the column layout
            and buf.shape[1] == A["vic_req"].shape[1]  # vu unchanged
            and st.get("buf_v") == st["v"]
        )
        if layout_stable and len(rows) <= 64:
            result = self._assemble_rows(st, rows)
        else:
            result = self._assemble(
                A, st["n"], st["v"], 1, A["pdbs"], A["matched_pdbs"],
                per_node, st["profile"], st["active"], st=st,
            )
            st["buf_v"] = st["v"]
        st["result"] = result
        return result

    def _assemble_rows(self, st: dict, rows: list) -> dict:
        """Rewrite only the dirty rows of the persistent mega-buffer and
        scatter them into the device copy — upload bytes scale with the
        number of changed nodes, not the cluster."""
        A, buf = st["A"], st["buf"]
        r = A["vic_req"].shape[2]
        idx = np.asarray(rows, np.int64)
        # No PDBs on the incremental path (cacheable gate): n_pdbs is the
        # floor bucket 1, the pdb word packs all-zero, and pdb_allowed
        # keeps its staged I32_MAX.
        self._pack_buf_rows(A, buf, idx, r, 1)
        nb = 8 if len(rows) <= 8 else 64  # only the two warmed shapes
        rows_pad = np.zeros(nb, np.int32)
        rows_pad[: len(rows)] = rows
        rows_pad[len(rows):] = rows[0]
        sub = buf[rows_pad]
        st["d_buf"] = _scatter_buf_rows(st["d_buf"], rows_pad, sub)
        prev = st["result"]
        layout = {
            "r": r, "n_pdbs": 1, "pdb_words": 1, "v": st["v"],
            "vf_cols": st["vf_cols"],
        }
        unpacked = _unpack_victims(st["d_buf"], self._unpack_spec(layout))
        d_prio, d_vic_req, d_vic_nonzero, d_vic_start, d_pdb, d_allowed = (
            unpacked[:6]
        )
        vf_keys = tuple(sorted(A["vfeat"]))
        d_vfeat = dict(zip(vf_keys, unpacked[6:]))
        return dict(
            prev, per_node=st["per_node"],
            d_prio=d_prio, d_vic_req=d_vic_req,
            d_vic_nonzero=d_vic_nonzero, d_vic_start=d_vic_start,
            d_vfeat=d_vfeat, d_pdb=d_pdb, d_allowed=d_allowed,
        )

    def _grow_victim_axis(self, st: dict, vmax: int) -> None:
        vu_new = _bucket(vmax, 1)
        A = st["A"]
        if vu_new > st["vu"]:
            grow = vu_new - st["vu"]

            def pad1(arr, fill):
                w = [(0, 0)] * arr.ndim
                w[1] = (0, grow)
                return np.pad(arr, w, constant_values=fill)

            A["vic_prio"] = pad1(A["vic_prio"], I32_MAX)
            A["vic_req"] = pad1(A["vic_req"], 0)
            A["vic_nonzero"] = pad1(A["vic_nonzero"], 0)
            A["vic_start"] = pad1(A["vic_start"], np.inf)
            A["vic_pdb"] = pad1(A["vic_pdb"], False)
            for k_ in list(A["vfeat"]):
                A["vfeat"][k_] = pad1(A["vfeat"][k_], _VFEAT_PAD.get(k_, 0))
            st["vu"] = vu_new
        st["v"] = max(st["v"], _bucket(vmax))

    # Paired slot-width groups: members share one width (the fill writes
    # them in lockstep), with the bucket floor the full pack uses.
    _WIDTH_GROUPS = (
        (("terms",), "own_terms", 8),
        (("vol_dev_ids", "vol_dev_rw"), "devices", 1),
        (("csi_ids", "csi_drv"), "csivols", 1),
    )

    def _grow_widths(self, st: dict, items: list) -> bool:
        """Grow per-victim slot dims (high-water) before refilling dirty
        rows — a new victim with more terms/volumes than any staged one
        would otherwise overflow its slots.  Returns True when any dim
        grew: the mega-buffer's column layout changed, so the incremental
        row-scatter path must rebuild the full buffer."""
        grew = False
        vf = st["A"]["vfeat"]
        cache = self.sched.cache
        for keys, delta_key, floor in self._WIDTH_GROUPS:
            if keys[0] not in vf:
                continue
            need = 0
            for _row, vics in items:
                for p in vics:
                    need = max(
                        need,
                        len(cache.pods[p.uid].delta.get(delta_key, ())),
                    )
            cur = vf[keys[0]].shape[2]
            if need > cur:
                grew = True
                target = _bucket(need, floor)
                for k_ in keys:
                    w = [(0, 0), (0, 0), (0, target - cur)]
                    vf[k_] = np.pad(
                        vf[k_], w, constant_values=_VFEAT_PAD.get(k_, 0)
                    )
        return grew

    @staticmethod
    def _clear_rows(A: dict, rows: list) -> None:
        for row in rows:
            A["vic_prio"][row] = I32_MAX
            A["vic_req"][row] = 0
            A["vic_nonzero"][row] = 0
            A["vic_start"][row] = np.inf
            A["vic_pdb"][row] = False
            for k_, arr in A["vfeat"].items():
                arr[row] = _VFEAT_PAD.get(k_, 0)

    def _fill_rows(self, A: dict, items) -> None:
        """Write victim slots for the given (row, victims) pairs into the
        staging arrays — shared by the full pack and the incremental
        dirty-row repack (a fill divergence would split their decisions)."""
        cache = self.sched.cache
        vic_prio, vic_req = A["vic_prio"], A["vic_req"]
        vic_nonzero, vic_start = A["vic_nonzero"], A["vic_start"]
        vic_pdb, vfeat = A["vic_pdb"], A["vfeat"]
        pdbs, matched_pdbs = A["pdbs"], A["matched_pdbs"]
        dra_slot_map = A["dra_slot_map"]
        csi_rows = self.sched.builder.csi_rows  # a shared claim's row; its own: -1
        for row, vics in items:
            for j, p in enumerate(vics):
                pr = cache.pods[p.uid]
                req = pr.delta["req"]
                vic_prio[row, j] = p.spec.priority
                vic_req[row, j, : req.shape[0]] = req
                vic_nonzero[row, j] = pr.delta["nonzero"]
                vic_start[row, j] = p.status.start_time
                if pdbs:
                    for i in matched_pdbs(p):
                        vic_pdb[row, j, i] = True
                if "group" in vfeat:
                    vfeat["group"][row, j] = pr.delta["group"]
                    for a, tid in enumerate(pr.delta["own_terms"]):
                        vfeat["terms"][row, j, a] = tid
                if "port_triples" in vfeat:
                    for a, (triple, pk) in enumerate(pr.delta["ports"]):
                        vfeat["port_triples"][row, j, a] = triple
                        vfeat["port_keys"][row, j, a] = pk
                if "vol_dev_ids" in vfeat:
                    for a, (vid, rw) in enumerate(pr.delta.get("devices", ())):
                        vfeat["vol_dev_ids"][row, j, a] = vid
                        vfeat["vol_dev_rw"][row, j, a] = int(bool(rw))
                if "csi_ids" in vfeat:
                    for a, (cuid, did) in enumerate(pr.delta.get("csivols", ())):
                        vfeat["csi_ids"][row, j, a] = csi_rows.get(cuid, -1)
                        vfeat["csi_drv"][row, j, a] = did
                if "dra_kid" in vfeat:
                    for a, (kid, cid, cnt, _un, first) in enumerate(
                        dra_slot_map.get((row, j), ())
                    ):
                        vfeat["dra_kid"][row, j, a] = kid
                        vfeat["dra_cid"][row, j, a] = cid
                        vfeat["dra_cnt"][row, j, a] = cnt
                        vfeat["dra_first"][row, j, a] = int(bool(first))

    @staticmethod
    def _pack_buf_rows(A: dict, buf, idx, r: int, n_pdbs: int) -> None:
        """Write the staging arrays' rows ``idx`` into the mega-buffer —
        the ONE definition of the buffer's column layout, shared by the
        full pack (idx = all rows) and the incremental dirty-row scatter
        (a divergence here would corrupt victim tensors on exactly one of
        the two paths)."""
        # ``idx`` may be slice(None) (full pack — plain slice writes, no
        # fancy-index temporaries) or an int row array (incremental).
        nrows = buf.shape[0] if isinstance(idx, slice) else len(idx)
        vic_req = A["vic_req"]
        buf[idx, :, 0] = A["vic_prio"][idx]
        buf[idx, :, 1 : 1 + r] = vic_req[idx]
        buf[idx, :, 1 + r : 3 + r] = A["vic_nonzero"][idx]
        buf[idx, :, 3 + r] = A["vic_start"][idx].view(np.int64)
        pdb_words = max(1, (n_pdbs + 63) // 64)
        # Accumulate each word OFF-buffer, then one assignment:
        # ``out=buf[idx, ...]`` would write into the copy a fancy index
        # returns, silently dropping every PDB bit.
        vic_pdb = A["vic_pdb"]
        for w_i in range(pdb_words):
            word = np.zeros((nrows, buf.shape[1]), np.int64)
            for i in range(w_i * 64, min((w_i + 1) * 64, n_pdbs)):
                word |= vic_pdb[idx, :, i].astype(np.int64) << (i % 64)
            buf[idx, :, 4 + r + w_i] = word
        off = 4 + r + pdb_words
        for key_ in sorted(A["vfeat"]):
            arr = A["vfeat"][key_]
            if arr.ndim == 2:
                buf[idx, :, off] = arr[idx]
                off += 1
            else:
                w = arr.shape[2]
                buf[idx, :, off : off + w] = arr[idx]
                off += w

    def _assemble(
        self, A: dict, n: int, v: int, n_pdbs: int, pdbs, matched_pdbs,
        per_node: dict, profile, active, st: dict | None = None,
    ) -> dict:
        """Pack the staging arrays into the single-transfer mega-buffer,
        ship it, and unpack device-side.  ONE larger transfer instead of
        seven small device_puts: the same bytes move as a single int64
        mega-buffer, and the jitted unpack (slice + astype + bitcast +
        pad-to-v, memoized per layout) reconstructs the per-field device
        arrays.  (Whether the coalescing still pays on a local chip is
        ROADMAP D5's to measure.)"""
        vic_req = A["vic_req"]
        vu = vic_req.shape[1]
        r = vic_req.shape[2]
        pdb_allowed = np.full(n_pdbs, I32_MAX, np.int64)
        for i, pdb in enumerate(pdbs):
            pdb_allowed[i] = max(pdb.disruptions_allowed, 0)
        pdb_words = max(1, (n_pdbs + 63) // 64)
        vfeat = A["vfeat"]
        vf_keys = tuple(sorted(vfeat))
        vf_cols: list[tuple[str, int, tuple[int, ...]]] = []
        col = 4 + r + pdb_words  # prio, req[r], nonzero[2], start, pdb words
        layout: dict = {
            "r": r, "n_pdbs": n_pdbs, "pdb_words": pdb_words, "v": v,
        }
        for key_ in vf_keys:
            arr = vfeat[key_]
            width = 1 if arr.ndim == 2 else arr.shape[2]
            vf_cols.append((key_, width, arr.shape))
            col += width
        k_cols = col
        # One extra FINAL column carries pdb_allowed (written below) —
        # allocated upfront so nothing re-copies the multi-MB buffer.
        buf = np.zeros((n, vu, k_cols + 1), np.int64)
        self._pack_buf_rows(A, buf, slice(None), r, n_pdbs)
        # pdb_allowed rides in the DEDICATED final column, one value per
        # node row (buf[i, 0, -1] = allowed[i]) — no extra round trip.
        # Only possible while n_pdbs ≤ N; beyond that (more PDBs than node
        # rows — tiny clusters with many budgets) it pays its own transfer.
        inline_allowed = n_pdbs <= n
        if inline_allowed:
            buf[:n_pdbs, 0, -1] = pdb_allowed
        layout["vf_cols"] = tuple(vf_cols)
        d_buf = jax.device_put(buf)
        if st is not None:
            st["buf"], st["d_buf"] = buf, d_buf
            st["vf_cols"] = tuple(vf_cols)
            # Warm the dirty-row scatter program at its bucketed shapes so
            # the first incremental repack doesn't compile inside a
            # measured window (idempotent: rewrites row 0 with itself).
            for nb in (8, 64):
                rows0 = np.zeros(nb, np.int32)
                st["d_buf"] = _scatter_buf_rows(
                    st["d_buf"], rows0, np.broadcast_to(buf[0], (nb,) + buf.shape[1:])
                )
            d_buf = st["d_buf"]
        unpacked = _unpack_victims(d_buf, self._unpack_spec(layout))
        d_prio, d_vic_req, d_vic_nonzero, d_vic_start, d_pdb, d_allowed = (
            unpacked[:6]
        )
        if not inline_allowed:
            d_allowed = jax.device_put(pdb_allowed)
        d_vfeat = dict(zip(vf_keys, unpacked[6:]))
        return dict(
            profile=profile, active=active, pdbs=pdbs, n_pdbs=n_pdbs,
            matched_pdbs=matched_pdbs, per_node=per_node,
            d_prio=d_prio, d_vic_req=d_vic_req, d_vic_nonzero=d_vic_nonzero,
            d_vic_start=d_vic_start, d_vfeat=d_vfeat, d_pdb=d_pdb,
            d_allowed=d_allowed,
        )

    def preempt_batch(
        self,
        pods: list[t.Pod],
        batch_rows: dict,
        active: frozenset[str] | None = None,
        inv: dict | None = None,
        profile=None,
        candidate_filter=None,
        prepacked: dict | None = None,
        dry_run: bool = False,
    ) -> list[PreemptionResult | None]:
        """Run preemption for the failed pods of one scheduling batch.
        ``batch_rows`` are each pod's already-built feature dict rows.

        ``candidate_filter(pod, node_name, victims) -> bool`` vetoes a
        chosen candidate BEFORE its victims are deleted — the extender
        ProcessPreemption hook (preemption.go:249 callExtenders).  The
        reference consults extenders over the full candidate list before
        selection; the batched engine selects first and filters the one
        chosen candidate (divergence documented in extender.py).

        ``dry_run`` returns the chosen candidates WITHOUT applying them
        (no victim deletion, PDB debit, or nomination) — the fleet's
        cross-shard arbitration evaluates every shard's best candidate
        and executes only the global winner (fleet/router.py)."""
        sched = self.sched
        profile = profile or sched.profile
        cache, builder = sched.cache, sched.builder
        schema = builder.schema

        eligible = self._eligibility(pods, batch_rows.get("req"))
        if not any(eligible):
            return [None] * len(pods)

        pack = prepacked
        if (
            pack is None
            or pack["profile"] is not profile
            or pack["active"] != active
        ):
            pack = self.pack_victims(profile, active)
        pdbs, n_pdbs = pack["pdbs"], pack["n_pdbs"]
        matched_pdbs, per_node = pack["matched_pdbs"], pack["per_node"]
        # Stack the failed pods' feature rows into a (K, …) batch; mark
        # ineligible rows invalid so their step is a no-op.  K is always the
        # scheduler's batch size (failed ⊆ batch): ONE compiled shape, so a
        # 1-pod warm preemption covers the full-batch measured shape (the
        # variable-bucket shapes used to recompile inside the measured
        # window).  Idle padded steps are cheap relative to a recompile.
        k = self.sched.batch_size
        batch: dict = {}
        for key_, rows in batch_rows.items():
            stacked = np.stack(rows)
            pad = [(0, k - len(pods))] + [(0, 0)] * (stacked.ndim - 1)
            batch[key_] = np.pad(stacked, pad)
        batch["valid"] = np.zeros(k, np.bool_)
        batch["valid"][: len(pods)] = eligible
        # Chunk-sharing signature: pods with the same featurize-cache key
        # have identical dry-runs and may split one evaluation's node
        # ranking (build_preempt_pass step).  Reuses the memoized featurize
        # signature — these pods were just featurized by the failing batch.
        sigs, sig_first = self._sig_ids(pods, profile, k)
        batch["sig"] = sigs

        if inv is None:
            inv = builder.batch_invariants()
        state = builder.state()
        # Chunk like the scheduling pass (same dispatch-overhead economics);
        # a batch whose eligible preemptors ALL share one signature (the
        # async-preemption shape: N identical VIPs) runs as ONE rank-split
        # step (_chunk_for).
        chunk = self._chunk_for(sig_first, k)
        # ONE coalesced host→device transfer for the per-call inputs (the
        # victim tensors were shipped by pack_victims, possibly overlapped
        # with the failing batch's device pass).
        batch_d, inv_d = jax.device_put((batch, inv))
        out, _final_state, _final_prio = self._pass(profile, active, n_pdbs, chunk)(
            state, batch_d, inv_d, pack["d_prio"], pack["d_vic_req"],
            pack["d_vic_nonzero"], pack["d_vic_start"], pack["d_vfeat"],
            pack["d_pdb"], pack["d_allowed"],
        )
        picks, vmasks = device_fetch((out.picks, out.vic_mask))
        # Chunk-deferred preemptors (same-node collisions, heterogeneous
        # signatures, exhausted ranks) return None: the scheduler requeues
        # them and the NEXT chunked pass — against post-eviction truth — is
        # far cheaper than a sequential k-step re-scan here (the victims'
        # delete events wake them).

        return self._interpret_dryrun(
            pods, picks, vmasks, pack, candidate_filter, dry_run=dry_run
        )

    def _eligibility(self, pods, batch_req=None) -> list[bool]:
        """Cheap host-side prunes: (a) a pod whose demand exceeds every
        node's allocatable can never be helped by deletion; (b) a pod
        whose priority doesn't exceed the LOWEST bound-pod priority has
        no victims anywhere.  Both prevent repacking victim tensors for
        perma-stuck pods every batch (the Unschedulable-workload shape)."""
        cache, builder = self.sched.cache, self.sched.builder
        max_alloc = builder.host["alloc"].max(axis=0)
        max_allowed = int(builder.host["allowed_pods"].max(initial=0))
        min_prio = min(
            (pr.pod.spec.priority for pr in cache.pods.values()), default=None
        )

        def can_ever_fit(i: int, p: t.Pod) -> bool:
            if batch_req is not None:
                req = np.asarray(batch_req[i])  # already featurized this batch
            else:
                pr = cache.pods.get(p.uid)
                delta = pr.delta if pr else builder.pod_delta_vectors(p)
                req = delta["req"]
            return bool((req <= max_alloc[: req.shape[0]]).all()) and max_allowed >= 1

        return [
            p.spec.preemption_policy != t.PREEMPT_NEVER
            and min_prio is not None
            and p.spec.priority > min_prio
            and can_ever_fit(i, p)
            for i, p in enumerate(pods)
        ]

    def _sig_ids(self, pods, profile, k: int):
        """Chunk-sharing signatures (first-index representative ids) for
        the dry-run's rank-split, padded to k."""
        from .engine.features import _claim_key, pod_sig

        sig_first: dict = {}
        sigs = np.zeros(k, np.int32)
        builder = self.sched.builder
        for i, p in enumerate(pods):
            memo = getattr(p, "_featsig", None)
            # the signature leaves the claims' names out; the key puts back
            # what featurization reads of them
            key_ = _claim_key(memo if memo is not None else pod_sig(p), p, builder)
            sigs[i] = sig_first.setdefault(key_, i)
        return sigs, sig_first

    def dispatch_speculative(self, ctx: dict, pack: dict):
        """Dispatch the dry-run CHAINED on the in-flight main pass's
        device-resident verdicts (valid = eligible ∧ pick < 0) — zero host
        round trips between the phases and no re-upload of the pod batch
        (ctx["batch_d"] is reused).  The dry-run sees the post-scan state
        (ctx["new_state"]); strict-tail commits land after dispatch, so
        the scheduler re-validates capacity before an INLINE commit of a
        speculative result (collect path) — nominate-and-retry results
        validate themselves on retry.  Returns a handle for
        collect_speculative, or None when speculation doesn't apply."""
        sched = self.sched
        if ctx.get("pinned") or "batch_d" not in ctx:
            return None
        infos, profile, active = ctx["infos"], ctx["profile"], ctx["active"]
        pods = [qp.pod for qp in infos]
        eligible = self._eligibility(pods, ctx["batch"].get("req"))
        if not any(eligible):
            return None
        k = sched.batch_size
        elig = np.zeros(k, np.bool_)
        elig[: len(pods)] = eligible
        sigs, sig_first = self._sig_ids(pods, profile, k)
        chunk = self._chunk_for(sig_first, k)
        fn = self._pass(profile, active, pack["n_pdbs"], chunk)
        # The scheduler's template-batch flag is a scalar the dry-run's
        # per-pod reshape cannot carry.
        batch_d = {
            k2: v for k2, v in ctx["batch_d"].items() if k2 != "uniform_all"
        }
        out, _fs, _fp = _chain_speculative(
            fn, ctx["new_state"], batch_d, ctx["result"].picks,
            jax.device_put((elig, sigs)), ctx["inv_d"], pack["d_prio"],
            pack["d_vic_req"], pack["d_vic_nonzero"], pack["d_vic_start"],
            pack["d_vfeat"], pack["d_pdb"], pack["d_allowed"],
        )
        return dict(out=out, pack=pack)

    def _chunk_for(self, sig_first: dict, k: int) -> int:
        """Dry-run chunking, shared by the sync and speculative paths (a
        divergence here would double the compiled-pass cache and split
        behavior for the same batch shape): uniform-signature batches
        collapse to ONE rank-split step; otherwise the scheduler's chunk
        clamped to 64, halved until it divides k."""
        if self.sched.chunk_size > 1 and len(sig_first) == 1:
            chunk = k
        else:
            chunk = min(
                self.sched.chunk_size if self.sched.chunk_size > 1 else 1, 64
            )
        chunk = max(1, min(chunk, k))
        while k % chunk:
            chunk //= 2
        return chunk

    def collect_speculative(
        self, spec: dict, fetched, failed_pods_by_index: dict
    ) -> dict:
        """Interpret speculative results for the batch indices that FAILED
        (scan or tail).  ``fetched`` = (picks, vic_mask) numpy arrays from
        the combined fetch; indices that placed in the strict tail are
        skipped (their dry-run was computed but never applied — pure
        compute, no side effects).  Returns {batch index: result}."""
        picks, vmasks = fetched
        idxs = sorted(failed_pods_by_index)
        pods = [failed_pods_by_index[i] for i in idxs]
        results = self._interpret_dryrun(
            pods, picks[idxs], vmasks[idxs], spec["pack"]
        )
        return dict(zip(idxs, results))

    def _interpret_dryrun(
        self, pods, picks, vmasks, pack, candidate_filter=None,
        dry_run: bool = False,
    ) -> list[PreemptionResult | None]:
        """prepareCandidate over fetched dry-run results: delete victims,
        nominate; consumed victims dedup across same-pass preemptors.
        Shared by the synchronous path and collect_speculative.  With
        ``dry_run`` the candidates are returned un-applied (see
        preempt_batch)."""
        sched = self.sched
        cache = sched.cache
        pdbs, matched_pdbs = pack["pdbs"], pack["matched_pdbs"]
        per_node = pack["per_node"]
        results: list[PreemptionResult | None] = []
        consumed: set[str] = set()
        for i, pod in enumerate(pods):
            pick = int(picks[i])
            if pick < 0 or pod is None:
                results.append(None)
                continue
            node_name = cache.node_name_at_row(pick)
            vics = per_node[pick]
            victims = [
                vics[j]
                for j in np.nonzero(vmasks[i])[0]
                if j < len(vics)
                and vics[j].spec.priority < pod.spec.priority
                and vics[j].uid not in consumed
            ]
            if candidate_filter is not None and not candidate_filter(
                pod, node_name, victims
            ):
                results.append(None)
                continue
            if dry_run:
                # Evaluation only: the fleet router compares this shard's
                # candidate against the other shards' before anything is
                # applied.  Victims still dedup within the pass so two
                # same-pass preemptors cannot both claim one victim.
                consumed.update(v.uid for v in victims)
                results.append(
                    PreemptionResult(node_name=node_name, victims=victims)
                )
                continue
            # prepareCandidate: delete victims, nominate the node.  The host
            # deltas mark rows dirty; the next state() flush re-syncs the
            # device (the in-scan release was resources-only).
            for vic in victims:
                consumed.add(vic.uid)
                # Full deletion path (DRA claim release, gang credit); the
                # caller fires ONE batched POD_DELETE for all victims.
                sched.delete_pod(vic.uid, notify=False)
                # Evicting a PDB-covered pod consumes its budget (the
                # disruption controller would rebuild DisruptionsAllowed;
                # in-process we decrement directly).
                for pi in matched_pdbs(vic):
                    pdbs[pi].disruptions_allowed -= 1
            pod.status.nominated_node_name = node_name
            results.append(PreemptionResult(node_name=node_name, victims=victims))
        return results
