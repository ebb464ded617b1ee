"""NodeResourcesFit + NodeResourcesBalancedAllocation, vectorized.

Reference semantics:
  * Filter — fitsRequest (plugins/noderesources/fit.go:488–560): pod count,
    then for each requested resource, request ≤ allocatable − requested(node).
    A resource the pod does not request never fails.
  * Score — strategy scorers (least_allocated.go / most_allocated.go /
    requested_to_capacity_ratio.go) over NonZeroRequested for cpu/memory and
    Requested for other resources (resource_allocation.go:89–114).
  * BalancedAllocation — 1 − std of resource utilization fractions
    (balanced_allocation.go:138 balancedResourceScorer), over plain Requested.

The per-node Go loop becomes a handful of (N,)/(N,R) int64 vector ops; the
whole node axis is evaluated in one shot on the VPU.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..api import types as t
from ..framework.config import (
    LEAST_ALLOCATED,
    MAX_NODE_SCORE,
    MOST_ALLOCATED,
    REQUESTED_TO_CAPACITY_RATIO,
)
from .common import FeaturizeContext, OpDef, PassContext, register
from .helpers import bounded_quotient

# Kind tags for strategy resource columns: where the "requested" number for a
# resource comes from (resource_allocation.go:89 calculateResourceAllocatableRequest).
_KIND_NONZERO_CPU = 0  # NodeInfo.NonZeroRequested.MilliCPU
_KIND_NONZERO_MEM = 1  # NodeInfo.NonZeroRequested.Memory
_KIND_REQ_COL = 2  # NodeInfo.Requested column


def strategy_columns(profile, builder_res_col: dict[str, int]):
    """Resolve the scoring strategy's resource list to (kind, col, weight)."""
    out = []
    for name, weight in profile.scoring_strategy.resources:
        if name == t.CPU:
            out.append((_KIND_NONZERO_CPU, 0, weight))
        elif name == t.MEMORY:
            out.append((_KIND_NONZERO_MEM, 1, weight))
        else:
            col = builder_res_col.get(name)
            if col is not None:
                out.append((_KIND_REQ_COL, col, weight))
    return tuple(out)


def featurize(pod: t.Pod, fctx: FeaturizeContext) -> dict:
    # Base req/nonzero features are provided by the engine; nothing extra here.
    return {}


def filter_fn(state, pf, ctx: PassContext):
    # Pod count check always applies (fit.go:491).
    fits = state.num_pods + 1 <= state.allowed_pods
    req = pf["req"]  # (R,) i64
    # NodeResourcesFitArgs.IgnoredResources: zero the demand in the FIT
    # check only — bind-time accounting still charges the full delta
    # (fit.go:488 skips ignoredExtendedResources in fitsRequest).
    ig = ctx.static.get("fit_ignored_cols", ()) if ctx.static else ()
    req_fit = req.at[np.array(ig, np.int32)].set(0) if ig else req
    free = state.alloc - state.req  # (N, R)
    fits &= jnp.all((req_fit[None, :] == 0) | (req_fit[None, :] <= free), axis=1)
    if ctx.nom is not None:
        # Nominated-pod accounting (RunFilterPluginsWithNominatedPods,
        # runtime/framework.go:973): the pod must ALSO fit with nominated
        # pods' resources counted on their nominated nodes.  Applied per
        # node when the pod's priority ≤ the node's max nominated priority
        # (conservative: the reference adds only the ≥-priority subset).
        # The pod's own nomination is excluded (framework.go skips same-UID).
        nom_req, nom_cnt, nom_prio = ctx.nom
        n = state.alloc.shape[0]
        own = pf["nominated_row"]
        self_mask = (jnp.arange(n) == own) & (own >= 0)
        eff_req = jnp.maximum(
            nom_req - jnp.where(self_mask[:, None], req[None, :], 0), 0
        )
        eff_cnt = jnp.maximum(nom_cnt - self_mask.astype(jnp.int32), 0)
        fits_nom = jnp.all(
            (req_fit[None, :] == 0) | (req_fit[None, :] <= free - eff_req), axis=1
        )
        fits_nom &= state.num_pods + 1 + eff_cnt <= state.allowed_pods
        applies = pf["priority"] <= nom_prio  # (N,)
        fits &= fits_nom | ~applies
    return fits


def _requested_totals(state, pf, cols):
    """Per strategy resource: (alloc (N,), requested-including-pod (N,))."""
    out = []
    for kind, col, weight in cols:
        if kind == _KIND_NONZERO_CPU:
            alloc = state.alloc[:, 0]
            reqd = state.nonzero_req[:, 0] + pf["nonzero"][0]
        elif kind == _KIND_NONZERO_MEM:
            alloc = state.alloc[:, 1]
            reqd = state.nonzero_req[:, 1] + pf["nonzero"][1]
        else:
            alloc = state.alloc[:, col]
            reqd = state.req[:, col] + pf["req"][col]
        out.append((alloc, reqd, weight))
    return out


def _least_requested(alloc, reqd):
    # least_allocated.go:97 — ((capacity-requested)*MaxNodeScore)/capacity,
    # 0 when capacity == 0 or requested > capacity. Int64 truncating division;
    # on the `ok` rows 0 <= requested <= capacity, so the quotient is in
    # [0, MaxNodeScore] and bounded_quotient is exact there.
    ok = (alloc > 0) & (reqd <= alloc)
    safe_alloc = jnp.maximum(alloc, 1)
    score = bounded_quotient((alloc - reqd) * MAX_NODE_SCORE, safe_alloc, MAX_NODE_SCORE)
    return jnp.where(ok, score, 0)


def _most_requested(alloc, reqd):
    # most_allocated.go — requested*MaxNodeScore/capacity, 0 outside [0, cap].
    ok = (alloc > 0) & (reqd <= alloc)
    safe_alloc = jnp.maximum(alloc, 1)
    score = bounded_quotient(reqd * MAX_NODE_SCORE, safe_alloc, MAX_NODE_SCORE)
    return jnp.where(ok, score, 0)


def _ratio_scorer(shape):
    """BuildBrokenLinearFunction over (utilization%, score 0..10) points,
    scaled to MaxNodeScore (requested_to_capacity_ratio.go)."""
    xs = np.array([p[0] for p in shape], np.float64)
    ys = np.array([p[1] for p in shape], np.float64)

    def f(alloc, reqd):
        util = jnp.where(
            alloc > 0, (reqd * 100.0) / jnp.maximum(alloc, 1).astype(jnp.float64), 0.0
        )
        raw = jnp.interp(util, jnp.asarray(xs), jnp.asarray(ys))
        ok = (alloc > 0) & (reqd <= alloc)
        return jnp.where(ok, (raw * (MAX_NODE_SCORE / 10)).astype(jnp.int64), 0)

    return f


def score_fn(state, pf, ctx: PassContext, feasible=None):
    cols = ctx.static["fit_strategy_cols"]
    strat = ctx.profile.scoring_strategy.type
    if strat == REQUESTED_TO_CAPACITY_RATIO:
        scorer = _ratio_scorer(ctx.profile.scoring_strategy.shape)
    elif strat == MOST_ALLOCATED:
        scorer = _most_requested
    else:
        assert strat == LEAST_ALLOCATED, strat
        scorer = _least_requested
    node_score = jnp.zeros(ctx.schema.N, jnp.int64)
    weight_sum = jnp.zeros(ctx.schema.N, jnp.int64)
    for alloc, reqd, weight in _requested_totals(state, pf, cols):
        # `if allocable[i] == 0 { continue }` skips the weight too
        # (least_allocated.go:72) — weightSum varies per node.
        present = alloc > 0
        node_score += jnp.where(present, scorer(alloc, reqd) * weight, 0)
        weight_sum += jnp.where(present, weight, 0)
    # Each scorer is in [0, MaxNodeScore] and each weight in [1, 100], so
    # node_score <= MaxNodeScore * weight_sum on every row.
    quotient = bounded_quotient(node_score, jnp.maximum(weight_sum, 1), MAX_NODE_SCORE)
    return jnp.where(weight_sum > 0, quotient, 0)


def balanced_score_fn(state, pf, ctx: PassContext, feasible=None):
    """balancedResourceScorer: fractions of Requested/Allocatable (capped at
    1), score = (1 − std) * MaxNodeScore.  Uses plain Requested (useRequested,
    balanced_allocation.go:135) — no nonzero defaults."""
    cols = ctx.static["balanced_cols"]
    fracs = []
    present = []
    for col, in cols:
        alloc = state.alloc[:, col]
        reqd = state.req[:, col] + pf["req"][col]
        f = jnp.minimum(reqd.astype(jnp.float64) / jnp.maximum(alloc, 1).astype(jnp.float64), 1.0)
        fracs.append(jnp.where(alloc > 0, f, 0.0))
        present.append(alloc > 0)
    fr = jnp.stack(fracs)  # (C, N)
    pres = jnp.stack(present)  # (C, N)
    count = pres.sum(axis=0)
    # Exactly two resources → std = |f0 - f1| / 2 (balanced_allocation.go:155);
    # otherwise root of mean squared deviation. With per-node presence masks we
    # compute both and select.
    mean = jnp.where(count > 0, fr.sum(0) / jnp.maximum(count, 1), 0.0)
    var = jnp.where(pres, (fr - mean[None, :]) ** 2, 0.0).sum(0) / jnp.maximum(count, 1)
    std_general = jnp.sqrt(var)
    # two-resource shortcut: requires identifying the two present fractions;
    # when count == 2, sum of |f - mean| / 2 over present == |f0-f1|/2.
    std_two = jnp.where(pres, jnp.abs(fr - mean[None, :]), 0.0).sum(0) / 2.0
    std = jnp.where(count == 2, std_two, jnp.where(count > 2, std_general, 0.0))
    return ((1.0 - std) * MAX_NODE_SCORE).astype(jnp.int64)


def static_features(profile, schema, builder_res_col: dict[str, int]) -> dict:
    """Static (non-tensor) per-profile config the score fns need."""
    from ..snapshot import FIXED_RESOURCES

    ignored = set(profile.fit_ignored_resources)
    groups = set(profile.fit_ignored_resource_groups)
    return {
        "fit_strategy_cols": strategy_columns(profile, builder_res_col),
        "balanced_cols": tuple(
            (builder_res_col[name],)
            for name, _ in profile.scoring_strategy.resources
            if name in builder_res_col
        ),
        # Only EXTENDED resources may be ignored (fit.go:488; built-ins are
        # always checked).  Groups match the "<group>/<name>" prefix.
        "fit_ignored_cols": tuple(
            sorted(
                col
                for name, col in builder_res_col.items()
                if name not in FIXED_RESOURCES
                and (
                    name in ignored
                    or ("/" in name and name.split("/", 1)[0] in groups)
                )
            )
        ),
    }


register(
    OpDef(
        name="NodeResourcesFit",
        featurize=featurize,
        filter=filter_fn,
        score=score_fn,
        static=static_features,
    )
)
register(
    OpDef(
        name="NodeResourcesBalancedAllocation",
        score=balanced_score_fn,
        static=static_features,
    )
)
