"""Shared jax helpers for vectorized score ops."""

from __future__ import annotations

import jax.numpy as jnp

from ..framework.config import MAX_NODE_SCORE


def bounded_quotient(num: jnp.ndarray, den: jnp.ndarray, qmax: int) -> jnp.ndarray:
    """``num // den`` as int64, exactly, for ``0 <= num <= qmax * den`` and
    ``den >= 1`` (with ``(qmax + 1) * den`` inside int64), without an int64
    division: the TPU has no 64-bit integer divider, and XLA expands each
    int64 ``//`` into a long chain of u32 shift/compare fusions.

    The quotient is estimated as ``floor(f32(num) / f32(den))``.  Each of the
    two conversions and the divide is off by a few units in the last place at
    most, a relative error under 2**-20 even where the device's f32 divide is
    not correctly rounded.  Since the true quotient is at most ``qmax <=
    2**16``, the estimate is within 1/16 of it, so its floor is the true
    floor or one either side.  Clamping it to ``[0, qmax + 1]`` keeps that,
    and one correction by the sign of the exact int64 remainder ``num - q *
    den`` (one down if below zero, one up if ``>= den``) lands on the floor:
    the result is the same integer, bit for bit.

    Outside the domain the result is a finite int64 in ``[-1, qmax + 2]``
    and nothing traps (the f32 denominator is held at >= 1); every caller
    masks the rows that can lie there."""
    assert 0 < qmax <= 2**16, qmax
    num = num.astype(jnp.int64)
    den = den.astype(jnp.int64)
    est = jnp.floor(
        num.astype(jnp.float32) / jnp.maximum(den.astype(jnp.float32), 1.0)
    )
    q = jnp.clip(est, 0.0, float(qmax + 1)).astype(jnp.int64)
    rem = num - q * den
    return q - (rem < 0).astype(jnp.int64) + (rem >= den).astype(jnp.int64)


def default_normalize_score(raw: jnp.ndarray, feasible: jnp.ndarray, reverse: bool) -> jnp.ndarray:
    """Vectorized DefaultNormalizeScore (plugins/helper/normalize_score.go):
    rescale raw scores by the max over *feasible* nodes to [0, MaxNodeScore];
    with ``reverse`` higher raw scores map to lower results.  maxCount == 0
    short-circuits (all MaxNodeScore when reversed, all 0 otherwise).  Raw
    scores are non-negative, so a feasible row's quotient lies in [0,
    MaxNodeScore]; an infeasible row's may not, and select_host never
    reads it."""
    raw = raw.astype(jnp.int64)
    max_count = jnp.max(jnp.where(feasible, raw, 0))
    safe_max = jnp.maximum(max_count, 1)
    scaled = bounded_quotient(raw * MAX_NODE_SCORE, safe_max, MAX_NODE_SCORE)
    if reverse:
        scaled = MAX_NODE_SCORE - scaled
        return jnp.where(max_count == 0, MAX_NODE_SCORE, scaled)
    return jnp.where(max_count == 0, 0, scaled)


def make_topo_onehot(topo_vals: jnp.ndarray, dv: int) -> jnp.ndarray:
    """(N, TK, DV) f32 one-hot of the per-node topology values.  Scan-invariant
    (node topology never changes while a batch commits pods), so the engine
    computes it ONCE per device pass and closes the scan body over it — the
    hoist that turns the per-step domain reductions from O(N·TK·DV) rebuilds
    into cheap table gathers.  Hostname-key values exceed DV by design
    (excluded from the vocabulary); ops take a per-node fast path for them,
    and any hostname ids that happen to fall inside [0, DV) produce garbage
    table rows that every reader masks out via its ``host`` flags."""
    return (
        (topo_vals[:, :, None] == jnp.arange(dv)[None, None, :])
        & (topo_vals >= 0)[:, :, None]
    ).astype(jnp.float32)


def domain_tables(state, slots, counts, dv, onehot=None):
    """Per-term domain sums as MXU matmuls (no scatters).

    ``slots`` (T,) topology-key slot per term; ``counts`` (T, N) f32
    contributions.  Returns (vals (T,N), key_present (T,N), masked (T,N),
    tbl (T, DV)) where ``tbl[t, d] = Σ_n masked[t, n]·[vals[t, n] == d]``.
    The one-hot of topo_vals is shared across terms, so the reduction is one
    ``(T,N)×(N,TK·DV)`` einsum — scatter-free, which is what the TPU wants.
    Pass the engine's hoisted ``onehot`` (ctx.dom.onehot) so the scan does not
    rebuild it every step."""
    vals_all = state.topo_vals  # (N, TK)
    vals = jnp.take(vals_all, slots, axis=1).T  # (T, N)
    key_present = vals >= 0
    masked = jnp.where(key_present, counts, 0.0)
    if onehot is None:
        onehot = make_topo_onehot(vals_all, dv)
    tbl_all = jnp.einsum("tn,nkd->tkd", masked, onehot.astype(counts.dtype))
    tbl = jnp.take_along_axis(
        tbl_all, slots[:, None, None].astype(jnp.int32), axis=1
    )[:, 0, :]  # (T, DV)
    return vals, key_present, masked, tbl


def gather_mask(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """table[ids] with -1-padded ids contributing False/0.

    ``table`` is a per-pod vocabulary mask (V,); ``ids`` node slot ids (N, S).
    """
    safe = jnp.maximum(ids, 0)
    return jnp.where(ids >= 0, table[safe], jnp.zeros((), table.dtype))
