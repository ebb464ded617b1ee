"""The score functions' int64 quotients, taken without an int64 division.

`ops.helpers.bounded_quotient(num, den, qmax)` is `num // den` for
`0 <= num <= qmax * den`, `den >= 1`: an f32 estimate corrected once by the
sign of the exact int64 remainder.  Held here: (1) the helper against
numpy's `//` on the edges of its domain (`num = q * den + {-1, 0, +1}`,
`q` 0..100, `den` from 1 to 2**50) and on random pairs, and its finite,
bounded answer outside the domain; (2) every scorer that takes such a
quotient against the same scorer with its former `//` expression (the
oracle: `bounded_quotient` swapped back for `num // den` at every call
site), on random small clusters, compared on the rows the pass uses; (3)
no pass program of the default profile holds an int64 divide or remainder
(the proof that the mechanism engages, as `scan_steps` is the trip
count's).

These run on the CPU, whose f32 divide is correctly rounded.  The chip's
f32 divide need not be: that case is covered by the error bound in
`bounded_quotient`'s docstring and by the benchmark cells' `correct`, not
by this file."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_ordered_pass import R, SEED, _scenario

from kubernetes_tpu.api import types as t
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.engine.features import build_pod_batch
from kubernetes_tpu.framework.config import (
    LEAST_ALLOCATED,
    MOST_ALLOCATED,
    REQUESTED_TO_CAPACITY_RATIO,
    Profile,
    ScoringStrategy,
)
from kubernetes_tpu.ops import (
    helpers,
    imagelocality,
    interpodaffinity,
    noderesources,
    podtopologyspread,
)
from kubernetes_tpu.ops.helpers import bounded_quotient
from kubernetes_tpu.scheduler import TPUScheduler

quotient = jax.jit(bounded_quotient, static_argnums=2)

# -- (1) the helper -----------------------------------------------------------

DENS = [
    1, 2, 3, 7, 99, 100, 101, 4_095, 1_000_003, 2**24 - 1, 2**24 + 1,
    2**31 - 1, 2**32 + 1, 2**40 + 13, 2**50 - 1, 2**50,
]


@pytest.mark.parametrize("den", DENS)
def test_edges_of_the_domain_match_floor_division(den):
    q = np.arange(101, dtype=np.int64)
    num = (q[:, None] * den + np.array([-1, 0, 1])[None, :]).ravel()
    num = np.clip(num, 0, 100 * den)
    d = np.full_like(num, den)
    got = np.asarray(quotient(num, d, 100))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, num // d)


@pytest.mark.parametrize(
    "qmax, top", [(1, 2**62), (100, 2**50), (100, 2**20), (2**16, 2**46)]
)
@pytest.mark.parametrize("seed", [0, 1])
def test_random_pairs_match_floor_division(seed, qmax, top):
    """Denominators log-uniform in [1, top], numerators uniform in
    [0, qmax * den]."""
    rng = np.random.default_rng(seed)
    den = np.exp2(rng.uniform(0, np.log2(top), 200_000)).astype(np.int64)
    den = np.maximum(den, 1)
    num = (rng.uniform(0, 1, den.shape) * qmax * den.astype(np.float64)).astype(np.int64)
    num = np.clip(num, 0, qmax * den)
    np.testing.assert_array_equal(np.asarray(quotient(num, den, qmax)), num // den)


@pytest.mark.parametrize(
    "num, den",
    [
        (-1, 7),  # a masked row's negative numerator
        (-(2**60), 3),
        (10**6, 3),  # a quotient far above qmax
        (2**62, 1),
        (5, 0),  # a zero or negative denominator
        (0, 0),
        (5, -4),
        (-5, -(2**40)),
    ],
)
def test_outside_the_domain_is_finite_and_bounded(num, den):
    got = np.asarray(quotient(np.array([num], np.int64), np.array([den], np.int64), 100))
    assert got.dtype == np.int64 and -1 <= int(got[0]) <= 102


# -- (2) every scorer that takes a quotient, against its former `//` ----------

SITES = (helpers, noderesources, interpodaffinity, podtopologyspread, imagelocality)
ZONE = "topology.kubernetes.io/zone"
GI = 1024**3
MB = 1024 * 1024


def _floor_quotient(num, den, qmax):
    """What every call site computed before: an int64 `//`."""
    return num // den


def _nodes(rng, n: int) -> list:
    """Capacities from a quarter core to 96 and from 256 MiB to 1 TiB, so
    that some nodes cannot hold the larger pods (infeasible rows); zones,
    a tier label, PreferNoSchedule taints and images at random."""
    out = []
    for i in range(n):
        w = (
            make_node(f"n{i:02d}")
            .capacity({
                "cpu": f"{int(rng.integers(250, 96_000))}m",
                "memory": str(int(rng.integers(256 * MB, 1024 * GI))),
                "pods": 110,
            })
            .zone(f"z{i % 3}")
            .label("tier", f"t{int(rng.integers(3))}")
        )
        for k in range(int(rng.integers(0, 8))):
            w = w.taint(f"soft{k}", effect=t.EFFECT_PREFER_NO_SCHEDULE)
        for k in range(3):
            if rng.random() < 0.5:
                w = w.image(f"img{k}:v1", int(rng.integers(10, 1500)) * MB)
        out.append(w.obj())
    return out


def _bound(rng, nodes) -> list:
    """Pods already on the nodes, labelled for the affinity and spread
    selectors, with preferred terms of their own for InterPodAffinity's
    existing-pod half."""
    out = []
    for i in range(2 * len(nodes)):
        w = (
            make_pod(f"b{i:02d}")
            .req({"cpu": f"{int(rng.integers(10, 200))}m", "memory": str(int(rng.integers(1, 64)) * MB)})
            .label("app", f"a{int(rng.integers(3))}")
            .node(nodes[int(rng.integers(len(nodes)))].metadata.name)
        )
        if i % 3 == 0:
            w = w.preferred_pod_affinity_in(
                "app", ["a0"], ZONE, weight=int(rng.integers(1, 101)), anti=bool(i % 2)
            )
        out.append(w.obj())
    return out


def _pending(rng, i: int):
    """One pending pod that every changed scorer has something to score
    for: requests up to 32 cores and 256 GiB, preferred node affinity,
    one toleration, preferred pod (anti-)affinity, two soft spread
    constraints and images.  Every pod has the same shape of terms."""
    return (
        make_pod(f"p{i:02d}")
        .req({
            "cpu": f"{int(rng.integers(1, 32_000))}m",
            "memory": str(int(rng.integers(1 * MB, 256 * GI))),
        })
        .label("app", f"a{int(rng.integers(3))}")
        .preferred_node_affinity_in("tier", ["t0"], weight=int(rng.integers(1, 101)))
        .preferred_node_affinity_in("tier", ["t0", "t1"], weight=int(rng.integers(1, 101)))
        .toleration("soft0", effect=t.EFFECT_PREFER_NO_SCHEDULE)
        .preferred_pod_affinity_in("app", ["a1"], ZONE, weight=int(rng.integers(1, 101)))
        .preferred_pod_affinity_in(
            "app", ["a2"], "kubernetes.io/hostname", weight=int(rng.integers(1, 101)), anti=True
        )
        .spread_constraint(1, ZONE, t.SCHEDULE_ANYWAY, "app", ["a0", "a1"])
        .spread_constraint(2, "kubernetes.io/hostname", t.SCHEDULE_ANYWAY, "app", ["a2"])
        .container_image("img0:v1", "img1:v1")
        .obj()
    )


def _strategy(kind: str) -> ScoringStrategy:
    res = (("cpu", 3), ("memory", 1))
    if kind == REQUESTED_TO_CAPACITY_RATIO:
        return ScoringStrategy(kind, res, ((0, 10), (40, 3), (75, 7), (100, 0)))
    return ScoringStrategy(kind, res)


# (case id, scorer, strategy, rows compared): DefaultNormalizeScore's
# scorers are exact on the feasible rows alone (an infeasible row's raw may
# exceed the feasible maximum, and select_host never reads it); every other
# scorer masks its out-of-domain rows itself, so all rows are compared.
SCORERS = [
    ("fit_least", "NodeResourcesFit", LEAST_ALLOCATED, "all"),
    ("fit_most", "NodeResourcesFit", MOST_ALLOCATED, "all"),
    ("fit_ratio", "NodeResourcesFit", REQUESTED_TO_CAPACITY_RATIO, "all"),
    ("interpodaffinity", "InterPodAffinity", LEAST_ALLOCATED, "all"),
    ("podtopologyspread", "PodTopologySpread", LEAST_ALLOCATED, "all"),
    ("tainttoleration", "TaintToleration", LEAST_ALLOCATED, "feasible"),
    ("nodeaffinity", "NodeAffinity", LEAST_ALLOCATED, "feasible"),
    ("imagelocality", "ImageLocality", LEAST_ALLOCATED, "all"),
]


@pytest.mark.parametrize(
    "scorer, strategy, rows", [c[1:] for c in SCORERS], ids=[c[0] for c in SCORERS]
)
def test_scorer_matches_its_former_floor_division(scorer, strategy, rows, monkeypatch):
    """Each pod's score column from the attribution pass (the same op calls
    as every pass program) equals the oracle's, on a random cluster of 14
    nodes where some nodes are infeasible for some pods."""
    rng = np.random.default_rng(sum(map(ord, scorer + strategy)))
    prof = Profile(
        name="quotient",
        filters=("NodeResourcesFit",),
        scorers=((scorer, 1),),
        scoring_strategy=_strategy(strategy),
    )
    s = TPUScheduler(profile=prof, batch_size=8, enable_preemption=False)
    nodes = _nodes(rng, 14)
    for node in nodes:
        s.add_node(node)
    for pod in _bound(rng, nodes):
        s.add_pod(pod)
    pods = [_pending(rng, i) for i in range(6)]

    def columns() -> list:
        s._attr_passes.clear()
        out = []
        for pod in pods:
            active, _ok, feasible, cols, _total = s._run_attribution_pass(pod, prof, -1)
            assert scorer in active and cols.shape[0] == 1
            out.append((np.asarray(feasible), np.asarray(cols[0])))
        return out

    got = columns()
    for mod in SITES:
        monkeypatch.setattr(mod, "bounded_quotient", _floor_quotient)
    want = columns()
    spread = set()
    for (feas, col), (feas_o, col_o) in zip(got, want):
        np.testing.assert_array_equal(feas, feas_o)
        keep = feas if rows == "feasible" else np.ones_like(feas)
        np.testing.assert_array_equal(col[keep], col_o[keep])
        spread.update(col[feas].tolist())
    # Not vacuous: some pods are infeasible somewhere, and the scores vary.
    assert not all(f.all() for f, _ in got)
    assert len(spread) > 2, spread


# -- (3) no int64 divide or remainder in a pass program ----------------------


@pytest.mark.parametrize("chunk", [1, 8])
def test_no_pass_program_of_the_default_profile_divides_int64(chunk):
    """The ordered (chunk 1) and the chunk-8 pass programs of the default
    profile, every plugin of it active, lowered at test size: the StableHLO
    holds no `divide` or `remainder` on `i64`."""
    s, state0, inv, _base, _active = _scenario("affinity")
    prof = s.profile
    every = frozenset(list(prof.filters) + [name for name, _ in prof.scorers])
    pods = [make_pod(f"q{i}").req({"cpu": "100m"}).obj() for i in range(4)]
    batch, _deltas, active = build_pod_batch(pods, s.builder, prof, R, force_active=every)
    assert active == every
    batch["nominated_row"] = np.full(R, -1, np.int32)
    dom = s._dom_placeholder()
    fn = s.passes.get(prof, s.builder.schema, s.builder.res_col, active, chunk, carry_dom=True)
    text = fn.lower(
        state0, dict(batch, uniform_all=np.bool_(False)), inv, SEED,
        dom[0], dom[1], np.bool_(False),
    ).as_text()
    assert "stablehlo.while" in text
    divides = [
        line.strip()
        for line in text.splitlines()
        if re.search(r"stablehlo\.(divide|remainder)\b", line) and "i64>" in line
    ]
    assert not divides, divides[:5]
    # The same check finds the former expression.
    probe = jax.jit(lambda a, b: a // b).lower(jnp.arange(4), jnp.arange(1, 5)).as_text()
    assert re.search(r"stablehlo\.divide .*i64>", probe)


def test_the_default_profile_lists_every_quotient_site():
    """Every plugin whose score takes a quotient is in the default profile,
    so the guard above traces it."""
    s, *_ = _scenario("affinity")
    scorers = {name for name, _ in s.profile.scorers}
    assert {c[1] for c in SCORERS} <= scorers


# -- (4) scripts/step_cost.py reads a step out of compiled HLO -----------------

STEP_HLO = """HloModule jit__run

%fused_computation.1 (param_0.1: s64[8]) -> s64[8] {
  %param_0.1 = s64[8]{0} parameter(0)
  ROOT %add.1 = s64[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(_run)/while/body/add"}
}

%branch.a (arg.2: s64[8]) -> s64[8] {
  %arg.2 = s64[8]{0} parameter(0)
  ROOT %fusion.9 = s64[8]{0:T(1024)} fusion(%arg.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_run)/while/body/pass/eval/InterPodAffinity/mul"}, backend_config={"flag_configs":[],"estimated_cycles":"50"}
}

%body.2 (arg.1: (s32[], s64[8])) -> (s32[], s64[8]) {
  %arg.1 = (s32[], s64[8]{0}) parameter(0)
  %get-tuple-element.1 = s64[8]{0} get-tuple-element(%arg.1), index=1
  %fusion.7 = (pred[8]{0:T(1024)(128)(4,1)S(1)}, u32[8]{0:T(1024)S(1)}) fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_run)/while/body/pass/eval/NodeResourcesFit/jit(floor_divide)/rem"}, backend_config={"flag_configs":[],"estimated_cycles":"300"}
  %fusion.8 = s64[8]{0:T(1024)} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_run)/while/body/pass/commit/scatter-add"}, backend_config={"estimated_cycles":"100"}
  %conditional.1 = s64[8]{0} conditional(%p, %get-tuple-element.1, %get-tuple-element.1), branch_computations={%branch.a, %branch.a}
  ROOT %tuple.1 = (s32[], s64[8]{0}) tuple(%c, %conditional.1)
}

%cond.3 (arg.3: (s32[], s64[8])) -> pred[] {
  %arg.3 = (s32[], s64[8]{0}) parameter(0)
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.4 (a.1: s64[8]) -> s64[8] {
  %a.1 = s64[8]{0} parameter(0)
  %while.5 = (s32[], s64[8]{0}) while(%tuple.0), condition=%cond.3, body=%body.2, metadata={op_name="jit(_run)/while"}
  ROOT %copy.1 = s64[8]{0} copy(%a.1), backend_config={"estimated_cycles":"7"}
}
"""


def test_step_cost_reads_the_step_loop_by_scope_and_primitive():
    """The step is the `while` body with the ops it reaches through control
    flow (every branch of a conditional, each computation once; a fusion's
    body never, its estimate is the fusion's); the entry's own ops are
    outside it."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "scripts", "step_cost.py")
    spec = importlib.util.spec_from_file_location("step_cost", path)
    step_cost = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(step_cost)

    loops = step_cost.step_loops(step_cost.parse_hlo(STEP_HLO))
    assert [(w["while"], w["op_name"]) for w in loops] == [("while.5", "jit(_run)/while")]
    ops = loops[0]["ops"]
    assert sum(c for _, c in ops) == 450 and len(ops) == 3
    by_scope = step_cost.breakdown(ops, step_cost.scope_of, 10)
    assert [(r["name"], r["ops"], r["cycles"]) for r in by_scope] == [
        ("pass/eval/NodeResourcesFit", 1, 300),
        ("pass/commit", 1, 100),
        ("pass/eval/InterPodAffinity", 1, 50),
    ]
    by_prim = step_cost.breakdown(ops, step_cost.primitive_of, 1)
    assert by_prim == [{"name": "floor_divide/rem", "ops": 1, "cycles": 300, "share": 0.6667}]
