"""Every pass stops at its last pod (ISSUE 30 for chunk 1, ISSUE 32 for all).

`build_pass` drives its `step` (and the fused tail's) with one loop whose
trip count is read on the device from the batch's `valid`: to the chunk that
holds the last True, `ceil((1 + last valid row) / chunk)` steps.  The program
reports the steps it ran (`PassResult.scan_steps` →
`scheduler_pass_scan_steps_total` and the flight record's `scan_steps`).
Held here: the served path (a one-colour required-affinity batch at
`chunk_size` 8 falls back to the ordered program; a short chunked batch runs
one step) against the sequential oracle, the program itself at chunk widths
1 and 8 for empty / one / full / a hole in `valid` against a walk of all
`k // chunk` steps of the same step — the affinity profile and the
node-axis-only one, whose program also holds the fused tail and the uniform
all-fail `cond` — truncated mode's rotating start, and that every program
holds the one driver and no scan.  Small shapes, CPU."""

import copy
import dataclasses
import functools
import inspect
from dataclasses import replace

import jax
import numpy as np
import pytest
from oracle_full import FullOracleScheduler
from test_parity import OracleScheduler
from test_parity import _nodes as parity_nodes
from test_parity import _pod as parity_pod

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.engine import pass_
from kubernetes_tpu.engine.features import build_pod_batch
from kubernetes_tpu.framework.config import DEFAULT_PROFILE, fit_only_profile
from kubernetes_tpu.ops.common import registered_subset
from kubernetes_tpu.scheduler import TPUScheduler

ZONE = "topology.kubernetes.io/zone"
K = 16
SEED = np.uint32(7)


def _steps(s: TPUScheduler) -> tuple[int, int]:
    c = s._scan_steps_counter
    return int(c.get(kind="run")), int(c.get(kind="padded_skipped"))


# -- (1) the served path ------------------------------------------------------


def _one_zone_nodes(n: int = 12) -> list:
    return [
        make_node(f"n{i:02d}")
        .capacity({"cpu": str(4 + i % 5), "memory": "16Gi", "pods": 110})
        .zone("zone1")
        .obj()
        for i in range(n)
    ]


def _blue(i: int):
    """podaffinity_5kn's pod: its required term selects every other pod of
    the batch, so the packer finds one class as large as the batch."""
    return (
        make_pod(f"blue-{i:03d}")
        .req({"cpu": "500m", "memory": "512Mi"})
        .label("color", "blue")
        .pod_affinity_in("color", ["blue"], ZONE)
        .obj()
    )


@pytest.mark.parametrize("n_pods", [11, 27])
def test_served_ordered_batch_stops_at_its_last_pod(n_pods):
    """chunk_size 8, batch_size 16: batches of 16 and 11 (or one of 11) run
    16 + 11 steps and skip the padding, and bind as the sequential oracle."""
    prof = replace(registered_subset(DEFAULT_PROFILE), percentage_of_nodes_to_score=100)
    nodes = _one_zone_nodes()
    s = TPUScheduler(profile=prof, batch_size=K, chunk_size=8, enable_preemption=False)
    for node in nodes:
        s.add_node(node)
    pods = [_blue(i) for i in range(n_pods)]
    for p in pods:
        s.add_pod(copy.deepcopy(p))
    got = {o.pod.name: o.node_name for o in s.schedule_all_pending()}
    assert s.metrics.pack_width == 1  # the ordered fallback engaged
    batches = -(-n_pods // K)
    assert _steps(s) == (n_pods, batches * K - n_pods)
    recs = [r for r in s.flight.records() if r.get("pods")]
    assert [r["scan_steps"] for r in recs] == [r["pods"] for r in recs]
    assert sum(r["scan_steps"] for r in recs) == n_pods
    oracle = FullOracleScheduler(
        nodes, pct=100, seed=prof.tie_break_seed,
        hard_pod_affinity_weight=prof.hard_pod_affinity_weight, batch_size=K,
    )
    want = {d.pod.name: d.node for d in oracle.run([copy.deepcopy(p) for p in pods])}
    assert got == want and all(got.values())


def test_served_chunked_batch_stops_at_its_last_chunk():
    """5 pods in a 16-row batch at chunk 8: one step run, the other skipped."""
    s = TPUScheduler(
        profile=fit_only_profile(), batch_size=K, chunk_size=8, enable_preemption=False
    )
    for node in _one_zone_nodes():
        s.add_node(node)
    for i in range(5):
        s.add_pod(make_pod(f"p{i}").req({"cpu": "250m"}).obj())
    assert all(o.node_name for o in s.schedule_all_pending())
    assert _steps(s) == (1, K // 8 - 1)
    assert s.flight.records()[-1]["scan_steps"] == 1


def _crowded_nodes() -> list:
    """Two nodes every pod prefers (the most room) that hold two pods each,
    and six plain ones: chunk-mates collide on `big0`, then on `big1`, and
    all but two of a chunk defer."""
    big = [
        make_node(f"big{i}").capacity({"cpu": str(64 - 16 * i), "memory": "64Gi", "pods": 2})
        for i in range(2)
    ]
    rest = [
        make_node(f"n{i}").capacity({"cpu": "8", "memory": "64Gi", "pods": 110})
        for i in range(6)
    ]
    return [n.obj() for n in big + rest]


def test_served_deferrals_of_the_last_chunk_resolve_in_the_fused_tail():
    """12 pods at chunk 8 are two steps; rows 8..11 collide on `big1`, two of
    them defer, and the fused tail (which stops where the walk stopped)
    still places them: every pod bound and no host tail dispatched."""
    s = TPUScheduler(
        profile=registered_subset(DEFAULT_PROFILE), batch_size=K, chunk_size=8,
        enable_preemption=False,
    )
    for node in _crowded_nodes():
        s.add_node(node)
    for i in range(12):
        s.add_pod(make_pod(f"p{i:02d}").req({"cpu": "1", "memory": "1Gi"}).obj())
    got = {o.pod.name: o.node_name for o in s.schedule_all_pending()}
    assert len(got) == 12 and all(got.values())
    on = list(got.values())
    assert on.count("big0") == 2 and on.count("big1") == 2
    assert sorted(n for p, n in got.items() if p >= "p08").count("big1") == 2
    assert _steps(s) == (2, 0)
    assert int(s._dispatch_counter.get(kind="tail")) == 0


# -- (2) the program ----------------------------------------------------------

R = 2 * K  # rows of a program-level batch: four chunks of 8
KINDS = ["affinity", "node_axis_only"]


@functools.lru_cache(maxsize=None)
def _scenario(kind: str = "affinity"):
    """K - 1 pods that fit and, in row K - 1, one that fits nowhere (it
    commits nothing and defers nobody, so a batch that holds it in its last
    row is a walk of all k // c steps of the same step over the same rows);
    featurized once into R rows so that the last row is the featurizer's
    own padding.  `affinity`: half the pods carry a required term, so the
    domain tables are in play and chunk-mates defer.  `node_axis_only`:
    plain pods on `_crowded_nodes`, whose chunked program holds the fused
    tail and the uniform all-fail `cond` and whose chunk-mates collide."""
    s = TPUScheduler(
        profile=registered_subset(DEFAULT_PROFILE), batch_size=R,
        enable_preemption=False,
    )
    if kind == "affinity":
        nodes = [
            make_node(f"n{i}")
            .capacity({"cpu": str(4 + i), "memory": "64Gi", "pods": 110})
            .zone(f"z{i % 3}")
            .obj()
            for i in range(12)
        ]
        pods = [
            (
                make_pod(f"p{i}").req({"cpu": "250m", "memory": "256Mi"})
                .label("color", f"c{i % 3}")
                .pod_affinity_in("color", [f"c{i % 3}"], ZONE)
                if i % 2 == 0
                else make_pod(f"p{i}").req({"cpu": "500m", "memory": "1Gi"})
                .label("app", f"a{i % 4}")
            ).obj()
            for i in range(K - 1)
        ]
    else:
        nodes = _crowded_nodes()
        pods = [
            make_pod(f"p{i}").req({"cpu": "1", "memory": "1Gi"}).obj()
            for i in range(K - 1)
        ]
    for node in nodes:
        s.add_node(node)
    pods.append(make_pod("giant").req({"cpu": "1000"}).obj())
    base, _deltas, active = build_pod_batch(pods, s.builder, s.profile, R)
    base["nominated_row"] = np.full(R, -1, np.int32)
    return s, s.builder.state(), s._full_inv(), base, active


def _batch(base: dict, rows: int, valid_rows, full: bool = False) -> dict:
    """`rows` rows of `base`: those in `valid_rows` as they are, the rest
    padding; `full` puts the pod that fits nowhere into the last row."""
    out = {}
    for key, arr in base.items():
        cut = np.repeat(np.array(arr[-1:]), rows, axis=0)
        cut[list(valid_rows)] = arr[list(valid_rows)]
        if full:
            cut[rows - 1] = arr[K - 1]
        out[key] = cut
    keep = np.zeros(rows, np.bool_)
    keep[list(valid_rows) + ([rows - 1] if full else [])] = True
    assert (out["valid"] == keep).all()
    return out


def _program(kind: str, chunk: int):
    s, _state0, _inv, _base, active = _scenario(kind)
    return s.passes.get(
        s.profile, s.builder.schema, s.builder.res_col, active, chunk, carry_dom=True
    )


def _run(batch, state=None, dom=None, chunk=1, kind="affinity", uniform=False):
    s, state0, inv, _base, _active = _scenario(kind)
    dom_in = dom if dom is not None else s._dom_placeholder()
    st, out, dom_out = _program(kind, chunk)(
        state0 if state is None else state,
        dict(batch, uniform_all=np.bool_(uniform)), inv, SEED,
        dom_in[0], dom_in[1], np.bool_(dom is not None),
    )
    return st, jax.tree_util.tree_map(np.asarray, out), dom_out


def _same_state(a, b) -> None:
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name)), f.name
        )


def _same_dom(a, b) -> None:
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


FIELDS = ("picks", "scores", "feasible_counts", "processed", "fail_masks")
ROWS = {
    "empty": [],
    "one": [0],
    "prefix": list(range(5)),
    "hole": [0, 1, 2, 6],
    "one_chunk": list(range(8)),
    "one_over": list(range(9)),
    "hole_across": [0, 1, 12],
    "all_that_fit": list(range(K - 1)),
}


@pytest.mark.parametrize("case", list(ROWS))
@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("kind", KINDS)
def test_trip_count_is_one_past_the_last_valid_row(kind, chunk, case):
    """`ceil(n / c)` steps for a batch whose last valid row is n - 1, and
    everything the pass returns (results, the carried state, the domain
    tables) bit-identical to a walk of all R // c steps."""
    rows = ROWS[case]
    n = max(rows) + 1 if rows else 0
    steps = -(-n // chunk)
    _s, state0, _inv, base, _active = _scenario(kind)
    st, out, dom = _run(_batch(base, R, rows), chunk=chunk, kind=kind)
    assert int(out.scan_steps) == steps
    st_full, full, dom_full = _run(_batch(base, R, rows, full=True), chunk=chunk, kind=kind)
    assert int(full.scan_steps) == R // chunk and full.picks[R - 1] == -1
    # Every field over the rows both walked; past them the full walk ran
    # padded steps, of which the host reads picks and processed alone.
    for name in FIELDS:
        upto = R - 1 if name in ("picks", "processed") else steps * chunk
        np.testing.assert_array_equal(
            getattr(out, name)[:upto], getattr(full, name)[:upto], name
        )
    _same_state(st, st_full)
    _same_dom(dom, dom_full)
    # What the host reads of a row that holds no pod, inside the walk (a
    # hole, the rest of the last chunk: the step ran) and past it (the
    # preallocated row).
    pad = np.ones(R, np.bool_)
    pad[rows] = False
    assert (out.picks[pad] == -1).all() and (out.processed[pad] == 0).all()
    for name in ("scores", "feasible_counts", "fail_masks"):
        assert (getattr(out, name)[steps * chunk :] == 0).all(), name
    # A pod is placed or, by a chunked program, deferred to the host's
    # strict tail (chunk-mates that collide again in the fused tail too).
    assert (out.picks[rows] >= (0 if chunk == 1 else -2)).all()
    assert (out.picks[rows] != -1).all()
    if not rows:
        _same_state(st, state0)  # zero steps: nothing committed


def test_fused_tail_resolves_the_deferrals_of_the_last_valid_chunk():
    """12 plain pods at chunk 8: rows 8..11 collide on `big1` (row 1), two of
    them defer, and the tail, which walks the same two steps, places them."""
    _s, _state0, _inv, base, _active = _scenario("node_axis_only")
    _st, out, _dom = _run(_batch(base, R, range(12)), chunk=8, kind="node_axis_only")
    assert int(out.scan_steps) == 2
    last = out.picks[8:12]
    assert (last >= 0).all() and (last == 1).sum() == 2
    assert (out.picks[:8] >= 0).all() and (out.picks[:8] == 0).sum() == 2


def test_uniform_all_fail_answers_without_a_step():
    """The template-batch shortcut in the other branch of the `cond`: five
    copies of the pod that fits nowhere, flagged uniform, are answered by
    one evaluation (0 steps) with what the walk answers."""
    _s, state0, _inv, base, _active = _scenario("node_axis_only")
    batch = _batch(base, R, [])
    for key, arr in batch.items():
        arr[:5] = base[key][K - 1]
    st, out, _dom = _run(batch, chunk=8, kind="node_axis_only", uniform=True)
    st_walk, walk, _dom = _run(batch, chunk=8, kind="node_axis_only")
    assert int(out.scan_steps) == 0 and int(walk.scan_steps) == 1
    for name in FIELDS:  # what the host reads of a pod without a node
        upto = R if name in ("picks", "processed") else 5
        if name != "scores":
            np.testing.assert_array_equal(
                getattr(out, name)[:upto], getattr(walk, name)[:upto], name
            )
    assert (out.picks == -1).all() and (out.fail_masks[:5] != 0).all()
    assert (out.fail_masks[5:] == 0).all()
    _same_state(st, state0)
    _same_state(st_walk, state0)


def test_full_batch_matches_one_pod_a_pass():
    """K rows in one ordered pass = K one-row passes (the sequential oracle
    of the program: state and DomTables threaded by hand, the pod's
    tie-break seed riding its row index)."""
    _s, _state0, _inv, base, _active = _scenario()
    st, out, dom = _run(_batch(base, K, range(K)))
    assert int(out.scan_steps) == K
    st1, dom1, picks, scores = None, None, [], []
    for r in range(K):
        one = {key: np.array(arr[r : r + 1]) for key, arr in base.items()}
        one["step_offset"] = np.array([r], np.int32)
        st1, row, dom1 = _run(one, st1, dom1)
        assert int(row.scan_steps) == 1
        picks.append(int(row.picks[0]))
        scores.append(int(row.scores[0]))
    assert out.picks.tolist() == picks and out.scores.tolist() == scores
    _same_state(st, st1)
    _same_dom(dom, dom1)


# -- (3) truncated mode -------------------------------------------------------


def test_truncated_mode_rotates_only_for_the_rows_it_holds():
    """Parity mode (asserted chunk 1), batches of 10 and of 64 + 6 in a
    64-row shape: `processed` and the rotating start after each short batch
    are the scalar oracle's, pod for pod."""
    nodes = parity_nodes(150, zones=3)
    prof = replace(fit_only_profile(), percentage_of_nodes_to_score=40)
    s = TPUScheduler(profile=prof, batch_size=64, chunk_size=1, enable_preemption=False)
    for node in nodes:
        s.add_node(node)
    oracle = OracleScheduler(nodes, pct=40, seed=prof.tie_break_seed)
    got, want = {}, {}
    for lo, hi in ((0, 10), (10, 80)):
        for i in range(lo, hi):
            s.add_pod(parity_pod(i))
        got.update({o.pod.name: o.node_name for o in s.schedule_all_pending()})
        want.update({f"pod-{i}": oracle.schedule(parity_pod(i)) for i in range(lo, hi)})
        assert s._next_start == oracle.start != 0
    assert got == want
    assert _steps(s) == (80, 3 * 64 - 80)


# -- (4) one driver in every program -------------------------------------------


def _primitives(jaxpr, names: list) -> list:
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, names)
    return names


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("kind", KINDS)
def test_every_program_holds_the_one_driver_and_no_scan(kind, chunk):
    """A `while` per place a scan stood, and no `scan`: one for the walk
    and, in basic_5kn's program (node-axis-only, chunked), one more for
    the fused tail, with the walk under the uniform all-fail `cond` and a
    `cond` a tail chunk."""
    s, state0, inv, base, _active = _scenario(kind)
    dom = s._dom_placeholder()
    closed = jax.make_jaxpr(_program(kind, chunk))(
        state0, dict(_batch(base, R, range(3)), uniform_all=np.bool_(False)),
        inv, SEED, dom[0], dom[1], np.bool_(False),
    )
    prims = _primitives(closed.jaxpr, [])
    fused = kind == "node_axis_only" and chunk > 1
    assert "scan" not in prims
    assert prims.count("while") == (2 if fused else 1)
    if fused:
        assert prims.count("cond") >= 2
    src = inspect.getsource(pass_.build_pass)
    assert "lax.scan" not in src and src.count("lax.fori_loop") == 1
