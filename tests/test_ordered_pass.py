"""The strictly ordered (chunk-1) pass stops at its last pod (ISSUE 30).

`build_pass(chunk=1)` drives its `step` with a loop whose trip count is read
on the device from the batch's `valid` (one past the last True), and reports
the steps it ran (`PassResult.scan_steps` → `scheduler_pass_scan_steps_total`
and the flight record's `scan_steps`).  A chunked pass keeps its `lax.scan`
over all `k // chunk` steps.  Held here: the served path (a one-colour
required-affinity batch at `chunk_size` 8 falls back to the ordered program)
against the sequential oracle, the program itself for empty / one / full / a
hole in `valid` against a full-length run of the same step, truncated mode's
rotating start, and the shape of the chunked program.  Small shapes, CPU."""

import copy
import dataclasses
import functools
from dataclasses import replace

import jax
import numpy as np
import pytest
from oracle_full import FullOracleScheduler
from test_parity import OracleScheduler
from test_parity import _nodes as parity_nodes
from test_parity import _pod as parity_pod

from kubernetes_tpu.api.wrappers import make_node, make_pod
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


def test_served_chunked_pass_reports_its_shape():
    """A chunked pass runs k // c steps whatever it holds, and skips none."""
    s = TPUScheduler(
        profile=fit_only_profile(), batch_size=K, chunk_size=8, enable_preemption=False
    )
    for node in _one_zone_nodes():
        s.add_node(node)
    for i in range(5):
        s.add_pod(make_pod(f"p{i}").req({"cpu": "250m"}).obj())
    assert all(o.node_name for o in s.schedule_all_pending())
    assert _steps(s) == (K // 8, 0)
    assert s.flight.records()[-1]["scan_steps"] == K // 8


# -- (2) the program ----------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _scenario():
    """K - 1 pods that fit and, in the last row, one that fits nowhere (it
    commits nothing, so a run that reaches it is a full-length run of the
    same step over the same rows); featurized once into 2K rows so that the
    batch's last row is the featurizer's own padding."""
    s = TPUScheduler(
        profile=registered_subset(DEFAULT_PROFILE), batch_size=2 * K,
        enable_preemption=False,
    )
    for i in range(12):
        s.add_node(
            make_node(f"n{i}")
            .capacity({"cpu": str(4 + i), "memory": "64Gi", "pods": 110})
            .zone(f"z{i % 3}")
            .obj()
        )
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
    ] + [make_pod("giant").req({"cpu": "1000"}).obj()]
    base, _deltas, active = build_pod_batch(pods, s.builder, s.profile, 2 * K)
    base["nominated_row"] = np.full(2 * K, -1, np.int32)
    return s, s.builder.state(), s._full_inv(), base, active


def _batch(base: dict, rows: int, valid_rows) -> dict:
    """`rows` rows of `base`; those outside `valid_rows` become padding."""
    keep = np.zeros(rows, np.bool_)
    keep[list(valid_rows)] = True
    out = {}
    for key, arr in base.items():
        cut = np.array(arr[:rows])
        cut[~keep] = arr[-1]
        out[key] = cut
    assert (out["valid"] == keep).all()
    return out


def _run(batch: dict, state=None, dom=None, chunk: int = 1):
    s, state0, inv, _base, active = _scenario()
    run = s.passes.get(
        s.profile, s.builder.schema, s.builder.res_col, active, chunk, carry_dom=True
    )
    dom_in = dom if dom is not None else s._dom_placeholder()
    st, out, dom_out = run(
        state0 if state is None else state, batch, inv, SEED,
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


ROWS = {
    "empty": [],
    "one": [0],
    "prefix": list(range(5)),
    "hole": [0, 1, 2, 6],
    "all_that_fit": list(range(K - 1)),
}


@pytest.mark.parametrize("case", list(ROWS))
def test_trip_count_is_one_past_the_last_valid_row(case):
    rows = ROWS[case]
    n = max(rows) + 1 if rows else 0
    _s, state0, _inv, base, _active = _scenario()
    st, out, dom = _run(_batch(base, K, rows))
    assert int(out.scan_steps) == n
    # The same rows and, in the last one, the pod that fits nowhere: the
    # loop runs all K steps, the full-length run of the same step.
    st_full, full, dom_full = _run(_batch(base, K, rows + [K - 1]))
    assert int(full.scan_steps) == K and full.picks[K - 1] == -1
    for name in ("picks", "scores", "feasible_counts", "processed", "fail_masks"):
        np.testing.assert_array_equal(
            getattr(out, name)[:n], getattr(full, name)[:n], name
        )
    _same_state(st, st_full)
    _same_dom(dom, dom_full)
    # What the host reads of a row that holds no pod, below the count (a
    # hole: the step ran) and past it (the preallocated row).
    pad = np.ones(K, np.bool_)
    pad[rows] = False
    assert (out.picks[pad] == -1).all() and (out.processed[pad] == 0).all()
    for name in ("scores", "feasible_counts", "fail_masks"):
        assert (getattr(out, name)[n:] == 0).all(), name
    assert (out.picks[rows] >= 0).all()
    if not rows:
        _same_state(st, state0)  # zero steps: nothing committed


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


# -- (4) the chunked program is the scan it was -------------------------------


def _primitives(jaxpr, names: list) -> list:
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, names)
    return names


def _pass_primitives(kind: str, chunk: int) -> list:
    s = TPUScheduler(
        profile=registered_subset(DEFAULT_PROFILE), batch_size=K,
        enable_preemption=False,
    )
    for node in _one_zone_nodes(4):
        s.add_node(node)
    if kind == "node_axis_only":
        pods = [make_pod(f"p{i}").req({"cpu": "250m"}).obj() for i in range(3)]
    else:
        pods = [_blue(i) for i in range(3)]
    batch, _deltas, active = build_pod_batch(pods, s.builder, s.profile, K)
    batch["nominated_row"] = np.full(K, -1, np.int32)
    if chunk > 1:
        batch["uniform_all"] = np.bool_(False)
    run = s.passes.get(
        s.profile, s.builder.schema, s.builder.res_col, active, chunk, carry_dom=True
    )
    dom = s._dom_placeholder()
    closed = jax.make_jaxpr(run)(
        s.builder.state(), batch, s._full_inv(), SEED, dom[0], dom[1], np.bool_(False)
    )
    return _primitives(closed.jaxpr, [])


@pytest.mark.parametrize("kind", ["node_axis_only", "affinity"])
def test_chunked_program_keeps_its_scan_and_gains_no_while(kind):
    chunked = _pass_primitives(kind, 8)
    ordered = _pass_primitives(kind, 1)
    assert "while" not in chunked
    assert ordered.count("while") == 1 and "scan" not in ordered
    if kind == "node_axis_only":
        # basic_5kn's program: the main scan under the uniform all-fail
        # cond, then the fused tail's scan with its per-chunk cond.
        assert chunked.count("scan") == 2 and chunked.count("cond") >= 2
    else:
        assert chunked.count("scan") == 1


def test_chunked_program_ships_no_step_count():
    """Its outputs are the parent's: the step count of a chunked program is
    its shape's, and the host reports it so."""
    _s, _state0, _inv, base, _active = _scenario()
    _st, out, _dom = _run(_batch(base, K, range(3)), chunk=8)
    assert out.scan_steps is None and out.picks.shape == (K,)
