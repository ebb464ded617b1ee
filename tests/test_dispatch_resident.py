"""A dispatch ships what changed since the last batch (ISSUE 34).

`TPUScheduler._dispatch_pass` keeps the inputs of the pass that did not
change on the device (`builder.resident`): the batch invariants, a uniform
batch's broadcast feature arrays, the constants of shape and the `valid`
mask of each pod count.  Held here, on the CPU at small shapes: (a) a run
with the mechanism engaged binds, scores and walks exactly as the same run
with every entry dropped before each dispatch; (b) the counter says
everything crossed on the first dispatch and only the per-batch leaves from
the second; (c) every key misses when it must, and the batch that made it
miss is still decided as with nothing resident; (d) a pass never writes an
array it was handed."""

import jax
import numpy as np
import pytest

from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.faults import FaultPlan
from kubernetes_tpu.framework.config import DEFAULT_PROFILE
from kubernetes_tpu.ops.common import registered_subset
from kubernetes_tpu.scheduler import TPUScheduler

ZONE = "topology.kubernetes.io/zone"
K = 16
# the top-level keys of the one device_put a dispatch makes
SHIP_KEYS = {"inv", "const", "valid", "small", "batch", "step_offset", "nominated_row"}


def _sched(**kw) -> TPUScheduler:
    kw.setdefault("batch_size", K)
    kw.setdefault("chunk_size", 4)
    return TPUScheduler(**kw)


def _nodes(s: TPUScheduler, n: int = 12, cpu: str = "8") -> None:
    for i in range(n):
        s.add_node(
            make_node(f"n{i:02d}")
            .capacity({"cpu": cpu, "memory": "32Gi", "pods": 110})
            .zone(f"z{i % 3}")
            .obj()
        )


def _pod(name: str, cpu: str = "100m"):
    return make_pod(name).req({"cpu": cpu, "memory": "64Mi"})


def _drop_before_every_dispatch(s: TPUScheduler) -> None:
    """The reference: nothing is ever resident when a dispatch starts."""
    inner = s._dispatch_pass

    def dispatch(infos, profile, work):
        s.builder.resident.clear()
        return inner(infos, profile, work)

    s._dispatch_pass = dispatch


def _drain(s: TPUScheduler) -> list:
    return [
        (o.pod.name, o.node_name, o.score, o.nominated_node)
        for o in s.schedule_all_pending(wait_backoff=True)
    ]


def _records(s: TPUScheduler) -> list[dict]:
    return [r for r in s.flight.records() if r["kind"] == "batch"]


def _counts(s: TPUScheduler) -> tuple[int, int]:
    c = s._pass_inputs_counter
    return int(c.get(kind="shipped")), int(c.get(kind="resident"))


@pytest.fixture
def shipped(monkeypatch):
    """The key sets of the dispatches' device_put calls, in order."""
    seen: list[set] = []
    real = jax.device_put

    def spy(x, *a, **kw):
        if isinstance(x, dict) and x and set(x) <= SHIP_KEYS:
            seen.append(set(x))
        return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", spy)
    return seen


# -- (a) engaged == dropped ---------------------------------------------------


@pytest.mark.parametrize("counts", [(1, 1, 1), (5, 5, 3), (K, K, K), (1, K, 7, K, 2)],
                         ids=["one_pod", "short", "full_batch", "mixed"])
def test_uniform_batches_bind_score_and_walk_as_with_nothing_resident(counts):
    runs = []
    for drop in (False, True):
        s = _sched()
        _nodes(s)
        if drop:
            _drop_before_every_dispatch(s)
        out, i = [], 0
        for n in counts:
            for _ in range(n):
                s.add_pod(_pod(f"p{i}").obj())
                i += 1
            out.append(_drain(s))
        recs = _records(s)
        assert [r["pods"] for r in recs] == list(counts)
        runs.append((out, [r["scan_steps"] for r in recs],
                     [r["inputs_shipped"] for r in recs]))
    (out_e, steps_e, shipped_e), (out_d, steps_d, shipped_d) = runs
    assert out_e == out_d
    assert steps_e == steps_d
    assert all(node for batch in out_e for _, node, _, _ in batch)
    # the mechanism engaged in one run and not in the other
    assert all(n == shipped_d[0] for n in shipped_d)
    assert shipped_e[0] == shipped_d[0] and all(n <= 2 for n in shipped_e[1:])


# -- (b) the counter ----------------------------------------------------------


def test_counter_everything_on_the_first_dispatch_then_the_per_batch_leaves(shipped):
    s = _sched()
    _nodes(s)
    for i in range(3):
        s.add_pod(_pod(f"a{i}").obj())
    _drain(s)
    first_shipped, first_resident = _counts(s)
    assert first_resident == 0 and first_shipped > 20
    assert shipped == [{"inv", "const", "valid", "small"}]
    rec = _records(s)[-1]
    assert rec["inputs_shipped"] == first_shipped
    assert rec["inputs_shipped_bytes"] > K  # the mask alone is K bytes
    # same pod count again: the cycle counter is all that crosses
    for i in range(3):
        s.add_pod(_pod(f"b{i}").obj())
    _drain(s)
    assert _counts(s) == (first_shipped + 1, first_shipped - 1)
    rec = _records(s)[-1]
    assert (rec["inputs_shipped"], rec["inputs_shipped_bytes"]) == (1, 4)
    assert shipped == [{"inv", "const", "valid", "small"}]  # no second put at all
    # a pod count not seen before: its mask crosses once, then stays
    for n in (5, 5):
        for i in range(n):
            s.add_pod(_pod(f"c{n}-{len(_records(s))}-{i}").obj())
        _drain(s)
    assert [(r["inputs_shipped"], r["inputs_shipped_bytes"]) for r in _records(s)[-2:]] == [
        (2, 4 + K), (1, 4)]
    assert shipped[1:] == [{"valid"}]
    assert set(s.builder.resident["valid"]) == {3, 5}


def test_the_masks_kept_are_bounded():
    s = _sched(batch_size=256, chunk_size=4)
    _nodes(s, n=40, cpu="64")
    i = 0
    for n in range(1, 140):
        for _ in range(n):
            s.add_pod(_pod(f"p{i}").obj())
            i += 1
        _drain(s)
    masks = s.builder.resident["valid"]
    assert 0 < len(masks) <= 128 and 139 in masks


# -- (c) each key misses when it must -----------------------------------------


def _steady(s: TPUScheduler, tag: str) -> None:
    """Two uniform batches of three pods: the second ships nothing."""
    for b in range(2):
        for i in range(3):
            s.add_pod(_pod(f"{tag}{b}-{i}").obj())
        _drain(s)


def _second_template(s):
    for i in range(3):
        s.add_pod(_pod(f"big{i}", cpu="250m").obj())


def _new_taint(s):
    s.add_node(
        make_node("tainted").capacity({"cpu": "8", "memory": "32Gi", "pods": 110})
        .taint("dedicated", "db").obj()
    )
    for i in range(3):
        s.add_pod(_pod(f"t{i}").obj())


def _new_term(s):
    for i in range(3):
        s.add_pod(_pod(f"aff{i}").label("app", "web")
                  .pod_affinity_in("app", ["web"], ZONE).obj())


def _new_resource_column(s):
    s.add_node(
        make_node("gpu").capacity(
            {"cpu": "8", "memory": "32Gi", "pods": 110,
             **{f"example.com/widget{j}": "4" for j in range(9)}}
        ).obj()
    )
    for i in range(3):
        s.add_pod(_pod(f"r{i}").obj())


def _two_templates_in_one_batch(s):
    for i in range(3):
        s.add_pod(_pod(f"m{i}", cpu="100m" if i % 2 else "300m").obj())


CASES = {
    # name: (what disturbs, keys the next dispatch must ship, keys it must not)
    "second_template": (_second_template, {"small"}, {"inv", "const", "batch"}),
    "vocabulary_growth_taint": (_new_taint, {"small"}, {"const", "batch"}),
    "vocabulary_growth_term": (_new_term, {"inv"}, {"const"}),
    "schema_growth_resource_column": (_new_resource_column, {"inv", "small"}, {"const"}),
    "non_uniform_batch": (_two_templates_in_one_batch, {"batch"}, {"inv", "const", "small"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_key_misses_when_it_must_and_the_batch_is_decided_the_same(case, shipped):
    disturb, must, must_not = CASES[case]
    outs = []
    for drop in (False, True):
        s = _sched()
        _nodes(s)
        if drop:
            _drop_before_every_dispatch(s)
        _steady(s, "w")
        if not drop:
            del shipped[:]
            held = dict(s.builder.resident)
        schema = s.builder.schema
        disturb(s)
        out = _drain(s)
        if case.startswith("schema_growth"):
            assert s.builder.schema != schema
        assert out and all(node for _, node, _, _ in out)
        outs.append(out)
        if not drop:
            assert len(shipped) == 1, shipped
            assert must <= shipped[0] and not (must_not & shipped[0]), shipped
            for slot in ("inv", "uniform", "const"):
                replaced = s.builder.resident[slot] is not held[slot]
                expect = {"inv": "inv" in must, "uniform": "small" in must,
                          "const": False}[slot]
                assert replaced == expect, slot
            # and the next batch of the old template rides what is there
            del shipped[:]
            for i in range(3):
                s.add_pod(_pod(f"again{i}").obj())
            _drain(s)
            if case == "second_template":
                # one entry: the last template's, so the old one ships again
                assert shipped == [{"small"}]
            elif case == "non_uniform_batch":
                assert shipped == []  # it never touched the template's entry
            # and once more: nothing
            del shipped[:]
            for i in range(3):
                s.add_pod(_pod(f"again2-{i}").obj())
            _drain(s)
            assert shipped == []
    assert outs[0] == outs[1]


def test_a_nominated_pod_takes_the_whole_path_and_its_node_is_honoured(shipped):
    outs = []
    for drop in (False, True):
        # as the sidecar serves: a preemptor waits for its node a batch
        s = _sched(batch_size=4, chunk_size=2, inline_preempt_commit=False)
        s.add_node(make_node("n0").capacity({"cpu": "1", "pods": 110}).obj())
        s.add_node(make_node("n1").capacity({"cpu": "2", "pods": 110}).obj())
        if drop:
            _drop_before_every_dispatch(s)
        s.add_pod(make_pod("victim").req({"cpu": "2"}).priority(1).node("n1").obj())
        for b in range(2):
            s.add_pod(make_pod(f"f{b}").req({"cpu": "100m"}).priority(1).obj())
            assert _drain(s)[0][1] == "n0"
        held = s.builder.resident.get("inv")
        s.add_pod(make_pod("vip").req({"cpu": "2"}).priority(100).obj())
        out1 = s.schedule_batch()  # vip fails, preempts, nominates n1
        assert out1[0].nominated_node == "n1" and "default/vip" in s.nominator
        del shipped[:]
        s.add_pod(make_pod("sneak").req({"cpu": "2"}).priority(1).obj())
        out = _drain(s)
        landed = {name: node for name, node, _, _ in out if node}
        assert landed.get("vip") == "n1" and "sneak" not in landed
        outs.append(out)
        if not drop:
            # the overlay is not the constant: invariants and the nominated
            # rows cross whole, and nothing of them is kept
            assert {"inv", "nominated_row"} <= shipped[0], shipped
            assert s.builder.resident.get("inv") is held
            assert "default/vip" not in s.nominator
    assert outs[0] == outs[1]


def test_a_packed_batch_ships_its_own_offsets(shipped):
    colors = [0, 0, 0] + list(range(1, 14))
    outs = []
    for drop in (False, True):
        s = TPUScheduler(profile=registered_subset(DEFAULT_PROFILE), batch_size=K,
                         chunk_size=8, enable_preemption=False)
        for i in range(24):
            s.add_node(make_node(f"n{i}").capacity({"cpu": "4", "memory": "16Gi", "pods": 8})
                       .zone(f"z{i % 4}").obj())
        if drop:
            _drop_before_every_dispatch(s)
        _steady(s, "w")
        const = s.builder.resident.get("const")
        del shipped[:]
        for i, color in enumerate(colors):
            s.add_pod(make_pod(f"p{i}").req({"cpu": "100m"}).label("color", f"c{color}")
                      .pod_anti_affinity_in("color", [f"c{color}"], ZONE).obj())
        outs.append(_drain(s))
        assert s.metrics.packed_batches >= 1
        if not drop:
            assert "step_offset" in shipped[0] and "const" not in shipped[0], shipped
            assert s.builder.resident["const"] is const
    assert outs[0] == outs[1]


def test_rebuild_device_state_after_an_engine_fault_drops_every_entry(shipped):
    s = _sched()
    _nodes(s)
    _steady(s, "w")
    assert {"inv", "uniform", "const", "valid"} <= set(s.builder.resident)
    FaultPlan().add_rule("engine", nth=1).install_engine(s)
    del shipped[:]
    for i in range(3):
        s.add_pod(_pod(f"x{i}").obj())
    out = _drain(s)
    assert len(out) == 3 and all(node for _, node, _, _ in out)
    assert int(s.metrics.registry.counter("scheduler_engine_faults_total").total()) == 1
    # the first dispatch after the recovery's rebuild_device_state() found
    # nothing resident and shipped everything, as a first dispatch does
    assert shipped[0] == {"inv", "const", "valid", "small"}
    s.builder.resident["marker"] = ("k", None)
    s.rebuild_device_state()
    assert s.builder.resident == {}


def test_set_mesh_drops_every_entry_and_a_sharded_run_binds_the_same():
    from kubernetes_tpu.parallel.mesh import make_mesh

    outs = []
    for mesh in (None, make_mesh(8)):
        s = _sched(mesh=mesh)
        _nodes(s, n=16)
        out = []
        for b, n in enumerate((3, 3, K, 3)):
            for i in range(n):
                s.add_pod(_pod(f"p{b}-{i}").obj())
            out.append(_drain(s))
        outs.append(out)
        assert [r["inputs_shipped"] for r in _records(s)][1:] == [1, 2, 1]
    assert outs[0] == outs[1]
    assert s.builder.resident
    s.builder.set_mesh(make_mesh(8))
    assert s.builder.resident == {}


# -- (d) a pass never writes what it was handed -------------------------------


def test_resident_arrays_read_back_equal_after_three_dispatches():
    s = _sched()
    _nodes(s)
    for i in range(3):
        s.add_pod(_pod(f"a{i}").obj())
    _drain(s)
    res = s.builder.resident
    held = {
        "inv": res["inv"][2], "uniform": res["uniform"][1],
        "const": res["const"], "valid": dict(res["valid"]),
    }
    before = jax.tree_util.tree_map(np.array, held)
    for b in range(3):
        for i in range(3):
            s.add_pod(_pod(f"b{b}-{i}").obj())
        _drain(s)
    assert res["inv"][2] is held["inv"] and res["uniform"][1] is held["uniform"]
    after = jax.tree_util.tree_map(np.array, held)
    flat_b, tree_b = jax.tree_util.tree_flatten(before)
    flat_a, tree_a = jax.tree_util.tree_flatten(after)
    assert tree_a == tree_b and len(flat_a) > 25
    for x, y in zip(flat_b, flat_a):
        assert x.dtype == y.dtype and np.array_equal(x, y)
