"""The CSI attach budget as a per-node count (ISSUE 36; ROADMAP C13).

A claim that ONE known pod uses charges ``csi_used[driver, node]`` by one
while its pod is there and takes no row anywhere; only a claim that two or
more known pods reference is promoted into a row of the small
``csivol_counts`` table, and released when its users fall back to one.

The nine cases of the issue, each against an oracle on seeded random
clusters at toy size:

  * (i), (v), (vi), (vii), (ix) run the engine in parity mode (chunk 1)
    beside ``oracle_full.FullOracleScheduler``, decision for decision;
  * (ii), (iii), (iv) need chunks and the pipeline, where decisions that a
    binding limit orders are not the sequential oracle's by design
    (engine/pass_.py's docstring), so their clusters are built to have ONE
    outcome whatever the order: every pod is pinned to a node whose attach
    limit is exactly the number of DISTINCT volumes aimed at it, so every
    pod binds if and only if each shared claim counts once a node — and
    that is what the sequential oracle answers too;
  * (viii) rebuilds the counts through a checkpoint, ``recover`` and replay.
"""

import copy
import random
from dataclasses import replace

import pytest

from kubernetes_tpu.api import types as t
from kubernetes_tpu.api.wrappers import make_node, make_pod, make_pv, make_pvc
from kubernetes_tpu.framework.config import DEFAULT_PROFILE, Profile
from kubernetes_tpu.framework.tracing import PROCESS
from kubernetes_tpu.ops.common import registered_subset
from kubernetes_tpu.scheduler import TPUScheduler

from oracle_full import FullOracleScheduler, RefVolumes

DRV = "ebs.csi.aws.com"
SEEDS = (1, 2, 3)


def _parity_profile() -> Profile:
    return replace(registered_subset(DEFAULT_PROFILE), percentage_of_nodes_to_score=None)


def _nodes(n: int, limit, cpu: str = "16"):
    nodes, csinodes = [], []
    for i in range(n):
        nodes.append(
            make_node(f"node-{i:03d}").capacity({"cpu": cpu, "memory": "64Gi", "pods": 64})
            .zone(f"zone-{i % 3}").label("slot", f"s{i}").obj()
        )
        lim = limit[i] if isinstance(limit, (list, tuple)) else limit
        csinodes.append(t.CSINode(name=nodes[-1].name, driver_limits={DRV: lim}))
    return nodes, csinodes


def _claim(name: str):
    """A claim and its volume, bound both ways (the row's shape)."""
    pv = make_pv(f"pv-{name}", csi_driver=DRV, access_modes=(t.RWX,))
    pv.claim_ref = f"default/{name}"
    return pv, make_pvc(name, volume_name=pv.name, access_modes=(t.RWX,))


def _pod(name: str, claims, rng=None, priority: int = 0, slot=None):
    cpu = f"{rng.choice((100, 200, 300, 500))}m" if rng else "100m"
    w = make_pod(name).req({"cpu": cpu, "memory": "128Mi"}).priority(priority)
    for c in claims:
        w = w.pvc_volume(c)
    if slot is not None:
        w = w.node_selector({"slot": slot})
    return w.obj()


def _engine(nodes, csinodes, pvs, pvcs, profile=None, **kw) -> TPUScheduler:
    s = TPUScheduler(profile=profile or _parity_profile(), **kw)
    # one requeue alignment for the A/B, as test_parity_default pins it
    s._prefetch_enabled = kw.get("pipeline_depth", 1) >= 2
    for n in nodes:
        s.add_node(n)
    for cn in csinodes:
        s.add_csinode(cn)
    for pv in pvs:
        s.add_pv(copy.deepcopy(pv))
    for pvc in pvcs:
        s.add_pvc(copy.deepcopy(pvc))
    return s


def _oracle(nodes, csinodes, pvs, pvcs, batch_size: int) -> FullOracleScheduler:
    prof = _parity_profile()
    return FullOracleScheduler(
        nodes, pct=None, seed=prof.tie_break_seed,
        hard_pod_affinity_weight=prof.hard_pod_affinity_weight, batch_size=batch_size,
        vols=RefVolumes(pvs=copy.deepcopy(pvs), pvcs=copy.deepcopy(pvcs), csinodes=copy.deepcopy(csinodes)),
    )


def _binds(out) -> dict:
    return {o.pod.name: o.node_name for o in out if o.node_name}


def _want(decisions) -> dict:
    return {d.pod.name: d.node for d in decisions if d.node}


def _settled(s: TPUScheduler) -> tuple:
    """(promoted, released) over every batch so far, as the
    `pipeline/csi_settle` spans of the flight record book them."""
    stats = [sp[4] for rec in s.flight.records() for sp in rec["spans"] if sp[0] == "pipeline/csi_settle"]
    return sum(x["promoted"] for x in stats), sum(x["released"] for x in stats)


def _distinct_per_node(s: TPUScheduler) -> dict:
    """node -> distinct claims of the driver its bound pods name: what
    csi_used has to read, computed from the cache and nothing else."""
    held: dict[str, set] = {}
    for pr in s.cache.pods.values():
        for v in pr.pod.spec.volumes:
            if v.pvc:
                held.setdefault(pr.node_name, set()).add(f"{pr.pod.namespace}/{v.pvc}")
    return {n: len(c) for n, c in held.items()}


def _assert_counts_exact(s: TPUScheduler) -> None:
    did = s.builder.interns.drivers.get(DRV)
    want = _distinct_per_node(s)
    for name, rec in s.cache.nodes.items():
        assert int(s.builder.host["csi_used"][did, rec.row]) == want.get(name, 0), name
        assert int(s.builder.host["csi_used"][did, rec.row]) <= int(s.builder.host["csi_limit"][did, rec.row])
    assert s.builder.host_mirror_equal()


def _ab(nodes, csinodes, pvs, pvcs, pending, batch_size: int = 16):
    s = _engine(nodes, csinodes, pvs, pvcs, batch_size=batch_size, chunk_size=1)
    o = _oracle(nodes, csinodes, pvs, pvcs, batch_size)
    for p in pending:
        s.add_pod(copy.deepcopy(p))
    got = s.schedule_all_pending(wait_backoff=True)
    want = o.run([copy.deepcopy(p) for p in pending], prefetch=False)
    return s, o, got, want


# -- (i) a claim of its own a pod, the limit reached ---------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_own_claims_fill_the_budget_and_the_rest_go_elsewhere_or_fail_as_the_oracles_do(seed):
    rng = random.Random(seed)
    nodes, csinodes = _nodes(5, 3)
    pvs, pvcs, pending = [], [], []
    for i in range(20):  # 15 volumes fit
        pv, pvc = _claim(f"own-{i}")
        pvs.append(pv)
        pvcs.append(pvc)
        pending.append(_pod(f"p-{i:02d}", [f"own-{i}"], rng))
    s, _o, got, want = _ab(nodes, csinodes, pvs, pvcs, pending)
    assert _binds(got) == _want(want)
    assert len(_want(want)) == 15  # the limit bites: five pods stay pending on both sides
    _assert_counts_exact(s)
    did = s.builder.interns.drivers.get(DRV)
    assert int(s.builder.host["csi_used"][did].max()) == 3
    # no claim took a row: each is one of its node's count
    assert s.builder.csi_rows == {} and not s.builder.host["csivol_counts"].any()
    assert s.builder.csi_claim_counts() == (20, 0)
    assert _settled(s) == (0, 0)


# -- (v) a shared claim counts once a node, (vi) and is given back with its last user


def _shared_fixture(seed: int):
    rng = random.Random(seed)
    nodes, csinodes = _nodes(4, 2)
    pvs, pvcs, pending = [], [], []
    for g in range(3):
        pv, pvc = _claim(f"shared-{g}")
        pvs.append(pv)
        pvcs.append(pvc)
    for i in range(5):
        pv, pvc = _claim(f"own-{i}")
        pvs.append(pv)
        pvcs.append(pvc)
    for i in range(12):
        pending.append(_pod(f"s-{i:02d}", [f"shared-{i % 3}"], rng))
    for i in range(5):
        pending.append(_pod(f"o-{i:02d}", [f"own-{i}"], rng))
    rng.shuffle(pending)
    return nodes, csinodes, pvs, pvcs, pending


@pytest.mark.parametrize("seed", SEEDS)
def test_a_shared_claim_counts_once_on_a_node_and_once_on_each_of_two(seed):
    nodes, csinodes, pvs, pvcs, pending = _shared_fixture(seed)
    s, _o, got, want = _ab(nodes, csinodes, pvs, pvcs, pending)
    assert _binds(got) == _want(want)
    _assert_counts_exact(s)
    # the three shared claims hold rows, the five others do not
    assert sorted(s.builder.csi_rows) == [f"default/shared-{g}" for g in range(3)]
    assert s.builder.csi_claim_counts() == (5, 3) and _settled(s) == (3, 0)
    spread = {n for p, n in _binds(got).items() if p.startswith("s-")}
    assert len(spread) > 1  # the sharers landed on more than one node: once on each


@pytest.mark.parametrize("seed", SEEDS)
def test_a_deleted_pod_gives_the_budget_back_a_shared_claim_with_its_last_user_on_the_node(seed):
    nodes, csinodes, pvs, pvcs, pending = _shared_fixture(seed)
    s, o, got, want = _ab(nodes, csinodes, pvs, pvcs, pending)
    assert _binds(got) == _want(want)
    bound = _binds(got)
    for p in pending:  # the oracle does not try the first wave's leftovers again: nor shall the engine
        if p.name not in bound:
            s.delete_pod(p.uid)
    rng = random.Random(100 + seed)
    # all users of shared-0 but one, one user of shared-1, two pods with a claim of their own
    leave = [p for p in sorted(bound) if p.startswith("s-") and int(p[2:]) % 3 == 0][1:]
    leave += [p for p in sorted(bound) if p.startswith("s-") and int(p[2:]) % 3 == 1][:1]
    leave += [p for p in sorted(bound) if p.startswith("o-")][:2]
    for name in leave:
        s.delete_pod(f"default/{name}")
        for st in o.states.values():
            for p in [p for p in st.pods if p.name == name]:
                st.pods.remove(p)
                for pvc in o.vols.pod_pvcs(p):
                    o.pvc_users[pvc.uid] -= 1
    _assert_counts_exact(s)
    # what came free is what the next pods find, on both sides
    more = [_pod(f"n-{i:02d}", [f"shared-{i % 3}"], rng) for i in range(4)]
    more += [_pod(f"m-{i:02d}", [f"own-{i}"], rng) for i in range(5)]  # two claims came free with their pods
    for p in more:
        s.add_pod(copy.deepcopy(p))
    got2 = s.schedule_all_pending(wait_backoff=True)
    want2 = o.run([copy.deepcopy(p) for p in more], prefetch=False)
    assert _binds(got2) == _want(want2)
    _assert_counts_exact(s)


def test_a_claim_left_with_one_user_is_released_and_its_row_used_again():
    nodes, csinodes = _nodes(2, 2)
    (pv_a, pvc_a), (pv_b, pvc_b) = _claim("a"), _claim("b")
    s = _engine(nodes, csinodes, [pv_a, pv_b], [pvc_a, pvc_b], batch_size=8, chunk_size=1)
    for i in range(2):
        s.add_pod(_pod(f"a-{i}", ["a"]))
    assert all(o.node_name for o in s.schedule_all_pending())
    row = s.builder.csi_rows["default/a"]
    s.delete_pod("default/a-0")
    s.add_pod(_pod("b-0", ["b"]))
    s.add_pod(_pod("b-1", ["b"]))
    assert all(o.node_name for o in s.schedule_all_pending())
    # a fell back to one user: released; b took the row it left
    assert s.builder.csi_rows == {"default/b": row}
    assert _settled(s) == (2, 1)
    _assert_counts_exact(s)
    assert s.builder.schema.CV == 8  # rows are recycled, the table does not grow


def test_a_node_that_goes_takes_its_pods_claims_with_it():
    """The sharers of ``a`` sit on two nodes (pinned); one node is removed.
    Its pods leave the claim's known users, the row is released with the
    one user left, and what a returning sharer finds is exact: nothing but
    the cache's records says where a claim's users are."""
    nodes, csinodes = _nodes(3, 2)
    (pv_a, pvc_a), (pv_b, pvc_b) = _claim("a"), _claim("b")
    s = _engine(nodes, csinodes, [pv_a, pv_b], [pvc_a, pvc_b], batch_size=8, chunk_size=1)
    for name, claims, slot in (("a-0", ["a"], "s0"), ("a-1", ["a"], "s0"), ("a-2", ["a"], "s1"), ("b-0", ["b"], "s0")):
        s.add_pod(_pod(name, claims, slot=slot))
    assert len(_binds(s.schedule_all_pending())) == 4
    _assert_counts_exact(s)
    assert sorted(s.builder.csi_rows) == ["default/a"] and s.builder.csi_claim_counts() == (1, 1)
    s.remove_node("node-000")
    assert s.builder.csi_users == {"default/a": "default/a-2"}
    s.add_pod(_pod("a-3", ["a"], slot="s2"))  # two users again before the release: the row stays
    s.add_pod(_pod("b-1", ["b"], slot="s1"))
    assert len(_binds(s.schedule_all_pending())) == 2
    _assert_counts_exact(s)
    assert sorted(s.builder.csi_rows) == ["default/a"]
    s.remove_node("node-002")
    s._settle_csi_claims()
    assert s.builder.csi_rows == {} and not s.builder.host["csivol_counts"].any()
    _assert_counts_exact(s)
    assert s.builder.csi_claim_counts() == (2, 0)


def test_pods_a_fleet_owner_reserves_are_known_users_of_their_claims():
    """The propose path: a foreign pod enters past add_pod, at
    ``reserve_proposed``.  Two sharers reserved onto one node hold ONE
    volume there from the owner's next evaluation on; an aborted one
    leaves the claim to its mate."""
    nodes, csinodes = _nodes(2, 2)
    pv, pvc = _claim("a")
    s = _engine(nodes, csinodes, [pv], [pvc], batch_size=8, chunk_size=1)
    first, second, third = (_pod(f"a-{i}", ["a"]) for i in range(3))
    assert s.commit_proposed(first, "node-000") is not None
    assert s.reserve_proposed(second, "node-000")
    assert s.builder.csi_users == {"default/a": {first.uid, second.uid}}
    s._settle_csi_claims()  # what the owner's next propose starts with
    _assert_counts_exact(s)
    assert sorted(s.builder.csi_rows) == ["default/a"]
    s.abort_reserved(second.uid)
    assert s.builder.csi_users == {"default/a": first.uid}
    assert s.commit_proposed(third, "node-001") is not None
    s._settle_csi_claims()
    _assert_counts_exact(s)
    did = s.builder.interns.drivers.get(DRV)
    assert [int(x) for x in s.builder.host["csi_used"][did][:2]] == [1, 1]


# -- (ix) a claim that is missing or unbound ----------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_a_pod_whose_claim_is_missing_or_unbound_is_treated_as_the_oracle_treats_it(seed):
    rng = random.Random(seed)
    nodes, csinodes = _nodes(4, 3)
    pvs, pvcs, pending = [], [], []
    for i in range(6):
        pv, pvc = _claim(f"own-{i}")
        pvs.append(pv)
        pvcs.append(pvc)
        pending.append(_pod(f"ok-{i}", [f"own-{i}"], rng))
    pvcs.append(make_pvc("unbound-immediate"))  # no volume, no class: the PV controller's to bind
    pvcs.append(make_pvc("lost", volume_name="pv-that-is-gone"))
    pending.append(_pod("no-claim", ["never-created"], rng))
    pending.append(_pod("unbound", ["unbound-immediate"], rng))
    pending.append(_pod("lost", ["lost"], rng))
    pending.append(_pod("mixed", ["own-0", "never-created"], rng))
    rng.shuffle(pending)
    s, _o, got, want = _ab(nodes, csinodes, pvs, pvcs, pending)
    assert _binds(got) == _want(want)
    assert set(_binds(got)) == {f"ok-{i}" for i in range(6)}
    _assert_counts_exact(s)


# -- (vii) preemption's dry run gives a victim's volumes back ---------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_preemptions_dry_run_removes_a_victims_volumes_from_the_budget(seed):
    """Every node's budget is full of low-priority pods.  On some nodes two
    of them share a claim, so evicting one of the pair frees nothing: the
    victim has to be the pod with a claim of its own (or both sharers).
    One preemptor a run: with several in one batch the engine chains its
    dry runs where the oracle's nominator overlay carries no volumes, on
    the parent as here."""
    rng = random.Random(seed)
    nodes, csinodes = _nodes(3, 2)
    pvs, pvcs, bound = [], [], []
    paired = [rng.random() < 0.6 for _ in nodes]
    paired[rng.randrange(3)] = True
    for i, nd in enumerate(nodes):
        for c in (f"low-{i}-0", f"low-{i}-1", f"pair-{i}"):
            pv, pvc = _claim(c)
            pvs.append(pv)
            pvcs.append(pvc)
        names = [[f"pair-{i}"], [f"pair-{i}"], [f"low-{i}-0"]] if paired[i] else [[f"low-{i}-0"], [f"low-{i}-1"]]
        rng.shuffle(names)
        for j, claims in enumerate(names):
            p = _pod(f"low-{i}-{j}", claims, rng, priority=1)
            p.spec.node_name = nd.name
            p.status.start_time = float(rng.randrange(100))
            bound.append(p)
    pv, pvc = _claim("vip")
    pvs.append(pv)
    pvcs.append(pvc)
    vip = _pod("vip", ["vip"], rng, priority=100)
    s = _engine(nodes, csinodes, pvs, pvcs, batch_size=16, chunk_size=1)
    o = _oracle(nodes, csinodes, pvs, pvcs, 16)
    for p in bound:
        s.add_pod(copy.deepcopy(p))
        o.add_bound(copy.deepcopy(p))
    # bound sharers arrive one by one: until the rows next settle (the next
    # batch's start) the second counts again, which errs on the safe side
    s._settle_csi_claims()
    _assert_counts_exact(s)
    s.add_pod(copy.deepcopy(vip))
    got = s.schedule_all_pending(wait_backoff=True)
    want = o.run([copy.deepcopy(vip)], prefetch=False)
    assert _binds(got) == _want(want) and set(_binds(got)) == {"vip"}
    got_nom = {o_.pod.name: o_.nominated_node for o_ in got if o_.nominated_node}
    got_vic = {o_.pod.name: tuple(sorted(o_.victim_uids)) for o_ in got if o_.victim_uids}
    assert got_nom == {d.pod.name: d.nominated for d in want if d.nominated}
    assert got_vic == {d.pod.name: tuple(sorted(d.victims)) for d in want if d.victims}
    assert got_vic, "the fixture no longer forces a preemption"
    node = _binds(got)["vip"]
    if paired[int(node[-3:])]:
        # one victim, and it is not one of the pair
        (victim,) = got_vic["vip"]
        assert [v.pvc for v in next(p for p in bound if p.uid == victim).spec.volumes] == [f"low-{int(node[-3:])}-0"]
    _assert_counts_exact(s)


# -- (ii), (iii), (iv): sharers of one chunk, of two chunks, of adjacent batches -------


def _pinned_fixture(seed: int, batch: int, chunk: int):
    """Pods pinned to nodes, each node's limit exactly the number of distinct
    claims aimed at it: every pod binds iff each shared claim counts once a
    node.  Sharers are placed as neighbours (one chunk), ``chunk`` apart
    (two chunks of one batch) and ``batch`` apart (adjacent batches)."""
    rng = random.Random(seed)
    n_pods = 3 * batch
    claims_of = [[f"own-{i}"] for i in range(n_pods)]
    slot_of = [rng.randrange(4) for _ in range(n_pods)]
    pairs = []
    for k, gap in enumerate((1, chunk, batch)):
        for rep in range(2):
            a = rng.randrange(0, n_pods - gap)
            a -= a % chunk  # the first of a chunk: its mate at +1 shares the chunk, at +chunk the next one
            b = a + gap
            if any(a in p or b in p for p in pairs):
                continue
            pairs.append((a, b))
            claims_of[a] = claims_of[b] = [f"pair-{k}-{rep}"]
            slot_of[b] = slot_of[a]
    names = sorted({c for cl in claims_of for c in cl})
    pvs, pvcs = zip(*(_claim(c) for c in names))
    aimed = [set() for _ in range(4)]
    for cl, sl in zip(claims_of, slot_of):
        aimed[sl].update(cl)
    nodes, csinodes = _nodes(4, [len(a) for a in aimed], cpu="64")
    pods = [_pod(f"p-{i:03d}", claims_of[i], slot=f"s{slot_of[i]}") for i in range(n_pods)]
    return nodes, csinodes, list(pvs), list(pvcs), pods, pairs


@pytest.mark.parametrize("seed", SEEDS)
def test_sharers_of_one_chunk_of_two_chunks_and_of_adjacent_batches_count_once(seed):
    batch, chunk = 16, 4
    nodes, csinodes, pvs, pvcs, pods, pairs = _pinned_fixture(seed, batch, chunk)
    assert len(pairs) >= 3
    want = _want(_oracle(nodes, csinodes, pvs, pvcs, batch).run([copy.deepcopy(p) for p in pods], prefetch=False))
    assert len(want) == len(pods)  # the sequential oracle binds every pod, each to its pinned node
    prof = registered_subset(DEFAULT_PROFILE)
    s = _engine(nodes, csinodes, pvs, pvcs, profile=prof, batch_size=batch, chunk_size=chunk, pipeline_depth=2)
    for p in pods:
        s.add_pod(copy.deepcopy(p))
    got = _binds(s.schedule_all_pending(wait_backoff=True))
    assert got == want
    _assert_counts_exact(s)
    # every pair was known before its first pod was featurized: all promoted at once, no batch waited
    assert _settled(s) == (len(pairs), 0) and len(pairs) == len(s.builder.csi_rows)
    assert s.metrics.deferred > 0  # chunk-mates on one node did collide: the deferral settled them


def _spans(rec) -> list[str]:
    return [sp[0] for sp in rec["spans"]]


def test_a_sharer_that_arrives_while_its_mate_is_in_flight_waits_for_the_drain():
    """(iv) as it bites: batch 2 is on the device, its pod A names claim X,
    and pod B, which names X too, arrives through the post-dispatch hook.
    The host does not know A's node yet, so no batch is prefetched behind
    the pass; it commits as it was featurized (X counted), and the next
    batch starts by promoting X into a row filled from where A landed."""
    batch = 8
    nodes, csinodes = _nodes(2, [9, 9], cpu="64")
    names = [f"own-{i}" for i in range(24)] + ["x"]
    pvs, pvcs = map(list, zip(*(_claim(c) for c in names)))
    prof = registered_subset(DEFAULT_PROFILE)
    s = _engine(nodes, csinodes, pvs, pvcs, profile=prof, batch_size=batch, chunk_size=4, pipeline_depth=2)
    # 17 distinct claims aim at node 0 (16 of their own and x), its limit is 9 + 8 = 17
    s.builder.set_csinode_limits(s.cache.nodes["node-000"].row, t.CSINode(name="node-000", driver_limits={DRV: 17}))
    pods = [_pod(f"p-{i:02d}", [f"own-{i}"], slot="s0" if i < 16 else "s1") for i in range(24)]
    pods[12] = _pod("p-12", ["x"], slot="s0")  # A, in the second batch; own-12 is never used
    late = _pod("late", ["x"], slot="s0")  # B
    calls = []

    def hook():
        calls.append(len(s.flight.records()))
        if len(calls) == 2:  # batch 2 (pods 8..15) is in flight
            s.add_pod(late)

    s.post_dispatch_hook = hook
    for p in pods:
        s.add_pod(copy.deepcopy(p))
    got = _binds(s.schedule_all_pending(wait_backoff=True))
    assert len(got) == 25 and got["late"] == got["p-12"] == "node-000"
    _assert_counts_exact(s)
    did = s.builder.interns.drivers.get(DRV)
    assert int(s.builder.host["csi_used"][did, s.cache.nodes["node-000"].row]) == 16  # x once, own-12 never
    recs = s.flight.records()
    first, second, third = recs[0], recs[1], recs[2]
    assert "batch/prefetch" in _spans(first)  # volume batches do pipeline...
    assert "batch/prefetch" not in _spans(second)  # ...but nothing was featurized behind A's pass
    assert "pipeline/csi_settle" in _spans(third) and "pipeline/csi_settle" not in _spans(first) + _spans(second)
    settle = next(sp for sp in third["spans"] if sp[0] == "pipeline/csi_settle")
    assert settle[4] == {"promoted": 1, "released": 0}
    assert _settled(s) == (1, 0) and s.builder.csi_rows == {"default/x": 0}
    # the row was filled from where A landed
    assert int(s.builder.host["csivol_counts"][0, s.cache.nodes["node-000"].row]) == 2


# -- volume batches pipeline: a delayed binding behind a batch in flight ---------------


@pytest.mark.parametrize("seed", SEEDS)
def test_delayed_binding_batches_decide_as_the_oracle_under_prefetch_and_predispatch(seed, monkeypatch):
    """Volume batches are no longer kept from prefetch and predispatch.
    Where that could bite: a claim that waits for its first consumer
    (``vol_unbound``) is featurized from the volumes still free, and the
    batch ahead binds one of them at its commit.  Four lanes (a class and
    two volumes each, in two zones), three pods a lane, one lane-mate a
    batch: the second batch is featurized while the first is in flight,
    with both volumes of its lane free.  The bind moves the catalog's
    epoch, the version check throws the prefetched rows away, and the
    decisions are the ordered oracle's: the second pod of a lane takes the
    volume left, the third stays pending."""
    rng = random.Random(seed)
    lanes, waves = 4, 3
    nodes, csinodes = _nodes(6, 9)
    classes = [t.StorageClass(name=f"local-{j}", binding_mode=t.BINDING_WAIT_FOR_FIRST_CONSUMER) for j in range(lanes)]
    pvs, pvcs, pods = [], [], []
    for j in range(lanes):
        for z, cap in zip(rng.sample(range(3), 2), rng.sample(("2Gi", "3Gi"), 2)):
            pvs.append(make_pv(f"pv-{j}-{z}", capacity=cap, storage_class=f"local-{j}",
                               node_affinity_zone=[f"zone-{z}"]))
    for i in range(lanes * waves):
        pvcs.append(make_pvc(f"w-{i:02d}", storage_class=f"local-{i % lanes}"))
        pods.append(_pod(f"p-{i:02d}", [f"w-{i:02d}"], rng))
    prof = _parity_profile()
    o = FullOracleScheduler(
        nodes, pct=None, seed=prof.tie_break_seed, hard_pod_affinity_weight=prof.hard_pod_affinity_weight,
        batch_size=lanes,
        vols=RefVolumes(pvs=copy.deepcopy(pvs), pvcs=copy.deepcopy(pvcs), classes=classes,
                        csinodes=copy.deepcopy(csinodes)),
    )
    want = _want(o.run([copy.deepcopy(p) for p in pods], prefetch=False))
    assert len(want) == 2 * lanes
    s = _engine(nodes, csinodes, [], [], batch_size=lanes, chunk_size=1, pipeline_depth=2)
    for sc in classes:
        s.add_storage_class(sc)
    for pv in pvs:
        s.add_pv(copy.deepcopy(pv))
    for pvc in pvcs:
        s.add_pvc(copy.deepcopy(pvc))
    for p in pods:
        s.add_pod(copy.deepcopy(p))
    featurized = []
    real = s._featurize_batch
    monkeypatch.setattr(s, "_featurize_batch", lambda infos, profile: featurized.append(len(infos)) or real(infos, profile))
    assert _binds(s.schedule_all_pending(wait_backoff=True)) == want
    recs = s.flight.records()
    assert [r["pods"] for r in recs[:waves]] == [lanes] * waves
    assert "batch/prefetch" in _spans(recs[0]) and "batch/prefetch" in _spans(recs[1])  # the path was taken...
    assert featurized[:2 * waves - 1] == [lanes] * (2 * waves - 1)  # ...and both prefetched batches were done again
    assert s.builder.host_mirror_equal()


# -- (viii) a checkpoint, recover and replay rebuild the same counts ------------------


def test_checkpoint_recover_and_replay_rebuild_the_same_counts(tmp_path):
    from kubernetes_tpu.journal import Journal, recover, scheduler_state

    nodes, csinodes, pvs, pvcs, pending = _shared_fixture(7)

    def fresh():
        return _engine(nodes, csinodes, pvs, pvcs, batch_size=8, chunk_size=1)

    s = fresh()
    j = Journal(str(tmp_path / "wal"), fsync="never")
    s.attach_journal(j)
    for p in pending[:10]:
        s.add_pod(copy.deepcopy(p))
    s.schedule_all_pending(wait_backoff=True)
    j.snapshot(scheduler_state(s))
    for p in pending[10:]:
        s.add_pod(copy.deepcopy(p))
    s.schedule_all_pending(wait_backoff=True)
    victim = sorted(n for n in (pr.pod.name for pr in s.cache.pods.values()) if n.startswith("s-"))[0]
    s.delete_pod(f"default/{victim}")
    _assert_counts_exact(s)
    j.close()
    # the host's relist brings the volume objects back before the journal is replayed
    r = fresh()
    j2 = Journal(str(tmp_path / "wal"), fsync="never")
    stats = recover(r, j2)
    assert stats["snapshot"] and stats["records"] > 0
    assert {u: pr.node_name for u, pr in r.cache.pods.items()} == {u: pr.node_name for u, pr in s.cache.pods.items()}
    r._settle_csi_claims()
    s._settle_csi_claims()
    did = s.builder.interns.drivers.get(DRV)

    def by_name(x):
        return {n: int(x.builder.host["csi_used"][did, rec.row]) for n, rec in x.cache.nodes.items()}

    assert by_name(r) == by_name(s)
    _assert_counts_exact(r)
    assert sorted(r.builder.csi_rows) == sorted(s.builder.csi_rows)

    def bound_users(x):  # a pod left pending after the checkpoint comes back with the host's relist, not the replay
        users = {c: ({u} if isinstance(u, str) else u) & set(x.cache.pods) for c, u in x.builder.csi_users.items()}
        return {c: u for c, u in users.items() if u}

    assert bound_users(r) == bound_users(s)

    def table(x):  # the shared claims' per-node counts, by names: rows and node rows may differ
        at = {x.builder.csi_rows[c]: c for c in x.builder.csi_rows}
        rows = {rec.row: n for n, rec in x.cache.nodes.items()}
        counts = x.builder.host["csivol_counts"]
        return {(at[int(i)], rows[int(j)]): int(counts[i, j]) for i, j in zip(*counts.nonzero())}

    assert table(r) == table(s) and table(s)


# -- nothing grows with the claims the cluster has seen -------------------------------


def test_no_array_shape_or_program_follows_the_number_of_single_user_claims():
    """Several buckets' worth of pods with a claim each, past what were
    Schema.CV's doublings (8 -> 16 -> 32 -> 64 -> 128 -> 256)."""
    nodes, csinodes = _nodes(8, 39, cpu="64")
    names = [f"own-{i}" for i in range(300)]
    pvs, pvcs = map(list, zip(*(_claim(c) for c in names)))
    prof = registered_subset(DEFAULT_PROFILE)
    s = _engine(nodes, csinodes, pvs, pvcs, profile=prof, batch_size=16, chunk_size=4)

    def shapes():
        st = s.builder.state()
        return {k: getattr(st, k).shape for k in st.__dataclass_fields__}, {k: v.shape for k, v in s.builder.host.items()}

    def wave(lo, hi):
        for i in range(lo, hi):
            s.add_pod(_pod(f"p-{i:03d}", [f"own-{i}"]))
        assert all(o.node_name for o in s.schedule_all_pending())

    wave(0, 40)  # warm: every program of the steady state has run
    schema0, shapes0, compiles0, programs0 = s.builder.schema, shapes(), PROCESS.compiles, len(s.passes)
    wave(40, 300)
    assert s.builder.schema == schema0 and shapes() == shapes0
    assert PROCESS.compiles == compiles0 and len(s.passes) == programs0
    assert s.builder.schema.CV == 8 and s.builder.csi_rows == {}
    text = s.metrics.registry.render_text()
    assert 'scheduler_csi_claims{kind="shared"} 0' in text and 'scheduler_csi_claims{kind="counted"} 300' in text
    _assert_counts_exact(s)


# -- (d) pods that differ only in the name of their claim share one featurization -------


def test_pods_that_differ_only_in_their_claims_name_are_featurized_once(monkeypatch):
    nodes, csinodes = _nodes(4, 39, cpu="64")
    names = [f"own-{i}" for i in range(32)]
    pvs, pvcs = map(list, zip(*(_claim(c) for c in names)))
    prof = registered_subset(DEFAULT_PROFILE)
    s = _engine(nodes, csinodes, pvs, pvcs, profile=prof, batch_size=16, chunk_size=4)
    calls = []
    real = s.builder.pod_delta_vectors
    monkeypatch.setattr(s.builder, "pod_delta_vectors", lambda pod: calls.append(pod.name) or real(pod))
    for i in range(16):
        s.add_pod(_pod(f"same-{i:02d}", [f"own-{i}"]))
    out = s.schedule_all_pending()
    assert all(o.node_name for o in out)
    # the first grows vocabularies (its row is not kept: features.py's
    # ordering invariant), the second is THE featurization, fourteen share it
    assert calls == ["same-00", "same-01"]
    # each pod's delta names its own claim all the same
    for i in range(16):
        d = s.cache.pods[f"default/same-{i:02d}"].delta
        assert d["pvcs"] == [f"default/own-{i}"] and [u for u, _ in d["csivols"]] == [f"default/own-{i}"]
    _assert_counts_exact(s)
    # and the commits behind a batch leave the cache standing (a claim's user
    # count is a catalog mutation only for ReadWriteOncePod): the next batch of
    # the template is not featurized at all, and ships no feature row
    del calls[:]
    pvs2, pvcs2 = map(list, zip(*(_claim(f"more-{i}") for i in range(16))))
    for pv, pvc in zip(pvs2, pvcs2):
        s.add_pv(pv)
        s.add_pvc(pvc)
    for i in range(16):
        s.add_pod(_pod(f"more-{i:02d}", [f"more-{i}"]))
    assert all(o.node_name for o in s.schedule_all_pending())
    assert calls == ["more-00"]  # new objects arrived (the catalog's epoch): one featurization
    del calls[:]
    for i in range(16, 32):
        pv, pvc = _claim(f"more-{i}")
        s.add_pv(pv)
        s.add_pvc(pvc)
    for i in range(16, 24):
        s.add_pod(_pod(f"more-{i:02d}", [f"more-{i}"]))
    assert all(o.node_name for o in s.schedule_all_pending())
    for i in range(24, 32):
        s.add_pod(_pod(f"more-{i:02d}", [f"more-{i}"]))
    assert all(o.node_name for o in s.schedule_all_pending())
    assert calls == ["more-16"]  # two batches behind one arrival of objects: one featurization
    assert s.flight.records()[-1]["inputs_shipped"] <= 2  # a uniform batch whose row the device still holds
    _assert_counts_exact(s)
    # pods that really differ are featurized each by itself
    del calls[:]
    for i in range(16, 32):
        p = make_pod(f"diff-{i}").req({"cpu": f"{100 + i}m", "memory": "128Mi"}).pvc_volume(f"own-{i}").obj()
        s.add_pod(p)
    assert all(o.node_name for o in s.schedule_all_pending())
    assert sorted(calls) == [f"diff-{i}" for i in range(16, 32)]
    _assert_counts_exact(s)


def test_a_claim_with_topology_of_its_own_is_not_shared_away():
    """Two pods of one template whose volumes sit in different zones are
    not one featurization: the claim's answer is part of the key."""
    nodes, csinodes = _nodes(3, 39)
    pvs, pvcs = [], []
    for i, z in enumerate(("zone-0", "zone-1")):
        pv = make_pv(f"pv-z{i}", csi_driver=DRV, node_affinity_zone=[z])
        pv.claim_ref = f"default/z{i}"
        pvs.append(pv)
        pvcs.append(make_pvc(f"z{i}", volume_name=pv.name))
    s = _engine(nodes, csinodes, pvs, pvcs, batch_size=8, chunk_size=1)
    for i in range(2):
        s.add_pod(_pod(f"p{i}", [f"z{i}"]))
    got = _binds(s.schedule_all_pending())
    zone = {n.name: n.metadata.labels["topology.kubernetes.io/zone"] for n in nodes}
    assert [zone[got["p0"]], zone[got["p1"]]] == ["zone-0", "zone-1"]


# -- the configurations without volumes ---------------------------------------------


def _rehearsal(kind: str):
    """The three accepted configurations' shapes at toy size, in process."""
    prof = registered_subset(DEFAULT_PROFILE)
    s = TPUScheduler(profile=prof, batch_size=32, chunk_size=8, pipeline_depth=2)
    zones = {"basic": [None], "podaffinity": ["zone1"], "spreading": ["moon-1", "moon-2", "moon-3"]}[kind]
    for i in range(48):
        w = make_node(f"node-{i}").capacity({"cpu": "4", "memory": "32Gi", "pods": 110})
        if zones[0] is not None:
            w = w.zone(zones[i % len(zones)])
        s.add_node(w.obj())
    for i in range(100):
        w = make_pod(f"pod-{i:03d}", namespace="namespace-2").req({"cpu": "100m", "memory": "500Mi"})
        if kind == "podaffinity":
            w = w.label("color", "blue").pod_affinity_in("color", ["blue"], "topology.kubernetes.io/zone")
        elif kind == "spreading":
            w = w.label("color", "blue").spread_constraint(
                5, "topology.kubernetes.io/zone", t.DO_NOT_SCHEDULE, "color", ["blue"])
        s.add_pod(w.obj())
    out = s.schedule_all_pending()
    return s, [(o.pod.name, o.node_name) for o in out]


# the bindings of 1bc2fba's program on these three scenarios (this file's
# _rehearsal run against a `git archive` of the parent): sha256 of repr(list)
PARENT_BINDINGS = {
    "basic": "bbef78a5b3e74d099399541ac53c25e108109e51d0fbc9e86f87d0e95f63de6a",
    "podaffinity": "1edc52f0291dff9a7c3cf2598656e878c7f591c01b3b40406ff0ed561ecc7c73",
    "spreading": "a5e69b76da278fdb9e993c0e83c247cdbcf2a4d12ba34f888cf85b8ddb85da15",
}
PARENT_STATE_LEAVES = [
    "valid", "name_id", "unschedulable", "num_pods", "allowed_pods", "alloc", "req", "nonzero_req",
    "label_key_ids", "label_pair_ids", "label_int_vals", "topo_vals", "taint_ids", "port_counts",
    "portkey_counts", "group_counts", "et_counts", "dev_counts", "dev_rw_counts", "csi_used", "csi_limit",
    "csivol_counts", "dra_cap", "dra_alloc", "dra_claim_counts", "image_ids", "image_sizes",
]


@pytest.mark.parametrize("kind", sorted(PARENT_BINDINGS))
def test_the_configurations_without_volumes_keep_their_state_and_their_bindings(kind):
    """What this change had to touch of their programs: the commit's
    ``csivol_counts`` / ``csi_used`` block (engine/pass_.py _commit_chunk),
    which every program carries because ``vol_csi_ids`` is a base feature;
    with no volume in the batch it adds nothing, as before.  The leaf set
    of ``ClusterState`` and its shapes are the parent's, and so is every
    binding."""
    import hashlib

    s, binds = _rehearsal(kind)
    st = s.builder.state()
    assert list(st.__dataclass_fields__) == PARENT_STATE_LEAVES
    assert st.csivol_counts.shape == (8, s.builder.schema.N) and s.builder.schema.CV == 8
    assert all(n for _, n in binds)
    assert hashlib.sha256(repr(binds).encode()).hexdigest() == PARENT_BINDINGS[kind]
    assert "NodeVolumeLimits" not in s.flight.records()[-1]["filter_rejecting"]


# -- C13 (c): a claim and a spread constraint pack wider than one ---------------------


def _claims_and_spread(chunk: int, n_pods: int = 48, colors: int = 8):
    prof = registered_subset(Profile(
        name="claims-and-spread",
        filters=("NodeResourcesFit", "PodTopologySpread", "VolumeBinding", "NodeVolumeLimits"),
        scorers=(),
    ))
    nodes, csinodes = _nodes(12, 39, cpu="64")
    names = [f"own-{i}" for i in range(n_pods)]
    pvs, pvcs = map(list, zip(*(_claim(c) for c in names)))
    s = _engine(nodes, csinodes, pvs, pvcs, profile=prof, batch_size=16, chunk_size=chunk)
    s.enable_preemption = False
    for i in range(n_pods):
        c = f"c{i % colors}"
        s.add_pod(
            make_pod(f"p{i:03d}").req({"cpu": "100m", "memory": "128Mi"}).label("color", c)
            .spread_constraint(2, "topology.kubernetes.io/zone", t.DO_NOT_SCHEDULE, "color", [c])
            .pvc_volume(f"own-{i}").obj()
        )
    out = s.schedule_all_pending()
    return s, {o.pod.name: o.node_name for o in out}


def test_a_batch_with_claims_and_a_spread_constraint_packs_wider_than_one():
    s8, got = _claims_and_spread(chunk=8)
    s1, want = _claims_and_spread(chunk=1)
    assert all(want.values()) and got == want  # what the ordered scan decides
    # eight colours, so eight classes of six: with the per-node budget left
    # to the pass's own deferral the packer keeps the configured width
    assert s8.metrics.pack_width == 8 and s8.metrics.pack_classes == 8
    _assert_counts_exact(s8)
