"""Partitioned scheduler fleet (kubernetes_tpu/fleet): shard-map
split/merge round-trips, misroute forwarding, cross-shard preemption,
gang 2PC spanning shards (including crash-between-phases replay), shard
takeover, and the N∈{2,4} vs single-scheduler bit-identical oracle on
the golden scenarios.

The oracle discipline carries over from every prior PR: a fleet of N
owners coordinated by the router must reproduce ONE scheduler's
decisions byte for byte — scatter-gather proposals, a host-side
selectHost mirror (global row order + splitmix32 counter-hash
tie-break), and the 2PC/preemption arbitration exist exactly to make
that true."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from gen_golden_transcripts import (  # noqa: E402
    scenario_objects,
    session_schedulers,
    wait_for_backoffs,
)

from kubernetes_tpu.api import types as t  # noqa: E402
from kubernetes_tpu.api.wrappers import make_node, make_pod  # noqa: E402
from kubernetes_tpu.fleet import (  # noqa: E402
    FleetRouter,
    ShardMap,
    ShardOwner,
)
from kubernetes_tpu.fleet.shardmap import (  # noqa: E402
    StaleMapError,
    stable_shard_hash,
)
from kubernetes_tpu.fleet.takeover import (  # noqa: E402
    absorb_shard,
    recover_shard,
    redo_handoff,
)
from kubernetes_tpu.framework.config import fit_only_profile  # noqa: E402
from kubernetes_tpu.scheduler import TPUScheduler  # noqa: E402


def mk_sched() -> TPUScheduler:
    return TPUScheduler(profile=fit_only_profile(), batch_size=8, chunk_size=1)


def big_node(name: str, cpu: str = "4"):
    return (
        make_node(name)
        .capacity({"cpu": cpu, "memory": "16Gi", "pods": 16})
        .obj()
    )


def build_fleet(
    n_shards: int = 2,
    pin: dict[str, int] | None = None,
    state_root: str | None = None,
    factory=mk_sched,
):
    """(router, owners, map): a fleet with optional node→shard pins (so
    targeted tests control ownership exactly) and optional journaling."""
    smap = ShardMap(n_shards=n_shards, n_buckets=16)
    for name, shard in (pin or {}).items():
        smap.overrides[name] = shard
    owners = {}
    for k in range(n_shards):
        sdir = os.path.join(state_root, f"shard{k}") if state_root else None
        owners[k] = ShardOwner(k, factory(), smap, state_dir=sdir)
        # A checkpoint behind every commit (a cadence of one record): the
        # takeover tests recover from a snapshot and the log's tail.
        owners[k].sched.snapshot_every_records = 1
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    return router, owners, smap


def name_homing_to(shard: int, n_shards: int, stem: str = "pod") -> str:
    """A pod name whose uid hash-routes to ``shard`` when all
    ``n_shards`` shards are viable (home_shard sorts viable ids, so with
    every shard populated the index IS the shard id)."""
    for i in range(1000):
        name = f"{stem}-{i}"
        if stable_shard_hash(f"default/{name}", n_shards) == shard:
            return name
    raise AssertionError("unreachable")


# -- shard map ---------------------------------------------------------------


def test_shardmap_split_merge_round_trip(tmp_path):
    m = ShardMap(n_shards=1, n_buckets=16)
    names = [f"node-{i}" for i in range(24)]
    assert all(m.owner_of(n) == 0 for n in names)

    rec = m.split(0, 1)
    assert rec["op"] == "split" and rec["version"] == 1
    split_owned = {n: m.owner_of(n) for n in names}
    assert set(split_owned.values()) == {0, 1}

    # Save/load round-trips the exact assignment.
    path = str(tmp_path / "map.json")
    m.save(path)
    loaded = ShardMap.load(path)
    assert {n: loaded.owner_of(n) for n in names} == split_owned
    assert loaded.version == m.version

    # Merge restores the pre-split world, at a strictly newer version.
    rec2 = m.merge(into=0, absorbed=1)
    assert rec2["version"] == 2
    assert all(m.owner_of(n) == 0 for n in names)


def test_shardmap_split_pins_survive_by_default():
    """ISSUE 11 regression: override pins naming the split shard are an
    operator/takeover decision — a split must NEVER silently remap them
    to the new shard; they stay pinned to the source."""
    m = ShardMap(n_shards=2, n_buckets=16)
    m.overrides["pinned-a"] = 0
    m.overrides["pinned-b"] = 0
    m.overrides["foreign"] = 1
    rec = m.split(0, 2)
    assert rec["pins_dropped"] == []
    assert m.overrides == {"pinned-a": 0, "pinned-b": 0, "foreign": 1}
    assert m.owner_of("pinned-a") == 0
    assert m.owner_of("pinned-b") == 0


def test_shardmap_split_drop_pins_is_explicit_and_recorded():
    """The only way a pin leaves a split: drop_pins=True removes the
    source's pins (they fall back to the bucket rule) and the handoff
    record carries the names so a takeover redo replays the choice."""
    m = ShardMap(n_shards=2, n_buckets=16)
    m.overrides["pinned-a"] = 0
    m.overrides["foreign"] = 1
    rec = m.split(0, 2, drop_pins=True)
    assert rec["pins_dropped"] == ["pinned-a"]
    assert "pinned-a" not in m.overrides
    assert m.overrides == {"foreign": 1}  # other shards' pins untouched
    # The redo replays the drop on a stale map.
    stale = ShardMap(n_shards=2, n_buckets=16)
    stale.overrides["pinned-a"] = 0
    stale.overrides["foreign"] = 1
    redo_handoff(stale, rec)
    assert stale.buckets == m.buckets
    assert stale.overrides == m.overrides


def test_shardmap_split_refuses_an_atomic_shard():
    """A shard owning fewer than two buckets cannot split — moving its
    only bucket would be a rename that empties the source.  Refused
    BEFORE any version bump (a refused action must not advance the
    ownership record)."""
    m = ShardMap(buckets=[0] + [1] * 15)
    with pytest.raises(ValueError):
        m.split(0, 2)
    assert m.version == 0


def test_shardmap_merge_refuses_self_and_reaches_n1():
    """merge(x, x) is refused pre-version-bump; merging the last two
    shards down to N=1 is legal and leaves the degenerate
    single-scheduler map."""
    m = ShardMap(n_shards=2, n_buckets=16)
    with pytest.raises(ValueError):
        m.merge(into=0, absorbed=0)
    assert m.version == 0
    rec = m.merge(into=0, absorbed=1)
    assert rec["version"] == 1
    assert m.shard_ids() == [0]
    assert all(s == 0 for s in m.buckets)


def test_live_merge_to_single_shard_through_the_router():
    """merge down to N=1 end-to-end: the handoff moves the absorbed
    shard's nodes AND bindings through the journaled path and the
    single remaining owner keeps scheduling."""
    router, owners, smap = build_fleet(2, pin={"s0": 0, "s1": 1})
    a, b = "s0", "s1"
    router.add_object("Node", big_node(a))
    router.add_object("Node", big_node(b, cpu="6"))
    for i in range(4):
        router.add_pod(
            make_pod(f"mrg{i}").req({"cpu": f"{400 + 10 * i}m"}).obj()
        )
    bound = router.schedule_all_pending(wait_backoff=True)
    assert sum(1 for o in bound if o.node_name) == 4
    before = router.bindings()
    rec = smap.merge(into=0, absorbed=1)
    router.apply_handoff(rec)
    drained = router.remove_owner(1)
    drained.close()
    assert router.shard_ids() == [0]
    assert router.bindings() == before
    assert owners[0].sched.cache.nodes.keys() >= {a, b}
    router.add_pod(make_pod("post-n1").req({"cpu": "300m"}).obj())
    out = router.schedule_all_pending(wait_backoff=True)
    assert any(o.node_name for o in out)


def test_shardmap_rebalance_respects_live_ids_and_pins():
    """Post-review regressions: a rebalance after merges (gapped id
    space) must deal buckets over the LIVE ids — never to an ownerless
    shard — and pins follow the split contract: survive by default,
    dropped only explicitly and recorded for the redo."""
    m = ShardMap(n_shards=2, n_buckets=16)
    m.split(0, 2)
    m.merge(into=0, absorbed=1)  # live ids now {0, 2} — 1 is a gap
    m.overrides["pinned"] = 2
    rec = m.rebalance(ids=[0, 2])
    assert set(m.buckets) == {0, 2}
    assert rec["ids"] == [0, 2] and rec["pins_dropped"] == []
    assert m.overrides == {"pinned": 2}  # survived
    rec2 = m.rebalance(ids=[0, 2], drop_pins=True)
    assert rec2["pins_dropped"] == ["pinned"]
    assert m.overrides == {}
    # The redo replays both: gapped ids and the recorded pin drop.
    stale = ShardMap(n_shards=2, n_buckets=16)
    stale.overrides["pinned"] = 2
    redo_handoff(stale, rec)
    assert set(stale.buckets) == {0, 2}
    assert stale.overrides == {"pinned": 2}
    redo_handoff(stale, rec2)
    assert stale.overrides == {}
    assert stale.buckets == m.buckets


def test_autoscaler_rebalance_action_carries_live_ids():
    """The decision core names the live shards in its rebalance action
    (the executor deals over them), so an id-gapped fleet at max_shards
    never re-deals buckets to an ownerless shard."""
    from kubernetes_tpu.fleet import AutoscalerConfig, choose_action

    act, _ = choose_action(
        {0: 9, 2: 1},
        {0: 8, 2: 8},
        AutoscalerConfig(max_shards=2, min_window_decisions=4),
    )
    assert act == {"op": "rebalance", "n_shards": 2, "shards": [0, 2]}


def test_shardmap_save_rejects_stale_writer(tmp_path):
    path = str(tmp_path / "map.json")
    m = ShardMap(n_shards=2, n_buckets=16)
    m.split(0, 1)
    m.save(path)
    stale = ShardMap(n_shards=2, n_buckets=16)  # version 0 < disk's 1
    with pytest.raises(StaleMapError):
        stale.save(path)


def test_handoff_record_redo_is_idempotent():
    """takeover.redo_handoff applied twice lands on the same map — the
    property that makes the append→map-rewrite crash window safe."""
    m = ShardMap(n_shards=2, n_buckets=16)
    rec = m.split(0, 2)
    stale = ShardMap(n_shards=2, n_buckets=16)
    redo_handoff(stale, rec)
    once = (list(stale.buckets), dict(stale.overrides), stale.version)
    redo_handoff(stale, rec)
    assert (list(stale.buckets), dict(stale.overrides), stale.version) == once
    assert stale.buckets == m.buckets


def test_shard_guard_drops_foreign_nodes():
    smap = ShardMap(n_shards=2, n_buckets=16)
    smap.overrides["mine"] = 0
    smap.overrides["yours"] = 1
    owner = ShardOwner(0, mk_sched(), smap)
    owner.sched.add_node(big_node("mine"))
    owner.sched.add_node(big_node("yours"))
    assert sorted(owner.sched.cache.nodes) == ["mine"]
    assert owner.sched.shard_rejected_nodes == 1


# -- routing and misroute forwarding ----------------------------------------


def test_misroute_forwards_to_global_winner():
    """A pod whose hash-home shard has no feasible node commits on the
    winning shard and is counted as forwarded."""
    pin = {"full": 0, "roomy": 1}
    router, owners, _ = build_fleet(2, pin=pin)
    router.add_object("Node", big_node("full", cpu="1"))
    router.add_object("Node", big_node("roomy", cpu="4"))
    # Saturate shard 0's node so only shard 1 is feasible.
    blocker = make_pod("blocker").req({"cpu": "1"}).node("full").obj()
    router.add_object("Pod", blocker)

    name = name_homing_to(0, 2, "misroute")
    pod = make_pod(name).req({"cpu": "2"}).obj()
    assert router.home_shard(pod) == 0
    router.add_pod(pod)
    outs = router.schedule_all_pending()
    assert [(o.pod.name, o.node_name) for o in outs] == [(name, "roomy")]
    assert router.bindings()[pod.uid] == "roomy"
    assert router._pod_shard[pod.uid] == 1
    assert router._forwarded.get() == 1
    # The owner caches agree with the router's bookkeeping.
    assert pod.uid in owners[1].bindings()
    assert pod.uid not in owners[0].bindings()


def test_home_shard_skips_empty_shards():
    """Feasibility-aware hashing: a shard owning zero nodes is never a
    home (hashing a pod there would guarantee a misroute)."""
    router, _, _ = build_fleet(2, pin={"only": 1})
    router.add_object("Node", big_node("only"))
    for i in range(8):
        pod = make_pod(f"p{i}").req({"cpu": "1"}).obj()
        assert router.home_shard(pod) == 1


# -- cross-shard preemption --------------------------------------------------


def test_cross_shard_preemption_with_pdb_broadcast():
    """A high-priority pod preempts a victim on a FOREIGN shard; the
    victim's PDB debit is broadcast so every owner's budget view stays
    cluster-global."""
    pin = {"away": 1, "spare": 0}
    router, owners, _ = build_fleet(2, pin=pin)
    router.add_object("Node", big_node("away", cpu="4"))
    victim = (
        make_pod("victim")
        .req({"cpu": "4"})
        .label("app", "sacrificial")
        .priority(1)
        .start_time(1.0)
        .node("away")
        .obj()
    )
    router.add_object("Pod", victim)
    pdb = t.PodDisruptionBudget(
        name="guard",
        selector={"app": "sacrificial"},
        disruptions_allowed=2,
    )
    router.add_object("PodDisruptionBudget", pdb)

    name = name_homing_to(0, 2, "vip")
    # Shard 0 needs a node or home_shard collapses to shard 1; too small
    # for the preemptor, so the only candidate is shard 1's victim.
    router.add_object("Node", big_node("spare", cpu="1"))
    vip = make_pod(name).req({"cpu": "3"}).priority(100).obj()
    assert router.home_shard(vip) == 0
    router.add_pod(vip)
    router.schedule_all_pending(wait_backoff=True)
    wait_for_backoffs(router.queue)
    router.schedule_all_pending(wait_backoff=True)

    bindings = router.bindings()
    assert bindings[vip.uid] == "away"
    assert victim.uid not in bindings
    assert router._preempt_xshard.get() == 1
    # The debit landed on BOTH owners' PDB copies.
    for owner in owners.values():
        assert owner.sched.pdbs["guard"].disruptions_allowed == 1


# -- gang 2PC spanning shards ------------------------------------------------


def gang_pod(name: str, group: str, cpu: str = "3") -> t.Pod:
    return make_pod(name).req({"cpu": cpu}).pod_group(group).obj()


def feed_gang_fleet(router, group: str = "g1", members: int = 2):
    router.add_object("Node", big_node("left", cpu="4"))
    router.add_object("Node", big_node("right", cpu="4"))
    router.add_object("PodGroup", t.PodGroup(name=group, min_member=members))
    pods = [gang_pod(f"m{i}", group) for i in range(members)]
    return pods


def test_gang_2pc_spans_shards():
    """minMember=2 with one feasible node per shard: phase 1 reserves on
    each winning shard, phase 2 commits both — and below quorum nothing
    schedules (the members park in the router queue's gang pool)."""
    pin = {"left": 0, "right": 1}
    router, owners, _ = build_fleet(2, pin=pin)
    pods = feed_gang_fleet(router)
    router.add_pod(pods[0])
    assert router.schedule_all_pending() == []
    assert router.bindings() == {}

    router.add_pod(pods[1])
    outs = router.schedule_all_pending()
    assert sorted(o.pod.name for o in outs if o.node_name) == ["m0", "m1"]
    bindings = router.bindings()
    assert sorted(bindings) == ["default/m0", "default/m1"]
    # One member per shard: the gang genuinely spanned the partition.
    assert {bindings[u] for u in bindings} == {"left", "right"}
    assert router.gang_bound == {"g1": 2}
    assert router._gang_commits.get(phase="reserve") == 2
    assert router._gang_commits.get(phase="commit") == 2
    for owner in owners.values():
        assert owner.sched.gang_bound == {"g1": 1}
        assert owner.sched._fleet_reserved == {}


def test_gang_2pc_rollback_on_reserve_refusal():
    """A member failing phase 1 aborts every held reservation: no
    partial gang survives, resources release, members retry via
    backoff."""
    pin = {"left": 0, "right": 1}
    router, owners, _ = build_fleet(2, pin=pin)
    router.add_object("Node", big_node("left", cpu="4"))
    router.add_object("Node", big_node("right", cpu="1"))  # can't host a member
    router.add_object("PodGroup", t.PodGroup(name="g1", min_member=2))
    for i in range(2):
        router.add_pod(gang_pod(f"m{i}", "g1"))
    router.schedule_all_pending()
    # Both feasible only on "left", which fits one member: the second's
    # reserve fails (insufficient room after the first's assume) or never
    # proposes — either way nothing may commit.
    assert router.bindings() == {}
    assert router.gang_bound == {}
    for owner in owners.values():
        assert owner.sched._fleet_reserved == {}
        assert not any(
            pr.bound for pr in owner.sched.cache.pods.values()
        )
    # Capacity arrives → the gang re-admits and commits whole.
    router.add_object("Node", big_node("more", cpu="8"))
    outs = router.schedule_all_pending(wait_backoff=True)
    assert sorted(o.pod.name for o in outs if o.node_name) == ["m0", "m1"]
    assert router.gang_bound == {"g1": 2}


def test_gang_2pc_crash_between_phases_replays_presumed_abort(tmp_path):
    """SIGKILL between phase 1 and phase 2: the journal holds
    ``gang_reserve`` intents with no bind records.  Recovery resolves
    them presumed-abort (nothing applied, intents surfaced), and a fresh
    fleet re-admits the gang from scratch — converging to the same
    bindings an uncrashed fleet lands."""
    pin = {"left": 0, "right": 1}

    # The uncrashed reference.
    ref_router, ref_owners, _ = build_fleet(2, pin=pin)
    pods = feed_gang_fleet(ref_router)
    for p in pods:
        ref_router.add_pod(p)
    ref_router.schedule_all_pending()
    reference = ref_router.bindings()
    assert sorted(reference) == ["default/m0", "default/m1"]

    # The crashed run: commit_gang "crashes" before any phase-2 call —
    # reserves are journaled, commits never happen, owners die.
    root = str(tmp_path / "crash")
    router, owners, _ = build_fleet(2, pin=pin, state_root=root)
    pods = feed_gang_fleet(router)
    for p in pods:
        router.add_pod(p)

    class Crashed(RuntimeError):
        pass

    def crash(_g, _trigger):
        raise Crashed()

    router._commit_gang = crash
    with pytest.raises(Crashed):
        router.schedule_all_pending()
    for owner in owners.values():
        assert owner.sched._fleet_reserved  # phase 1 really happened
        # Simulate the kill: no abort runs, nothing is unwound.  The
        # flock must drop (a dead process's does instantly) or the
        # takeover's blocking acquire would wait on ourselves; release
        # keeps the epoch, so the successor still fences above it.
        owner.journal.close()
        owner.lease.release()

    # Takeover: fresh owners replay each shard's journal.
    recovered = {}
    for k in range(2):
        recovered[k] = recover_shard(
            os.path.join(root, f"shard{k}"), mk_sched, k,
            ShardMap(n_shards=2, n_buckets=16, overrides=pin),
        )
        stats = recovered[k].recovery_stats
        assert stats["in_doubt_reservations"] == 1  # the orphaned intent
        assert not any(
            pr.bound for pr in recovered[k].sched.cache.pods.values()
        )

    smap = ShardMap(n_shards=2, n_buckets=16, overrides=pin)
    router2 = FleetRouter(recovered, smap, batch_size=8)
    router2.profile_filters = tuple(recovered[0].sched.profile.filters)
    # Host-truth re-feed first (nodes relist), then parked journal
    # bindings re-apply, then the router adopts the recovered truth —
    # the same order the shard-failover kill matrix drives.
    pods = feed_gang_fleet(router2)
    router2.reconcile_recovered()
    router2.adopt_bindings()
    # Gang re-admission from scratch.
    for p in pods:
        router2.add_pod(p)
    router2.schedule_all_pending(wait_backoff=True)
    assert router2.bindings() == reference
    for owner in recovered.values():
        owner.close()


def test_gang_2pc_crash_mid_phase_two_converges(tmp_path):
    """Crash AFTER one member committed but before the other: replay
    binds the committed member (its bind record is durable), presumed-
    aborts the other's intent, and re-admission completes the gang —
    quorum credit counts the already-bound member."""
    pin = {"left": 0, "right": 1}
    root = str(tmp_path / "crash2")
    router, owners, _ = build_fleet(2, pin=pin, state_root=root)
    pods = feed_gang_fleet(router)
    for p in pods:
        router.add_pod(p)

    class Crashed(RuntimeError):
        pass

    orig = FleetRouter._commit_gang
    calls = {"n": 0}

    def crash_after_first(self, g, trigger):
        room = self._gang_rooms[g]
        uid, shard = room.members[0]
        self._call(shard, "commit_reserved", {"uid": uid})  # member 1 lands
        raise Crashed()

    router._commit_gang = crash_after_first.__get__(router)
    with pytest.raises(Crashed):
        router.schedule_all_pending()
    for owner in owners.values():
        owner.journal.close()  # the kill: no abort, lease flock drops
        owner.lease.release()

    recovered = {
        k: recover_shard(
            os.path.join(root, f"shard{k}"), mk_sched, k,
            ShardMap(n_shards=2, n_buckets=16, overrides=pin),
        )
        for k in range(2)
    }
    in_doubt = sum(
        o.recovery_stats["in_doubt_reservations"] for o in recovered.values()
    )
    assert in_doubt == 1  # the other member's orphaned intent

    smap = ShardMap(n_shards=2, n_buckets=16, overrides=pin)
    router2 = FleetRouter(recovered, smap, batch_size=8)
    router2.profile_filters = tuple(recovered[0].sched.profile.filters)
    pods = feed_gang_fleet(router2)  # host-truth node relist
    router2.reconcile_recovered()
    router2.adopt_bindings()
    # Exactly the phase-2 half that landed survived the crash.
    bound_now = {u for o in recovered.values() for u in o.bindings()}
    assert len(bound_now) == 1
    assert router2.gang_bound == {"g1": 1}  # adopted credit
    for p in pods:
        router2.add_pod(p)  # the bound member's re-feed is a no-op
    router2.schedule_all_pending(wait_backoff=True)
    bindings = router2.bindings()
    assert sorted(bindings) == ["default/m0", "default/m1"]
    assert router2.gang_bound == {"g1": 2}
    for owner in recovered.values():
        owner.close()


def test_rebalance_handoff_moves_nodes_live(tmp_path):
    """A rebalance record (no single src/dst) sweeps every owner pair:
    pinned nodes return to their bucket owners with their bound pods,
    and the map file lands at the record's version."""
    pin = {"a": 0, "b": 1}
    router, owners, smap = build_fleet(2, pin=pin)
    router.add_object("Node", big_node("a"))
    router.add_object("Node", big_node("b"))
    pods = [make_pod(f"p{i}").req({"cpu": "1"}).obj() for i in range(4)]
    for p in pods:
        router.add_pod(p)
    router.schedule_all_pending()
    before = router.bindings()
    assert len(before) == 4

    map_path = str(tmp_path / "map.json")
    rec = smap.rebalance(2)  # drops the overrides: bucket rule decides
    router.apply_handoff(rec, map_path)
    assert router.bindings() == before  # bindings survive the reshuffle
    # Every node now lives where the bucket rule puts it.
    for name in ("a", "b"):
        holder = [
            k for k, o in owners.items() if name in o.sched.cache.nodes
        ]
        assert holder == [smap.owner_of(name)]
    assert ShardMap.load(map_path).version == rec["version"]
    # Routing still works post-rebalance.
    extra = make_pod("post").req({"cpu": "1"}).obj()
    router.add_pod(extra)
    router.schedule_all_pending()
    assert extra.uid in router.bindings()


# -- takeover ---------------------------------------------------------------


def test_survivor_absorbs_dead_shard(tmp_path):
    """absorb_shard: the survivor adopts a dead owner's nodes AND
    bindings through the journaled merge path; the merged map routes
    everything to the survivor."""
    pin = {"left": 0, "right": 1}
    root = str(tmp_path / "fleet")
    router, owners, smap = build_fleet(2, pin=pin, state_root=root)
    router.add_object("Node", big_node("left"))
    router.add_object("Node", big_node("right"))
    for i in range(3):
        router.add_pod(make_pod(f"p{i}").req({"cpu": "1"}).obj())
    router.schedule_all_pending()
    before = router.bindings()
    assert len(before) == 3

    # Shard 1 dies (journal closed, lease released — the flock frees).
    dead_bindings = owners[1].bindings()
    owners[1].close()

    map_path = str(tmp_path / "map.json")
    smap.save(map_path)
    record = absorb_shard(
        owners[0], os.path.join(root, "shard1"), 1, mk_sched, smap,
        map_path=map_path,
    )
    assert record["op"] == "merge"
    # The survivor now holds every binding, including the dead shard's.
    survivor = owners[0].bindings()
    assert before == dict(survivor)
    for uid in dead_bindings:
        assert survivor[uid] == dead_bindings[uid]
    assert smap.owner_of("right") == 0
    assert ShardMap.load(map_path).owner_of("right") == 0
    owners[0].close()


def test_router_restart_adopts_without_double_scheduling():
    """A cold router rebuild (the fleet's cold-consumer analog) adopts
    the owners' truth: bound pods re-fed as objects do not re-queue, and
    the row-allocator mirror re-derives from the node re-feed."""
    pin = {"left": 0, "right": 1}
    router, owners, smap = build_fleet(2, pin=pin)
    nodes = [big_node("left"), big_node("right")]
    for n in nodes:
        router.add_object("Node", n)
    pods = [make_pod(f"p{i}").req({"cpu": "1"}).obj() for i in range(4)]
    for p in pods:
        router.add_pod(p)
    router.schedule_all_pending()
    before = router.bindings()
    assert len(before) == 4

    router2 = FleetRouter(owners, smap, batch_size=8)
    router2.profile_filters = tuple(owners[0].sched.profile.filters)
    for n in nodes:
        router2.add_object("Node", n)
    router2.adopt_bindings()
    for p in pods:
        router2.add_pod(p)  # all already bound → no-ops
    assert len(router2.queue) == 0
    assert router2.schedule_all_pending() == []
    assert router2.bindings() == before


# -- the oracle --------------------------------------------------------------


def run_single(stem: str) -> dict:
    sched = session_schedulers()[stem]()
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        sched.add_node(n)
    for p in bound:
        sched.add_pod(p)
    for p in pending:
        sched.add_pod(p)
    sched.schedule_all_pending(wait_backoff=True)
    wait_for_backoffs(sched.queue)
    sched.schedule_all_pending(wait_backoff=True)
    return {
        uid: pr.node_name
        for uid, pr in sorted(sched.cache.pods.items())
        if pr.bound
    }


def run_fleet(stem: str, n_shards: int) -> dict:
    smap = ShardMap(n_shards=n_shards, n_buckets=16)
    factory = session_schedulers()[stem]
    owners = {k: ShardOwner(k, factory(), smap) for k in range(n_shards)}
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    nodes, bound, pending = scenario_objects()
    for n in nodes:
        router.add_object("Node", n)
    for p in bound:
        router.add_object("Pod", p)
    for p in pending:
        router.add_pod(p)
    router.schedule_all_pending(wait_backoff=True)
    wait_for_backoffs(router.queue)
    router.schedule_all_pending(wait_backoff=True)
    return router.bindings()


@pytest.mark.parametrize("stem", ["basic_session", "default_session"])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_fleet_binds_bit_identical_to_single_scheduler(stem, n_shards):
    """The acceptance oracle: an N-shard fleet reproduces the single
    scheduler's bindings on the golden scenario — same nodes, same pods,
    same preemption victim, same unschedulable leftover — for both the
    fit-only and the full default profile."""
    assert run_fleet(stem, n_shards) == run_single(stem)


# -- the fleet-native failure-response loop (ISSUE 10) -----------------------

# ONE definition of the partition-exact node-loss profile and its clock
# constants: the chaos matrix owns them (run_fault_matrix.py documents
# why TaintToleration stays filter-only there), and this suite's
# "fleet == armed single" oracle must assert the SAME claim the matrix
# sweeps — two drifting copies would silently split them.
import run_fault_matrix as _rfm  # noqa: E402  (scripts/ on sys.path above)

LIFECYCLE = _rfm.FLEET_LIFECYCLE


def mk_lifecycle_sched() -> TPUScheduler:
    return _rfm._fleet_node_loss_sched()


def arm_single() -> TPUScheduler:
    sched = mk_lifecycle_sched()
    sched.node_lifecycle.arm(
        grace_period_s=LIFECYCLE["node_grace_s"],
        unreachable_after_s=LIFECYCLE["node_unreachable_s"],
    )
    sched.pod_gc.arm(gc_horizon_s=LIFECYCLE["gc_horizon_s"])
    return sched


def build_lifecycle_fleet(
    n_shards: int = 2,
    pin: dict[str, int] | None = None,
    state_root: str | None = None,
):
    router, owners, smap = build_fleet(
        n_shards, pin=pin, state_root=state_root, factory=mk_lifecycle_sched
    )
    # build_fleet constructs disarmed owners; re-arm through the same
    # dict `serve --shard-of --node-grace-s` passes.
    for owner in owners.values():
        owner.sched.node_lifecycle.arm(
            grace_period_s=LIFECYCLE["node_grace_s"],
            unreachable_after_s=LIFECYCLE["node_unreachable_s"],
        )
        owner.sched.pod_gc.arm(gc_horizon_s=LIFECYCLE["gc_horizon_s"])
    return router, owners, smap


def graced_pod(name: str, seconds: int, cpu: str = "1"):
    from kubernetes_tpu.controllers import (
        NOT_READY_TAINT_KEY,
        UNREACHABLE_TAINT_KEY,
    )

    return (
        make_pod(name)
        .req({"cpu": cpu})
        .toleration(NOT_READY_TAINT_KEY, op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=seconds)
        .toleration(UNREACHABLE_TAINT_KEY, op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=seconds)
    )


def test_lease_frames_route_to_owning_shard_only():
    """A Lease renewal reaches exactly the owning shard's lifecycle
    controller — a foreign owner tracking the heartbeat would taint a
    node it does not hold."""
    pin = {"left": 0, "right": 1}
    router, owners, _ = build_lifecycle_fleet(2, pin=pin)
    router.add_object("Node", big_node("left"))
    router.add_object("Node", big_node("right"))
    router.add_object("Lease", t.Lease("left", 1.0))
    router.add_object("Lease", t.Lease("right", 2.0))
    router.add_object("Lease", t.Lease("left", 3.0))
    assert owners[0].sched.node_lifecycle.heartbeats == {"left": 3.0}
    assert owners[1].sched.node_lifecycle.heartbeats == {"right": 2.0}
    assert router._lease_frames.get(shard="0") == 2
    assert router._lease_frames.get(shard="1") == 1


def test_node_death_evicts_and_rebinds_on_another_shard():
    """The cross-shard half of loop closure: a node dies inside shard 0,
    the owner's lifecycle controller taints + evicts, and the router
    requeues the pod to rebind on shard 1 — routing purged, gang credit
    debited, PDB debits broadcast, cross-shard rebind counted."""
    pin = {"doomed": 0, "spare": 0, "roomy": 1}
    router, owners, _ = build_lifecycle_fleet(2, pin=pin)
    router.add_object("Node", big_node("doomed", cpu="4"))
    # spare keeps shard 0 viable for hashing but cannot host the victim.
    router.add_object("Node", big_node("spare", cpu="1"))
    router.add_object("Node", big_node("roomy", cpu="4"))
    victim = (
        graced_pod("victim", 4, cpu="2")
        .label("app", "guarded")
        .node("doomed")
        .obj()
    )
    router.add_object("Pod", victim)
    pdb = t.PodDisruptionBudget(
        name="guard", selector={"app": "guarded"}, disruptions_allowed=3
    )
    router.add_object("PodDisruptionBudget", pdb)
    assert router._pod_shard[victim.uid] == 0

    for name in ("doomed", "spare", "roomy"):
        router.add_object("Lease", t.Lease(name, 0.0))
    for ts in range(2, 13, 2):  # doomed goes silent after t=0
        for name in ("spare", "roomy"):
            router.add_object("Lease", t.Lease(name, float(ts)))
    # Staleness (>5) wrote the NotReady pair on shard 0 and the 4s
    # toleration expired: the eviction rode a Lease response back.
    assert owners[0].sched.taint_eviction.evictions == 1
    assert victim.uid in router.evicted_pending
    assert victim.uid not in router._pod_shard
    assert router._lifecycle_evictions.get(shard="0") == 1
    # PDB debit broadcast: both owners' copies show the disruption.
    for owner in owners.values():
        assert owner.sched.pdbs["guard"].disruptions_allowed == 2

    outs = router.schedule_all_pending(wait_backoff=True)
    assert [(o.pod.name, o.node_name) for o in outs if o.node_name] == [
        ("victim", "roomy")
    ]
    assert router._pod_shard[victim.uid] == 1
    assert victim.uid not in router.evicted_pending
    assert router._lifecycle_rebinds.get(cross_shard="true") == 1
    assert router.lifecycle_stats()["cross_shard_rebinds"] == 1


def node_loss_feed(router_or_sched, fleet: bool):
    """The scripted node-death op stream (run_fault_matrix's scenario),
    driven identically through a single armed scheduler or the fleet."""
    import run_fault_matrix as rfm

    nodes, bound, pending = rfm.node_loss_objects()
    if fleet:
        r = router_or_sched
        for n in nodes:
            r.add_object("Node", n)
        for p in bound:
            r.add_object("Pod", p)
        for p in pending:
            r.add_pod(p)
        r.schedule_all_pending(wait_backoff=True)
        for name in ("nd1", "n2", "n3", "n4"):
            r.add_object("Lease", t.Lease(name, 0.0))
        for ts in rfm.NODE_LOSS_LEASE_TS:
            for name in ("n2", "n3", "n4"):
                r.add_object("Lease", t.Lease(name, ts))
        wait_for_backoffs(r.queue)
        r.schedule_all_pending(wait_backoff=True)
        return r.bindings()
    s = router_or_sched
    for n in nodes:
        s.add_node(n)
    for p in bound + pending:
        s.add_pod(p)
    s.schedule_all_pending(wait_backoff=True)
    for name in ("nd1", "n2", "n3", "n4"):
        s.renew_node_lease(t.Lease(name, 0.0))
    for ts in rfm.NODE_LOSS_LEASE_TS:
        for name in ("n2", "n3", "n4"):
            s.renew_node_lease(t.Lease(name, ts))
    wait_for_backoffs(s.queue)
    s.schedule_all_pending(wait_backoff=True)
    return {
        uid: pr.node_name
        for uid, pr in sorted(s.cache.pods.items())
        if pr.bound
    }


@pytest.mark.parametrize("n_shards", [2, 4])
def test_fleet_node_loss_binds_bit_identical_to_armed_single(n_shards):
    """The node-loss oracle: an N-shard fleet with per-owner lifecycle
    reproduces the ARMED single scheduler's response to a scripted node
    death bit for bit — same taint timeline, same evictions (graced v1/
    v2 plus the GC-horizon sticky pod), same rebind placements."""
    single = node_loss_feed(arm_single(), fleet=False)
    # The doomed node's pods all rebound somewhere real.
    for uid in ("default/v1", "default/v2", "default/sticky"):
        assert single.get(uid) not in (None, "", "nd1"), single
    smap = ShardMap(n_shards=n_shards, n_buckets=16)
    owners = {
        k: ShardOwner(k, mk_lifecycle_sched(), smap, lifecycle=LIFECYCLE)
        for k in range(n_shards)
    }
    router = FleetRouter(owners, smap, batch_size=8)
    router.profile_filters = tuple(owners[0].sched.profile.filters)
    assert node_loss_feed(router, fleet=True) == single
    # Loop closure is visible fleet-side: evictions absorbed, all
    # rebound, nothing pending.
    stats = router.lifecycle_stats()
    assert stats["evictions_absorbed"] == 3
    assert stats["rebinds"] == 3
    assert stats["pending_rebinds"] == 0


def test_owner_snapshot_persists_lifecycle_clock(tmp_path):
    """The per-owner snapshot carries the logical clock, heartbeats and
    the GC's unreachable stamps: a takeover resumes the incident's
    timeline instead of rewinding to zero."""
    pin = {"left": 0}
    root = str(tmp_path / "fleet")
    smap = ShardMap(n_shards=1, n_buckets=16, overrides=pin)
    owner = ShardOwner(
        0, mk_lifecycle_sched(), smap,
        state_dir=os.path.join(root, "shard0"), lifecycle=LIFECYCLE,
    )
    owner.sched.snapshot_every_records = 1  # a checkpoint behind every commit
    owner.add_object("Node", big_node("left"))
    sticky = (
        make_pod("sticky").req({"cpu": "1"})
        .toleration("", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE)
        .node("left").obj()
    )
    owner.add_object("Pod", sticky)
    owner.add_object("Lease", t.Lease("left", 0.0))
    # Advance the clock via a second (pinned) node's renewals until left
    # is Unreachable, then snapshot.
    smap.overrides["other"] = 0
    owner.add_object("Node", big_node("other"))
    owner.add_object("Lease", t.Lease("other", 14.0))
    assert owner.sched.node_lifecycle.stats()["states"]["unreachable"] == 1
    since = dict(owner.sched.pod_gc._unreachable_since)
    assert since.get("left") is not None
    from kubernetes_tpu import journal as journal_mod

    owner.journal.snapshot(journal_mod.scheduler_state(owner.sched))
    owner.close()

    recovered = recover_shard(
        os.path.join(root, "shard0"), mk_lifecycle_sched, 0, smap,
        lifecycle=LIFECYCLE,
    )
    nl = recovered.sched.node_lifecycle
    assert nl.now() == 14.0
    assert nl.heartbeats["other"] == 14.0
    assert recovered.sched.pod_gc._unreachable_since == since
    recovered.close()


def test_takeover_replays_incident_and_finishes_eviction(tmp_path):
    """The double failure: the node dies in shard 0, the owner journals
    the NotReady taint, then the OWNER is killed inside the taint-write→
    eviction window.  Takeover (recover_shard) replays the taint, the
    host-truth re-feed keeps it (the owner-side recovered-taints
    overlay), the remaining lease schedule finishes the eviction, and
    the router requeues the pod onto the surviving shard — converging to
    the unkilled fleet's bindings."""
    pin = {"doomed": 0, "spare": 0, "roomy": 1}
    nodes = lambda: [  # noqa: E731
        big_node("doomed", cpu="4"),
        big_node("spare", cpu="1"),
        big_node("roomy", cpu="4"),
    ]
    victim = lambda: graced_pod("victim", 4, cpu="2").node("doomed").obj()  # noqa: E731

    def feed(router, upto: float):
        for n in nodes():
            router.add_object("Node", n)
        router.add_object("Pod", victim())
        for name in ("doomed", "spare", "roomy"):
            router.add_object("Lease", t.Lease(name, 0.0))
        for ts in range(2, int(upto) + 1, 2):
            for name in ("spare", "roomy"):
                router.add_object("Lease", t.Lease(name, float(ts)))

    # The unkilled reference.
    ref_router, _, _ = build_lifecycle_fleet(2, pin=pin)
    feed(ref_router, 12.0)
    ref_router.schedule_all_pending(wait_backoff=True)
    reference = ref_router.bindings()
    assert reference["default/victim"] == "roomy"

    # The doomed run: stop at t=6 — the NotReady taint (grace 5) is
    # journaled, the 4s tolerationSeconds deadline (6+4=10) has NOT
    # fired.  Checkpoint shard 0 (the snapshot carries the tainted node,
    # the heartbeats and the clock), then kill the owners (journals
    # close, leases release).
    root = str(tmp_path / "crash")
    router, owners, _ = build_lifecycle_fleet(2, pin=pin, state_root=root)
    feed(router, 6.0)
    assert owners[0].sched.node_lifecycle.stats()["states"]["notready"] == 1
    assert owners[0].sched.taint_eviction.evictions == 0
    assert owners[0].sched.taint_eviction.pending  # deadline armed
    from kubernetes_tpu import journal as journal_mod

    owners[0].journal.snapshot(journal_mod.scheduler_state(owners[0].sched))
    for owner in owners.values():
        owner.journal.close()
        owner.lease.release()

    # Takeover: fresh armed owners replay each shard's journal; the
    # taint record re-applies and re-arms the deadline against the
    # RESTORED clock.
    recovered = {
        k: recover_shard(
            os.path.join(root, f"shard{k}"), mk_lifecycle_sched, k,
            ShardMap(n_shards=2, n_buckets=16, overrides=pin),
            lifecycle=LIFECYCLE,
        )
        for k in range(2)
    }
    from kubernetes_tpu.controllers import NODE_NOT_READY

    assert recovered[0].sched.node_lifecycle.states == {
        "doomed": NODE_NOT_READY
    }
    smap2 = ShardMap(n_shards=2, n_buckets=16, overrides=pin)
    router2 = FleetRouter(recovered, smap2, batch_size=8)
    router2.profile_filters = tuple(recovered[0].sched.profile.filters)
    # Host-truth re-feed: the dead node relists UNTAINTED — the owner's
    # recovered-taints overlay must keep the journal-authored pair.
    for n in nodes():
        router2.add_object("Node", n)
    router2.reconcile_recovered()
    router2.adopt_bindings()
    router2.drain_evictions()
    router2.add_object("Pod", victim())  # still bound per host truth
    rec0 = recovered[0].sched.cache.nodes["doomed"]
    assert any(
        taint.key == "node.kubernetes.io/not-ready"
        for taint in rec0.node.spec.taints
    )
    # Re-run the FULL lease schedule (renewals are monotone: the replayed
    # prefix is a no-op against recovered state) — t=8..12 expires the
    # re-armed grace, the eviction journals on shard 0 and the pod
    # rebinds on shard 1.
    for name in ("doomed", "spare", "roomy"):
        router2.add_object("Lease", t.Lease(name, 0.0))
    for ts in range(2, 13, 2):
        for name in ("spare", "roomy"):
            router2.add_object("Lease", t.Lease(name, float(ts)))
    router2.schedule_all_pending(wait_backoff=True)
    assert router2.bindings() == reference
    assert router2._lifecycle_rebinds.get(cross_shard="true") == 1
    for owner in recovered.values():
        owner.close()


def test_absorb_shard_carries_pending_evictions(tmp_path):
    """Survivor takeover mid-incident: the dead owner's journal holds an
    evict record whose pod never rebound.  absorb_shard transfers the
    pending requeue (and the heartbeat history) to the survivor; the
    adopting router drains it and completes the loop."""
    pin = {"doomed": 0, "spare": 0, "roomy": 1}
    root = str(tmp_path / "fleet")
    router, owners, smap = build_lifecycle_fleet(2, pin=pin, state_root=root)
    router.add_object("Node", big_node("doomed", cpu="4"))
    router.add_object("Node", big_node("spare", cpu="1"))
    router.add_object("Node", big_node("roomy", cpu="4"))
    victim = graced_pod("victim", 4, cpu="2").node("doomed").obj()
    router.add_object("Pod", victim)
    for name in ("doomed", "spare", "roomy"):
        router.add_object("Lease", t.Lease(name, 0.0))
    for ts in (2.0, 4.0, 6.0, 8.0):
        for name in ("spare", "roomy"):
            router.add_object("Lease", t.Lease(name, ts))
    # Checkpoint mid-incident (the taint is in the snapshotted node
    # state, the heartbeat history with it; no commit ever ticked the
    # cadence on shard 0), then let the eviction fire — its record lands
    # in the post-barrier WAL.
    from kubernetes_tpu import journal as journal_mod

    owners[0].journal.snapshot(journal_mod.scheduler_state(owners[0].sched))
    for ts in (10.0, 12.0):
        for name in ("spare", "roomy"):
            router.add_object("Lease", t.Lease(name, ts))
    # Evicted on shard 0, absorbed by the router — but shard 0 dies
    # before any rebind: the requeue is lost WITH the router (a fresh
    # one adopts from the owners), so the journaled evict record is the
    # only durable copy.
    assert victim.uid in router.evicted_pending
    owners[0].journal.close()
    owners[0].lease.release()

    survivor = owners[1]
    record = absorb_shard(
        survivor, os.path.join(root, "shard0"), 0, mk_lifecycle_sched,
        smap, lifecycle=LIFECYCLE,
    )
    assert record["op"] == "merge"
    # The replayed evict transferred to the survivor's RECOVERED bucket
    # (only the adopting router's explicit drain takes it).
    assert [e["uid"] for e in survivor.recovered_evictions] == [victim.uid]
    router2 = FleetRouter({1: survivor}, smap, batch_size=8)
    router2.profile_filters = tuple(survivor.sched.profile.filters)
    # Host-truth node re-feed (UNTAINTED shapes): the absorbed
    # recovered-taints overlay must keep the dead node cordoned.
    for n in ("doomed", "spare", "roomy"):
        router2.add_object("Node", big_node(n, cpu={"doomed": "4",
                                                    "spare": "1",
                                                    "roomy": "4"}[n]))
    assert any(
        taint.key == "node.kubernetes.io/not-ready"
        or taint.key == "node.kubernetes.io/unreachable"
        for taint in survivor.sched.cache.nodes["doomed"].node.spec.taints
    )
    router2.adopt_bindings()
    assert router2.drain_evictions() == 1
    router2.schedule_all_pending(wait_backoff=True)
    assert router2.bindings()["default/victim"] == "roomy"
    survivor.close()


def test_wire_owner_deadline_retry_and_unreachable(tmp_path):
    """WireShardOwner: a hung owner trips the per-call deadline (counted),
    an idempotent op reconnects and retries (counted), and a dead owner
    exhausts the budget into FleetOwnerUnreachable — takeover's cue."""
    from kubernetes_tpu.faults import FaultPlan
    from kubernetes_tpu.fleet import FleetOwnerUnreachable, WireShardOwner
    from kubernetes_tpu.framework.metrics import MetricsRegistry
    from kubernetes_tpu.sidecar.server import SidecarClient, SidecarServer

    smap = ShardMap(n_shards=1, n_buckets=16)
    owner = ShardOwner(0, mk_sched(), smap)
    sock = str(tmp_path / "owner.sock")
    srv = SidecarServer(sock, scheduler=owner.sched, fleet_owner=owner)
    srv.serve_background()
    try:
        registry = MetricsRegistry()
        # First connection hangs on the first fleet frame: the deadline
        # fires, the wire owner reconnects (fresh, unwrapped socket) and
        # the retry succeeds.
        plan = FaultPlan(seed=3).add_rule("hang", op="fleet", nth=1)
        client = SidecarClient(sock, deadline_s=0.5)
        client.sock = plan.wrap(client.sock)
        wire = WireShardOwner(
            client, path=sock, deadline_s=0.5, max_retries=2,
            registry=registry, shard_id=0,
        )
        stats = wire.call("stats", {})
        assert stats["shard"] == 0
        assert registry.counter(
            "scheduler_fleet_call_timeouts_total"
        ).get(op="stats") == 1
        assert registry.counter(
            "scheduler_fleet_call_retries_total"
        ).get(op="stats") == 1
        # Dead owner: the server goes away, reconnects are refused, and
        # the bounded budget degrades to FleetOwnerUnreachable.
        srv.close()
        if os.path.exists(sock):
            os.unlink(sock)
        with pytest.raises(FleetOwnerUnreachable):
            wire.call("stats", {})
        # A non-idempotent op never retries — straight to takeover.
        with pytest.raises(FleetOwnerUnreachable):
            wire.call("commit", {"pod": {}, "node": "x"})
        wire.close()
    finally:
        srv.close()


# -- the warm-standby owner pool (ISSUE 18) ---------------------------------

from kubernetes_tpu.fleet.standby import (  # noqa: E402
    JOURNAL_NAME,
    StandbyPool,
    StandbyServe,
)


def slot_factory(log=None):
    """A pool factory whose payload records its slot id (and carries a
    real warm scheduler, so a promoted payload is immediately usable)."""

    def factory(slot_id: int):
        if log is not None:
            log.append(slot_id)
        return {"slot": slot_id, "sched": mk_sched()}

    return factory


def test_standby_promotes_oldest_slot_and_refills(tmp_path):
    sd = str(tmp_path / "pool")
    pool = StandbyPool(sd, slot_factory(), size=2)
    assert pool.status()["pool_size"] == 2
    payload = pool.promote(1, "takeover")
    # Oldest warm slot first, claim file written, pool topped back up
    # BEHIND the promotion (the next incident finds it full again).
    assert payload["slot"] == 0
    assert os.path.exists(os.path.join(sd, "slot-0.claim"))
    assert pool.status()["pool_size"] == 2
    assert pool.status()["promotions"] == {"takeover": 1}
    # The promoted payload is a live scheduler: it can own a shard and
    # bind immediately — that is what "warm" means.
    owner = ShardOwner(0, payload["sched"], ShardMap(n_shards=1))
    owner.sched.add_node(big_node("sb-n1"))
    owner.sched.add_pod(make_pod("sb-p1").req({"cpu": "1"}).obj())
    out = owner.sched.schedule_all_pending(wait_backoff=True)
    assert [o.node_name for o in out if o.pod.name == "sb-p1"] == ["sb-n1"]


def test_standby_stale_schema_never_promoted_evicted_instead(tmp_path):
    retired = []
    pool = StandbyPool(
        str(tmp_path / "pool"),
        slot_factory(),
        size=2,
        schema_version=1,
        retire=lambda payload: retired.append(payload["slot"]),
    )
    # The live featurization schema moves on while the pool hasn't
    # synced yet: every warm slot is stale, promote must MISS (a stale
    # XLA cache would recompile mid-incident — the exact cost the pool
    # pre-pays), never hand one out.
    pool.schema_version = 2
    assert pool.promote(0, "takeover") is None
    assert pool.misses == 1
    # sync_schema retires + respawns: the stale slots exit via eviction
    # only, and the respawned slots (new ids, live schema) promote.
    pool.schema_version = 1
    assert pool.sync_schema(2) == 2
    assert retired == [0, 1]
    assert pool.stale_evictions == 2
    payload = pool.promote(0, "revive")
    assert payload is not None and payload["slot"] >= 2
    assert pool.status()["schema_stale_evictions"] == 2


def test_standby_claim_race_loser_skips_to_next_slot(tmp_path):
    pool = StandbyPool(str(tmp_path / "pool"), slot_factory(), size=2)
    # Another promoter (a racing router over the same state_dir) wins
    # slot 0's O_EXCL claim first; this promoter must skip to slot 1,
    # never double-offer the claimed one.
    assert pool._try_claim(0)
    payload = pool.promote(3, "takeover")
    assert payload["slot"] == 1
    assert any(s.state == "claimed-elsewhere" for s in pool.slots)


def test_standby_wal_replay_never_reoffers_consumed_slots(tmp_path):
    sd = str(tmp_path / "pool")
    pool = StandbyPool(sd, slot_factory(), size=2)
    assert pool.promote(1, "takeover")["slot"] == 0
    pool.close()
    # Reopen (a restarted router): the WAL says slot 0 was consumed and
    # ids 0-2 were spawned — the new incarnation spawns FRESH ids only
    # and still remembers the promotion ledger.
    reopened = StandbyPool(sd, slot_factory(), size=2)
    assert {s.slot_id for s in reopened.idle()}.isdisjoint({0, 1, 2})
    assert reopened.promotions == {"takeover": 1}
    assert reopened.promote(0, "revive")["slot"] >= 3


def test_standby_orphan_claim_is_conservatively_consumed(tmp_path):
    sd = str(tmp_path / "pool")
    pool = StandbyPool(sd, slot_factory(), size=1)
    # A promotion that died between the claim and the WAL append leaves
    # only the claim file behind (the standby-pre-claim/-mid-promotion
    # kill window).  Reopen must treat the id as consumed.
    assert pool._try_claim(0)
    pool.close()
    reopened = StandbyPool(sd, slot_factory(), size=1)
    assert all(s.slot_id != 0 for s in reopened.slots)
    assert reopened.promote(0, "takeover")["slot"] != 0


def test_standby_wal_tolerates_torn_tail(tmp_path):
    sd = str(tmp_path / "pool")
    pool = StandbyPool(sd, slot_factory(), size=1)
    pool.promote(1, "takeover")
    pool.close()
    # SIGKILL mid-append tears the last record: the complete prefix
    # stands, the torn line is dropped, reopen still never re-offers.
    with open(os.path.join(sd, JOURNAL_NAME), "a", encoding="utf-8") as f:
        f.write('{"op": "promote", "slot": 1, "rea')
    reopened = StandbyPool(sd, slot_factory(), size=1)
    assert reopened.promotions == {"takeover": 1}
    assert all(s.slot_id not in (0,) for s in reopened.idle())


def test_standby_mirror_is_atomic_and_current(tmp_path):
    import json as _json

    sd = str(tmp_path / "pool")
    pool = StandbyPool(sd, slot_factory(), size=2)
    pool.promote(1, "takeover")
    with open(os.path.join(sd, "standby.json"), encoding="utf-8") as f:
        mirror = _json.load(f)
    # `fleet status --sockets` renders THIS file without touching the
    # pool: it must match live status (modulo the monotonic ages).
    live = pool.status()
    for doc in (mirror, live):
        for s in doc["slots"]:
            s.pop("warm_age_s", None)
    assert mirror == live
    assert mirror["promotions_total"] == 1


def test_standby_serve_adopts_via_dispatch(tmp_path):
    sched = mk_sched()
    serve = StandbyServe(sched, schema_version=7)
    st = serve.standby_dispatch("standby_status", {})
    assert st["standby"] is True and st["schema_version"] == 7
    # Pre-adoption, real fleet ops are refused — the child owns nothing.
    with pytest.raises(ValueError):
        serve.standby_dispatch("stats", {})
    res = serve.standby_dispatch(
        "adopt_shard",
        {
            "shard_id": 1,
            "map": {"buckets": ShardMap(n_shards=2).buckets},
            "journal_dir": str(tmp_path / "journal"),
        },
    )
    assert res["adopted"] == 1 and res["already"] is False
    # Post-adoption the SAME dispatch surface flips to the real owner.
    st = serve.standby_dispatch("standby_status", {})
    assert st["standby"] is False and st["adopted_shard"] == 1
    again = serve.standby_dispatch("adopt_shard", {"shard_id": 1})
    assert again["already"] is True


def test_standby_serve_preadoption_preempt_is_eval_only(tmp_path):
    sched = mk_sched()
    sched.add_node(
        make_node("pe-n1").capacity({"cpu": "1", "pods": 110}).obj()
    )
    sched.add_pod(
        make_pod("pe-victim").req({"cpu": "1"}).priority(1).node("pe-n1").obj()
    )
    serve = StandbyServe(sched)
    from kubernetes_tpu.api import serialize

    contender = serialize.to_dict(
        make_pod("pe-contender").req({"cpu": "1"}).priority(100).obj()
    )
    res = serve.standby_dispatch("preempt_propose", {"pod": contender})
    # Dry run only: whatever the proposal says, NOTHING was deleted or
    # nominated — the child is still parked and unadopted, the victim
    # still bound.
    assert isinstance(res, dict)
    assert serve.owner is None
    assert "default/pe-victim" in sched.cache.pods
