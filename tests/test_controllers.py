"""DisruptionController — the PDB-status reconcile
(pkg/controller/disruption/disruption.go:732 trySync; formula at :803
getExpectedPodCount and :993 updatePdbStatus)."""

import pytest

from kubernetes_tpu.api import types as t
from kubernetes_tpu.api.wrappers import make_node, make_pod
from kubernetes_tpu.controllers import scale_int_or_percent
from kubernetes_tpu.framework.config import fit_only_profile
from kubernetes_tpu.scheduler import TPUScheduler


def sched(batch_size=8):
    return TPUScheduler(profile=fit_only_profile(), batch_size=batch_size)


def _pdb(name, labels, **kw):
    return t.PodDisruptionBudget(
        name=name,
        selector=t.LabelSelector(match_labels=tuple(labels.items())),
        **kw,
    )


def test_scale_int_or_percent_matches_intstr():
    # intstr.GetScaledValueFromIntOrPercent semantics.
    assert scale_int_or_percent(3, 10, True) == 3  # ints pass through
    assert scale_int_or_percent("50%", 3, True) == 2  # ceil(1.5)
    assert scale_int_or_percent("50%", 3, False) == 1  # floor(1.5)
    assert scale_int_or_percent("100%", 7, True) == 7
    assert scale_int_or_percent("0%", 7, True) == 0
    with pytest.raises(ValueError):
        scale_int_or_percent("half", 10, True)


def _bind_app_pods(s, n, label=("app", "db")):
    s.add_node(make_node("n1").capacity({"cpu": "64", "pods": 110}).obj())
    for i in range(n):
        s.add_pod(
            make_pod(f"p{i}").req({"cpu": "1"}).label(*label).node("n1").obj()
        )


def test_min_available_int():
    s = sched()
    _bind_app_pods(s, 5)
    pdb = _pdb("db", {"app": "db"}, min_available=3)
    s.add_pdb(pdb)
    # 5 healthy − 3 desired = 2 allowed, computed at add time.
    assert pdb.disruptions_allowed == 2


def test_min_available_percent_rounds_up():
    s = sched()
    _bind_app_pods(s, 3)
    pdb = _pdb("db", {"app": "db"}, min_available="50%")
    s.add_pdb(pdb)
    # desired = ceil(3 × 50%) = 2 → allowed = 1.
    assert pdb.disruptions_allowed == 1


def test_max_unavailable():
    s = sched()
    _bind_app_pods(s, 4)
    pdb = _pdb("db", {"app": "db"}, max_unavailable=1)
    s.add_pdb(pdb)
    assert pdb.disruptions_allowed == 1
    pdb2 = _pdb("db2", {"app": "db"}, max_unavailable="50%")
    s.add_pdb(pdb2)
    # mu = ceil(4 × 50%) = 2 → desired = 2 → allowed = 2.
    assert pdb2.disruptions_allowed == 2


def test_selector_and_namespace_scope():
    s = sched()
    _bind_app_pods(s, 2)
    s.add_pod(
        make_pod("other").req({"cpu": "1"}).label("app", "web").node("n1").obj()
    )
    pdb = _pdb("db", {"app": "db"}, min_available=1, namespace="prod")
    s.add_pdb(pdb)
    assert pdb.disruptions_allowed == 0  # wrong namespace: zero matching
    pdb2 = _pdb("db2", {"app": "db"}, min_available=1)
    s.add_pdb(pdb2)
    assert pdb2.disruptions_allowed == 1  # the web pod doesn't count


def test_queued_pods_are_not_healthy():
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "2", "pods": 110}).obj())
    s.add_pod(make_pod("bound").req({"cpu": "1"}).label("app", "db").node("n1").obj())
    # Queued (never scheduled): matches the selector but is not healthy.
    s.queue.add(make_pod("pending").req({"cpu": "999"}).label("app", "db").obj())
    pdb = _pdb("db", {"app": "db"}, min_available=1)
    s.add_pdb(pdb)
    assert pdb.disruptions_allowed == 0  # 1 healthy − 1 desired


def test_spec_less_pdb_keeps_informer_status():
    s = sched()
    _bind_app_pods(s, 5)
    pdb = _pdb("db", {"app": "db"}, disruptions_allowed=7)
    s.add_pdb(pdb)
    assert pdb.disruptions_allowed == 7  # untouched: wire-fed status


def test_preemption_honors_controller_computed_budget():
    # End-to-end: the controller computes allowed=1 for three db victims;
    # a preemptor needing two evictions must take at most one db pod
    # without violating — the PDB-violating victim sorts into the
    # reprieve-first class and the final set violates as little as the
    # reference would (criterion 1 minimizes violations, it does not
    # forbid them).
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "4", "pods": 110}).obj())
    for i in range(3):
        s.add_pod(
            make_pod(f"db{i}").req({"cpu": "1"}).priority(1)
            .label("app", "db").start_time(float(i)).node("n1").obj()
        )
    s.add_pod(
        make_pod("loose").req({"cpu": "1"}).priority(1).node("n1").obj()
    )
    pdb = _pdb("db", {"app": "db"}, min_available=2)
    s.add_pdb(pdb)
    assert pdb.disruptions_allowed == 1
    s.add_pod(make_pod("vip").req({"cpu": "2"}).priority(100).obj())
    out = s.schedule_all_pending(wait_backoff=True)
    vip = [o for o in out if o.pod.name == "vip" and o.node_name]
    assert vip and vip[0].node_name == "n1"
    evicted = {u.split("/")[-1] for o in out for u in o.victim_uids}
    # Two evictions needed; the unprotected pod must be among them and at
    # most one db pod may go (budget 1).
    assert "loose" in evicted
    assert len(evicted & {"db0", "db1", "db2"}) <= 1
    # The eviction debited the budget; a resync from live state agrees
    # (2 healthy db pods, minAvailable 2 → 0 allowed).
    s.disruption_controller.sync()
    assert pdb.disruptions_allowed == 0


# ---------------------------------------------------------------------------
# TaintEvictionController (pkg/controller/tainteviction/taint_eviction.go)
# ---------------------------------------------------------------------------


def _tainted(name, *taints):
    n = make_node(name).capacity({"cpu": "8", "pods": 110})
    for key, effect in taints:
        n = n.taint(key, "true", effect)
    return n.obj()


def test_no_execute_evicts_intolerant_pod():
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(make_pod("victim").req({"cpu": "1"}).node("n1").obj())
    s.add_pod(
        make_pod("safe").req({"cpu": "1"})
        .toleration("maint", op=t.TOLERATION_OP_EXISTS, effect=t.EFFECT_NO_EXECUTE)
        .node("n1").obj()
    )
    s.update_node(_tainted("n1", ("maint", t.EFFECT_NO_EXECUTE)))
    assert "default/victim" not in s.cache.pods  # evicted immediately
    assert "default/safe" in s.cache.pods  # tolerates forever
    assert s.taint_eviction.evictions == 1
    assert not s.taint_eviction.pending


def test_no_schedule_taint_does_not_evict():
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(make_pod("p").req({"cpu": "1"}).node("n1").obj())
    s.update_node(_tainted("n1", ("maint", t.EFFECT_NO_SCHEDULE)))
    assert "default/p" in s.cache.pods


def test_toleration_seconds_schedules_delayed_eviction():
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(
        make_pod("graced").req({"cpu": "1"})
        .toleration(
            "maint", op=t.TOLERATION_OP_EXISTS,
            effect=t.EFFECT_NO_EXECUTE, seconds=30,
        )
        .node("n1").obj()
    )
    tec = s.taint_eviction
    tainted = _tainted("n1", ("maint", t.EFFECT_NO_EXECUTE))
    s.update_node(tainted)
    uid = "default/graced"
    assert uid in s.cache.pods and uid in tec.pending
    # Not due yet.
    assert tec.tick(tec.pending[uid][1] - 1.0) == 0
    assert uid in s.cache.pods
    # Due: evicted.
    deadline = tec.pending[uid][1]
    assert tec.tick(deadline) == 1
    assert uid not in s.cache.pods


def test_min_toleration_seconds_wins():
    # Two matching tolerations, 300s and 30s: min wins
    # (getMinTolerationTime).
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration("maint", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=300)
        .toleration("", op=t.TOLERATION_OP_EXISTS, seconds=30)
        .node("n1").obj()
    )
    now = 1000.0
    s.taint_eviction.handle_node(
        s.cache.nodes["n1"].node, now
    )  # no taints yet: no-op
    s.update_node(_tainted("n1", ("maint", t.EFFECT_NO_EXECUTE)))
    uid = "default/p"
    armed, dl = s.taint_eviction.pending[uid]
    assert dl - armed == pytest.approx(30)  # min(300, 30): the 30s toleration bounds it


def test_taint_removal_cancels_pending():
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration("maint", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=60)
        .node("n1").obj()
    )
    s.update_node(_tainted("n1", ("maint", t.EFFECT_NO_EXECUTE)))
    assert s.taint_eviction.pending
    s.update_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    assert not s.taint_eviction.pending
    assert "default/p" in s.cache.pods


def test_pod_arriving_bound_to_tainted_node_is_judged():
    s = sched()
    s.add_node(_tainted("n1", ("maint", t.EFFECT_NO_EXECUTE)))
    s.add_pod(make_pod("late").req({"cpu": "1"}).node("n1").obj())
    assert "default/late" not in s.cache.pods  # evicted on arrival


def test_taint_churn_does_not_rearm_deadline():
    # Regression (r5 review): unrelated taint changes re-run evaluate();
    # the pending deadline must not be pushed out from `now` each time
    # (upstream keeps the scheduled eviction's original start).
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration("maint", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=300)
        .toleration("extra", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE)
        .node("n1").obj()
    )
    s.update_node(_tainted("n1", ("maint", t.EFFECT_NO_EXECUTE)))
    uid = "default/p"
    first = s.taint_eviction.pending[uid]
    # A second, tolerated-forever taint appears later: re-evaluation must
    # keep the original armed time AND deadline (300s grace unchanged).
    s.update_node(_tainted(
        "n1", ("maint", t.EFFECT_NO_EXECUTE), ("extra", t.EFFECT_NO_EXECUTE)
    ))
    assert s.taint_eviction.pending[uid] == first


def test_self_scheduled_pod_gets_no_execute_timer():
    # Regression (r5 review): a pod THIS scheduler places onto a tainted
    # node (it tolerates the taint, so the filter admits it) must start
    # its tolerationSeconds clock at bind, like the reference's
    # handlePodUpdate on the binding update.
    s = sched()
    s.add_node(_tainted("n1", ("maint", t.EFFECT_NO_EXECUTE)))
    s.add_pod(
        make_pod("timed").req({"cpu": "1"})
        .toleration("maint", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=60)
        .obj()
    )
    out = s.schedule_all_pending(wait_backoff=True)
    placed = [o for o in out if o.pod.name == "timed" and o.node_name]
    assert placed and placed[0].node_name == "n1"
    assert "default/timed" in s.taint_eviction.pending


def test_deleted_pod_pending_eviction_dies_with_it():
    # Regression (r5 review): delete_pod must clear the pending deadline —
    # a re-created pod with the same namespace/name must not inherit it.
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_node(make_node("n2").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration("maint", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=60)
        .node("n1").obj()
    )
    s.update_node(_tainted("n1", ("maint", t.EFFECT_NO_EXECUTE)))
    uid = "default/p"
    assert uid in s.taint_eviction.pending
    s.delete_pod(uid)
    assert uid not in s.taint_eviction.pending
    # Same name re-created on an UNTAINTED node: no deadline, never evicted.
    s.add_pod(make_pod("p").req({"cpu": "1"}).node("n2").obj())
    assert uid not in s.taint_eviction.pending
    assert s.taint_eviction.tick(1e18) == 0
    assert uid in s.cache.pods


def test_removed_short_grace_taint_restores_longer_deadline():
    # Regression (r5 review): deadline = armed_at + min over the CURRENT
    # taints' graces — removing the short-grace taint while a
    # longer-tolerated one remains must restore the longer deadline.
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration("a", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=30)
        .toleration("b", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=600)
        .node("n1").obj()
    )
    tec = s.taint_eviction
    uid = "default/p"
    taints_ab = [t.Taint("a", "true", t.EFFECT_NO_EXECUTE),
                 t.Taint("b", "true", t.EFFECT_NO_EXECUTE)]
    tec.evaluate(uid, s.cache.pods[uid].pod, taints_ab, 1000.0)
    armed, dl = tec.pending[uid]
    assert (armed, dl) == (1000.0, 1030.0)  # min(30, 600)
    # Taint a removed, b remains: grace recomputes from the SAME start.
    tec.evaluate(
        uid, s.cache.pods[uid].pod,
        [t.Taint("b", "true", t.EFFECT_NO_EXECUTE)], 1010.0,
    )
    assert tec.pending[uid] == (1000.0, 1600.0)
    # Taint a RE-ADDED at 1020: its grace clock restarts at the re-add
    # (1020 + 30 = 1050), it does not inherit the stale 1000-based timer
    # (the ISSUE 9 re-arm fix) — while b keeps its original 1000 start.
    tec.evaluate(uid, s.cache.pods[uid].pod, taints_ab, 1020.0)
    assert tec.pending[uid] == (1000.0, 1050.0)


def test_taint_removed_and_readded_resets_deadline():
    # The ISSUE 9 re-arm gap: with ANOTHER NoExecute taint keeping the
    # pending entry alive, a taint removed and re-added must reset its
    # tolerationSeconds deadline rather than inherit the stale timer.
    s = sched()
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration("short", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=10)
        .toleration("forever", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE)
        .node("n1").obj()
    )
    tec = s.taint_eviction
    uid = "default/p"
    pod = s.cache.pods[uid].pod
    short = t.Taint("short", "true", t.EFFECT_NO_EXECUTE)
    forever = t.Taint("forever", "true", t.EFFECT_NO_EXECUTE)
    tec.evaluate(uid, pod, [short, forever], 100.0)
    assert tec.pending[uid] == (100.0, 110.0)
    # `short` removed at 105 — `forever` keeps the entry pending (its
    # matching toleration is nil-seconds, so nothing bounds a deadline
    # but the pod stays judged).
    tec.evaluate(uid, pod, [forever], 105.0)
    assert uid not in tec.pending  # no bounded grace left
    # Re-judged with `short` back at 108: a fresh 10s clock from 108,
    # NOT the stale 110 deadline inherited from the first arming.
    tec.evaluate(uid, pod, [short, forever], 108.0)
    assert tec.pending[uid][1] == 118.0
    # The stale-timer shape (the bug): eviction must NOT fire at 110.
    assert tec.tick(110.0) == 0
    assert tec.tick(118.0) == 1
    assert uid not in s.cache.pods


# ---------------------------------------------------------------------------
# NodeLifecycleController + PodGCController — the failure-response WRITER
# half (ISSUE 9): heartbeat staleness → taint write → eviction → requeue.
# ---------------------------------------------------------------------------


from kubernetes_tpu.controllers import (  # noqa: E402
    NODE_NOT_READY,
    NODE_UNREACHABLE,
    NOT_READY_TAINT_KEY,
    UNREACHABLE_TAINT_KEY,
)


def _lease(s, name, ts):
    s.renew_node_lease(t.Lease(name, ts))


def _armed_sched(grace=5.0, unreachable=12.0, gc=30.0):
    # TaintToleration in the filter set: a requeued eviction victim must
    # not land straight back on the tainted node it was evicted from.
    from kubernetes_tpu.framework.config import Profile

    s = TPUScheduler(
        profile=Profile(
            name="fit-taints",
            filters=(
                "NodeUnschedulable", "NodeName", "TaintToleration",
                "NodeResourcesFit",
            ),
            scorers=(("NodeResourcesFit", 1),),
        ),
        batch_size=8,
    )
    s.node_lifecycle.arm(grace_period_s=grace, unreachable_after_s=unreachable)
    s.pod_gc.arm(gc_horizon_s=gc)
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_node(make_node("n2").capacity({"cpu": "8", "pods": 110}).obj())
    _lease(s, "n1", 0.0)
    _lease(s, "n2", 0.0)
    return s


def test_lifecycle_transitions_ready_notready_unreachable():
    s = _armed_sched()
    # n2 keeps renewing; n1 went quiet at t=0.
    _lease(s, "n2", 4.0)
    assert s.node_lifecycle.states == {}  # age 4 <= grace 5
    _lease(s, "n2", 6.0)
    assert s.node_lifecycle.states == {"n1": NODE_NOT_READY}
    keys = {taint.key for taint in s.cache.nodes["n1"].node.spec.taints}
    assert keys == {NOT_READY_TAINT_KEY}
    effects = {
        taint.effect for taint in s.cache.nodes["n1"].node.spec.taints
    }
    assert effects == {t.EFFECT_NO_SCHEDULE, t.EFFECT_NO_EXECUTE}
    _lease(s, "n2", 13.0)
    assert s.node_lifecycle.states == {"n1": NODE_UNREACHABLE}
    keys = {taint.key for taint in s.cache.nodes["n1"].node.spec.taints}
    assert keys == {UNREACHABLE_TAINT_KEY}


def test_lifecycle_recovery_clears_taints():
    s = _armed_sched()
    _lease(s, "n2", 6.0)
    assert s.node_lifecycle.states == {"n1": NODE_NOT_READY}
    # n1 comes back: a fresh renewal clears the lifecycle taints and the
    # state returns to ready.
    _lease(s, "n1", 7.0)
    assert s.node_lifecycle.states == {}
    assert s.cache.nodes["n1"].node.spec.taints == ()


def test_lifecycle_taint_write_preserves_foreign_taints():
    s = _armed_sched()
    s.update_node(
        make_node("n1").capacity({"cpu": "8", "pods": 110})
        .taint("dedicated", "gpu", t.EFFECT_NO_SCHEDULE).obj()
    )
    _lease(s, "n2", 6.0)
    keys = {taint.key for taint in s.cache.nodes["n1"].node.spec.taints}
    assert keys == {"dedicated", NOT_READY_TAINT_KEY}
    _lease(s, "n1", 7.0)  # recovery keeps the foreign taint
    keys = {taint.key for taint in s.cache.nodes["n1"].node.spec.taints}
    assert keys == {"dedicated"}


def test_lifecycle_eviction_requeues_and_reschedules():
    # The full loop in-process: staleness → taint → tolerationSeconds
    # grace → eviction → requeue → rebind on the surviving node.
    s = _armed_sched()
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration(NOT_READY_TAINT_KEY, op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=3)
        .toleration(UNREACHABLE_TAINT_KEY, op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=3)
        .node("n1").obj()
    )
    _lease(s, "n2", 6.0)  # n1 → NotReady at logical 6; grace clock arms
    assert "default/p" in s.taint_eviction.pending
    _lease(s, "n2", 8.0)  # not due yet (6 + 3 = 9)
    assert "default/p" in s.cache.pods
    _lease(s, "n2", 9.5)  # due: evicted and requeued unbound
    assert "default/p" not in s.cache.pods
    assert s.taint_eviction.evictions == 1
    out = s.schedule_all_pending(wait_backoff=True)
    placed = [o for o in out if o.pod.uid == "default/p" and o.node_name]
    assert placed and placed[0].node_name == "n2"


def test_journaled_taint_write_is_noop_when_identical():
    s = _armed_sched()
    _lease(s, "n2", 6.0)
    taints = s.cache.nodes["n1"].node.spec.taints
    assert s.write_node_taints("n1", taints) is False  # identical set
    assert s.write_node_taints("missing", ()) is False  # unknown node


def test_pod_gc_unreachable_horizon_collects_tolerating_pods():
    # A tolerate-forever pod sits through NotReady and Unreachable; the
    # GC horizon finally requeues it.
    s = _armed_sched(gc=20.0)
    s.add_pod(
        make_pod("sticky").req({"cpu": "1"})
        # Tolerates every NoExecute taint forever (eviction immunity) but
        # not NoSchedule — the realistic daemon shape: the GC must reclaim
        # it, and the rebind must avoid the still-cordoned dead node.
        .toleration("", op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE)
        .node("n1").obj()
    )
    _lease(s, "n2", 13.0)  # n1 unreachable at 13
    assert "default/sticky" in s.cache.pods  # tolerated: no eviction
    _lease(s, "n2", 30.0)  # 13 + 20 = 33 not reached
    assert "default/sticky" in s.cache.pods
    _lease(s, "n2", 34.0)
    assert "default/sticky" not in s.cache.pods
    assert s.pod_gc.collected["unreachable"] == 1
    out = s.schedule_all_pending(wait_backoff=True)
    placed = [o for o in out if o.pod.uid == "default/sticky" and o.node_name]
    assert placed and placed[0].node_name == "n2"


def test_pod_gc_clears_stale_terminating_entries():
    s = _armed_sched()
    s.add_pod(
        make_pod("p").req({"cpu": "1"})
        .toleration(NOT_READY_TAINT_KEY, op=t.TOLERATION_OP_EXISTS,
                    effect=t.EFFECT_NO_EXECUTE, seconds=60)
        .node("n1").obj()
    )
    _lease(s, "n2", 6.0)
    assert "default/p" in s.taint_eviction.pending
    # The node vanishes entirely (informer delete): its pods vaporize,
    # but the pending deadline would leak without the GC's terminating
    # sweep.
    s.remove_node("n1")
    assert "default/p" not in s.cache.pods
    _lease(s, "n2", 7.0)
    assert "default/p" not in s.taint_eviction.pending
    assert s.pod_gc.collected["terminating"] == 1


def test_unleased_nodes_are_exempt():
    # Nodes that never renew a Lease are invisible to the lifecycle
    # controller even when armed — embedders feeding only Node objects
    # keep the consumer-only behavior.
    s = sched()
    s.node_lifecycle.arm(grace_period_s=1.0, unreachable_after_s=2.0)
    s.add_node(make_node("n1").capacity({"cpu": "8", "pods": 110}).obj())
    s.add_node(make_node("n2").capacity({"cpu": "8", "pods": 110}).obj())
    _lease(s, "n2", 0.0)
    _lease(s, "n2", 50.0)
    assert s.cache.nodes["n1"].node.spec.taints == ()
    assert s.node_lifecycle.states == {}


def test_preemptor_onto_tainted_node_evicts_cleanly():
    # Regression (r5 review): _commit_preempted judges AFTER
    # finish_binding — an inline-committed preemptor that does not
    # tolerate its freed node's NoExecute taint (fit-only profile: the
    # taint filter is absent) is evicted without crashing the batch.
    s = sched()
    n = make_node("n1").capacity({"cpu": "2", "pods": 110}) \
        .taint("maint", "true", t.EFFECT_NO_EXECUTE).obj()
    s.add_node(n)
    s.add_pod(make_pod("victim").req({"cpu": "2"}).priority(1).node("n1").obj())
    s.add_pod(make_pod("vip").req({"cpu": "2"}).priority(100).obj())
    out = s.schedule_all_pending(wait_backoff=True)
    assert "default/victim" not in s.cache.pods  # preempted
    assert "default/vip" not in s.cache.pods  # then taint-evicted at bind
    assert s.taint_eviction.evictions >= 1
    assert any(o.pod.name == "vip" and o.node_name for o in out)
