"""``references/topology_spreading.py``, the plain reference that decides
``correct`` for ``topology_spreading_5kn``: its control comes out as not
correct and a sound stand-in as correct, each way of breaking a guarantee
fails a number of its own, and the filter's arithmetic is upstream's
(``podtopologyspread/filtering.go``), worked by hand.  Sizes a test run
can hold: 300 nodes in three zones, 2,400 decisions; the chip readings at
the cell's own sizes are in PERF.md."""

import copy
import json
import os

import pytest

import _pb
from perfbench import cell, control, correct, objects, traffic

ZONE = "topology.kubernetes.io/zone"
NAME = "topology_spreading_5kn"
OTHERS = ("unanswered", "journal_lost", "answer_conflicts", "over_capacity_nodes")


def _config():
    with open(os.path.join(_pb.ROOT, "perfbench", "configs", NAME + ".json")) as f:
        return json.load(f)


def _small():
    """The cell's configuration at 300 nodes, 100 a zone."""
    config = _config()
    mix = traffic.load(os.path.join(_pb.ROOT, "perfbench", "traffic", "backlog.json"))
    config["cluster"]["nodes"] = 300
    config["serve"]["batch_size"] = 256
    config["serve"]["chunk_size"] = 16
    config["initial_pods"] = 200
    mix["warmup"]["short_pods"] = 40
    return config, mix


def _compare(config, mix, stale, seed=5, window=2400, **how):
    """``control.answers`` where it can say what is asked; ``how`` may also
    hold ``drop_spread``, which only this reference's ``place`` takes."""
    if "drop_spread" not in how:
        node_jsons, names, by_uid, order, asked, measured = control.answers(
            config, mix, seed, window, stale, **how)
    else:
        ref = correct.load_reference(config["reference"])
        nodes = objects.Nodes(config, seed)
        plan = cell.pods_needed(config, mix, 0.0, 0)
        setup = plan["initial"] + plan["warm"]
        pods = objects.Pods(config, seed, setup + window, plan["initial"])
        cluster = ref.Cluster(nodes.jsons, nodes.names)
        stream = [(uid, ref.pod_facts(raw)) for uid, raw in zip(pods.uids, pods.jsons)]
        order = ref.place(cluster, stream[:setup], config["serve"]["chunk_size"], seed)
        order += ref.place(cluster, stream[setup:], stale, seed + 1, **how)
        node_jsons, names, by_uid = nodes.jsons, nodes.names, dict(zip(pods.uids, pods.jsons))
        asked, measured = dict(order), set(pods.uids[setup:])
    res = correct.compare(config, node_jsons, names, by_uid, order, asked, measured, dict(asked))
    return res["numbers"], correct.verdict(res["numbers"]), res["info"]


def _limit_at_test_size(config, mix):
    """The configuration's limit was read at 5,000 nodes from a pass that
    decides strictly in order; the stand-in at 300 nodes scores on a view
    one chunk of 16 old and reads higher.  The test sets its own the way
    the file's was set: the largest sound reading, times the same factor."""
    sound = [_compare(config, mix, config["serve"]["chunk_size"], seed=s)[0]["score_gap_mean"]["value"]
             for s in (11, 12, 13)]
    assert 0 < max(sound) < 2 * min(sound), sound
    config["correct"]["score_gap_mean_limit"] = config["correct"]["limit_over_lower_reading"] * max(sound)
    return max(sound)


def test_the_control_fails_and_a_sound_stand_in_passes():
    config, mix = _small()
    assert 0 < config["correct"]["score_gap_mean_limit"] < config["correct"]["upper_reading"] / 3
    lower = _limit_at_test_size(config, mix)
    for seed in (5, 6, 7):
        sound, ok, info = _compare(config, mix, stale=config["serve"]["chunk_size"], seed=seed)
        assert ok, sound
        # the constraint bit: the zones were driven to maxSkew apart, and never past it
        assert info["infeasible_examples"] == ["largest skew among the measured pods at any commit: 5"]
        ctl, ok, info = _compare(config, mix, stale=config["serve"]["batch_size"] * 16, seed=seed)
        assert not ok
        # it is the staleness that the control breaks, and only that number reads it
        assert ctl["score_gap_mean"]["value"] > 3 * lower
        assert not any(ctl[other]["value"] for other in OTHERS + ("infeasible",))
        assert info["infeasible_examples"][0].endswith(": 5")


def test_one_decision_in_fifty_sent_to_the_lowest_scoring_feasible_node_is_not_correct():
    """Every answer feasible (the constraint included), journaled and
    within capacity: only the score gap can see it."""
    config, mix = _small()
    _limit_at_test_size(config, mix)
    for seed in (5, 6, 7):
        numbers, ok, _ = _compare(config, mix, config["serve"]["chunk_size"], seed=seed,
                                  wander=0.02, wander_to="worst")
        assert not ok, numbers
        assert not any(numbers[other]["value"] for other in OTHERS + ("infeasible",))


def test_dropping_the_constraint_reads_as_infeasible_and_as_nothing_else():
    config, mix = _small()
    config["correct"]["score_gap_mean_limit"] = 100.0
    numbers, ok, _ = _compare(config, mix, stale=16, drop_spread=False)
    assert ok, numbers
    numbers, ok, info = _compare(config, mix, stale=16, drop_spread=True)
    assert not ok and numbers["infeasible"]["value"] > 0
    assert not any(numbers[other]["value"] for other in OTHERS)
    assert numbers["score_gap_mean"]["value"] <= 100.0
    assert int(info["infeasible_examples"][0].split()[-1]) > 5  # the skew it let through


# -- the filter's arithmetic, by hand ------------------------------------------


@pytest.fixture
def ref():
    return correct.load_reference("topology_spreading")


def _cluster(ref, zones=("a", "b", "c"), per_zone=2, extra=()):
    """``per_zone`` nodes a zone, named <zone><i>, and ``extra`` label sets."""
    config = _config()
    labels = [{ZONE: z} for z in zones for _ in range(per_zone)] + list(extra)
    names, jsons = [], []
    for i, lab in enumerate(labels):
        node = copy.deepcopy(config["cluster"]["node_template"])
        node["metadata"]["labels"] = lab
        names.append(f"{lab.get(ZONE, 'nozone')}{i}")
        node["metadata"]["name"] = names[-1]
        jsons.append(json.dumps(node).encode())
    return ref.Cluster(jsons, names)


def _pod(ns="namespace-2", labels=None, **constraint):
    pod = copy.deepcopy(_config()["pod"]["template"])
    pod["metadata"].update(name="p", namespace=ns, labels={"color": "blue"} if labels is None else labels)
    pod["spec"]["topology_spread_constraints"][0].update(constraint)
    return json.dumps(pod).encode()


def _fill(ref, cl, counts, raw=None):
    """Commit ``counts[z]`` pods of ``raw`` into the first node of zone z."""
    facts = ref.pod_facts(raw or _pod())
    cpu, mem, ns, labels, term = facts[0] if len(facts) == 2 else facts
    for z, n in counts.items():
        row = next(i for i, name in enumerate(cl.names) if name.startswith(z))
        for _ in range(n):
            cl.commit(row, cpu, mem, ns, labels, term)


def _mask(ref, cl, raw):
    (cpu, mem, ns, labels, term), spread = ref.pod_facts(raw)
    return list(cl.spread_mask(ns, spread))


def test_a_hand_worked_three_zone_example(ref):
    """Zones a, b, c hold 7, 3 and 2 matching pods; maxSkew 5, the pod
    matches its own selector: a: 7 + 1 - 2 = 6 > 5 is out, b: 3 + 1 - 2 = 2
    and c: 2 + 1 - 2 = 1 are in.  One more pod into c lifts the minimum to
    3 and a comes back: 7 + 1 - 3 = 5."""
    blue = (("color", "blue"),)
    cl = _cluster(ref)
    _fill(ref, cl, {"a": 7, "b": 3, "c": 2})
    assert list(cl.matching("namespace-2", ZONE, blue)) == [7, 3, 2]
    assert _mask(ref, cl, _pod()) == [False, False, True, True, True, True]
    _fill(ref, cl, {"c": 1})
    assert all(_mask(ref, cl, _pod()))
    # maxSkew is the constraint's own: at 1, a: 5 > 1 is out, b and c: 3 + 1 - 3 = 1 are in
    assert _mask(ref, cl, _pod(max_skew=1)) == [False, False, True, True, True, True]
    # the replay reads the same, before each commit: the first pod bound
    # into a is feasible and leaves the zones 5 apart, the second is not
    rp = ref.Replay(cl)
    rp.step("namespace-2/x", "a0", ref.pod_facts(_pod()), True)
    assert (rp.infeasible, len(rp.gaps), rp.skew_max) == (0, 1, 5)
    assert list(cl.matching("namespace-2", ZONE, blue)) == [8, 3, 3]
    rp.step("namespace-2/y", "a1", ref.pod_facts(_pod()), True)
    assert (rp.infeasible, len(rp.gaps), rp.skew_max) == (1, 1, 6)
    assert rp.examples == ["largest skew among the measured pods at any commit: 6", "namespace-2/y->a1"]
    # a pod that is not measured is committed and counted, and never judged
    rp.step("namespace-2/z", "a1", ref.pod_facts(_pod()), False)
    assert rp.infeasible == 1 and list(cl.matching("namespace-2", ZONE, blue)) == [10, 3, 3]


def test_an_empty_zone_sets_the_minimum(ref):
    """a and b are level at 6 and c is empty: a pod into a or b would stand
    6 + 1 - 0 = 7 over c, though a and b are level with each other."""
    cl = _cluster(ref)
    _fill(ref, cl, {"a": 6, "b": 6})
    assert _mask(ref, cl, _pod()) == [False, False, False, False, True, True]
    # a zone is one that some node carries: without c's nodes a and b are all there is
    cl = _cluster(ref, zones=("a", "b"))
    _fill(ref, cl, {"a": 6, "b": 6})
    assert all(_mask(ref, cl, _pod()))


def test_pods_of_another_namespace_that_carry_the_label_are_not_counted(ref):
    cl = _cluster(ref)
    _fill(ref, cl, {"a": 9}, _pod(ns="namespace-1"))
    assert all(_mask(ref, cl, _pod()))  # nothing of namespace-2 anywhere yet
    assert _mask(ref, cl, _pod(ns="namespace-1")) == [False, False, True, True, True, True]
    # nor are pods of the namespace that lack the label (the initial pods' template)
    _fill(ref, cl, {"b": 9}, _pod(labels={}))
    assert all(_mask(ref, cl, _pod()))
    # a count first asked for late is built from what was committed before
    assert list(cl.matching("namespace-2", ZONE, ())) == [0, 9, 0]


def test_self_counts_only_where_the_pods_own_labels_match(ref):
    cl = _cluster(ref)
    _fill(ref, cl, {"a": 5})
    # a blue pod into a: 5 + 1 - 0 = 6 > 5; a red pod held to the same
    # selector does not count itself: 5 + 0 - 0 = 5
    assert _mask(ref, cl, _pod()) == [False, False, True, True, True, True]
    assert all(_mask(ref, cl, _pod(labels={"color": "red"})))
    assert ref.pod_facts(_pod())[1] == ((ZONE, 5, (("color", "blue"),), 1),)
    assert ref.pod_facts(_pod(labels={"color": "red"}))[1][0][3] == 0
    # and, committed, the red pod is not counted either
    _fill(ref, cl, {"a": 3}, _pod(labels={"color": "red"}))
    assert list(cl.matching("namespace-2", ZONE, (("color", "blue"),))) == [5, 0, 0]


def test_a_node_without_the_key_is_infeasible_and_is_no_domain(ref):
    cl = _cluster(ref, extra=[{}])
    assert _mask(ref, cl, _pod()) == [True] * 6 + [False]
    rp = ref.Replay(cl)
    rp.step("namespace-2/x", "nozone6", ref.pod_facts(_pod()), True)
    assert rp.infeasible == 1
    assert list(cl.matching("namespace-2", ZONE, (("color", "blue"),))) == [0, 0, 0]


def test_do_not_schedule_constraints_add_no_score(ref):
    """The totals compared are ``default_profile``'s: where the constraint
    rules nothing out, a pod with it reads the gap that the same pod
    without it reads from ``default_profile`` itself."""
    plain = json.loads(_pod())
    plain["spec"]["topology_spread_constraints"] = []
    gaps = []
    for module, raw in ((ref, _pod()), (ref.base, json.dumps(plain).encode())):
        cl = _cluster(module)
        rp = module.Replay(cl)
        for k, node in enumerate(["a0", "a0", "b2", "a0", "c4", "a1"]):
            rp.step(f"namespace-2/p{k}", node, module.pod_facts(raw), True)
        assert rp.infeasible == 0
        gaps.append(rp.gaps)
    assert gaps[0] == gaps[1] and any(gaps[0])
    # and where it does rule nodes out, the best is taken over what it leaves:
    # a holds 2 + 1, b and c 8 each in one node, so b and c are out
    # (8 + 1 - 3 = 6) though each has an empty node that would score highest
    gaps = []
    for module, raw in ((ref, _pod()), (ref.base, json.dumps(plain).encode())):
        cl = _cluster(module)
        _fill(module, cl, {"a": 2, "b": 8, "c": 8}, raw)
        rp = module.Replay(cl)
        rp.step("namespace-2/q", "a1", module.pod_facts(raw), False)
        rp.step("namespace-2/r", "a1", module.pod_facts(raw), True)
        gaps.append(rp.gaps)
    assert gaps[0] == [0] and gaps[1][0] > 0


REFUSED = {
    "ScheduleAnyway": dict(when_unsatisfiable="ScheduleAnyway"),
    "matchExpressions": dict(label_selector={"match_labels": [], "match_expressions": [
        {"key": "color", "operator": "In", "values": ["blue"]}]}),
    "null selector": dict(label_selector=None),
    "minDomains": dict(min_domains=3),
    "matchLabelKeys": dict(match_label_keys=["pod-template-hash"]),
    "nodeAffinityPolicy": dict(node_affinity_policy="Ignore"),
    "nodeTaintsPolicy": dict(node_taints_policy="Honor"),
}


@pytest.mark.parametrize("field", sorted(REFUSED))
def test_the_reference_refuses_each_field_it_does_not_implement_by_name(ref, field):
    with pytest.raises(ref.Unsupported, match=field.split()[0]):
        ref.pod_facts(_pod(**REFUSED[field]))


def test_the_reference_refuses_a_second_topology_key_and_what_default_profile_refuses(ref):
    pod = json.loads(_pod())
    second = dict(pod["spec"]["topology_spread_constraints"][0], topology_key="kubernetes.io/hostname")
    pod["spec"]["topology_spread_constraints"].append(second)
    with pytest.raises(ref.Unsupported, match="second topology key"):
        ref.pod_facts(json.dumps(pod).encode())
    # two constraints over one key are both held
    second["topology_key"], second["max_skew"] = ZONE, 1
    assert [c[1] for c in ref.pod_facts(json.dumps(pod).encode())[1]] == [5, 1]
    pod = json.loads(_pod())
    pod["spec"]["tolerations"] = [{"key": "x"}]
    with pytest.raises(ref.Unsupported, match="tolerations"):
        ref.pod_facts(json.dumps(pod).encode())
    # a pod without constraints is default_profile's, and the initial pods' template is one
    facts, spread = ref.pod_facts(json.dumps(_config()["pod"]["initial_template"]).encode())
    assert facts[:2] == (100, 524288000) and facts[3] == () and spread == ()
    # numpy and the standard library: nothing of the program
    with open(ref.__file__) as f:
        text = f.read()
    assert "kubernetes_tpu" not in text and "import jax" not in text


# -- the reader that waits for its entry ---------------------------------------


def test_the_spread_rejecting_share_reader_on_hand_made_scrapes():
    """``metrics/spread_rejecting_share.py`` has no entry in BENCHMARK.json
    yet (an accepted test pins the end of ``per_layer``; PERF.md section 8
    says which edit a ``benchmark`` PR makes): held here to its arithmetic,
    and to silence where the program has no such counter."""
    import types

    from perfbench import report

    key = 'scheduler_pass_filter_rejecting_pods_total{plugin="PodTopologySpread"}'
    reader = report.load_reader(os.path.join(_pb.ROOT, "perfbench"), "spread_rejecting_share")

    def ctx(before, after, pods):
        c = types.SimpleNamespace(before=before, after=after, window_pods=lambda: pods)
        c.delta = lambda k: after.get(k, 0.0) - before.get(k, 0.0)
        return c

    assert reader.read(ctx({key: 1900.0}, {key: 9460.0}, 35000)) == pytest.approx(21.6)
    assert reader.read(ctx({key: 5.0}, {key: 5.0}, 35000)) == 0.0  # the counter is there and the filter never bit
    assert reader.read(ctx({}, {}, 35000)) is None  # the parent: no such counter
    assert reader.read(ctx({}, {key: 7.0}, 0)) is None
    assert reader.__doc__ and callable(reader.read)
