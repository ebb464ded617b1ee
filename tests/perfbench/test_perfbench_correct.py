"""The comparison that decides ``correct``: its control comes out as not
correct, a sound stand-in as correct, and each fault a cell can have
(an answer altered where it is produced, an answer that never comes, a
binding the journal lost) fails a number of its own.  Sizes a test run can
hold: 300 nodes, 2,400 decisions; the chip readings at the cells' own
sizes are in PERF.md."""

import copy
import json
import os

import pytest

import _pb
from perfbench import control, correct, spec, traffic

ZONE = "topology.kubernetes.io/zone"


def _small(name, colours=0):
    """The cell's configuration at 300 nodes.  ``colours`` > 0 lays the
    nodes out over that many zones and gives pod ``i`` colour ``i`` mod
    ``colours`` with a term on its own colour, so that the affinity filter
    has something to refuse (upstream's one colour in one zone never does)."""
    home = os.path.join(_pb.ROOT, "perfbench")
    with open(os.path.join(home, "configs", name + ".json")) as f:
        config = json.load(f)
    mix = traffic.load(os.path.join(home, "traffic", "backlog.json"))
    config["cluster"]["nodes"] = 300
    config["serve"]["batch_size"] = 256
    config["serve"]["chunk_size"] = 16
    config["initial_pods"] = 200
    if colours:
        cyc = {"prefix": "c", "count": colours}
        config["cluster"]["cycles"] = {"zone": cyc}
        config["cluster"]["node_template"]["metadata"]["labels"][ZONE] = "{zone}"
        config["pod"]["cycles"] = {"colour": cyc}
        tmpl = config["pod"]["template"]
        tmpl["metadata"]["labels"]["color"] = "{colour}"
        tmpl["spec"]["affinity"]["pod_affinity"]["required"][0]["label_selector"]["match_labels"] = [["color", "{colour}"]]
    mix["warmup"]["short_pods"] = 40
    return config, mix


def _compare(config, mix, stale, seed=5, mutate=None, drop_affinity=False, journal=None,
             wander=0.0, wander_to="random"):
    node_jsons, names, by_uid, order, asked, measured = control.answers(
        config, mix, seed, 2400, stale, drop_affinity=drop_affinity, wander=wander, wander_to=wander_to)
    recovered = dict(asked)
    if mutate:
        mutate(order, asked, recovered, names)
    if journal is not None:
        recovered = journal
    res = correct.compare(config, node_jsons, names, by_uid, order, asked, measured, recovered)
    return res["numbers"], correct.verdict(res["numbers"])


def _limit_at_test_size(config, mix):
    """The configuration's limit was read at 5,000 nodes; 300 nodes with
    chunks of 16 read higher.  The test sets its own the same way: the
    largest sound reading over three seeds, times the same factor."""
    sound = [_compare(config, mix, config["serve"]["chunk_size"], seed=s)[0]["score_gap_mean"]["value"]
             for s in (11, 12, 13)]
    assert max(sound) < 2 * min(sound), sound
    config["correct"]["score_gap_mean_limit"] = config["correct"]["limit_over_lower_reading"] * max(sound)
    return max(sound)


@pytest.mark.parametrize("name", ["basic_5kn", "podaffinity_5kn"])
def test_the_control_fails_and_a_sound_stand_in_passes(name):
    config, mix = _small(name)
    assert 0 < config["correct"]["score_gap_mean_limit"] < config["correct"]["upper_reading"] / 3
    lower = _limit_at_test_size(config, mix)
    for seed in (5, 6, 7):
        sound, ok = _compare(config, mix, stale=config["serve"]["chunk_size"], seed=seed)
        assert ok, sound
        ctl, ok = _compare(config, mix, stale=config["serve"]["batch_size"] * 16, seed=seed)
        assert not ok
        # it is the staleness that the control breaks, and only that number reads it
        assert ctl["score_gap_mean"]["value"] > 3 * lower
        for other in ("unanswered", "journal_lost", "answer_conflicts", "over_capacity_nodes", "infeasible"):
            assert ctl[other]["value"] == 0


@pytest.mark.parametrize("name", ["basic_5kn", "podaffinity_5kn"])
def test_one_decision_in_fifty_sent_to_a_wrong_node_is_not_correct(name):
    """Every answer feasible, journaled and within capacity, and one in
    fifty on a node of the lowest score instead of the highest (an inverted
    comparison on some lanes): only the score gap can see it.  (At the
    cells' own 5,000 nodes the sound reading is lower and one in a hundred
    is enough: PERF.md section 5.)
    Sent to a node drawn at random instead, it reads as sound, and rightly:
    on identical nodes that LeastAllocated keeps level a random feasible
    node scores within one pod's worth of the best (PERF.md section 5)."""
    config, mix = _small(name)
    lower = _limit_at_test_size(config, mix)
    chunk = config["serve"]["chunk_size"]
    for seed in (5, 6, 7):
        numbers, ok = _compare(config, mix, chunk, seed=seed, wander=0.02, wander_to="worst")
        assert not ok, numbers
        assert numbers["infeasible"]["value"] == numbers["over_capacity_nodes"]["value"] == 0
        numbers, ok = _compare(config, mix, chunk, seed=seed, wander=0.02)
        assert ok and numbers["score_gap_mean"]["value"] < 1.5 * lower


def test_dropping_the_affinity_filter_reads_as_infeasible():
    config, mix = _small("podaffinity_5kn", colours=10)
    config["correct"]["score_gap_mean_limit"] = 100.0
    numbers, ok = _compare(config, mix, stale=16)
    assert ok, numbers
    numbers, ok = _compare(config, mix, stale=16, drop_affinity=True)
    assert not ok and numbers["infeasible"]["value"] > 0


def test_a_term_reads_only_the_namespaces_it_names():
    ref = correct.load_reference("default_profile")
    config, _ = _small("podaffinity_5kn", colours=3)
    node_tmpl = json.dumps(config["cluster"]["node_template"])
    nodes = [node_tmpl.replace("{zone}", f"z{i}").replace("{name}", f"n{i}").encode() for i in range(3)]
    cl = ref.Cluster(nodes, ["n0", "n1", "n2"])
    pod = json.dumps(config["pod"]["template"]).replace("{colour}", "blue").replace("{name}", "p")
    cpu, mem, ns, labels, term = ref.pod_facts(pod.replace("{namespace}", "sched-1").encode())
    assert ns == "sched-1" and term == (("sched-0", "sched-1"), "color", ("blue",), ZONE)
    cl.watch(term)
    # nobody matches yet: the first pod of a self-affine group may go anywhere
    assert cl.affinity_mask(ns, labels, term).all()
    # a blue pod in a namespace the term does not name pulls nobody
    cl.commit(0, cpu, mem, "elsewhere", labels, None)
    assert cl.affinity_mask(ns, labels, term).all()
    assert not cl.affinity_mask("elsewhere", labels, term).any()
    # one in sched-0 does, into its zone alone
    cl.commit(1, cpu, mem, "sched-0", labels, term)
    assert list(cl.affinity_mask(ns, labels, term)) == [False, True, False]


def _alter_answer(order, asked, recovered, names):
    # the client is told another node than the one committed and journaled
    uid = order[-100][0]
    asked[uid] = next(n for n in names if n != asked[uid])


def _alter_commit(order, asked, recovered, names):
    # the node is altered where it is produced: commit, journal and answer
    # agree with each other and the pod sits on a full node
    full = order[0][1]
    for k in range(len(order) - 400, len(order)):
        uid = order[k][0]
        order[k] = (uid, full)
        asked[uid] = recovered[uid] = full


def _drop_answer(order, asked, recovered, names):
    asked[order[-50][0]] = ""


def _lose_binding(order, asked, recovered, names):
    del recovered[order[-10][0]]


@pytest.mark.parametrize("fault,number", [
    (_alter_answer, "journal_lost"), (_alter_commit, "over_capacity_nodes"),
    (_drop_answer, "unanswered"), (_lose_binding, "journal_lost"),
])
def test_each_fault_fails_a_number_of_its_own(fault, number):
    config, mix = _small("basic_5kn")
    numbers, ok = _compare(config, mix, stale=16, mutate=fault)
    assert not ok and numbers[number]["value"] > numbers[number]["limit"], numbers


def test_a_journal_that_cannot_be_read_is_not_correct():
    config, mix = _small("basic_5kn")
    node_jsons, names, by_uid, order, asked, measured = control.answers(config, mix, 5, 600, 16)
    res = correct.compare(config, node_jsons, names, by_uid, order, asked, measured, None)
    assert res["numbers"]["journal_lost"]["value"] == len(asked)
    assert not correct.verdict(res["numbers"])


def test_the_reference_refuses_what_it_does_not_implement():
    ref = correct.load_reference("default_profile")
    bench = spec.load(os.path.join(_pb.ROOT, "BENCHMARK.json"))
    _, config, _ = spec.cell(bench, "basic_5kn.backlog")
    pod = copy.deepcopy(config["pod"]["template"])
    assert ref.pod_facts(json.dumps(pod).encode())[:3] == (100, 524288000, "{namespace}")
    pod["spec"]["tolerations"] = [{"key": "x"}]
    with pytest.raises(ref.Unsupported):
        ref.pod_facts(json.dumps(pod).encode())
    # and imports nothing of the program
    with open(ref.__file__) as f:
        assert "kubernetes_tpu" not in f.read().replace("kubernetes_tpu's", "")


def test_reference_scores_follow_the_upstream_arithmetic():
    ref = correct.load_reference("default_profile")
    node = {"metadata": {"labels": {"z": "a"}}, "spec": {},
            "status": {"allocatable": {"cpu": 16000, "memory": 64 << 30, "pods": 110}}}
    cl = ref.Cluster([json.dumps(node).encode()] * 2, ["n0", "n1"])
    cl.commit(0, 900, 2 << 30, "ns", (), None)
    import numpy as np

    total = cl.scores(900, 2 << 30, "ns", (), np.ones(2, bool))
    # n0 after two pods: cpu (16000-1800)*100//16000 = 88, memory 93 -> 90;
    # balanced int((1 - |0.1125 - 0.0625| / 2) * 100) = 97
    # n1 after one: 94 and 96 -> 95; balanced int((1 - 0.0125) * 100) = 98
    assert list(total) == [90 + 97, 95 + 98]
    assert list(cl.fit_mask(16000 - 900 + 1, 1)) == [False, True]
