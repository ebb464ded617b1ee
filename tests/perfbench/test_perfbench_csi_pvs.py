"""``csi_pvs_5kn``'s own files: its plain reference
(``perfbench/references/csi_pvs.py``), the limit of its comparison, its six
readers, and its cell rehearsed on the CPU through ``perfbench/run.py``.

The reference is held the way ``topology_spreading``'s is: a sound stand-in
at toy size with an attach limit that is reached reads 0 and 0; the same
answers under a reference that reads the limit one lower read
``over_capacity_nodes`` > 0 and nothing else; one decision in fifty sent to
the lowest-scoring feasible node moves only the gap; and the program itself
with NodeVolumeLimits out of its profile reads ``infeasible`` > 0.
"""

import json
import os
import types
from dataclasses import replace

import pytest

import _pb
from perfbench import cell, control, correct, objects, report, spec, traffic

NAME = "csi_pvs_5kn"
CELL = NAME + ".backlog"
HOME = os.path.join(_pb.ROOT, "perfbench")
OTHERS = ("unanswered", "journal_lost", "answer_conflicts")
DRIVER = "ebs.csi.aws.com"


def _config():
    with open(os.path.join(HOME, "configs", NAME + ".json")) as f:
        return json.load(f)


def _small(limit: int = 3):
    """The cell's configuration at 200 nodes with an attach limit of
    ``limit`` (the file's 39 is never reached at a size a test holds)."""
    config = _config()
    mix = traffic.load(os.path.join(HOME, "traffic", "backlog.json"))
    config["cluster"]["nodes"] = 200
    config["serve"]["batch_size"] = 64
    config["serve"]["chunk_size"] = 8
    config["initial_pods"] = 40
    mix["warmup"]["short_pods"] = 20
    (csinode,) = config["cluster"]["companions"]
    csinode["template"]["driver_limits"][DRIVER] = limit
    config["capacity"]["pods_per_node_max"] = limit
    config["correct"]["score_gap_mean_limit"] = 100.0  # the gap has a test of its own
    return config, mix


def _compare(config, mix, stale, seed=5, window=400, reads=None, **how):
    """``control.stand_in`` where it can say what is asked; ``how`` may also
    hold ``drop_limit``, which only this reference's ``place`` takes."""
    if "drop_limit" not in how:
        node_jsons, names, by_uid, order, asked, measured, companions = control.stand_in(
            config, mix, seed, window, stale, **how)
    else:
        ref = correct.load_reference(config["reference"])
        nodes = objects.Nodes(config, seed)
        plan = cell.pods_needed(config, mix, 0.0, 0)
        setup = plan["initial"] + plan["warm"]
        pods = objects.Pods(config, seed, setup + window, plan["initial"])
        companions = objects.Companions(config, nodes, pods, plan["initial"])
        cluster, facts = correct.stand_up(ref, nodes.jsons, nodes.names, companions)
        stream = [(uid, facts(uid, raw)) for uid, raw in zip(pods.uids, pods.jsons)]
        order = ref.place(cluster, stream[:setup], config["serve"]["chunk_size"], seed)
        order += ref.place(cluster, stream[setup:], stale, seed + 1, **how)
        node_jsons, names, by_uid = nodes.jsons, nodes.names, dict(zip(pods.uids, pods.jsons))
        asked, measured = dict(order), set(pods.uids[setup:])
    if reads is not None:
        # the same answers under a copy of the reference that reads the
        # CSINodes' limit as ``reads``
        with open(os.path.join(HOME, "references", "csi_pvs.py")) as f:
            text = f.read()
        assert text.count("LIMIT_READ_AS = None") == 1
        path = os.path.join(reads[1], "csi_pvs_reads_%d.py" % reads[0])
        with open(path, "w") as f:
            f.write(text.replace("LIMIT_READ_AS = None", "LIMIT_READ_AS = %d" % reads[0]).replace(
                'os.path.dirname(os.path.abspath(__file__)), "default_profile.py"',
                repr(os.path.join(HOME, "references")) + ', "default_profile.py"'))
        altered = spec.load_file_module(path, "csi_pvs_reads_%d" % reads[0])
        real = correct.load_reference
        correct.load_reference = lambda name: altered
        try:
            res = correct.compare(config, node_jsons, names, by_uid, order, asked, measured, dict(asked), companions)
        finally:
            correct.load_reference = real
    else:
        res = correct.compare(config, node_jsons, names, by_uid, order, asked, measured, dict(asked), companions)
    return res["numbers"], correct.verdict(res["numbers"]), res["info"]


def test_a_sound_stand_in_with_the_limit_reached_reads_zero_and_zero():
    config, mix = _small()
    for seed in (5, 6, 7):
        # 40 + 148 set-up pods and 400 more on 200 nodes of 3: 588 of 600 places
        numbers, ok, info = _compare(config, mix, stale=config["serve"]["chunk_size"], seed=seed)
        assert ok, numbers
        assert numbers["over_capacity_nodes"]["value"] == 0 and numbers["infeasible"]["value"] == 0
        assert not any(numbers[k]["value"] for k in OTHERS)
        assert info["infeasible_examples"] == ["most distinct volumes of one driver on a node: 3"]


def test_reading_the_limit_one_lower_finds_nodes_over_it_and_nothing_else(tmp_path):
    config, mix = _small()
    numbers, ok, info = _compare(config, mix, stale=8, reads=(2, str(tmp_path)))
    assert not ok and numbers["over_capacity_nodes"]["value"] > 0
    assert not any(numbers[k]["value"] for k in OTHERS)
    # a commit onto a node the altered reference holds full is what it calls
    # infeasible; the nodes over the limit are what this test is about
    assert numbers["score_gap_mean"]["value"] <= 100.0


def test_one_decision_in_fifty_sent_to_the_lowest_scoring_feasible_node_moves_only_the_gap():
    """Every answer feasible (the attach limit included), journaled and
    within capacity: only the score gap can see it.  The file's limit was
    read at 5,000 nodes; at 200 the stand-in reads higher, so the test sets
    its own the way the file's was set: the largest sound reading times the
    file's factor."""
    config, mix = _small(limit=39)
    sound = [_compare(config, mix, 8, seed=s, window=2000)[0]["score_gap_mean"]["value"] for s in (11, 12, 13)]
    assert 0 < max(sound) < 3 * min(sound), sound
    c = _config()["correct"]
    limit = c["score_gap_mean_limit"] / c["lower_reading_max"] * max(sound)
    for seed in (5, 6, 7):
        numbers, _, _ = _compare(config, mix, 8, seed=seed, window=2000, wander=0.02, wander_to="worst")
        assert numbers["score_gap_mean"]["value"] > limit, (numbers, sound)
        assert not any(numbers[k]["value"] for k in OTHERS + ("infeasible", "over_capacity_nodes"))


def test_dropping_the_limit_reads_as_infeasible_and_as_nodes_over_it():
    config, mix = _small()
    numbers, ok, info = _compare(config, mix, stale=8, drop_limit=True)
    assert not ok and numbers["infeasible"]["value"] > 0 and numbers["over_capacity_nodes"]["value"] > 0
    assert not any(numbers[k]["value"] for k in OTHERS)
    assert int(info["infeasible_examples"][0].split()[-1]) > 3


def test_the_limit_of_the_gap_lies_between_the_files_two_readings():
    c = _config()["correct"]
    assert c["lower_reading_max"] < c["score_gap_mean_limit"] < c["upper_reading"]
    assert c["score_gap_mean_limit"] >= 1.5 * c["lower_reading_max"]
    assert c["upper_reading"] >= 3 * c["score_gap_mean_limit"]


# -- the reference's arithmetic, by hand ----------------------------------------


@pytest.fixture
def ref():
    return correct.load_reference("csi_pvs")


def _node(name):
    return json.dumps({"metadata": {"name": name, "labels": {}}, "spec": {"taints": [], "unschedulable": False},
                       "status": {"allocatable": {"cpu": 4000, "memory": 1 << 35, "pods": 110}}}).encode()


def _pod_json(name, claims, ns="namespace-2"):
    with open(os.path.join(HOME, "configs", NAME + ".json")) as f:
        pod = json.load(f)["pod"]["template"]
    pod = json.loads(json.dumps(pod).replace("{name}", name).replace("{namespace}", ns))
    pod["spec"]["volumes"] = [{"name": f"v{i}", "pvc": c, "device_id": "", "read_only": False}
                              for i, c in enumerate(claims)]
    return json.dumps(pod).encode()


def _objects(claims, ns="namespace-2", driver=DRIVER, points_back=True, bound=True):
    pvcs = [json.dumps({"name": c, "namespace": ns, "storage_class": "", "access_modes": ["ReadOnlyMany"],
                        "request": 1 << 30, "volume_name": f"pv-{c}" if bound else ""}).encode() for c in claims]
    pvs = [json.dumps({"name": f"pv-{c}", "capacity": 1 << 30, "access_modes": ["ReadOnlyMany"], "storage_class": "",
                       "node_affinity": None, "labels": {}, "csi_driver": driver,
                       "claim_ref": f"{ns}/{c}" if points_back else f"{ns}/another"}).encode() for c in claims]
    return {"CSINode": [], "PersistentVolumeClaim": pvcs, "PersistentVolume": pvs}


def _cluster(ref, limits):
    names = [f"n{i}" for i in range(len(limits))]
    csinodes = [json.dumps({"name": n, "driver_limits": {DRIVER: lim}}).encode()
                for n, lim in zip(names, limits) if lim is not None]
    return ref.Cluster([_node(n) for n in names], names,
                       companions={"CSINode": csinodes, "PersistentVolumeClaim": [], "PersistentVolume": []})


def test_distinct_means_distinct_a_claim_two_pods_of_one_node_share_is_one_volume(ref):
    cl = _cluster(ref, [2, 2, None])
    replay = ref.Replay(cl)
    for k, (uid, claims, node) in enumerate([("a", ["x"], "n0"), ("b", ["x"], "n0"), ("c", ["y"], "n0"),
                                             ("d", ["x"], "n1"), ("e", ["x", "x"], "n1")]):
        replay.step(uid, node, ref.pod_facts(_pod_json(uid, claims), _objects(set(claims))), True)
    assert (replay.infeasible, cl.over_capacity(), cl.fullest) == (0, 0, 2)
    assert cl.count[DRIVER].tolist() == [2, 1, 0]
    # n0 is full for a new volume, not for one it holds; a node without a CSINode has no limit
    facts = ref.pod_facts(_pod_json("f", ["z"]), _objects(["z"]))
    assert cl.attach_mask(facts[1]).tolist() == [False, True, True]
    assert cl.attach_mask(ref.pod_facts(_pod_json("g", ["x"]), _objects(["x"]))[1]).tolist() == [True, True, True]
    replay.step("f", "n0", facts, True)  # a decision onto the full node
    assert (replay.infeasible, cl.over_capacity()) == (1, 1)
    assert replay.examples[0].endswith(": 3") and replay.examples[1] == "f->n0"


@pytest.mark.parametrize("how", ["missing", "unbound", "no_volume", "points_elsewhere"])
def test_a_measured_pod_whose_claim_does_not_resolve_is_infeasible(ref, how):
    cl = _cluster(ref, [5])
    companions = _objects(["x"], bound=how != "unbound", points_back=how != "points_elsewhere")
    if how == "missing":
        companions["PersistentVolumeClaim"] = []
    if how == "no_volume":
        companions["PersistentVolume"] = []
    facts = ref.pod_facts(_pod_json("a", ["x"]), companions)
    assert facts[1:] == ((), 1)
    replay = ref.Replay(cl)
    replay.step("a", "n0", facts, False)  # a pod of the set-up is not judged
    assert replay.infeasible == 0
    replay.step("b", "n0", facts, True)
    assert replay.infeasible == 1 and "unresolved" in replay.examples[1]
    assert ref.place(cl, [("c", facts)], 8, 1) == [("c", "")]  # the stand-in binds it nowhere


def test_the_reference_refuses_what_it_does_not_implement_by_name(ref):
    pod = json.loads(_pod_json("a", ["x"]))
    pod["spec"]["volumes"][0].update(pvc="", device_id="disk-1")
    with pytest.raises(ref.Unsupported, match="name a claim"):
        ref.pod_facts(json.dumps(pod).encode(), _objects(["x"]))
    companions = _objects(["x"])
    pv = json.loads(companions["PersistentVolume"][0])
    pv["labels"] = {"topology.kubernetes.io/zone": "a"}
    companions["PersistentVolume"] = [json.dumps(pv).encode()]
    with pytest.raises(ref.Unsupported, match="zone labels"):
        ref.pod_facts(_pod_json("a", ["x"]), companions)
    assert ref.COMPANION_KINDS == ("CSINode", "PersistentVolumeClaim", "PersistentVolume")


# -- the program itself against the reference -------------------------------------


def _replayed(drop_limit: bool):
    """A ``TPUScheduler`` chunked as served over 40 nodes of limit 2 and the
    configuration's own objects in the wire's JSON: the companions first, as
    the cell sends them, every binding through the reference's replay."""
    from kubernetes_tpu.api import serialize
    from kubernetes_tpu.framework.config import DEFAULT_PROFILE
    from kubernetes_tpu.ops.common import registered_subset
    from kubernetes_tpu.scheduler import TPUScheduler

    config, _ = _small(limit=2)
    config["cluster"]["nodes"] = 40
    seed, initial, measured = 36, 20, 70
    profile = replace(registered_subset(DEFAULT_PROFILE), percentage_of_nodes_to_score=100)
    if drop_limit:
        profile = replace(profile, filters=tuple(f for f in profile.filters if f != "NodeVolumeLimits"))
    nodes = objects.Nodes(config, seed)
    pods = objects.Pods(config, seed, initial + measured, initial)
    companions = objects.Companions(config, nodes, pods, initial)
    s = TPUScheduler(profile=profile, batch_size=32, chunk_size=8, enable_preemption=False)
    for raw in nodes.jsons:
        s.add_node(serialize.node_from_json(raw))
    for kind, jsons in companions.of_nodes:
        for raw in jsons:
            getattr(s, serialize.KINDS[kind][1])(serialize.build(serialize.KINDS[kind][0], json.loads(raw)))
    order = []
    for lo, hi in ((0, initial), (initial, initial + measured)):
        for uid, raw in zip(pods.uids[lo:hi], pods.jsons[lo:hi]):
            for kind, jsons in companions.of_uid(uid).items():
                for obj in jsons:
                    getattr(s, serialize.KINDS[kind][1])(serialize.build(serialize.KINDS[kind][0], json.loads(obj)))
            s.add_pod(serialize.pod_from_json(raw))
        order += [(o.pod.uid, o.node_name) for o in s.schedule_all_pending()]
    assert all(node for _, node in order)
    ref = correct.load_reference(config["reference"])
    cluster, facts = correct.stand_up(ref, nodes.jsons, nodes.names, companions)
    replay = ref.Replay(cluster)
    by_uid, judged = dict(zip(pods.uids, pods.jsons)), set(pods.uids[initial:])
    for uid, node in order:
        replay.step(uid, node, facts(uid, by_uid[uid]), uid in judged)
    return s, replay


def test_the_programs_bindings_pass_the_references_filter_with_the_limit_reached():
    s, replay = _replayed(drop_limit=False)
    assert (replay.infeasible, replay.unknown_node, replay.cluster.over_capacity()) == (0, 0, 0)
    assert replay.cluster.fullest == 2 and len(replay.gaps) == 70
    assert s.builder.csi_rows == {} and s.builder.csi_claim_counts() == (70, 0)
    rejecting = {dict(k)["plugin"]: int(v) for k, v in s._filter_rejecting_counter.values.items()}
    assert rejecting.get("NodeVolumeLimits", 0) > 0  # the limit bites


def test_without_nodevolumelimits_in_its_profile_the_program_reads_infeasible_and_over_capacity():
    s, replay = _replayed(drop_limit=True)
    assert replay.infeasible > 0 and replay.cluster.over_capacity() > 0 and replay.cluster.fullest > 2
    assert replay.unknown_node == 0


# -- the six readers on hand-made material ----------------------------------------


def _ctx(records=(), before=None, after=None, window=None):
    before, after = before or {}, after or {}
    c = types.SimpleNamespace(records=list(records), window_records=list(records), before=before, after=after,
                              window=window or types.SimpleNamespace(), trace=None)
    c.delta = lambda key: after.get(key, 0.0) - before.get(key, 0.0)
    c.pods = c.window_pods = lambda: sum(int(r.get("pods", 0)) for r in c.records)
    return c


RECORDS = [
    {"pods": 4095, "spans": [["batch/featurize", 10, 30000, -1], ["batch/prefetch", 50000, 9000, -1]]},
    {"pods": 905, "spans": [["batch/featurize", 10, 1000, -1]]},
]
COMPILE, SHARED, DEFERRED = ("scheduler_jax_compile_seconds_total", 'scheduler_csi_claims{kind="shared"}',
                             "scheduler_deferred_pods_total")
WINDOW = types.SimpleNamespace(companion_s=0.9, companion_objects=10000, echo_s=0.52, echo_objects=5000)
READINGS = {
    "window_compile_s": (_ctx(RECORDS, {COMPILE: 26.25}, {COMPILE: 31.5}), 5.25),
    "csi_shared_claims": (_ctx(RECORDS, {}, {SHARED: 3.0}), 3.0),
    "attach_deferred_share": (_ctx(RECORDS, {DEFERRED: 100.0}, {DEFERRED: 350.0}), 100.0 * 250 / 5000),
    "featurize_us_per_pod.backlog": (_ctx(RECORDS), (30000 + 9000 + 1000) / 5000),
    "companion_us_per_object": (_ctx(RECORDS, window=WINDOW), 90.0),
    "echo_us_per_pod": (_ctx(RECORDS, window=WINDOW), 104.0),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_each_new_reader_gives_the_hand_computed_value_and_nothing_where_nothing_is_to_read(name):
    reader = report.load_reader(HOME, name)
    ctx, want = READINGS[name]
    assert reader.read(ctx) == pytest.approx(want)
    # a program without the counter or the span, a cell without companions or echoes: nothing, never 0
    old = [{"pods": 4000, "phases": {"featurize": 0.5}}, {"pods": 1000, "phases": {}}]
    empty = types.SimpleNamespace(companion_s=0.0, companion_objects=0, echo_s=0.0, echo_objects=0)
    assert reader.read(_ctx(old, {}, {}, empty)) is None
    assert reader.read(_ctx([], {}, {}, empty)) is None


def test_the_engagement_readers_report_a_true_zero():
    """0 is the sound reading of both: nothing compiled, no claim shared."""
    ctx = _ctx(RECORDS, {COMPILE: 31.5, DEFERRED: 7.0}, {COMPILE: 31.5, SHARED: 0.0, DEFERRED: 7.0})
    assert report.load_reader(HOME, "window_compile_s").read(ctx) == 0.0
    assert report.load_reader(HOME, "csi_shared_claims").read(ctx) == 0.0
    assert report.load_reader(HOME, "attach_deferred_share").read(ctx) == 0.0


NEW_READERS = ("window_compile_s", "csi_shared_claims", "attach_deferred_share", "featurize_us_per_pod.backlog",
               "companion_us_per_object", "echo_us_per_pod")
# what the benchmark held when this cell entered (PR 35): found by name, so
# that whatever a later PR appends leaves this file as it is
ACCEPTED_CONFIGS = ("basic_5kn", "podaffinity_5kn", "topology_spreading_5kn")
ACCEPTED_CELLS = ("basic_5kn.backlog", "basic_5kn.arrivals", "podaffinity_5kn.backlog",
                  "topology_spreading_5kn.backlog")
LAST_ACCEPTED_METRIC = "spread_rejecting_share"


def test_the_new_entries_stand_after_the_accepted_ones_and_list_the_new_cell_alone():
    bench = _pb.bench()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    accepted = names[:names.index(LAST_ACCEPTED_METRIC) + 1]
    assert len(accepted) == 30 and not set(accepted) & set(NEW_READERS)
    layers = {per_layer[n]["layer"] for n in accepted}
    at = [names.index(n) for n in NEW_READERS]
    assert at == sorted(at) and at[0] >= len(accepted)  # after the accepted ones, in the issue's order
    for n in NEW_READERS:
        m = per_layer[n]
        assert m["workloads"][0] == CELL and m["moves"] == "pods_per_s" and m["layer"] in layers
        assert os.path.exists(os.path.join(HOME, "metrics", n + ".py"))
    cells = [w["name"] for w in bench["workloads"]]
    assert all(cells.index(CELL) > cells.index(c) for c in ACCEPTED_CELLS)
    mine = bench["workloads"][cells.index(CELL)]
    assert mine == {"name": CELL, "config": NAME, "traffic": "backlog", "chips": 1, "why": mine["why"]}
    for m in bench["per_layer"] + bench["end_to_end"]:
        on = m.get("workloads", ())
        if CELL in on:  # appended: after every cell the list had
            assert all(on.index(CELL) > on.index(c) for c in ACCEPTED_CELLS if c in on), m["name"]
    configs = [c["name"] for c in bench["configs"]]
    assert all(configs.index(NAME) > configs.index(c) for c in ACCEPTED_CONFIGS)
    assert bench["configs"][configs.index(NAME)]["reduced"] == []
    assert not [m["name"] for m in bench["per_layer"] if m["name"].startswith("pack_") and CELL in m["workloads"]]


# -- the cell, rehearsed ---------------------------------------------------------------


def test_the_cell_rehearsed_on_the_cpu_sends_its_companions_inside_the_window(tmp_path):
    """``test_perfbench_rehearsal.py`` runs every cell of BENCHMARK.json and
    holds each to ``companion_objects`` 0, which was true of every cell when
    it was written and may not be edited here (conftest.py says what became
    of that case): this is the new cell's rehearsal, as the driver would run
    it, traced."""
    rc, out, err = _pb.run_cell(CELL, str(tmp_path), seconds=1.5, trace=1)
    assert rc == 0, err[-3000:]
    res, timeline = json.loads(out[-1]), json.loads(out[-2])["timeline"]
    assert _pb.RESULT_KEYS <= set(res) and list(res)[-1] == "compared"
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert "not a chip run" in res["rehearsal"] and res["device"]["platform"] == "cpu"
    exact = {k: v for k, v in res["compared"].items() if k != "score_gap_mean"}
    assert all(v["value"] == 0 and v["limit"] == 0 for v in exact.values()), exact
    assert res["compared"]["score_gap_mean"]["limit"] == _config()["correct"]["score_gap_mean_limit"]
    sent = timeline["companions"]
    initial, warm = timeline["plan"]["initial"], timeline["plan"]["warm"]
    # a CSINode a node; a claim and a volume for every pod of the measured
    # template: the warm-up's in the set-up, the window's inside the window
    assert sent["of_nodes"] == 600 and sent["setup"] == 2 * warm and sent["window"] == 2 * res["attempted"]
    assert res["companion_objects"] == 600 + 2 * (warm + res["attempted"]) and res["companion_s"] > 0
    assert sent["setup_echoes"] == initial + warm and sent["window_echoes"] == res["attempted"]
    assert timeline["push"]["invalidations"] == 0
    # nothing compiles in the window, and no program's shape follows the claims
    assert timeline["compiled_in_window"] == 0, {k: timeline[k] for k in (
        "jax_compiles_in_window", "compiled_programs", "cache_entries")}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    declared = {x["name"] for x in spec.metrics_for(_pb.bench(), "per_layer", CELL)}
    assert set(m) <= declared
    assert m["window_compile_s"] == 0.0 and m["csi_shared_claims"] == 0.0
    assert m["featurize_us_per_pod.backlog"] > 0 and m["companion_us_per_object"] > 0 and m["echo_us_per_pod"] > 0
    assert "attach_deferred_share" in m and "pack_width" not in m
    assert timeline["compare_info"]["infeasible_examples"][0].startswith("most distinct volumes of one driver")
