"""A row with a priority churn: the spec comparison, the churn's pods, the
closed loop acting on nominations and recycling room, the comparison told
of nominations and removals, all without a child.  The rehearsal through
``perfbench/run.py`` is ``test_perfbench_preemption_rehearsal.py``'s."""

import copy
import json
import os
import shutil

import pytest

import _pb
import _upstream
import preemption_stand_in as stand_in
from perfbench import cell, correct, loops, objects, report, spec, traffic, wire

CONFIG_HOME = os.path.join(_pb.ROOT, "perfbench", "configs")
ACCEPTED = sorted(f for f in os.listdir(CONFIG_HOME) if f.endswith(".json"))


@pytest.fixture
def row(tmp_path):
    """(configuration, home): the throw-away row, entered into a copy of
    ``perfbench/configs`` as new files."""
    home = str(tmp_path / "configs")
    shutil.copytree(CONFIG_HOME, home)
    return stand_in.row(home), home


def test_a_row_in_preemption_asyncs_shape_enters_beside_the_accepted_ones(row):
    """Three pod templates (the initial pods', the churn's, the measured
    pods'), a churn op, priorities, a field the wire has no room for."""
    doc, home = row
    _upstream.hold(doc, home)
    for name in ACCEPTED:
        with open(os.path.join(home, name)) as f:
            _upstream.hold(json.load(f), home)
    assert doc["pod"]["churn"]["template"]["spec"]["priority"] == 10
    assert doc["pod"]["initial_template"]["spec"]["priority"] == doc["pod"]["template"]["spec"]["priority"] == 0


def _excerpt(old, new):
    def alter(doc, home):
        path = os.path.join(home, doc["upstream"]["row"])
        with open(path) as f:
            text = f.read()
        assert old in text
        with open(path, "w") as f:
            f.write(text.replace(old, new))
    return alter


def _yaml_field(which, key, value):
    def alter(doc, home):
        import yaml

        path = os.path.join(home, doc["upstream"][which])
        with open(path) as f:
            pod = yaml.safe_load(f)
        pod["spec"][key] = value
        with open(path, "w") as f:
            yaml.safe_dump(pod, f)
    return alter


def _churn(doc):
    return doc["pod"]["churn"]


ALTERATIONS = {
    "the_churns_priority": (lambda doc, home: _churn(doc)["template"]["spec"].update(priority=5), "spec.priority"),
    "the_initial_pods_priority": (lambda doc, home: doc["pod"]["initial_template"]["spec"].update(priority=1),
                                  "spec.priority"),
    "a_preemption_policy": (_yaml_field("churn_template", "preemptionPolicy", "Never"), "preemptionPolicy"),
    "the_interval": (lambda doc, home: _churn(doc).update(interval_ms=500), "intervalMilliseconds"),
    "the_number": (lambda doc, home: _churn(doc).update(number=100), "churn number"),
    "the_namespace": (lambda doc, home: _churn(doc).update(namespace="namespace-3"), "churn namespace"),
    "the_churn_left_out": (lambda doc, home: doc["pod"].pop("churn"), "pod.churn"),
    "a_key_of_its_own": (lambda doc, home: _churn(doc).update(burst=2), "pod.churn"),
    "mode_recreate": (_excerpt("mode: create", "mode: recreate"), "opcode churn: mode 'recreate'"),
    "two_templates": (_excerpt(f"    - config/templates/{stand_in.HIGH}\n",
                               f"    - config/templates/{stand_in.HIGH}\n    - config/templates/{stand_in.LOW}\n"),
                      "opcode churn: mode 'create' with 2"),
    "a_grace_period_left_unsaid": (lambda doc, home: doc["assumed"]["no_wire_field"].clear(), "no_wire_field"),
    "a_recycle_of_another_kind": (lambda doc, home: doc["pod"].update(recycle="all"), "pod.recycle"),
    "a_recycle_left_unexplained": (lambda doc, home: doc["assumed"].pop("recycle"), "assumed.recycle"),
}


@pytest.mark.parametrize("how", sorted(ALTERATIONS))
def test_an_altered_churn_or_priority_fails_the_comparison_by_name(row, how):
    doc, home = row
    alter, named = ALTERATIONS[how]
    alter(doc, home)
    with pytest.raises(AssertionError, match=named.replace(".", r"\.")):
        _upstream.hold(doc, home)


def test_a_churn_in_the_configuration_of_a_row_without_one_is_refused(row):
    doc, home = row
    with open(os.path.join(home, "basic_5kn.json")) as f:
        basic = json.load(f)
    basic["pod"]["churn"] = doc["pod"]["churn"]
    with pytest.raises(AssertionError, match="pod.churn without a churn op"):
        _upstream.hold(basic, home)


# -- the churn's pods --------------------------------------------------------------


def test_the_churns_pods_are_drawn_apart_and_the_others_as_without_one(row):
    config, _ = row
    pods = objects.Pods(config, 2**31 + 7, 50, 20)
    bare = dict(config, pod={k: v for k, v in config["pod"].items() if k != "churn"})
    assert objects.Pods(bare, 2**31 + 7, 50, 20).jsons == pods.jsons
    churn = objects.Churn(config, 2**31 + 7, 6)
    assert churn.interval_s == 0.2 and len(churn) == 6 and len(set(churn.names)) == 6
    assert not set(churn.names) & set(pods.names) and all(n.startswith("c-") for n in churn.names)
    assert objects.Churn(config, 2**31 + 7, 6).names == churn.names != objects.Churn(config, 8, 6).names
    first = json.loads(churn.jsons[0])
    assert first["metadata"]["namespace"] == "namespace-2" and churn.uids[0] == "namespace-2/" + churn.names[0]
    assert first["spec"]["priority"] == 10 and first["spec"]["containers"][0]["requests"]["cpu"] == 3000
    # a one-pod hint, the pod asked for with its nominated node, its echo
    assert churn.hint_frame(0) == wire.pending_pods_frame([churn.jsons[0]])
    asked = wire._pb().Envelope()
    asked.ParseFromString(churn.nominated_frame(0, "node-7")[4:])
    nominated = json.loads(asked.schedule.pod_json[0])
    assert nominated["status"]["nominated_node_name"] == "node-7" and nominated["spec"]["node_name"] == ""
    assert dict(nominated, status=first["status"]) == first
    echo = wire._pb().Envelope()
    echo.ParseFromString(churn.bound_frame(0, "node-7")[4:])
    assert echo.add.kind == "Pod" and json.loads(echo.add.object_json)["spec"]["node_name"] == "node-7"


def test_a_removal_is_the_informers_delete():
    env = wire._pb().Envelope()
    data = wire.remove_frame("Pod", "ns/p-1")
    env.ParseFromString(data[4:])
    assert (env.remove.kind, env.remove.uid) == ("Pod", "ns/p-1") and len(data) == 4 + env.ByteSize()
    frames, n = objects.removal_frames(["a/1", "a/2"])
    assert n == 2 and frames == wire.remove_frame("Pod", "a/1") + wire.remove_frame("Pod", "a/2")


def test_a_rollback_takes_its_place_in_the_order():
    """What the sidecar takes back before it was delivered is in the push
    map's order, where it happened; a full rollback takes back what the
    map had not handed out."""
    push = wire.PushMap.__new__(wire.PushMap)
    push.map, push.order, push.batches, push.invalidations = {}, [], [], 0
    pb = wire._pb()
    decided = pb.Push()
    for uid in ("a", "b", "c"):
        d = decided.decisions.add()
        d.pod_uid, d.node_name = uid, "n1"
    push._apply(decided)
    assert push.pop("a") == "n1"
    scoped = pb.Push()
    scoped.invalidate_uids.append("b")
    push._apply(scoped)
    everything = pb.Push()
    everything.invalidate_all = True
    push._apply(everything)
    push.note_nomination("p", "n2", ["v1", "v2"])
    push.note_removed(["v1", "v2"])
    assert push.order == [("a", "n1"), ("b", "n1"), ("c", "n1"), ("b", "", wire.ROLLED_BACK),
                          ("c", "", wire.ROLLED_BACK), ("p", "n2", ("v1", "v2")),
                          ("v1", "", wire.REMOVED), ("v2", "", wire.REMOVED)]
    assert push.invalidations == 2 and push.map == {}


# -- the room ----------------------------------------------------------------------


def test_a_recycling_row_has_room_for_one_backlog_beside_the_initial_pods_and_the_churn(row):
    config, _ = row
    full = stand_in.row(row[1], nodes=5000, initial=20000, measure=5000)
    # 4 x 900m a node leave 400m: four pods of 100m; a churn pod takes at most a node's room
    assert cell.cluster_pod_capacity(full) == 20000
    assert cell.cluster_pod_capacity(full, 100) == 20000 - 400
    # an unlimited churn prebuilds what falls due in the set-up's allowance and the window
    assert cell.churn_count(full, 18.0) == int((18 + cell.CHURN_SETUP_S) * 5) + 1
    assert cell.churn_count(dict(full, pod=dict(full["pod"], churn=dict(full["pod"]["churn"], number=7))), 18.0) == 7
    mix = traffic.load(os.path.join(_pb.ROOT, "perfbench", "traffic", "backlog.json"))
    plan = cell.pods_needed(full, mix, 18.0, 0)
    # nothing closes short below the other cells' ceiling of ~10,500 pods/s in 18 s
    assert plan["window"] % 5000 == 0 and plan["window"] >= 10500 * 18
    # not counted against the cluster's room: the loop gives each backlog's room back
    assert plan["window"] > cell.cluster_pod_capacity(full)
    small = dict(full, cluster=dict(full["cluster"], nodes=1000), initial_pods=4000)
    with pytest.raises(loops.ClusterFull, match="not for a backlog of 5000"):
        cell.pods_needed(small, mix, 18.0, 0)
    # a configuration that does not recycle is reckoned as it was
    assert cell.cluster_pod_capacity(dict(config, pod={k: v for k, v in config["pod"].items()
                                                       if k != "recycle"})) == 100 * 4
    with pytest.raises(SystemExit, match="pod.recycle.*'measured'.*'all'"):
        cell.recycles(dict(config, pod=dict(config["pod"], recycle="all")))


def test_a_rehearsal_keeps_a_recycling_rows_shares_and_a_small_chunk(row):
    config, _ = row
    mix = traffic.load(os.path.join(_pb.ROOT, "perfbench", "traffic", "backlog.json"))
    full = stand_in.row(row[1], nodes=5000, initial=20000, measure=5000, chunk_size=1)
    spec.shrink(full, mix)
    assert (full["cluster"]["nodes"], full["initial_pods"], full["measure_pods"]) == (600, 2400, 150)
    assert (full["serve"]["batch_size"], full["serve"]["chunk_size"]) == (64, 1)
    small = copy.deepcopy(config)
    spec.shrink(small, copy.deepcopy(mix))
    assert (small["cluster"]["nodes"], small["initial_pods"], small["serve"]["chunk_size"]) == (100, 400, 8)


def test_a_churn_or_a_recycle_is_refused_where_nothing_could_release_what_it_removes(row, tmp_path):
    config, _ = row
    for mix, reference, said in (({"loop": "open"}, config["reference"], "open mix.*churn or a recycle"),
                                 ({"loop": "closed"}, "default_profile", "does not define PREEMPTION")):
        with pytest.raises(SystemExit, match=said):
            cell.run(str(tmp_path / "root"), {"name": "a.b", "chips": 1},
                     dict(config, reference=reference, pod=dict(config["pod"], bind_echo=None)),
                     mix, 1, 1.0, False, True, 0.0, out_root=str(tmp_path / "out"))
    assert sorted(p.name for p in tmp_path.iterdir()) == ["configs"]  # before anything was started or written


# -- the closed loop with a churn ----------------------------------------------------


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class Result:
    def __init__(self, node="", nominated="", victims=()):
        self.node_name, self.nominated_node, self.victim_uids = node, nominated, list(victims)


class Sidecar:
    """Decides ``batch`` hinted pods a call on one node, 1 s a call; a churn
    pod (uid ``c...``) is nominated with two victims unless it asks with its
    nominated node, and binds then; every frame is logged in order."""

    def __init__(self, clock, batch=8, binds_after=1):
        self.clock, self.batch, self.binds_after = clock, batch, binds_after
        self.hinted, self.pushed, self.map = [], {}, {}
        self.log, self.order = [], []
        self.asks = {}

    # conn
    def call_raw(self, frame):
        self.log.append(("hint", tuple(frame)))
        self.hinted.extend(frame)
        self.clock.t += 0.001

    def schedule_raw(self, uid):
        self.log.append(("call", uid))
        take = [uid] + [u for u in self.hinted if u != uid and not u.startswith("c")][: self.batch - 1]
        self.hinted = [u for u in self.hinted if u not in set(take)]
        self.clock.t += 1.0
        for u in take[1:]:
            self.pushed[u] = "n1"
        return "n1"

    def schedule_result(self, frame):
        uid, nominated = frame if isinstance(frame, tuple) else (frame, "")
        self.log.append(("ask", uid, nominated))
        self.clock.t += 0.5
        self.asks[uid] = self.asks.get(uid, 0) + 1
        if nominated and self.asks[uid] > self.binds_after:
            return Result(node="n9")
        return Result(nominated="n9", victims=(f"v-{uid}-1", f"v-{uid}-0"))

    def call_many(self, frames, want):
        self.log.append(("remove", tuple(frames)))
        self.clock.t += 0.01 * want

    def post(self, frame):
        self.log.append(("echo", frame))

    # push
    def pop(self, uid):
        return self.map.pop(uid, None)

    def drain(self):
        self.map.update(self.pushed)
        self.pushed = {}

    def note(self, uid, node):
        self.order.append((uid, node))

    def note_nomination(self, uid, node, victims):
        self.order.append((uid, node, tuple(victims)))

    def note_removed(self, uids):
        self.order.extend((u, "", wire.REMOVED) for u in uids)


class Pods:
    def __init__(self, n):
        self.uids = [f"u{i}" for i in range(n)]
        self.frames = list(self.uids)


class ChurnPods:
    interval_s = 2.5

    def __init__(self, n):
        self.uids = [f"c{j}" for j in range(n)]
        self.frames = list(self.uids)

    def __len__(self):
        return len(self.uids)

    def hint_frame(self, j):
        return [self.uids[j]]

    def nominated_frame(self, j, node):
        return (self.uids[j], node)

    def bound_frame(self, j, node):
        return (self.uids[j], node)


def _loop(seconds=7.0, number=0, binds_after=1, recycle=False, churn_pods=10, removals=None):
    clock = Clock()
    side = Sidecar(clock, binds_after=binds_after)
    pods = Pods(400)
    churn = loops.ChurnState(ChurnPods(churn_pods), number)
    removals = removals or loops.Removals(lambda uids: (tuple(uids), len(uids)))
    hints = [pods.uids[a: a + 20] for a in range(0, 400, 20)]
    w = loops.closed_loop(side, side, pods, hints, 0, 20, seconds, clock=clock, churn=churn,
                          removals=removals, recycle=recycle, echo=lambda k, node: (pods.uids[k], node))
    return w, side, churn


def test_a_churn_pod_due_is_asked_first_and_its_nomination_acted_on_as_postfilter_does():
    w, side, churn = _loop()
    # c0 is due at the opening, before the first pod is asked for: a one-pod
    # hint, the ask, the victims removed in the answer's order, the ask again
    # with the nominated node, which binds, and its echo
    assert side.log[:6] == [("hint", tuple(f"u{i}" for i in range(20))), ("hint", ("c0",)), ("ask", "c0", ""),
                            ("remove", ("v-c0-1", "v-c0-0")), ("ask", "c0", "n9"), ("echo", ("c0", "n9"))]
    assert side.log[6] == ("call", "u0")
    # the order the comparison replays: the nomination after what the batch
    # pushed, its removals after it, the bind after them
    assert side.order[:4] == [("c0", "n9", ("v-c0-1", "v-c0-0")), ("v-c0-1", "", wire.REMOVED),
                              ("v-c0-0", "", wire.REMOVED), ("c0", "n9")]
    # one churn pod a point: c1 falls due 2.5 s in and waits for the next ask
    asked_at = [k for k, e in enumerate(side.log) if e == ("hint", ("c1",))]
    assert asked_at and side.log[asked_at[0] - 1][0] in ("call", "echo")
    n = w.churn_pods
    assert n == churn.next == len(churn.answers) >= 3
    assert (w.nominations, w.victims_removed, w.preempt_retries) == (n, 2 * n, n)
    assert all(node == "n9" for node, _, _ in churn.answers)
    # due to bound: c0 was due at the opening and bound 1.022 s later
    assert w.churn_due_to_bound_s[0] == pytest.approx(1.022)
    assert all(x > 0 for x in w.churn_due_to_bound_s)
    # the rate counts the measured pods only
    assert w.asked == w.bound == len(w.nodes) and w.asked % 20 == 0


def test_a_churn_pod_that_never_binds_is_asked_at_most_preempt_asks_times():
    w, side, churn = _loop(binds_after=99, churn_pods=1)
    asks = [e for e in side.log if e[0] == "ask"]
    assert len(asks) == loops.PREEMPT_ASKS and w.preempt_retries == loops.PREEMPT_ASKS - 1
    assert w.nominations == loops.PREEMPT_ASKS and churn.answers[0][0] == ""
    assert w.churn_due_to_bound_s == [None] and ("echo", ("c0", "")) not in side.log
    assert not churn.due(1e9) and churn.exhausted  # one prebuilt pod, unlimited: spent


def test_a_churns_number_bounds_it_and_its_clock_restarts_where_a_loop_starts():
    w, _, churn = _loop(seconds=30.0, number=2)
    assert churn.limit == 2 and w.churn_pods == 2 and not churn.exhausted
    # the window's schedule does not hang on how long the set-up took: the
    # next pod falls due where the loop starts, the one after an interval later
    churn = loops.ChurnState(ChurnPods(10), 0)
    churn.start(10.0)
    churn.next = 3
    churn.start(500.0)
    assert not churn.due(499.9) and churn.due(500.0)
    churn.next = 4
    assert not churn.due(502.4) and churn.due(502.5)


def test_a_churn_pods_ask_starts_the_traced_slice_where_it_is_the_first_call_after_a_hint_frame():
    clock = Clock()
    side = Sidecar(clock)
    pods = Pods(400)
    churn = loops.ChurnState(ChurnPods(10), 0)
    seen = []
    loops.closed_loop(side, side, pods, [pods.uids[a: a + 20] for a in range(0, 400, 20)], 0, 20, 3.0, clock=clock,
                      churn=churn, removals=loops.Removals(lambda uids: (tuple(uids), len(uids))),
                      on_boundary=lambda elapsed, first: seen.append((first, [e for e in side.log if e[0] in ("ask", "call")][-1:])))
    # c0's first ask follows the first hint frame; its second ask is no first call, nor the calls after it
    assert seen[0] == (True, []) and seen[1][0] is False and seen[1][1] == [("ask", "c0", "")]
    assert [first for first, _ in seen].count(True) == sum(1 for e in side.log if e[0] == "hint" and len(e[1]) == 20)


def test_a_churn_that_ran_out_before_the_window_closed_is_said_before_the_result():
    """A program slower than the churn's interval asks for every prebuilt
    pod in the set-up (each churn pod is asked before any other), and the
    window then holds no churn: the run says so."""
    summary = {"compiled_in_window": 0, "window_short": "", "rehearsal": False,
               "churn": {"exhausted": True, "prebuilt": 1591, "window": {"churn_pods": 0}}}
    (line,) = report.earlier_lines(summary)
    assert line.startswith("perfbench: WARNING the churn used every one of its 1591 prebuilt pods (0 of them")
    summary["churn"]["exhausted"] = False
    assert report.earlier_lines(summary) == []
    assert report.earlier_lines(dict(summary, churn=None)) == []


def test_a_victim_not_yet_asked_for_is_not_asked_for():
    removals = loops.Removals(lambda uids: (tuple(uids), len(uids)))
    removals.gone.update({"u3", "u25"})
    w, side, _ = _loop(removals=removals)
    assert ("call", "u3") not in side.log and w.nodes[3] is None and w.nodes[25] is None
    assert w.asked == len(w.nodes) - 2 and w.bound == w.asked


def test_room_is_recycled_between_backlogs_and_out_of_the_windows_length():
    w, side, _ = _loop(seconds=7.0, recycle=True)
    removes = [e[1] for e in side.log if e[0] == "remove" and e[1][0].startswith("u")]
    backlogs = w.asked // 20
    assert backlogs >= 2 and len(removes) == backlogs - 1
    # each the previous backlog's bound pods, before the next hint frame
    for b, frames in enumerate(removes):
        assert frames == tuple(f"u{i}" for i in range(20 * b, 20 * b + 20))
        at = side.log.index(("remove", frames))
        assert side.log[at + 1][0] == "hint" and side.log[at + 1][1][0] == f"u{20 * b + 20}"
    assert w.recycle_objects == 20 * (backlogs - 1)
    assert w.recycle_s == pytest.approx(0.01 * w.recycle_objects)
    assert w.seconds == pytest.approx(w.t_close - w.t_open - w.recycle_s) and w.seconds >= 7.0
    # the comparison is told of them, in order
    assert [u for u, *rest in side.order if rest == ["", wire.REMOVED] and u.startswith("u")] == \
        [u for frames in removes for u in frames]


# -- the comparison ------------------------------------------------------------------


def _tiny(reprieve="bound_last"):
    """Two nodes of 4 CPU, each four low pods of 900m, one measured pod of
    100m on node-1; a preemptor of 3 CPU.  Returns what ``compare`` takes."""
    with open(os.path.join(CONFIG_HOME, "basic_5kn.json")) as f:
        config = json.load(f)
    config["cluster"]["nodes"] = 2
    nodes = objects.Nodes(config, 1)
    low = copy.deepcopy(config["pod"]["template"])
    for side in ("requests", "limits"):
        low["spec"]["containers"][0][side]["cpu"] = 900
    high = copy.deepcopy(low)
    for side in ("requests", "limits"):
        high["spec"]["containers"][0][side]["cpu"] = 3000
    high["spec"]["priority"] = 10

    def js(t, name):
        return json.dumps(t, sort_keys=True).replace("{name}", name).replace("{namespace}", "ns").encode()

    by_uid = {f"ns/low-{i}": js(low, f"low-{i}") for i in range(8)}
    by_uid["ns/m-0"] = js(config["pod"]["template"], "m-0")
    by_uid["ns/hi"] = js(high, "hi")
    a, b = nodes.names
    order = [(f"ns/low-{i}", (a, b)[i % 2]) for i in range(8)] + [("ns/m-0", b)]
    asked = {u: n for u, n in order}
    return config, nodes, by_uid, order, asked, (a, b)


@pytest.fixture
def compared(monkeypatch):
    def run(order, asked, by_uid, nodes, config, recovered, reprieve="bound_last"):
        monkeypatch.setattr(stand_in, "REPRIEVE", reprieve)
        monkeypatch.setattr(correct, "load_reference", lambda name: stand_in)
        return correct.compare(config, nodes.jsons, nodes.names, by_uid, order, asked, set(), recovered)
    return run


def test_a_nomination_the_reference_would_make_matches_and_its_victims_are_not_lost(compared):
    config, nodes, by_uid, order, asked, (a, b) = _tiny()
    # node a: low-0, -2, -4, -6; the latest bound is reprieved first among ties,
    # so low-0, -2 and -4 go (node b costs three too, and node a joined first)
    victims = ("ns/low-0", "ns/low-2", "ns/low-4")
    order += [("ns/hi", a, victims)] + [(v, "", wire.REMOVED) for v in victims] + [("ns/hi", a)]
    asked["ns/hi"] = a
    recovered = {u: n for u, n in asked.items() if u not in victims}
    res = compared(order, asked, by_uid, nodes, config, recovered)
    numbers = {k: v["value"] for k, v in res["numbers"].items()}
    assert list(res["numbers"])[-2:] == ["preemption_mismatch", "score_gap_mean"]
    assert {k: v for k, v in numbers.items() if k != "score_gap_mean"} == dict.fromkeys(
        ["unanswered", "journal_lost", "answer_conflicts", "over_capacity_nodes", "infeasible",
         "preemption_mismatch"], 0)
    assert res["info"]["removed"] == 3
    # a removed pod that recovery brings back is lost
    res = compared(order, asked, by_uid, nodes, config, dict(recovered, **{victims[0]: a}))
    assert res["numbers"]["journal_lost"]["value"] == 1
    # the stand-in that reprieves the pod bound first chooses other victims
    res = compared(order, asked, by_uid, nodes, config, recovered, reprieve="bound_first")
    assert res["numbers"]["preemption_mismatch"]["value"] == 1 and not correct.verdict(res["numbers"])
    assert "the reference" in res["info"]["infeasible_examples"][-1]


def test_another_victim_or_another_node_is_a_mismatch_and_victims_left_in_place_overfill(compared):
    config, nodes, by_uid, order, asked, (a, b) = _tiny()
    base = list(order)
    for node, victims in ((a, ("ns/low-0", "ns/low-2", "ns/low-6")), (b, ("ns/low-1", "ns/low-3", "ns/low-5"))):
        order = base + [("ns/hi", node, victims)] + [(v, "", wire.REMOVED) for v in victims] + [("ns/hi", node)]
        res = compared(order, dict(asked, **{"ns/hi": node}), by_uid, nodes, config, None)
        assert res["numbers"]["preemption_mismatch"]["value"] == 1
    # the preemptor bound where its victims were never removed
    order = base + [("ns/hi", a)]
    res = compared(order, dict(asked, **{"ns/hi": a}), by_uid, nodes, config, dict(asked, **{"ns/hi": a}))
    numbers = {k: v["value"] for k, v in res["numbers"].items()}
    assert numbers["infeasible"] == 1 and numbers["over_capacity_nodes"] == 1 and numbers["preemption_mismatch"] == 0


def test_a_nominated_nodes_room_is_held_until_its_pod_binds(compared):
    """Upstream filters a pod with the nominated pods of at least its
    priority on their nodes: a measured pod placed into the room a
    nomination holds is infeasible, and the same pod after the bind is not
    (what the program does with chunk-mates, PERF.md section 8)."""
    config, nodes, by_uid, order, asked, (a, b) = _tiny()
    victims = ("ns/low-0", "ns/low-2", "ns/low-4")
    for u in ("m-1", "m-2"):
        by_uid[f"ns/{u}"] = by_uid["ns/m-0"].replace(b"m-0", u.encode())
    nominated = [("ns/hi", a, victims)] + [(v, "", wire.REMOVED) for v in victims]
    # 900m stays on node a beside the 3 CPU held: room for one pod of 100m, not two
    for tail, infeasible in (([("ns/m-1", a), ("ns/m-2", a)], 1),
                             ([("ns/hi", a), ("ns/m-1", b), ("ns/m-2", b)], 0)):
        got = dict(asked, **{u: n for u, n in tail})
        res = compared(order + nominated + tail, got, by_uid, nodes, config, None)
        # outside the window here; the stand-in holds every bind to the filter
        assert res["numbers"]["infeasible"]["value"] == infeasible, tail


def test_a_decision_taken_back_and_made_again_is_no_conflict(compared):
    config, nodes, by_uid, order, asked, (a, b) = _tiny()
    order = order[:-1] + [("ns/m-0", a), ("ns/m-0", "", wire.ROLLED_BACK), ("ns/m-0", b)]
    # a reference without PREEMPTION is called as it always was: the
    # rollback is not its to know: the decision made again conflicts, and
    # the answer with the first
    res = correct.compare(dict(config, reference="default_profile"), nodes.jsons, nodes.names, by_uid, order,
                          asked, set(), dict(asked))
    assert res["numbers"]["answer_conflicts"]["value"] == 2 and "preemption_mismatch" not in res["numbers"]
    res = compared(order, asked, by_uid, nodes, config, dict(asked))
    numbers = {k: v["value"] for k, v in res["numbers"].items()}
    assert numbers["answer_conflicts"] == 0 and numbers["over_capacity_nodes"] == 0
    assert res["info"]["rolled_back"] == 1
