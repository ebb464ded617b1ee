"""The window rules, on synthetic timelines: a fake sidecar with a fake
clock stands where the server is."""

import random

import numpy as np
import pytest

import _pb
from perfbench import loops


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeSidecar:
    """A sidecar that decides ``batch`` hinted pods per wire call, each call
    costing ``batch_s`` (plus a stall on chosen calls), and pushes the
    co-scheduled decisions before the response."""

    def __init__(self, clock, batch=8, batch_s=1.0, stalls=None):
        self.clock, self.batch, self.batch_s = clock, batch, batch_s
        self.stalls = stalls or {}
        self.hinted: list[str] = []
        self.pushed: dict[str, str] = {}
        self.map: dict[str, str] = {}
        self.calls = 0
        self.order: list = []

    # conn
    def call_raw(self, frame):
        self.hinted.extend(frame)
        self.clock.t += 0.001

    def schedule_raw(self, frame):
        self.calls += 1
        uid = frame
        take = [uid] + [u for u in self.hinted if u != uid][: self.batch - 1]
        taken = set(take)
        self.hinted = [u for u in self.hinted if u not in taken]
        self.clock.t += self.batch_s + self.stalls.get(self.calls, 0.0)
        for u in take[1:]:
            self.pushed[u] = "n1"
        return "n1"

    # push
    def pop(self, uid):
        return self.map.pop(uid, None)

    def drain(self):
        self.map.update(self.pushed)
        self.pushed = {}

    def note(self, uid, node):
        self.order.append((uid, node))


class FakePods:
    def __init__(self, n):
        self.uids = [f"u{i}" for i in range(n)]
        self.frames = list(self.uids)


def _closed(seconds, stalls=None, backlog=20, batch=8):
    clock = FakeClock()
    side = FakeSidecar(clock, batch=batch, stalls=stalls)
    pods = FakePods(400)
    hints = [pods.uids[a: a + backlog] for a in range(0, 400, backlog)]
    w = loops.closed_loop(side, side, pods, hints, 0, backlog, seconds, clock=clock)
    return w, side


def test_window_closes_on_a_backlog_boundary_and_never_cuts_a_batch():
    # backlogs of 20 in batches of 8, 8 and 4, 3.003 s each.  Whatever
    # length is asked, the pods answered are whole backlogs: the same mix
    # of full and short batches in every window.
    for seconds in (0.5, 3.0, 3.01, 5.0, 7.3, 11.0):
        w, side = _closed(seconds)
        assert w.asked % 20 == 0 and w.asked > 0, (seconds, w.asked)
        assert w.seconds >= seconds - 1e-9
        assert w.seconds < seconds + 3.004  # at most one backlog past it
        # it ends at the last answer, which is when the last batch landed
        assert w.t_close == pytest.approx(w.answer_t[-1])
        assert w.misses == side.calls == 3 * w.asked // 20


def test_rate_is_all_pods_over_all_time_and_a_stall_lowers_it():
    calm, _ = _closed(10.0)
    stalled, _ = _closed(10.0, stalls={3: 2.5})
    rate = lambda w: w.bound / w.seconds  # noqa: E731
    assert rate(stalled) < rate(calm) * 0.85
    # no median of pieces could hide it: the stall is inside the window
    assert stalled.seconds >= 10.0


def test_window_opens_at_the_first_hint_frame():
    w, _ = _closed(3.0)
    assert w.t_open == 100.0
    assert w.hint_frames >= 1 and w.answer_t[0] > w.t_open


class Companioned(FakeSidecar):
    """A sidecar that also takes pipelined adds, 1 ms a hundred objects, and
    posted frames, 10 us of the loop's own time each, and keeps the order
    in which everything reached it."""

    def __init__(self, clock, **kw):
        super().__init__(clock, **kw)
        self.log = []

    def call_raw(self, frame):
        self.log.append(("hint", len(frame), self.clock.t))
        super().call_raw(frame)

    def schedule_raw(self, frame):
        self.log.append(("call", frame, self.clock.t))
        return super().schedule_raw(frame)

    def call_many(self, data, want):
        self.log.append((data, want, self.clock.t))
        self.clock.t += 0.00001 * want

    def post(self, frame):
        self.log.append(("echo", frame, self.clock.t))
        self.clock.t += 0.00001


def _closed_with_companions(seconds, companions="each", echo=True, backlog=20):
    clock = FakeClock()
    side = Companioned(clock)
    pods = FakePods(400)
    hints = [pods.uids[a: a + backlog] for a in range(0, 400, backlog)]
    if companions == "each":  # a claim and a volume a pod
        companions = [("companions", 2 * backlog)] * len(hints)
    w = loops.closed_loop(side, side, pods, hints, 0, backlog, seconds, clock=clock, companions=companions,
                          echo=(lambda k, node: (pods.uids[k], node)) if echo else None)
    return w, side


def test_companions_go_out_before_each_hint_frame_and_inside_the_window():
    w, side = _closed_with_companions(7.0)
    kinds = [e[0] for e in side.log]
    backlogs = w.asked // 20
    assert backlogs == 3
    # a backlog: its companions, its hint frame, and three batches (8, 8 and
    # 4 pods): each a call, then every pod of it echoed as it is answered
    assert kinds == (["companions", "hint"] + ["call"] + ["echo"] * 8 + ["call"] + ["echo"] * 8
                     + ["call"] + ["echo"] * 4) * backlogs
    assert [e[1] for e in side.log if e[0] == "companions"] == [40] * backlogs
    # the window opens at the first companion frame and counts them all
    assert side.log[0][2] == w.t_open == 100.0
    assert w.companion_objects == 2 * w.asked and w.companion_s == pytest.approx(backlogs * 0.0004)
    assert w.hint_frames == backlogs
    # on_boundary(first=True) is still the first wire call after the hint frame
    assert [side.log[k + 1][0] for k, e in enumerate(side.log) if e[0] == "hint"] == ["call"] * backlogs


def test_a_pod_goes_back_bound_as_soon_as_it_is_answered_and_the_window_closes_at_the_last_answer():
    w, side = _closed_with_companions(7.0, companions=None)
    echoes = [e for e in side.log if e[0] == "echo"]
    # every answered pod once, in the order answered, with the node it got,
    # posted at its answer: nothing waits for a backlog's end
    assert [e[1] for e in echoes] == [(f"u{k}", "n1") for k in range(w.asked)]
    assert [e[2] for e in echoes] == w.answer_t
    assert w.echo_objects == w.asked and w.echo_s == pytest.approx(w.asked * 0.00001)
    # one rule for the window's end: the last answer, as without echoes
    assert w.t_close == w.answer_t[-1] == echoes[-1][2]
    plain, _ = _closed(7.0)
    assert w.asked == plain.asked and w.seconds == pytest.approx(plain.seconds + (w.asked - 1) * 0.00001)
    # a pod that came back without a node is not echoed
    clock = FakeClock()
    side = Companioned(clock)
    side.schedule_raw = lambda frame: (FakeSidecar.schedule_raw(side, frame), "")[1]
    pods = FakePods(40)
    w = loops.closed_loop(side, side, pods, [pods.uids[:20]], 0, 20, 1.0, clock=clock, max_backlogs=1,
                          echo=lambda k, node: side.log.append(("made", k)) or (k, node))
    assert w.asked == 20 and w.bound == 17 and w.echo_objects == 17
    assert [e[1] for e in side.log if e[0] == "made"] == [k for k in range(20) if k not in (0, 8, 16)]


def test_a_configuration_without_companions_sends_what_it_sent_and_opens_where_it_did():
    plain, side0 = _closed(7.0)
    for companions in (None, [(b"", 0)] * 20):
        w, side = _closed_with_companions(7.0, companions=companions, echo=False)
        assert [e[0] for e in side.log] == ["hint", "call", "call", "call"] * 3  # no add of any kind
        assert (w.t_open, w.t_close, w.asked, w.hint_frames, w.misses) == \
            (plain.t_open, plain.t_close, plain.asked, plain.hint_frames, plain.misses)
        assert w.answer_t == plain.answer_t and w.nodes == plain.nodes
        assert (w.companion_objects, w.companion_s, w.echo_objects, w.echo_s) == (0, 0.0, 0, 0.0)
    assert not hasattr(side0, "call_many") and not hasattr(side0, "post")  # the accepted loops never ask for either


def test_an_open_mix_on_a_configuration_with_pod_companions_is_refused_at_the_start(tmp_path):
    from perfbench import cell

    for pod in ({"companions": [{"kind": "PersistentVolumeClaim", "of": "measured", "template": {}}]},
                {"bind_echo": "answered"}):
        with pytest.raises(SystemExit, match="open mix.*companions.*bind echo.*refused"):
            cell.run(str(tmp_path), {"name": "a.arrivals", "chips": 1}, {"pod": pod}, {"loop": "open"}, 1, 1.0,
                     False, True, 0.0, out_root=str(tmp_path))
    assert not list(tmp_path.iterdir())  # before anything was started or written


def test_posted_frames_are_acknowledged_behind_the_loop_and_settled_before_the_next_call(tmp_path):
    """``wire.Conn.post`` against a server that takes a connection's frames
    in order, answers each, and is slow to start reading: 3,000 frames of
    2 KB outgrow both socket buffers, so a post that neither drained
    acknowledgements nor waited for room would hang; the call that follows
    finds every acknowledgement read, and a refused frame is reported."""
    import socket
    import threading

    from perfbench import wire

    pb = wire._pb()
    path = str(tmp_path / "s.sock")
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(path)
    srv.listen(1)
    seen = []

    def serve():
        peer, _ = srv.accept()
        threading.Event().wait(0.2)  # the client runs ahead of it
        try:
            while True:
                env = wire.read_envelope(peer)
                seen.append(env.add.kind or env.WhichOneof("msg"))
                out = pb.Envelope()
                out.response.SetInParent()
                if env.add.kind == "Refused":
                    out.response.error = "no such kind"
                peer.sendall(wire.frame(out))
        except ConnectionError:
            peer.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    conn = wire.Conn(path)
    frame = wire.add_frame("Pod", b"x" * 2048)
    for _ in range(3000):
        conn.post(frame)
    assert 0 < conn._owed <= 3000
    assert conn.call_raw(wire.add_frame("Node", b"{}")).error == ""
    assert conn._owed == 0 and seen == ["Pod"] * 3000 + ["Node"]
    # acknowledgements still owed ride the next pipelined call
    conn.post(frame)
    conn.call_many(wire.add_frame("Node", b"{}") * 5, 5)
    assert conn._owed == 0 and seen[-6:] == ["Pod"] + ["Node"] * 5
    # a posted frame that the server refuses is reported by the call that settles it
    conn.post(wire.add_frame("Refused", b"{}"))
    with pytest.raises(RuntimeError, match="1 adds failed.*first: no such kind"):
        conn.call_raw(frame)
    conn.close()
    t.join(5.0)
    srv.close()


def test_percentile_matches_numpy():
    rng = random.Random(5)
    xs = [rng.random() for _ in range(997)]
    for q in (50, 95, 99, 0, 100):
        assert loops.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_open_loop_latency_runs_from_the_due_time():
    """A sidecar that takes 30 ms a batch: a pod due while a batch is in
    flight waits it out, its hint goes out before the next wire call, and
    its latency counts the wait."""
    import time

    class Side(FakeSidecar):
        def __init__(self):
            super().__init__(time.perf_counter, batch=64)
            self.frames = 0

        def call_raw(self, frame):
            self.hinted.extend(frame)
            self.frames += 1

        def schedule_raw(self, frame):
            take = [frame] + [u for u in self.hinted if u != frame]
            self.hinted = []
            time.sleep(0.03)
            for u in take[1:]:
                self.pushed[u] = "n1"
            return "n1"

    side = Side()
    pods = FakePods(50)
    offsets = [0.004 * (k + 1) for k in range(50)]  # 250 pods/s for 0.2 s
    w = loops.open_loop(side, side, pods, 0, offsets, lambda a, z: pods.uids[a:z])
    assert w.asked == 50 and w.bound == 50
    lat = [a - d for a, d in zip(w.answer_t, w.due_t)]
    assert min(lat) >= 0.0
    # a pod waits out the rest of a 30 ms batch and then its own
    assert loops.percentile(lat, 50) >= 0.015
    # batches of about 250/s x 30 ms = 7 or 8 pods, not one pod each
    assert 4 <= w.misses <= 12 and w.hits == 50 - w.misses
    # every pod was hinted, in frames that precede the wire calls
    assert w.hint_frames == side.frames and side.frames <= 50
    # the window closed when the last pod due in it was answered
    assert w.t_close == pytest.approx(w.answer_t[-1])
    assert w.t_close - w.t_open >= offsets[-1]
    assert all(x >= 0 for x in w.lag_s)


def test_open_schedule_is_the_same_work_for_every_seed():
    from perfbench import traffic

    mix = {"rate_pods_per_s": 200}
    a = traffic.open_offsets(mix, 5.0, 1)
    b = traffic.open_offsets(mix, 5.0, 2**31 + 5)
    assert len(a) == len(b) == 1000
    assert a != b
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip([0.0] + xs[:-1], xs))  # noqa: E731
    assert gaps(a)[:-1] == pytest.approx(gaps(b)[:-1], abs=1e-8)
    assert a == sorted(a) and a[-1] < 5.0
    # exponential gaps: the coefficient of variation of a Poisson process
    g = np.diff([0.0] + a)
    assert 0.9 < g.std() / g.mean() < 1.1
    # a study's other rate scales every segment
    assert len(traffic.open_offsets(mix, 5.0, 1, rate_override=100.0)) == 500
    # a mix may give its rates piecewise; a single rate is one piece
    steps = dict(mix, segments=[{"share": 0.5, "rate_pods_per_s": 100}, {"share": 0.5, "rate_pods_per_s": 300}])
    c = traffic.open_offsets(steps, 5.0, 1)
    assert len(c) == 1000 and sum(1 for x in c if x < 2.5) == 250


def test_the_arrivals_readers_time_every_pod_from_its_due_time():
    """The median and the observed tails, as their reader files give them,
    on a window whose answers are known: 100 pods due 10 ms apart, each
    answered 200 ms after it was due but for five that waited out a stall."""
    import os

    from perfbench import report

    w = loops.Window(t_open=50.0)
    w.due_t = [50.0 + 0.01 * k for k in range(100)]
    w.answer_t = [d + (2.0 if 40 <= k < 45 else 0.2) for k, d in enumerate(w.due_t)]
    w.nodes = ["n1"] * 100
    w.lag_s = [0.0002] * 99 + [0.004]
    raw = {"window": w, "records": [], "scrape0": {}, "scrape1": {}, "config": {}, "mix": {},
           "device": {"kind": "TPU v5 lite"}, "setup_s": 1.0}
    ctx = report.Ctx(raw, None)
    home = os.path.join(_pb.ROOT, "perfbench")
    read = lambda name: report.load_reader(home, name).read(ctx)  # noqa: E731
    assert read("decision_p50_ms") == pytest.approx(200.0)
    lat = [a - d for a, d in zip(w.answer_t, w.due_t)]
    assert read("decision_p95_ms.observed") == pytest.approx(np.percentile(lat, 95) * 1e3)
    assert read("decision_p99_ms.observed") == pytest.approx(2000.0)
    assert read("generator_lag_p99_ms") == pytest.approx(np.percentile(w.lag_s, 99) * 1e3)
    # the rate's reader has nothing to say of a window that bound nothing
    assert read("pods_per_s") is None


# -- the traced slice, the plan's headroom -------------------------------------


def test_the_slice_starts_at_a_backlogs_first_call_and_nowhere_else():
    """``on_boundary`` runs before every wire call; ``first`` is true for
    the call that follows a backlog's hint frame, when nothing of the
    backlog is on the device yet."""
    clock = FakeClock()
    side = FakeSidecar(clock)
    pods = FakePods(400)
    hints = [pods.uids[a: a + 20] for a in range(0, 400, 20)]
    seen = []
    loops.closed_loop(side, side, pods, hints, 0, 20, 7.0, clock=clock,
                      on_boundary=lambda elapsed, first: seen.append((round(elapsed, 3), first, side.calls)))
    # three backlogs of three calls each (8 + 8 + 4 pods): one boundary a call
    assert len(seen) == side.calls == 9
    assert [first for _, first, _ in seen] == [True, False, False] * 3
    # each before its call, after the hint frame (1 ms) of its backlog
    assert [(e, c) for e, _, c in seen[:4]] == [(0.001, 0), (1.001, 1), (2.001, 2), (3.002, 3)]


def test_the_slice_ends_at_its_bound_and_the_loop_is_not_held_by_the_stop(tmp_path):
    """The launcher's session ends itself ``seconds`` after its start, on a
    thread of its own: the asking thread gets its answer at once and goes
    on working, and whoever asks to stop later is told when the stop began,
    not when they asked."""
    import time

    from perfbench import launcher, trace

    sl = launcher.Slice(str(tmp_path))
    t_ask = time.monotonic()
    start = sl.start(0.3)
    assert time.monotonic() - t_ask < 0.25  # the start does not wait for the end
    turns = 0
    while time.monotonic() - t_ask < 0.8:  # the loop goes on through the armed stop
        turns += 1
        time.sleep(0.001)
    assert turns > 100 and sl.stop_marks is not None
    began, done = sl.stop()  # asked half a second after the stop
    assert (began, done) == sl.stop_marks
    assert (began - start[1]) * 1e-9 == pytest.approx(0.3, abs=0.1)
    ev = trace.read_events(trace.find_xplane(str(tmp_path)), rehearsal=True)
    assert ev["slice"][1] - ev["slice"][0] == pytest.approx(0.3, abs=0.1)
    with pytest.raises(RuntimeError):
        sl.start(0.1)  # one slice a run


def test_records_closed_after_the_stop_began_are_left_out_of_a_traced_runs_readers():
    from perfbench import cell, report

    records = [{"ts": 1000.0 + k, "pods": 100, "phases": {"drain": 0.5}} for k in range(6)]
    stop = (int(1003.5e9), int(1010.0e9))  # the stop began at 1003.5 and took 6.5 s
    kept = cell.closed_before(records, stop)
    assert [r["ts"] for r in kept] == [1000.0, 1001.0, 1002.0, 1003.0]
    assert cell.closed_before(records, None) == records  # no profiler, nothing left out
    raw = {"window": loops.Window(), "records": records, "records_before_stop": kept, "scrape0": {},
           "scrape1": {}, "config": {}, "mix": {}, "device": {"kind": "TPU v5 lite"}, "setup_s": 1.0}
    ctx = report.Ctx(raw, None)
    assert ctx.pods() == 400 and ctx.window_pods() == 600
    assert ctx.phase_s("drain") == pytest.approx(2.0)
    del raw["records_before_stop"]  # an untraced run's readers see every record
    assert report.Ctx(raw, None).pods() == 600


def _plan(name):
    import os

    from perfbench import cell, spec

    bench = spec.load(os.path.join(_pb.ROOT, "BENCHMARK.json"))
    _, config, mix = spec.cell(bench, name)
    return cell.pods_needed(config, mix, float(bench["run_seconds"]), 0), bench["run_seconds"]


@pytest.mark.parametrize("name,backlogs", [("basic_5kn.backlog", 19), ("podaffinity_5kn.backlog", 37)])
def test_the_plan_is_capped_by_the_clusters_room(name, backlogs):
    plan, _ = _plan(name)
    assert plan["window"] == backlogs * plan["backlog"]
    assert plan["initial"] + plan["warm"] + plan["window"] <= 200000


def _served(pods_per_s, plan, seconds):
    """The cell's plan against a sidecar that answers ``pods_per_s``."""
    clock = FakeClock()
    side = FakeSidecar(clock, batch=4096, batch_s=4096 / pods_per_s)
    pods = FakePods(plan["window"])
    hints = [pods.uids[a: a + plan["backlog"]] for a in range(0, plan["window"], plan["backlog"])]
    return loops.closed_loop(side, side, pods, hints, 0, plan["backlog"], float(seconds), clock=clock)


def test_a_server_twice_as_fast_as_the_ledgers_newest_reading_still_closes_its_window():
    plan, seconds = _plan("basic_5kn.backlog")
    w = _served(2 * 4904.7, plan, seconds)  # ledger, PR 26: 4,904.7 pods/s
    assert not w.short and w.seconds >= seconds and w.asked % plan["backlog"] == 0
    assert w.asked < plan["window"]


def test_a_window_that_runs_out_of_pods_closes_early_and_says_so():
    from perfbench import report

    plan, seconds = _plan("basic_5kn.backlog")
    w = _served(3 * 4904.7, plan, seconds)  # past the ceiling of ~10,500 pods/s
    assert w.asked == w.bound == plan["window"] and w.seconds < seconds
    assert "early" in w.short and str(plan["window"]) in w.short
    said = report.earlier_lines({"compiled_in_window": 0, "rehearsal": False, "window_short": w.short})
    assert len(said) == 1 and "WARNING" in said[0] and "not a measurement" in said[0]
    # a plan that holds not even one backlog is an error of the plan, not a window
    with pytest.raises(loops.ClusterFull):
        _served(1000.0, dict(plan, window=plan["backlog"] - 1), seconds)
