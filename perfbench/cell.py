"""One run of one cell: start the server, build the cluster, warm up, open
the window, close it, stop the server, read the journal back, compare."""

from __future__ import annotations

import gc
import os
import shutil
import signal
import tempfile
import threading
import time

from . import correct, loops, objects, server, traffic, wire


class NoChip(RuntimeError):
    """The serving process did not land on the chips the cell asks for."""


def cache_dir(root: str) -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")


def cache_entries(root: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir(root)) if not n.endswith("-atime"))
    except OSError:
        return 0


def prom(text: str) -> dict[str, float]:
    """A Prometheus text scrape as {``name{labels}``: value}."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                pass
    return out


def cluster_pod_capacity(config: dict) -> int:
    """Pods of the configuration's templates the cluster can hold, by the
    arithmetic its file states: per node the least of cpu, memory and the
    pod limit, each resource at the larger request of the initial and the
    measured pods' template, and where the file states one a further cap a
    node (``capacity.pods_per_node_max``: an attach limit at one volume a
    pod, say), so that the plan never prebuilds pods the cluster has no
    room for."""
    alloc = config["cluster"]["node_template"]["status"]["allocatable"]
    pod = config["pod"]
    req = {"cpu": 0, "memory": 0}
    for template in (pod["template"], pod.get("initial_template", pod["template"])):
        for k in req:
            req[k] = max(req[k], sum(int(c.get("requests", {}).get(k, 0))
                                     for c in template["spec"]["containers"]))
    per_node = min([int(alloc["pods"])] + [int(alloc[k]) // v for k, v in req.items() if v])
    cap = (config.get("capacity") or {}).get("pods_per_node_max")
    if cap is not None:
        per_node = min(per_node, int(cap))
    return per_node * int(config["cluster"]["nodes"])


def bind_echo(config: dict) -> bool:
    """Whether the configuration states that a pod goes back bound when it
    is answered (``pod.bind_echo``: ``"answered"``); anything else it may
    say is refused by name, there being one shape so far."""
    stated = config["pod"].get("bind_echo")
    if stated not in (None, "answered"):
        raise SystemExit(f"perfbench: pod.bind_echo: 'answered' or absent, not {stated!r}")
    return stated is not None


def pods_needed(config: dict, mix: dict, seconds: float, n_open: int) -> dict:
    s = config["serve"]
    w = mix["warmup"]
    initial = traffic.resolve(w["initial_pods"], config)
    second = int(w["full_batches"]) * s["batch_size"] + int(w["short_pods"])
    plan = {"initial": initial, "warm": second}
    if mix["loop"] == "closed":
        backlog = traffic.resolve(mix["backlog_pods"], config)
        est = (seconds + float(mix["prebuild_seconds_margin"])) * float(mix["prebuild_pods_per_s"])
        plan["backlog"] = backlog
        room = cluster_pod_capacity(config) - initial - second
        plan["window"] = min(int(est // backlog) + 2, room // backlog) * backlog
    else:
        plan["window"] = n_open
    return plan


def closed_before(records, stop_marks) -> list:
    """The flight records closed (``ts``, the serving process's clock, to
    the millisecond) before the profiler's stop began (``stop_marks``: the
    same clock in nanoseconds just before and after it); all of them
    where no profiler was stopped."""
    if not stop_marks:
        return list(records)
    return [x for x in records if float(x["ts"]) <= stop_marks[0] * 1e-9]


class Run:
    """Everything one run holds; ``close`` stops what it started."""

    def __init__(self):
        self.srv = None
        self.conn = None
        self.push = None
        self.sock_dir = None

    def close(self) -> None:
        for c in (self.conn, self.push):
            if c is not None:
                try:
                    c.close()
                except OSError:
                    pass
        self.conn = self.push = None
        if self.srv is not None:
            self.srv.stop(signal.SIGKILL, grace_s=10.0)
        if self.sock_dir is not None:
            shutil.rmtree(self.sock_dir, ignore_errors=True)


def run(root: str, cell: dict, config: dict, mix: dict, seed: int, seconds: float,
        traced: bool, rehearsal: bool, t_start: float, rate: float | None = None,
        out_root: str | None = None, log=print) -> dict:
    """Returns the raw material of the result: window, flight records,
    scrapes, correctness numbers, device, trace directory."""
    if mix["loop"] == "open" and (config["pod"].get("companions") or bind_echo(config)):
        raise SystemExit(f"perfbench: {cell['name']}: an open mix on a configuration whose pods have companions "
                         "or a bind echo is refused: only the closed loop sends them, until a cell needs more")
    out = os.path.join(out_root or os.path.join(root, ".perfbench_out"), cell["name"])
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(os.path.join(out, "flight"))
    journal_dir = os.path.join(out, "journal")
    trace_dir = os.path.join(out, "trace")
    r = Run()
    r.sock_dir = tempfile.mkdtemp(prefix="pb-")
    sock = os.path.join(r.sock_dir, "s.sock")
    trace_sock = os.path.join(r.sock_dir, "t.sock")
    if len(sock) > 100:
        raise RuntimeError(f"TMPDIR too deep for a unix socket: {sock}")
    env = dict(os.environ)
    env["TPU_FLIGHT_DIR"] = os.path.join(out, "flight")
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    try:
        entries_start = cache_entries(root)
        r.srv = server.Server(
            server.serve_argv(config, sock, journal_dir, traced, trace_sock, trace_dir),
            root, os.path.join(out, "serve.log"), env,
        )
        # Built while the server starts: every object this run will send.
        t_build = time.monotonic()
        nodes = objects.Nodes(config, seed)
        offsets: list[float] = []
        warm_offsets: list[float] = []
        if mix["loop"] == "open":
            offsets = traffic.open_offsets(mix, seconds, seed, rate)
            warm_offsets = traffic.open_offsets(
                mix, float(mix["warmup"].get("arrivals_s", 0.0)), seed + 1, rate)
        plan = pods_needed(config, mix, seconds, len(offsets))
        total = plan["initial"] + plan["warm"] + len(warm_offsets) + plan["window"]
        pods = objects.Pods(config, seed, total, plan["initial"])
        pod_json_by_uid = dict(zip(pods.uids, pods.jsons))
        companions = objects.Companions(config, nodes, pods, plan["initial"])

        def hint_frame(a: int, z: int) -> bytes:
            return wire.pending_pods_frame(pods.jsons[a:z])

        first_window = plan["initial"] + plan["warm"] + len(warm_offsets)
        window_hint_frames = []
        setup_groups = [(0, plan["initial"]), (plan["initial"], plan["initial"] + plan["warm"])]
        window_companions = None
        setup_companions = [None] * len(setup_groups)
        echo = pods.bound_frame if bind_echo(config) else None
        if mix["loop"] == "closed":
            starts = range(first_window, total, plan["backlog"])
            window_hint_frames = [hint_frame(a, a + plan["backlog"]) for a in starts]
            if companions.per_pod:
                window_companions = [companions.frames(a, a + plan["backlog"]) for a in starts]
                setup_companions = [[companions.frames(a, z)] for a, z in setup_groups]
        built_s = time.monotonic() - t_build
        r.srv.wait_listening(sock, 900.0)
        listening_s = time.monotonic() - t_start
        r.conn = wire.Conn(sock)
        health = r.conn.health()
        device = {"platform": str(health.get("platform")), "kind": str(health.get("device_kind")),
                  "count": int(health.get("n_devices", 0))}
        if not rehearsal and (device["platform"] != "tpu" or device["count"] < cell["chips"]):
            raise NoChip(f"serve runs on {device}, the cell asks for {cell['chips']} TPU chip(s)")
        r.conn.add_many("Node", nodes.jsons)
        for kind, jsons in companions.of_nodes:  # right after the nodes, in the nodes' order
            r.conn.add_many(kind, jsons)
        nodes_s = time.monotonic() - t_start
        r.push = wire.PushMap(sock)
        asked: dict[str, str] = {}
        commit_order = r.push.order

        conn = r.conn

        def take(w: loops.Window) -> None:
            for k, node in enumerate(w.nodes):
                asked[pods.uids[w.first + k]] = node

        # Set-up traffic: the configuration's initial pods, then a warm-up
        # that uses every program of the window twice, over the same calls.
        cursor = 0
        sent_setup = {"objects": 0, "s": 0.0, "echoes": 0}
        for (a, z), group_companions in zip(setup_groups, setup_companions):
            if z > a:
                ws = loops.closed_loop(conn, r.push, pods, [hint_frame(a, z)], a, z - a, float("inf"),
                                       max_backlogs=1, companions=group_companions, echo=echo)
                take(ws)
                sent_setup["objects"] += ws.companion_objects
                sent_setup["s"] += ws.companion_s
                sent_setup["echoes"] += ws.echo_objects
                cursor = z
        if warm_offsets:
            take(loops.open_loop(conn, r.push, pods, cursor, warm_offsets, hint_frame,
                                 float(mix["hint_flush_delay_s"])))
            cursor += len(warm_offsets)
        assert cursor == first_window
        unbound_setup = sum(1 for n in asked.values() if not n)
        if unbound_setup:
            raise RuntimeError(f"{unbound_setup} set-up pods came back without a node\n" + r.srv.log_tail())
        r.push.drain()
        r.push.map.clear()

        trace_ctl = None
        on_boundary = None
        marks: dict = {}
        if traced:
            trace_ctl = server.TraceControl(trace_sock)
            # One rule for the traced slice.  It starts just before the
            # first wire call that follows a hint frame (nothing of that
            # batch is on the device yet) at or after the window's last
            # ``trace.seconds`` of the mix: steady state.  It ends after
            # the configuration's ``trace.seconds`` where the file has
            # them (events a second follow the pass's shape, not the
            # traffic), else the mix's, wherever a pass stands, or when
            # the window has closed, whichever comes first.  The serving
            # process ends it itself: this loop never waits on the stop.
            t_from = max(seconds - float(mix["trace"]["seconds"]), 0.0)
            slice_s = float((config.get("trace") or mix["trace"])["seconds"])

            def on_boundary(elapsed: float, first: bool) -> None:
                if first and not trace_ctl.started and elapsed >= t_from:
                    marks["start"] = trace_ctl.start(slice_s)

        scrape0 = prom(r.conn.metrics_text())
        flight0 = r.conn.flight(limit=1).get("recorded", 0)
        entries0 = cache_entries(root)
        gc.collect()
        gc.freeze()
        setup_s = time.monotonic() - t_start
        wall_open = time.time()
        if mix["loop"] == "closed":
            w = loops.closed_loop(conn, r.push, pods, window_hint_frames, first_window,
                                  plan["backlog"], seconds, on_boundary=on_boundary,
                                  companions=window_companions, echo=echo)
        else:
            w = loops.open_loop(conn, r.push, pods, first_window, offsets, hint_frame,
                                float(mix["hint_flush_delay_s"]), on_boundary=on_boundary)
        wall_close = time.time()
        gc.unfreeze()
        take(w)
        if trace_ctl is not None and trace_ctl.started:
            marks["stop"] = trace_ctl.stop()
        entries1 = cache_entries(root)
        scrape1 = prom(r.conn.metrics_text())
        fl = r.conn.flight()
        records = [x for x in fl.get("records", []) if x.get("kind") == "batch" and x.get("seq", 0) > flight0]
        markers = [x for x in fl.get("records", []) if x.get("kind") == "marker" and x.get("seq", 0) > flight0]
        # Stopping the profiler takes seconds of the serving process, and
        # the slice's end may fall inside the window: a traced run's
        # readers see the batches closed before the stop began.
        before_stop = closed_before(records, marks.get("stop"))
        r.push.drain()
        measured = set(pods.uids[w.first: w.first + w.asked])
        r.conn.close()
        r.conn = None
        rc = r.srv.stop(signal.SIGTERM, grace_s=60.0)
        r.srv = None
        # ``recover`` is a child that reads files; the reference replays the
        # commits in this process meanwhile, and asks for the journal last.
        read_back: dict = {}

        def recover() -> None:
            t0 = time.monotonic()
            try:
                read_back["bindings"] = server.recover_bindings(
                    config, journal_dir, root, os.path.join(out, "recover.log"), env)
            except (RuntimeError, ValueError, KeyError, OSError) as exc:
                log(f"perfbench: the journal could not be read back: {exc}")
            read_back["s"] = time.monotonic() - t0

        def journal():
            reader.join()
            return read_back.get("bindings")

        reader = threading.Thread(target=recover)
        t0 = time.monotonic()
        reader.start()
        try:
            verdict = correct.compare(config, nodes.jsons, nodes.names, pod_json_by_uid,
                                      list(commit_order), asked, measured, journal, companions)
        finally:
            reader.join()
        recovered = read_back.get("bindings")
        verdict["info"]["compare_s"] = round(time.monotonic() - t0, 3)
        verdict["info"]["recover_s"] = round(read_back["s"], 3)
        verdict["info"]["journal_bindings"] = None if recovered is None else len(recovered)
        return {
            "window": w, "records": records, "records_before_stop": before_stop, "markers": markers,
            "scrape0": scrape0, "scrape1": scrape1, "device": device,
            "setup_s": setup_s, "listening_s": listening_s, "nodes_s": nodes_s, "built_s": built_s,
            "cache_entries": {"at_start": entries_start, "window_open": entries0, "window_close": entries1},
            "verdict": verdict, "trace_dir": trace_dir if traced else None,
            "trace_marks": marks if traced else None,  # the serving process's clock around start and stop
            "wall_open": wall_open, "wall_close": wall_close, "serve_rc": rc,
            "out": out, "plan": plan, "config": config, "mix": mix, "cell": cell,
            "companions": {"of_nodes": companions.node_objects, "setup": sent_setup["objects"],
                           "setup_s": sent_setup["s"], "setup_echoes": sent_setup["echoes"]},
            "seconds": seconds, "push": {"frames": r.push.frames, "invalidations": r.push.invalidations,
                                         "decided": len(commit_order)},
        }
    finally:
        r.close()
