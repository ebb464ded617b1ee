"""The measured loops: what the host scheduler's one thread does with the
plugin, for a closed backlog and for open arrivals.

Both take their server through three small objects (``conn`` for wire
calls, ``push`` for the plugin-local map) and a clock, so the tests drive them against a synthetic timeline.

The window rules live here and nowhere else:

  closed  the window opens at the first hint frame of the first measured
          backlog.  It can close only where the loop is about to send the
          next backlog, and does so at the first such point at or after
          ``seconds``: every pod of every batch committed so far has been
          answered, so a batch is never cut, and every window holds whole
          backlogs, the same mix of full and short batches whatever its
          length.  The window's end is the time of the last answer, and
          the rate is every pod answered with a node over that whole
          length: stalls included, no median of pieces.  Where the
          configuration gives its pods companions (objects.Companions:
          upstream creates a pod's claim and volume inside the measured
          createPods op, and an operator's job brings its claims with
          it), a backlog's companions go out as pipelined adds
          immediately before its hint frame, inside the window: it opens
          at the first companion frame of the first measured backlog.
          Where the configuration says so (``pod.bind_echo``), every
          pod goes back bound the moment it is answered (objects.Pods.
          bound_frame), as the plugin forwards the informer's update of a
          pod the host scheduler has bound (go/tpubatchscore/plugin.go,
          upsertPod: one AddObject a pod on the request connection): the
          frame is posted, not waited for, so the sidecar takes the
          echoes while the loop goes on answering, and the window closes
          at the last answer as in every cell.  (The sidecar rolls back
          every decision it does not know to be bound when an object
          arrives that the decision depends on, as the next backlog's
          claims and volumes do; a deployment's binds are long confirmed
          by then.  The accepted configurations state no echo and send
          none: PERF.md section 8.)  A run prebuilds
          its pods; a system so fast that they are all answered before
          ``seconds`` closes its window there, early, and the window says
          so (``short``): the result line carries it, not a traceback.
  open    pods fall due on a schedule drawn before the window and are
          asked for in arrival order at their due time or when the loop
          is free, whichever is later.  The window closes when the last
          pod due in it is answered, and a pod's latency runs from its
          due time to its answer.
"""

from __future__ import annotations

import bisect
import time
from dataclasses import dataclass, field


class ClusterFull(RuntimeError):
    """The run has no prebuilt pods for a single backlog or for its
    arrivals: the plan outgrew the cluster's stated capacity."""


@dataclass
class Window:
    t_open: float = 0.0
    t_close: float = 0.0
    asked: int = 0
    bound: int = 0
    hits: int = 0
    misses: int = 0
    wire_s: float = 0.0
    hint_frames: int = 0
    hint_s: float = 0.0
    companion_objects: int = 0  # closed loop: the pods' companions sent inside the window
    companion_s: float = 0.0  # ... and the time their pipelined adds took
    echo_objects: int = 0  # closed loop, where the configuration states them: bind echoes posted inside the window
    echo_s: float = 0.0  # ... and the loop's own time in making and posting them
    first: int = 0  # index of the first pod of the window
    nodes: list = field(default_factory=list)  # node per asked pod, in order
    answer_t: list = field(default_factory=list)  # clock at each answer
    due_t: list = field(default_factory=list)  # open loop: clock each was due
    lag_s: list = field(default_factory=list)  # open loop: generator lateness
    miss_at: list = field(default_factory=list)  # (pod index, t_before, t_after)
    short: str = ""  # closed loop: why the window closed before ``seconds``

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def closed_loop(conn, push, pods, hint_frames, first: int, backlog: int,
                seconds: float, clock=time.perf_counter, on_boundary=None,
                max_backlogs: int | None = None, companions=None, echo=None) -> Window:
    """Backlogs of ``backlog`` pods from ``pods[first:]`` until the window
    rule closes it.  ``hint_frames[b]`` is the prebuilt PendingPods frame
    of the b-th backlog, ``companions[b]`` (where given) the prebuilt
    AddObject frames of its pods' companions and their count, sent just
    before it, ``echo(k, node)`` (where given) what makes the bind echo
    of pod ``k``, posted as soon as the pod is answered with a node.
    ``on_boundary(elapsed, first)`` runs at every
    batch boundary, just before the wire call that starts a batch;
    ``first`` says that the call is the first after its backlog's hint
    frame, when nothing of the backlog is on the device yet (the traced
    run starts its slice there)."""
    w = Window(first=first)
    uids, frames = pods.uids, pods.frames
    nodes, answer_t = w.nodes, w.answer_t
    pop, drain = push.pop, push.drain
    t_open = w.t_open = clock()
    last = t_open
    i = first
    b = 0
    while True:
        now = clock()
        if i > first and now - t_open >= seconds:
            break
        if i + backlog > len(uids) or b >= len(hint_frames):
            if i == first:
                raise ClusterFull(f"no pods prebuilt for one backlog of {backlog}")
            w.short = (f"every prebuilt pod ({i - first}) was answered {now - t_open:.3f} s into a "
                       f"window of {seconds} s: it closed there, early")
            break
        if companions is not None and companions[b][1]:
            t0 = clock()
            conn.call_many(*companions[b])
            w.companion_s += clock() - t0
            w.companion_objects += companions[b][1]
        t0 = clock()
        conn.call_raw(hint_frames[b])
        w.hint_s += clock() - t0
        w.hint_frames += 1
        b += 1
        for k in range(i, i + backlog):
            uid = uids[k]
            node = pop(uid)
            if node is None:
                drain()
                node = pop(uid)
            if node is None:
                now = clock()
                if on_boundary is not None:
                    on_boundary(now - t_open, k == i)
                    now = clock()
                node = conn.schedule_raw(frames[k])
                last = clock()
                push.note(uid, node)
                w.wire_s += last - now
                w.misses += 1
                w.miss_at.append((k, now, last))
                drain()
            else:
                w.hits += 1
                last = clock()
            nodes.append(node)
            answer_t.append(last)
            if echo is not None and node:
                t0 = clock()
                conn.post(echo(k, node))
                w.echo_objects += 1
                w.echo_s += clock() - t0
        i += backlog
        if max_backlogs is not None and b >= max_backlogs:
            break
    w.asked = len(nodes)
    w.bound = sum(1 for n in nodes if n)
    w.t_close = last
    return w


def open_loop(conn, push, pods, first: int, offsets, make_hint_frame,
              flush_delay_s: float = 0.002, clock=time.perf_counter,
              sleep=time.sleep, on_boundary=None) -> Window:
    """Pods ``pods[first + q]`` fall due at ``offsets[q]`` seconds after
    the opening and are asked for in arrival order, each at its due time
    or when the loop is free, whichever is later.  Hints ride the same
    connection as the calls, as the plugin's flusher shares its client's
    (go/tpubatchscore/subscriber.go): every pod due and not yet hinted
    goes out as one PendingPods frame once the oldest of them has waited
    ``flush_delay_s`` and before any wire call, so a call that blocks
    holds the hints of the pods that fall due behind it, and the batch it
    starts holds all that were due before it."""
    w = Window(first=first)
    n = len(offsets)
    uids, frames = pods.uids, pods.frames
    if first + n > len(uids):
        raise ClusterFull(f"{n} arrivals but {len(uids) - first} pods left")
    t_open = w.t_open = clock() + 0.02
    nodes, answer_t, due_t = w.nodes, w.answer_t, w.due_t
    pop, drain = push.pop, push.drain
    woke_late = w.lag_s
    hinted = 0
    last = t_open

    def flush(now: float, force: bool) -> None:
        nonlocal hinted
        if hinted >= n or offsets[hinted] > now - t_open:
            return
        if not force and now - t_open - offsets[hinted] < flush_delay_s:
            return
        k = bisect.bisect_right(offsets, now - t_open, hinted)
        t0 = clock()
        conn.call_raw(make_hint_frame(first + hinted, first + k))
        w.hint_s += clock() - t0
        w.hint_frames += 1
        hinted = k

    for q in range(n):
        due = t_open + offsets[q]
        now = clock()
        if now < due:
            sleep(due - now)
            now = clock()
            woke_late.append(max(now - due, 0.0))
        flush(now, False)
        uid = uids[first + q]
        node = pop(uid)
        if node is None:
            drain()
            node = pop(uid)
        if node is None:
            now = clock()
            if on_boundary is not None:
                # every call of this loop follows its own hint flush
                on_boundary(now - t_open, True)
                now = clock()
            flush(now, True)
            now = clock()
            node = conn.schedule_raw(frames[first + q])
            last = clock()
            push.note(uid, node)
            w.wire_s += last - now
            w.misses += 1
            w.miss_at.append((first + q, now, last))
            drain()
        else:
            w.hits += 1
            last = clock()
        nodes.append(node)
        answer_t.append(last)
        due_t.append(due)
    w.asked = len(nodes)
    w.bound = sum(1 for x in nodes if x)
    w.t_close = last
    return w


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics, as ``numpy.percentile``'s default does."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
