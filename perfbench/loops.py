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
          Where the configuration gives the row's churn (``pod.churn``,
          objects.Churn), it starts with the set-up's warm-up (upstream's
          churn op runs before the measured op and through it, and the
          preemption pass compiles there) and again at the window's
          opening: the next churn pod falls due where a loop starts and
          each after it one interval later, so the window's schedule does
          not depend on how long the set-up took; at every point
          where the loop would ask for its next pod, one churn pod that is
          due is asked first, as the host's activeQ pops by priority: a
          one-pod hint frame, then the pod from the map or over the wire.
          A preemption verdict is acted on as the plugin's PostFilter
          does: one RemoveObject a victim, in the answer's order (the
          API DELETEs come back through the informer), and once they are
          acknowledged the pod is asked for again, with its nominated node
          in its status; it counts as unanswered if it has not bound after
          PREEMPT_ASKS asks.  ``pods_per_s`` counts the measured pods only,
          as upstream's collector counts the op with collectMetrics.
          Where the configuration says so (``pod.recycle``: "measured"),
          before each backlog after the first the previous backlog's
          measured pods still bound are removed as pipelined RemoveObject
          frames, and the seconds that takes are taken out of the window's
          length: the harness fitting many backlogs into a cluster of the
          row's own size, not the row's work.
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


# A churn pod is asked for at most this many times: the first ask, the ask
# after its victims are gone (the nominated node's room is held for it, so
# this one binds), and two more for a nomination that had to be acted on
# again because the room was taken in between; upstream requeues the pod on
# each victim's delete, so it too asks again until the pod binds.
PREEMPT_ASKS = 4


class ClusterFull(RuntimeError):
    """The run has no prebuilt pods for a single backlog or for its
    arrivals: the plan outgrew the cluster's stated capacity."""


class Removals:
    """The pods the host deletes, over the set-up's loops and the window's:
    ``frames(uids)`` makes their RemoveObject frames and their count,
    ``gone`` holds every pod deleted so far (a victim the loop has not yet
    asked for is not asked for: the informer's delete takes a pending pod
    out of the host's queue)."""

    def __init__(self, frames):
        self.frames = frames
        self.gone: set = set()

    def send(self, conn, push, uids) -> None:
        """Pipelined, acknowledged; in the push map's order after what the
        sidecar pushed before them, and before the invalidations they cause."""
        push.drain()
        push.note_removed(uids)
        conn.call_many(*self.frames(uids))
        self.gone.update(uids)
        push.drain()


class ChurnState:
    """The row's churn over the set-up's warm-up and the window: from each
    ``start`` the next pod of ``pods`` (objects.Churn) falls due at once
    and each after it one interval later; at most ``number`` of them
    (every prebuilt pod where it is 0).  ``answers[j]`` = (node, "" where
    it never bound; due; answered at)."""

    def __init__(self, pods, number: int):
        self.pods = pods
        self.limit = min(int(number), len(pods)) if number else len(pods)
        self.unlimited = not number
        self.t0 = None
        self.next = 0
        self.answers: list = []

    def start(self, now: float) -> None:
        self.t0 = now - self.next * self.pods.interval_s

    def due(self, now: float) -> bool:
        return self.t0 is not None and self.next < self.limit and now >= self.t0 + self.next * self.pods.interval_s

    @property
    def exhausted(self) -> bool:
        """An unlimited churn that used every prebuilt pod."""
        return self.unlimited and self.next >= len(self.pods)


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
    churn_pods: int = 0  # where the configuration has a churn: its pods asked for inside the window
    nominations: int = 0  # ... preemption verdicts acted on
    victims_removed: int = 0  # ... RemoveObject frames sent for their victims
    preempt_retries: int = 0  # ... asks of a churn pod after its first
    churn_due_to_bound_s: list = field(default_factory=list)  # ... each one's seconds from due to bound (None: never)
    recycle_objects: int = 0  # where the configuration recycles: bound measured pods removed between backlogs
    recycle_s: float = 0.0  # ... and the seconds that took, out of the window's length
    first: int = 0  # index of the first pod of the window
    nodes: list = field(default_factory=list)  # node per asked pod, in order
    answer_t: list = field(default_factory=list)  # clock at each answer
    due_t: list = field(default_factory=list)  # open loop: clock each was due
    lag_s: list = field(default_factory=list)  # open loop: generator lateness
    miss_at: list = field(default_factory=list)  # (pod index, t_before, t_after)
    short: str = ""  # closed loop: why the window closed before ``seconds``

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open - self.recycle_s


def closed_loop(conn, push, pods, hint_frames, first: int, backlog: int,
                seconds: float, clock=time.perf_counter, on_boundary=None,
                max_backlogs: int | None = None, companions=None, echo=None,
                churn: ChurnState | None = None, removals: Removals | None = None,
                recycle: bool = False) -> Window:
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
    run starts its slice there; a churn pod's ask is such a call too).
    ``churn`` (where given) starts again with this loop, and ``removals`` deletes its
    victims and, with ``recycle``, the previous backlog's bound pods.  A
    pod deleted before it was asked for has ``None`` for its node."""
    w = Window(first=first)
    uids, frames = pods.uids, pods.frames
    nodes, answer_t = w.nodes, w.answer_t
    pop, drain = push.pop, push.drain
    gone = removals.gone if removals is not None else ()
    t_open = w.t_open = clock()
    if churn is not None:
        churn.start(t_open)
    last = t_open
    i = first
    b = 0
    while True:
        now = clock()
        if i > first and now - t_open - w.recycle_s >= seconds:
            break
        if i + backlog > len(uids) or b >= len(hint_frames):
            if i == first:
                raise ClusterFull(f"no pods prebuilt for one backlog of {backlog}")
            w.short = (f"every prebuilt pod ({i - first}) was answered {now - t_open - w.recycle_s:.3f} s into a "
                       f"window of {seconds} s: it closed there, early")
            break
        if recycle and i > first:
            t0 = clock()
            bound = [u for u, n in zip(uids[i - backlog: i], nodes[i - backlog - first:]) if n and u not in gone]
            if bound:
                removals.send(conn, push, bound)
            w.recycle_objects += len(bound)
            w.recycle_s += clock() - t0
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
        fresh = True  # no wire call has followed the hint frame yet
        for k in range(i, i + backlog):
            if churn is not None and churn.due(clock()):
                if _ask_churn(conn, push, churn, removals, w, clock, echo is not None,
                              on_boundary and (lambda first: on_boundary(clock() - t_open, first and fresh))):
                    fresh = False
            uid = uids[k]
            if uid in gone:
                nodes.append(None)
                answer_t.append(last)
                continue
            node = pop(uid)
            if node is None:
                drain()
                node = pop(uid)
            if node is None:
                now = clock()
                if on_boundary is not None:
                    on_boundary(now - t_open, fresh)
                    now = clock()
                fresh = False
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
    w.asked = sum(1 for n in nodes if n is not None)
    w.bound = sum(1 for n in nodes if n)
    w.t_close = last
    return w


def _ask_churn(conn, push, churn: ChurnState, removals: Removals, w: Window, clock, echo: bool,
               boundary=None) -> int:
    """The next churn pod, due now: its hint frame, then the pod, from the
    map or over the wire, a preemption verdict acted on (module docstring).
    ``boundary(first)`` runs before each ask, ``first`` for the first.
    Returns how many times it was asked for over the wire."""
    j = churn.next
    churn.next += 1
    cp = churn.pods
    uid = cp.uids[j]
    due = churn.t0 + j * cp.interval_s
    conn.call_raw(cp.hint_frame(j))
    node = push.pop(uid)
    if node is None:
        push.drain()
        node = push.pop(uid)
    frame = cp.frames[j]
    asks = 0
    while not node and asks < PREEMPT_ASKS:
        if boundary is not None:
            boundary(asks == 0)
        asks += 1
        r = conn.schedule_result(frame)
        if r is not None and r.node_name:
            node = r.node_name
            push.note(uid, node)
            push.drain()
            break
        push.drain()  # the batch that carried it committed before its verdict
        if r is not None and r.nominated_node:
            push.note_nomination(uid, r.nominated_node, r.victim_uids)
            victims = list(r.victim_uids)
            removals.send(conn, push, victims)
            w.nominations += 1
            w.victims_removed += len(victims)
            frame = cp.nominated_frame(j, r.nominated_node)
        else:
            push.note(uid, "")
    at = clock()
    node = node or ""
    if node and echo:
        conn.post(cp.bound_frame(j, node))
    w.churn_pods += 1
    w.preempt_retries += max(asks - 1, 0)
    w.churn_due_to_bound_s.append(at - due if node else None)
    churn.answers.append((node, due, at))
    return asks


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
