"""Tracing: the utiltrace analog + the JAX profiler hook.

The reference wraps each scheduling cycle in a poor-man's span trace and
dumps the step log only when the cycle was slow (schedule_one.go:412
``utiltrace.New("Scheduling", ...)`` + ``LogIfLong(100ms)``); real OTel
spans exist in the apiserver/kubelet but not the scheduler.  This module
is that shape — cheap always-on step timestamps, emitted only past a
threshold — extended two ways for the two-process split:

* **Nested child spans** (``Trace.nest``, the ``utiltrace.Nest`` analog):
  a slow root logs its whole subtree, children indented with their own
  steps, so "the batch was slow" decomposes into which phase was.
* **Stable trace/span ids**: every span carries a random ``trace_id``
  (inherited from its parent) and its own ``span_id``; the sidecar
  envelope threads the client's ids to the server (ScheduleBatchRequest
  trace_id/parent_span_id), so a server-side batch span logged here
  carries the HOST's trace id and the two processes' logs join on it.

* **One span primitive for the served path** (``SpanSink.span``, used
  as ``with sched.span("drain/apply"):``): every layer boundary of the
  batch loop is timed ONCE, and the one interval feeds the open batch's
  flight record (``spans`` and today's ``phases``), the
  ``scheduler_phase_duration_seconds`` histogram, the ``ScheduleBatch``
  step log and — as a ``jax.profiler.TraceAnnotation`` — the profiler's
  trace, on the device ops' clock, whenever a session is open.

For deep device-side visibility the CLI's ``bench --profile-dir`` wraps
the run in ``jax.profiler.trace`` (SURVEY §5: "add JAX profiler traces on
the sidecar")."""

from __future__ import annotations

import gc
import logging
import os
import time
from threading import get_ident

from jax.profiler import TraceAnnotation

from .metrics import Histogram, _labels_key

logger = logging.getLogger("kubernetes_tpu")

_TRACING = TraceAnnotation.is_enabled  # a profiler session is recording


def new_id(nbytes: int = 8) -> str:
    """Random lowercase-hex id (the W3C traceparent shape, truncated)."""
    return os.urandom(nbytes).hex()


class Trace:
    """utiltrace.New analog: record (step, t) pairs; log them all iff the
    total exceeded ``threshold_s`` (LogIfLong).  Children created with
    ``nest()`` share the trace id and are logged (and serialized by
    ``as_dict``) as a subtree of their root."""

    __slots__ = (
        "name", "threshold_s", "fields", "trace_id", "span_id",
        "parent_span_id", "children", "remote_children", "_parent", "_t0",
        "_t_end", "_steps", "_logged", "_on_slow",
    )

    def __init__(
        self,
        name: str,
        threshold_s: float = 0.1,
        *,
        parent: "Trace | None" = None,
        trace_id: str | None = None,
        parent_span_id: str | None = None,
        on_slow=None,
        **fields,
    ):
        self.name = name
        self.threshold_s = threshold_s
        self.fields = fields
        self._parent = parent
        if parent is not None:
            trace_id = parent.trace_id
            parent_span_id = parent.span_id
        self.trace_id = trace_id or new_id(8)
        self.span_id = new_id(4)
        # Set without a parent object when the parent span lives in another
        # process (the sidecar envelope's trace_id/parent_span_id pair).
        self.parent_span_id = parent_span_id
        self.children: list[Trace] = []
        # Serialized span trees from ANOTHER process that joined this
        # span (a fleet owner's op span riding back on the RPC
        # response).  Rendered and dumped as children; they carry their
        # own ids so the tree stays greppable across process logs.
        self.remote_children: list[dict] = []
        self._t0 = time.perf_counter()
        self._t_end: float | None = None
        self._steps: list[tuple[str, float]] = []
        self._logged = False
        self._on_slow = on_slow

    def step(self, msg: str) -> None:
        self._steps.append((msg, time.perf_counter()))

    def step_at(self, msg: str, ts: float) -> None:
        """A step whose ``perf_counter`` reading the caller already took
        (a span's end: the boundary is timed once)."""
        self._steps.append((msg, ts))

    def nest(self, name: str, **fields) -> "Trace":
        """Open a child span (utiltrace.Nest): same trace id, own span id.
        Children never self-log — the root emits the whole tree."""
        child = Trace(name, threshold_s=self.threshold_s, parent=self, **fields)
        self.children.append(child)
        return child

    def attach_remote(self, span_dict: dict) -> None:
        """Join a serialized span tree from another process as a child of
        THIS span (the router attaches the owner's op span returned on
        the fleet RPC).  The remote dict keeps its own trace/span ids —
        a well-formed remote span carries this trace's id and this
        span's id as its parent, which ``stitch_spans`` also verifies
        post-hoc over dumps."""
        if span_dict:
            self.remote_children.append(span_dict)

    def end(self) -> None:
        if self._t_end is None:
            self._t_end = time.perf_counter()

    def total_s(self) -> float:
        return (self._t_end if self._t_end is not None else time.perf_counter()) - self._t0

    def _header(self) -> str:
        ids = f"trace={self.trace_id} span={self.span_id}"
        if self.parent_span_id:
            ids += f" parent={self.parent_span_id}"
        tail = " ".join(f"{k}={v}" for k, v in self.fields.items())
        return (
            f'"{self.name}" total={self.total_s() * 1000:.1f}ms {ids}'
            + (f" {tail}" if tail else "")
        )

    def _render(self, parts: list[str], indent: str) -> None:
        parts.append(indent + self._header())
        events: list[tuple[float, str, Trace | None]] = [
            (ts, msg, None) for msg, ts in self._steps
        ]
        events.extend((c._t0, "", c) for c in self.children)
        prev = self._t0
        for ts, msg, child in sorted(events, key=lambda e: e[0]):
            if child is not None:
                child._render(parts, indent + "  ")
            else:
                parts.append(f"{indent}  {msg} (+{(ts - prev) * 1000:.1f}ms)")
                prev = ts
        for rc in self.remote_children:
            render_span_dict(rc, parts, indent + "  ")

    def log_if_long(self, threshold_s: float | None = None) -> bool:
        """Emit the span tree when the span ran long.  Returns whether it
        logged THIS call (the reference logs at V(2) through klog; here the
        ``kubernetes_tpu`` logger at INFO).  Emission is idempotent: a span
        already logged by an explicit call is not re-logged by ``__exit__``
        (or a second explicit call)."""
        if self._logged:
            return False
        threshold = self.threshold_s if threshold_s is None else threshold_s
        if self.total_s() <= threshold:
            return False
        self._logged = True
        parts: list[str] = []
        self._render(parts, "")
        logger.info("\n".join(parts))
        if self._on_slow is not None:
            self._on_slow(self)
        return True

    def as_dict(self) -> dict:
        """JSON-ready span tree (the `dump` frame's slow-span payload)."""
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "duration_ms": round(self.total_s() * 1000, 3),
            "fields": {k: str(v) for k, v in self.fields.items()},
            "steps": [
                [msg, round((ts - self._t0) * 1000, 3)] for msg, ts in self._steps
            ],
            "children": [c.as_dict() for c in self.children]
            + list(self.remote_children),
        }

    def __enter__(self) -> "Trace":
        return self

    def __exit__(self, *exc) -> None:
        self.end()
        if self._parent is None:
            self.log_if_long()


def render_span_dict(span: dict, parts: list[str], indent: str = "") -> None:
    """Render a SERIALIZED span tree (``as_dict`` shape) the way a live
    span renders — used for remote children stitched into a local tree
    and by profile_report's slow-span view."""
    ids = f"trace={span.get('trace_id')} span={span.get('span_id')}"
    if span.get("parent_span_id"):
        ids += f" parent={span['parent_span_id']}"
    tail = " ".join(f"{k}={v}" for k, v in (span.get("fields") or {}).items())
    parts.append(
        f'{indent}"{span.get("name")}" '
        f"total={span.get('duration_ms', 0):.1f}ms {ids}"
        + (f" {tail}" if tail else "")
    )
    for msg, offset_ms in span.get("steps") or ():
        parts.append(f"{indent}  {msg} (@{offset_ms:.1f}ms)")
    for child in span.get("children") or ():
        render_span_dict(child, parts, indent + "  ")


def stitch_spans(spans: list[dict]) -> list[dict]:
    """Join serialized span trees from MULTIPLE processes into forests:
    a span whose ``(trace_id, parent_span_id)`` matches another span's
    ``(trace_id, span_id)`` becomes that span's child (copies — inputs
    are not mutated).  Returns the roots (spans whose parent is absent
    from the input), each carrying its full cross-process subtree —
    the post-hoc version of ``Trace.attach_remote`` for dumps collected
    after the fact (router → owner → sidecar joined offline)."""
    import copy

    nodes = [copy.deepcopy(s) for s in spans]

    by_id: dict[tuple, dict] = {}

    def index(span: dict) -> None:
        by_id[(span.get("trace_id"), span.get("span_id"))] = span
        for child in span.get("children") or ():
            index(child)

    for span in nodes:
        index(span)
    roots = []
    for span in nodes:
        parent = by_id.get((span.get("trace_id"), span.get("parent_span_id")))
        if parent is not None and parent is not span:
            parent.setdefault("children", []).append(span)
        else:
            roots.append(span)
    return roots


# -- the span primitive -------------------------------------------------------
#
# Span names are a contract (PERF.md lists them; perfbench readers and
# perfbench/spans.py match on them).  In the profiler's trace every span
# is the event ``sched/<name>`` of the /host:CPU plane with the stat
# ``batch`` (the flight record's ``bid``) plus the call site's keywords.


class Span:
    """One timed interval.  ``t0``/``t1`` are its ``perf_counter``
    readings and ``dur_s`` their difference, for call sites that feed a
    derived number (a boundary shared with a phase that spans calls)."""

    __slots__ = ("_sink", "name", "_phase", "_label", "_kw", "_ann", "_rec",
                 "_idx", "stats", "t0", "t1", "dur_s")

    def __init__(self, sink: "SpanSink", name: str, phase, label, kw: dict):
        self._sink = sink
        self.name = name
        self._phase = phase
        # a span that feeds a phase reaches the histogram in the phase's
        # batch sum (observed when the record is written), not on its own
        self._label = label if label is not None else ("" if phase else name)
        self._kw = kw
        self.stats: dict | None = None
        self.t1 = self.dur_s = 0.0

    def set(self, key: str, value) -> None:
        """A number known only at the end (an accumulated sub-time): kept
        as the fifth element of the record's span entry."""
        if self.stats is None:
            self.stats = {}
        self.stats[key] = value

    def __enter__(self) -> "Span":
        sink = self._sink
        rec = sink._rec
        if rec is not None and sink._owner == get_ident():
            spans = rec["spans"]
            self._idx = len(spans)
            spans.append(None)  # list order is start order
            sink._stack.append(self._idx)
            self._rec = rec
        else:
            self._rec = None
        # No profiler session: no annotation (it would record nothing; the
        # check is a tenth of its cost, and spans fire once a frame).
        ann = self._ann = (
            TraceAnnotation("sched/" + self.name, batch=sink.bid, **self._kw)
            if _TRACING() else None
        )
        if ann is not None:
            ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        t1 = self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        dur = self.dur_s = t1 - self.t0
        sink = self._sink
        rec = self._rec
        if rec is not None:
            stack = sink._stack
            while stack and stack.pop() != self._idx:
                pass
            entry = (
                self.name,
                int((self.t0 - sink._t0) * 1e6),
                int(dur * 1e6),
                stack[-1] if stack else -1,
            )
            rec["spans"][self._idx] = (
                entry if self.stats is None else entry + (self.stats,)
            )
            if self._phase is not None:
                ph = rec["phases"]
                ph[self._phase] = ph.get(self._phase, 0.0) + dur
            tr = sink.trace
            if tr is not None:
                tr.step_at(self.name, t1)
        if self._label and sink._hist is not None:
            sink.cell(self._label).observe(dur)
        return False


class SpanSink:
    """Where a scheduler's spans land.  ``open`` starts a batch (the
    accumulator the flight record is built from gains ``spans``, ``t0_ns``
    and ``bid``); until ``close`` every span entered on the opening thread
    is appended to it.  Spans entered with no batch open, or on another
    thread (a handler waiting for the dispatch lock), only observe the
    histogram and annotate the profiler's trace; such a thread reads
    ``_rec`` and ``_owner`` unlocked, which can only tell it "not yours",
    and observes only where its span ends holding the dispatch lock.
    Always on: with no profiler session an annotation costs a check."""

    __slots__ = ("_hist", "_cells", "_rec", "_stack", "_owner", "_t0",
                 "bid", "trace")

    def __init__(self, hist=None):
        self._hist = hist  # HistogramFamily keyed by phase=
        self._cells: dict = {}
        self._rec: dict | None = None
        self._stack: list[int] = []
        self._owner = 0
        self._t0 = 0.0
        self.bid = 0
        self.trace: Trace | None = None

    def cell(self, label: str):
        """The histogram cell of one phase label (created on first use)."""
        c = self._cells.get(label)
        if c is None:
            c = self._cells[label] = self._hist.cells.setdefault(
                _labels_key({"phase": label}), Histogram()
            )
        return c

    def span(self, name: str, phase: str | None = None,
             label: str | None = None, **kw) -> Span:
        """``phase``: the flight record's phase key this span's seconds
        add to.  ``label``: the histogram's ``phase=`` value where it is
        not the span's name (``hint_decode`` keeps the one it has); ``""``
        for no observation: an interval the histogram already holds
        under a phase's label, or one that ends on a thread outside the
        dispatch lock."""
        return Span(self, name, phase, label, kw)

    def open(self, acc: dict) -> None:
        self.bid += 1
        self._owner = get_ident()
        self._stack.clear()
        acc["spans"] = []
        acc["bid"] = self.bid
        acc["t0_ns"] = time.time_ns()
        self._rec = acc
        self._t0 = time.perf_counter()

    def add(self, name: str, t0: float, t1: float) -> None:
        """An interval between two boundaries spans already timed (a pass
        in flight across calls): record entry only, top level.  ``t0`` may
        lie before the batch's start, so the start may be negative."""
        rec = self._rec
        if rec is not None:
            rec["spans"].append(
                (name, int((t0 - self._t0) * 1e6), int((t1 - t0) * 1e6), -1)
            )

    def close(self) -> float:
        """Ends the batch; returns its wall seconds."""
        wall = time.perf_counter() - self._t0
        rec, self._rec = self._rec, None
        self.trace = None
        if rec is not None and self._stack:
            # a span still open (an exception unwinding past the batch)
            # must not leave a hole in the list; indexes stay as they are
            rec["spans"] = [s or ("?", 0, -1, -1) for s in rec["spans"]]
            self._stack.clear()
        return wall


# For code that may run without a scheduler (a bare Journal): annotates the
# profiler's trace and nothing else.
NULL_SINK = SpanSink()


# -- process counters ---------------------------------------------------------
#
# Two things a serving process does behind the batch loop's back, counted
# where they happen: XLA programs built or loaded (every program of the
# process, not only the pass variants the scheduler holds), and the
# collector's pauses.  Totals are the process's; a scheduler's registry
# exports them at scrape time.
#
# The collector's old generation is the process's too.  A full collection
# walks every tracked object, and a scheduler's store only grows, so the
# interpreter's own schedule spends a quarter of a backlog's window walking
# pods that nothing will free.  `serve` (and nothing else: the collector is
# process-global, a library user keeps the interpreter's defaults) arms the
# policy below: every batch boundary that bound a pod runs a young
# collection and freezes what is left, so automatic collections walk one
# batch's worth; the full collection runs where the server already stops
# to walk the whole store, at the checkpoint.  Frozen objects are still
# freed by reference count; a cycle frozen before it became garbage waits
# for the next checkpoint.

# Fires around compile_or_get_cached: a load from the persistent cache
# counts like a build, with the seconds it took.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class ProcessCounters:
    def __init__(self) -> None:
        self.compiles = 0  # programs handed to the backend: built or loaded
        self.compile_s = 0.0
        self.gc_collections = [0, 0, 0]
        self.gc_pause_s = 0.0
        self.gc_hooked = False
        self.heap_armed = False
        self.gc_freezes = 0
        self.gc_sweep_reclaimed = 0  # unreachable objects the checkpoints' full collections found
        self._compile_hooked = False
        self._gc_t0 = 0.0

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0:
            self.gc_pause_s += time.perf_counter() - self._gc_t0
            self.gc_collections[min(int(info.get("generation", 0)), 2)] += 1
            self._gc_t0 = 0.0

    def hook_compiles(self) -> None:
        if not self._compile_hooked:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(self._on_duration)
            self._compile_hooked = True

    def hook_gc(self) -> None:
        if not self.gc_hooked:
            gc.callbacks.append(self._on_gc)
            self.gc_hooked = True

    def settle_heap(self) -> None:
        """A batch boundary: what the batch made and dropped in a cycle (an
        exception and its traceback, a closure) is reclaimed young, what
        survives (the store, the queue, tickets in flight) leaves the
        collector's walk.  ``gc.freeze`` is a list splice, whatever the
        heap holds."""
        gc.collect(1)
        gc.freeze()
        self.gc_freezes += 1

    def sweep_heap(self) -> None:
        """A checkpoint: the one full collection, so cyclic garbage that
        was frozen lives one checkpoint's records at most."""
        gc.unfreeze()
        self.gc_sweep_reclaimed += gc.collect()
        gc.freeze()
        self.gc_freezes += 1


PROCESS = ProcessCounters()
