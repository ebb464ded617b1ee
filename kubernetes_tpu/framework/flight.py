"""Flight recorder: a bounded in-memory ring of per-batch attribution.

The headline throughput claim is one wall-clock number; when it regresses
— or when a batch is quarantined, the breaker trips, or a host dies —
nothing in a metrics scrape says *which phase* ate the time or what the
scheduler was doing in the seconds before the event.  Production
schedulers live on per-phase attribution (Gavel's heterogeneity-aware
policies, arxiv 2008.09213, schedule against measured per-phase costs;
the constraint-packing line of arxiv 2511.08373 likewise assumes the
operator can see where scheduling latency goes).  This module is the
black box that survives the incident:

- one structured :class:`dict` record per scheduled batch — batch seq,
  trace id, pod counts, per-phase timings (featurize / device / commit /
  journal append+fsync / snapshot), per-plugin durations when the batch
  was sampled, and dispatch kind;
- state-transition **markers** (breaker trip, degraded entry/exit,
  quarantine, engine fault, recovery, resync) interleaved in the same
  ring, so a dump reads as a timeline;
- automatic JSON **dumps** on the events an operator will be paged for
  (engine fault, quarantine, breaker trip, SIGTERM) plus on-demand dumps
  via the sidecar ``flight`` frame, ``GET /debug/flight``, and the
  ``flight`` CLI subcommand.

The ring is bounded (default ``DEFAULT_CAPACITY`` records) and appends
are O(1) under one lock — always-on is the point: the interesting batch
is the one you didn't know to instrument.  Timing uses ``perf_counter``
(monotonic; exempt from the det-wallclock lint); the wall-clock ``ts``
on each record exists for operators joining dumps to external logs and
never feeds a scheduling decision.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from collections import deque

DEFAULT_CAPACITY = 4096

# Auto-dump destination: TPU_FLIGHT_DIR wins (the chaos harness points it
# at the cell's state dir), else the system temp dir.
ENV_DUMP_DIR = "TPU_FLIGHT_DIR"


class FlightRecorder:
    """Bounded ring of batch records + transition markers.

    Thread-safe: the scheduling thread appends while HTTP/sidecar scrape
    threads snapshot.  ``component`` tags records and dump filenames so a
    host-side and a sidecar-side recorder dumping into one directory stay
    distinguishable."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        component: str = "scheduler",
        dump_dir: str | None = None,
        clock=time.time,
    ):
        self.capacity = max(1, int(capacity))
        self.component = component
        self.dump_dir = dump_dir
        self._clock = clock
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._seq = 0
        self.dumps = 0
        self.last_dump_path: str | None = None
        self.last_dump_reason: str | None = None

    # -- recording ---------------------------------------------------------

    def record_batch(self, rec: dict) -> dict:
        """Append one per-batch record (the caller fills phases/ids); the
        recorder stamps seq + wall-clock ts and returns the stored dict."""
        with self._lock:
            self._seq += 1
            # Reserved stamps win over caller fields — the ring's seq/ts
            # are ITS timeline, not the caller's numbering space.
            stored = dict(rec)
            stored.update(
                kind="batch", seq=self._seq, ts=round(self._clock(), 3)
            )
            self._ring.append(stored)
        return stored

    def record_marker(self, event: str, **fields) -> dict:
        """Append a state-transition marker (breaker_trip, degraded_enter,
        degraded_exit, quarantine, engine_fault, recovery, resync, …)."""
        with self._lock:
            self._seq += 1
            stored = dict(fields)
            stored.update(
                kind="marker",
                seq=self._seq,
                ts=round(self._clock(), 3),
                event=event,
            )
            self._ring.append(stored)
        return stored

    # -- reading -----------------------------------------------------------

    def records(self, limit: int | None = None) -> list[dict]:
        """Newest-last records; ``limit`` keeps the newest N (None/0 = all)."""
        with self._lock:
            out = list(self._ring)
        if limit:
            out = out[-limit:]
        return out

    def snapshot(self, limit: int | None = None) -> dict:
        """The JSON-ready dump payload (also what auto-dumps write)."""
        records = self.records(limit)
        return {
            "component": self.component,
            "capacity": self.capacity,
            "recorded": self._seq,
            "count": len(records),
            "dumps": self.dumps,
            "records": records,
        }

    # -- dumping -----------------------------------------------------------

    def _resolve_dump_dir(self) -> str:
        return (
            self.dump_dir
            or os.environ.get(ENV_DUMP_DIR)
            or tempfile.gettempdir()
        )

    def dump(self, reason: str, path: str | None = None) -> str | None:
        """Write the ring as JSON.  Returns the path, or None when the
        write failed — a failing dump must never take the scheduler with
        it (the recorder is an observer, not a participant)."""
        payload = self.snapshot()
        payload["reason"] = reason
        if path is None:
            self.dumps += 1
            path = os.path.join(
                self._resolve_dump_dir(),
                f"flight-{self.component}-{os.getpid()}-"
                f"{self.dumps:03d}-{reason}.json",
            )
        try:
            with open(path, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                f.write("\n")
        except OSError:
            return None
        self.last_dump_path = path
        self.last_dump_reason = reason
        return path

    def install_sigterm(self) -> bool:
        """Dump on SIGTERM (chaining any previous handler) — the graceful
        half of the kill story; SIGKILL is what the chaos harness proves
        recovery against.  Main-thread only (signal module contract);
        returns whether the handler installed."""
        import signal

        try:
            prev = signal.getsignal(signal.SIGTERM)

            def _on_term(signum, frame):
                self.dump("sigterm")
                if callable(prev):
                    prev(signum, frame)
                else:
                    raise SystemExit(143)

            signal.signal(signal.SIGTERM, _on_term)
            return True
        except ValueError:  # not the main thread
            return False


def load_dump(path: str) -> dict:
    """Read one flight dump (the profile_report.py entry point)."""
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


# -- federated fleet merge ---------------------------------------------------
#
# A partitioned fleet sheds N disjoint flight logs (one per owner, plus
# the router's).  ``merge_fleet`` folds them into ONE fleet document with
# two distinct sections:
#
# - ``timeline`` — the deterministic event sequence, ordered on the
#   LOGICAL clock (the ``lc`` field callers stamp on records: the soak's
#   scenario clock, the router's cycle counter).  Wall-derived fields
#   (ts, wall_s, phases) are stripped, so two same-seed runs produce a
#   byte-identical timeline (``timeline_sha256`` is the replayability
#   hash the soak artifact records).
# - ``wall`` / ``critical_path`` — the attribution sections, computed
#   from the records' wall timestamps: per-component busy time, fleet
#   union busy time, the overlap between components (parallelism), and
#   a critical-path sweep that attributes each instant of fleet busy
#   time to the (component, phase) slice doing the gating WORK — among
#   the slices active at that instant, the innermost one (shortest
#   enclosing batch), so a router blocked on an owner RPC credits the
#   owner's device pass, not its own wait.
#   Honest about being wall-derived: excluded from the timeline hash.

# Phase keys that nest inside (or overlap) the tiled phases — excluded
# from tiling, same list profile_report uses.
TILED_EXCLUDE = ("journal_append", "journal_fsync", "hint_decode")
# Canonical within-batch tiling order for the critical-path sweep;
# phases not listed sort after, alphabetically.  predispatch (the next
# batch's early dispatch) and drain (the group-committed journal fsync +
# applies) are the pipeline stages ISSUE 15 added after commit.
PHASE_ORDER = (
    "featurize", "eval", "device", "scatter", "select", "commit",
    "predispatch", "drain", "snapshot", "other",
)

# Deterministic record fields the merged timeline keeps (everything
# wall-derived stays out — the hash must replay).  ``hetero`` (the
# per-record {workload_class|accel: binds} split) and ``drained``/
# ``group_fsyncs`` (the pipeline drain's counts) ride along so a merged
# fleet doc still carries the inputs framework/measured.py folds into
# measured throughput rows and the trace exporter sizes stages from.
# Top-level spans of a record (framework/tracing.SpanSink) and the phase
# each stands for on the critical path, where a record carries real span
# starts (``spans`` + ``t0_ns``) instead of phase sums alone.
SPAN_PHASE = {
    "batch/featurize": "featurize", "pass/inflight": "device",
    "commit/stage": "commit", "commit/failed": "commit",
    "pipeline/predispatch": "predispatch", "pipeline/drain": "drain",
    "pipeline/snapshot": "snapshot",
}

_TIMELINE_FIELDS = (
    "event", "pods", "scheduled", "unschedulable", "deferred",
    "dispatch", "tenant", "op", "shard", "from", "to", "clock", "version",
    "hetero", "drained", "group_fsyncs",
)


def _phase_rank(name: str) -> tuple:
    try:
        return (PHASE_ORDER.index(name), "")
    except ValueError:
        return (len(PHASE_ORDER), name)


def _merge_intervals(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def _critical_path(slices: list[tuple]) -> dict[tuple[str, str], float]:
    """Sweep the phase slices ((start, end, component, phase,
    batch_len)) and attribute each elementary busy segment to the
    INNERMOST active slice — the one belonging to the shortest enclosing
    batch.  A router batch encloses the owner RPCs it blocks on, so
    during an owner's device pass the owner's slice (not the router's
    wait) gets the time; when only the enclosing component is busy
    (select, bookkeeping) it takes the attribution itself.  Ties break
    on (component, phase) — stable and deterministic."""
    import heapq

    events: list[tuple[float, int, int]] = []
    for i, (start, end, _c, _p, _bl) in enumerate(slices):
        if end > start:
            events.append((start, 1, i))
            events.append((end, 0, i))
    events.sort()
    out: dict[tuple[str, str], float] = {}
    active: set[int] = set()
    heap: list[tuple] = []  # (batch_len, component, phase, idx), lazy-deleted
    prev: float | None = None
    for ts, kind, idx in events:
        if prev is not None and active and ts > prev:
            while heap and heap[0][3] not in active:
                heapq.heappop(heap)
            if heap:
                _bl, comp, phase, _i = heap[0]
                key = (comp, phase)
                out[key] = out.get(key, 0.0) + (ts - prev)
        if kind == 1:
            active.add(idx)
            _s, _e, comp, phase, batch_len = slices[idx]
            heapq.heappush(heap, (batch_len, comp, phase, idx))
        else:
            active.discard(idx)
        prev = ts
    return out


def merge_fleet(
    snapshots: list[dict], names: list[str] | None = None
) -> dict:
    """Merge per-component flight snapshots (``FlightRecorder.snapshot``
    documents) into one fleet timeline + attribution document.  ``names``
    overrides the components' self-reported names (the fleet soak labels
    owners ``owner-K`` and the front door ``router``); duplicate names
    get ``#2``-style suffixes so records stay attributable."""
    comps: list[tuple[str, list[dict]]] = []
    seen: set[str] = set()
    for i, snap in enumerate(snapshots):
        name = (
            names[i]
            if names is not None and i < len(names)
            else snap.get("component", f"component-{i}")
        )
        base, k = name, 2
        while name in seen:
            name = f"{base}#{k}"
            k += 1
        seen.add(name)
        comps.append((name, list(snap.get("records") or ())))

    timeline: list[dict] = []
    slices: list[tuple] = []
    comp_stats: dict[str, dict] = {}
    comp_intervals: dict[str, list] = {}
    for name, records in comps:
        stats = comp_stats.setdefault(
            name,
            {"records": 0, "batches": 0, "markers": 0, "busy_s": 0.0,
             "phases": {}},
        )
        for rec in records:
            stats["records"] += 1
            entry = {
                "component": name,
                "seq": rec.get("seq", 0),
                "kind": rec.get("kind", "?"),
            }
            if rec.get("lc") is not None:
                entry["lc"] = rec["lc"]
            for key in _TIMELINE_FIELDS:
                if key in rec:
                    entry[key] = rec[key]
            timeline.append(entry)
            if rec.get("kind") == "marker":
                stats["markers"] += 1
                continue
            if rec.get("kind") != "batch":
                continue
            stats["batches"] += 1
            wall = float(rec.get("wall_s") or 0.0)
            ts = rec.get("ts")
            if wall <= 0 or ts is None:
                continue
            end = float(ts)
            start = end - wall
            placed = None  # [(start, seconds, phase)] where the record has starts
            if rec.get("spans") and rec.get("t0_ns"):
                # Real starts: the batch began at t0_ns and each
                # top-level span sits where the primitive measured it.
                start = float(rec["t0_ns"]) * 1e-9
                end = start + wall
                placed = [
                    (start + sp[1] * 1e-6, sp[2] * 1e-6, SPAN_PHASE[sp[0]])
                    for sp in rec["spans"]
                    if sp[3] < 0 and sp[0] in SPAN_PHASE and sp[2] > 0
                ]
            comp_intervals.setdefault(name, []).append((start, end))
            cursor = start
            phases = rec.get("phases") or {}
            for phase in sorted(phases, key=_phase_rank):
                if phase in TILED_EXCLUDE:
                    continue
                dur = float(phases[phase])
                if dur <= 0:
                    continue
                stats["phases"][phase] = (
                    stats["phases"].get(phase, 0.0) + dur
                )
                if placed is None:
                    # no starts on the record: lay the phases end to end
                    slices.append(
                        (cursor, min(cursor + dur, end), name, phase, wall)
                    )
                    cursor += dur
            for s0, dur, phase in placed or ():
                slices.append((max(s0, start), min(s0 + dur, end), name, phase, wall))
    # The deterministic spine: logical-clock order, lc-less records after
    # (grouped per component in ring order).
    timeline.sort(
        key=lambda e: (
            0 if "lc" in e else 1,
            e.get("lc", 0.0),
            e["component"],
            e["seq"],
        )
    )
    import hashlib

    timeline_sha = hashlib.sha256(
        json.dumps(timeline, sort_keys=True).encode()
    ).hexdigest()

    all_intervals: list[tuple[float, float]] = []
    for name, intervals in comp_intervals.items():
        merged = _merge_intervals(intervals)
        comp_stats[name]["busy_s"] = round(
            sum(e - s for s, e in merged), 6
        )
        all_intervals.extend(merged)
    union = _merge_intervals(all_intervals)
    union_s = sum(e - s for s, e in union)
    busy_total = sum(c["busy_s"] for c in comp_stats.values())
    crit = _critical_path(slices)
    critical_path = [
        {
            "component": comp,
            "phase": phase,
            "seconds": round(secs, 6),
            "share": round(secs / union_s, 4) if union_s else 0.0,
        }
        for (comp, phase), secs in sorted(
            crit.items(), key=lambda kv: (-kv[1], kv[0])
        )
    ]
    for stats in comp_stats.values():
        stats["phases"] = {
            k: round(v, 6) for k, v in sorted(stats["phases"].items())
        }
    return {
        "metric": "fleet_flight_merge",
        "components": {k: comp_stats[k] for k in sorted(comp_stats)},
        "timeline": timeline,
        "timeline_events": len(timeline),
        "timeline_sha256": timeline_sha,
        "wall": {
            "busy_s_total": round(busy_total, 6),
            "union_busy_s": round(union_s, 6),
            "overlap_s": round(max(busy_total - union_s, 0.0), 6),
            "parallelism": round(busy_total / union_s, 4) if union_s else 0.0,
        },
        "critical_path": critical_path,
    }
