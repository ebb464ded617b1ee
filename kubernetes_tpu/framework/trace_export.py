"""Perfetto/Chrome trace-event export of flight documents (ISSUE 16).

The flight recorder (PR 5/12) and merge_fleet already hold the whole
story of a run — per-batch phase tilings, pipeline stage flights
(predispatch/drain, PR 15), markers, and the router→owner op records —
but only as JSON dicts.  This module renders any flight dump or
``merge_fleet`` document as ``trace_event`` JSON browsable in Perfetto /
chrome://tracing (the "JSON Object Format": ``{"traceEvents": [...]}``),
shared by ``scripts/export_trace.py``, ``GET /debug/trace`` and the
``trace`` CLI subcommand.

Two timebases:

- ``logical`` (default): the deterministic timeline.  Records are laid
  out on their logical order (``lc`` when stamped, ring ``seq``
  otherwise), one fixed-width slot each; phase slices tile the slot by
  PRESENCE (equal widths — wall durations differ run to run and are
  stripped, as are ``ts``/``wall_s``/``plugins``/span ids, mirroring
  merge_fleet's timeline-hash discipline).  Two same-seed runs render
  byte-identical traces — the diffable artifact.  The pipeline stages
  (``predispatch``/``drain``) render on their own per-component track
  overlapping the batch's stage tiling, so PR 15's "commit hides under
  the next in-flight pass" story is visible as overlapping tracks, not
  a scalar coverage ratio.
- ``wall``: honest wall attribution.  A record that carries ``spans``
  and ``t0_ns`` (the span primitive, framework/tracing.py) is drawn
  from them: the batch starts at ``t0_ns`` and every span sits at its
  measured start, nested by containment, with the pipeline stages and
  the pass in flight (which may have been dispatched in the call
  before) on the overlap track.  A record without them spans
  ``[ts - wall_s, ts]`` and its phases tile by their measured seconds
  (the cursor walk merge_fleet's critical path falls back to as well).
  Not stable across runs, by construction.

Stdlib-only: no JAX, no package-internal imports — profile_report-style
consumers load this module by file path.
"""

from __future__ import annotations

import json

# One logical record slot, in trace microseconds (1 ms per record reads
# well at Perfetto's default zoom).
LOGICAL_UNIT_US = 1000

# Phase keys that nest inside the tiled phases (same list merge_fleet
# and profile_report exclude from tiling).
_TILED_EXCLUDE = ("journal_append", "journal_fsync", "hint_decode")
# Canonical tiling order (framework/flight.PHASE_ORDER) minus the
# pipeline stages, which render on the overlap track instead.
_PHASE_ORDER = (
    "featurize", "eval", "device", "scatter", "select", "commit",
    "snapshot", "other",
)
_PIPELINE_PHASES = ("predispatch", "drain")

# Record fields that are wall-derived or run-unstable — stripped from
# logical-timebase event args so the rendered trace is sha-stable
# across same-seed runs.
_WALL_ARG_FIELDS = (
    "ts", "wall_s", "phases", "plugins", "journal", "overlap",
    "trace_id", "span_id", "spans", "t0_ns", "queue_wait", "bid",
)
# Top-level spans drawn on the overlap track in the wall timebase.
_OVERLAP_SPANS = ("pipeline/predispatch", "pipeline/drain", "pass/inflight")

_TRACK_BATCH = 0
_TRACK_STAGES = 1
_TRACK_PIPELINE = 2
_TRACK_NAMES = {
    _TRACK_BATCH: "batches",
    _TRACK_STAGES: "stages",
    _TRACK_PIPELINE: "pipeline (overlapped)",
}


def _components(doc) -> list[tuple[str, list[dict]]]:
    """Normalize a flight snapshot, a merge_fleet document, or a bare
    record list to ``[(component, records)]``, components sorted."""
    if isinstance(doc, list):
        return [("records", doc)]
    if not isinstance(doc, dict):
        raise ValueError(f"not a flight document: {type(doc).__name__}")
    if doc.get("metric") == "fleet_flight_merge":
        comps: dict[str, list[dict]] = {}
        for entry in doc.get("timeline") or ():
            comps.setdefault(entry.get("component", "?"), []).append(entry)
        return sorted(comps.items())
    name = str(doc.get("component", "component"))
    return [(name, list(doc.get("records") or ()))]


def _position(rec: dict) -> float:
    lc = rec.get("lc")
    if lc is not None:
        return float(lc)
    return float(rec.get("seq", 0))


def _logical_args(rec: dict) -> dict:
    """Deterministic args only: everything the record carries minus the
    wall/run-unstable fields (sorted for byte-stable rendering)."""
    return {
        k: rec[k] for k in sorted(rec) if k not in _WALL_ARG_FIELDS
    }


def _phase_tiling(rec: dict) -> tuple[list[str], list[str]]:
    """(tiled phases in canonical order, pipeline phases present)."""
    phases = rec.get("phases") or {}
    tiled = [p for p in _PHASE_ORDER if phases.get(p, 0) > 0]
    # Phases outside the canonical order sort after, alphabetically —
    # same rule as flight._phase_rank.
    known = set(_PHASE_ORDER) | set(_PIPELINE_PHASES) | set(_TILED_EXCLUDE)
    tiled += sorted(p for p in phases if p not in known and phases[p] > 0)
    pipe = [p for p in _PIPELINE_PHASES if phases.get(p, 0) > 0]
    return tiled, pipe


def _event(ph, name, pid, tid, ts, dur=None, args=None, cat="flight"):
    ev = {
        "ph": ph,
        "name": name,
        "cat": cat,
        "pid": pid,
        "tid": tid,
        "ts": ts,
    }
    if dur is not None:
        ev["dur"] = dur
    if args:
        ev["args"] = args
    if ph == "i":
        ev["s"] = "t"  # instant scope: thread
    return ev


def _meta(name, pid, tid=None, value=""):
    ev = {"ph": "M", "name": name, "pid": pid, "args": {"name": value}}
    if tid is not None:
        ev["tid"] = tid
    return ev


def _emit_logical(comps, events) -> None:
    # One global ordinal lane: records interleave across components in
    # deterministic (position, component, seq) order — the merged-fleet
    # sort key — so a router slot and the owner ops it fanned out to
    # render adjacently.
    flat = []
    for ci, (name, records) in enumerate(comps):
        for rec in records:
            flat.append((_position(rec), name, rec.get("seq", 0), ci, rec))
    flat.sort(key=lambda e: (e[0], e[1], e[2]))
    for ordinal, (_pos, _name, _seq, ci, rec) in enumerate(flat):
        pid = ci + 1
        start = ordinal * LOGICAL_UNIT_US
        if rec.get("kind") == "marker":
            events.append(
                _event(
                    "i", str(rec.get("event", "marker")), pid, _TRACK_BATCH,
                    start, args=_logical_args(rec),
                )
            )
            continue
        name = str(rec.get("op") or "batch")
        events.append(
            _event(
                "X", name, pid, _TRACK_BATCH, start,
                dur=LOGICAL_UNIT_US, args=_logical_args(rec),
            )
        )
        tiled, pipe = _phase_tiling(rec)
        if tiled:
            width = LOGICAL_UNIT_US // len(tiled)
            for i, phase in enumerate(tiled):
                events.append(
                    _event(
                        "X", phase, pid, _TRACK_STAGES,
                        start + i * width,
                        dur=width if i < len(tiled) - 1
                        else LOGICAL_UNIT_US - (len(tiled) - 1) * width,
                        cat="stage",
                    )
                )
        # The overlap track: predispatch fires first (the next batch's
        # early device dispatch), the drain's group fsync + applies run
        # under that in-flight pass — both slices overlap the stage
        # tiling above, which is the point.
        pipe_args = {}
        if rec.get("drained"):
            pipe_args["drained"] = rec["drained"]
        if rec.get("group_fsyncs"):
            pipe_args["group_fsyncs"] = rec["group_fsyncs"]
        if "predispatch" in pipe:
            events.append(
                _event(
                    "X", "predispatch", pid, _TRACK_PIPELINE,
                    start, dur=(2 * LOGICAL_UNIT_US) // 5, cat="pipeline",
                )
            )
        if "drain" in pipe:
            events.append(
                _event(
                    "X", "drain", pid, _TRACK_PIPELINE,
                    start + (2 * LOGICAL_UNIT_US) // 5,
                    dur=LOGICAL_UNIT_US // 2, cat="pipeline",
                    args=pipe_args or None,
                )
            )


def _emit_wall(comps, events) -> None:
    # Wall attribution: anchor each batch slice at [ts - wall_s, ts],
    # microseconds relative to the earliest timestamp in the document.
    t0 = None
    for _name, records in comps:
        for rec in records:
            ts = rec.get("ts")
            if ts is None:
                continue
            t_start = _wall_start(rec)
            for sp in rec.get("spans") or ():
                # a predispatched pass began before its batch's call did
                t_start = min(t_start, t_start + sp[1] * 1e-6)
            t0 = t_start if t0 is None else min(t0, t_start)
    if t0 is None:
        # No wall data anywhere (a merged timeline) — logical layout is
        # the only honest rendering.
        _emit_logical(comps, events)
        return
    for ci, (name, records) in enumerate(comps):
        pid = ci + 1
        for rec in records:
            ts = rec.get("ts")
            if ts is None:
                continue
            at = (float(ts) - t0) * 1e6
            args = {
                k: rec[k] for k in sorted(rec) if k not in ("phases", "spans")
            }
            if rec.get("kind") == "marker":
                events.append(
                    _event(
                        "i", str(rec.get("event", "marker")), pid,
                        _TRACK_BATCH, round(at, 3), args=args,
                    )
                )
                continue
            wall = float(rec.get("wall_s") or 0.0)
            start = round((_wall_start(rec) - t0) * 1e6, 3)
            events.append(
                _event(
                    "X", str(rec.get("op") or "batch"), pid, _TRACK_BATCH,
                    start, dur=round(wall * 1e6, 3), args=args,
                )
            )
            if rec.get("spans") and rec.get("t0_ns"):
                # Real starts: every span where the primitive measured it.
                for sp in rec["spans"]:
                    sname, s_us, d_us, parent = sp[0], sp[1], sp[2], sp[3]
                    overlap = parent < 0 and sname in _OVERLAP_SPANS
                    events.append(
                        _event(
                            "X", sname, pid,
                            _TRACK_PIPELINE if overlap else _TRACK_STAGES,
                            round(start + s_us, 3), dur=max(d_us, 0),
                            cat="pipeline" if overlap else "stage",
                            args=sp[4] if len(sp) > 4 else None,
                        )
                    )
                continue
            phases = rec.get("phases") or {}
            tiled, pipe = _phase_tiling(rec)
            cursor = start
            for phase in tiled:
                dur = float(phases[phase]) * 1e6
                events.append(
                    _event(
                        "X", phase, pid, _TRACK_STAGES,
                        round(cursor, 3), dur=round(dur, 3), cat="stage",
                    )
                )
                cursor += dur
            # The overlapped stages ran under the in-flight device pass:
            # anchor them at the batch start on their own track.
            pcursor = start
            for phase in pipe:
                dur = float(phases[phase]) * 1e6
                events.append(
                    _event(
                        "X", phase, pid, _TRACK_PIPELINE,
                        round(pcursor, 3), dur=round(dur, 3),
                        cat="pipeline",
                    )
                )
                pcursor += dur


def _wall_start(rec: dict) -> float:
    """A batch's start in wall seconds: ``t0_ns`` where the record has
    it, else its millisecond close stamp less its wall time."""
    t0_ns = rec.get("t0_ns")
    if t0_ns:
        return float(t0_ns) * 1e-9
    return float(rec["ts"]) - float(rec.get("wall_s") or 0.0)


def trace_document(doc, timebase: str = "logical", limit: int = 0) -> dict:
    """Render one flight-shaped document as a trace-event JSON object
    (``{"traceEvents": [...], "displayTimeUnit": "ms", "otherData":
    {...}}``).  ``limit`` keeps the newest N records per component
    (0 = all)."""
    if timebase not in ("logical", "wall"):
        raise ValueError(f"unknown timebase {timebase!r}")
    comps = _components(doc)
    if limit:
        comps = [(name, records[-limit:]) for name, records in comps]
    events: list[dict] = []
    for ci, (name, _records) in enumerate(comps):
        pid = ci + 1
        events.append(_meta("process_name", pid, value=name))
        for tid in sorted(_TRACK_NAMES):
            events.append(
                _meta("thread_name", pid, tid=tid, value=_TRACK_NAMES[tid])
            )
    if timebase == "logical":
        _emit_logical(comps, events)
    else:
        _emit_wall(comps, events)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "generator": "kubernetes_tpu trace_export",
            "timebase": timebase,
            "components": [name for name, _r in comps],
            "records": sum(len(r) for _n, r in comps),
        },
    }


def render(doc, timebase: str = "logical", limit: int = 0) -> str:
    """The byte-stable serialization (sorted keys, indent 1, trailing
    newline) — what the golden test and the committed artifacts pin."""
    return (
        json.dumps(
            trace_document(doc, timebase=timebase, limit=limit),
            indent=1,
            sort_keys=True,
        )
        + "\n"
    )
