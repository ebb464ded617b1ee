"""From a profiler trace to numbers: the device's busy time as the union
of the intervals in which an operation ran, the operations that took most
of it, the programs (XLA modules) that ran and for how long, and the idle
gaps laid against what the host was doing."""

from __future__ import annotations

import glob
import os

NESTING = ("while", "conditional", "call")  # events that enclose other ops


def op_name(raw: str) -> str:
    """An op event's name is the op's, or (for some) its whole HLO line:
    keep what stands before `` = ``, without the ``%``."""
    return raw.split(" = ", 1)[0].lstrip("%")[:64]


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_events(path: str, rehearsal: bool = False) -> dict:
    """{"ops": [(name, start_s, dur_s)], "modules": [...], "planes": [names]}
    from the first device plane (one chip per cell).  On the CPU there is
    no device plane; a rehearsal reads the CPU client's executor threads
    instead, to exercise this code, and the result says so."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    names = [p.name for p in planes]
    device = [p for p in planes if p.name.startswith("/device:") and "CUSTOM" not in p.name.upper()]
    ops: list[tuple[str, float, float]] = []
    modules: list[tuple[str, float, float]] = []
    if device:
        plane = sorted(device, key=lambda p: p.name)[0]
        for line in plane.lines:
            lname = line.name
            if lname == "XLA Ops":
                ops.extend((op_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
            elif lname == "XLA Modules":
                modules.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
    elif rehearsal:
        for plane in planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                               for e in line.events
                               if e.duration_ns > 0 and "::" not in e.name)
    return {"ops": ops, "modules": modules, "planes": names}


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted union of (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(ops, t0: float, t1: float) -> tuple[float, list[tuple[float, float]]]:
    """Seconds of [t0, t1) in which some op ran, and the busy intervals."""
    merged = union((max(s, t0), min(s + d, t1)) for _, s, d in ops)
    return sum(b - a for a, b in merged), merged


def gaps(busy: list[tuple[float, float]], t0: float, t1: float) -> list[tuple[float, float]]:
    out = []
    cur = t0
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def attribute(gap_list, spans, default: str = "between_batches") -> dict[str, float]:
    """Seconds of idle time by what the host was doing: each gap is cut
    against ``spans`` [(name, start, end)], earlier spans winning where two
    overlap, and what no span covers goes to ``default``."""
    totals: dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1])
    for g0, g1 in gap_list:
        covered = 0.0
        cur = g0
        for name, a, b in spans:
            if b <= cur:
                continue
            if a >= g1:
                break
            lo, hi = max(a, cur), min(b, g1)
            if hi > lo:
                totals[name] = totals.get(name, 0.0) + (hi - lo)
                covered += hi - lo
                cur = hi
        rest = (g1 - g0) - covered
        if rest > 0:
            totals[default] = totals.get(default, 0.0) + rest
    return totals


def top_ops(ops, n: int = 10) -> list[list]:
    totals: dict[str, float] = {}
    for name, _, d in ops:
        if name.startswith(NESTING):
            continue
        totals[name] = totals.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


PHASE_ORDER = ("featurize", "packing", "device", "commit", "predispatch", "drain", "snapshot", "other")


def flight_spans(records, to_trace_s) -> list[tuple[str, float, float]]:
    """Host spans from the flight recorder's per-batch records.  A record
    carries the wall-clock time it was closed (to the millisecond), the
    call's wall time and each phase's seconds, but no phase's start: the
    phases are laid end to end in the order the batch loop runs them,
    shrunk to the call's wall time where overlap made their sum longer.
    ``to_trace_s`` maps a wall-clock second onto the trace's clock."""
    spans = []
    for rec in records:
        end = to_trace_s(float(rec["ts"]))
        wall = float(rec.get("wall_s", 0.0))
        phases = rec.get("phases", {})
        total = sum(phases.values())
        if wall <= 0 or total <= 0:
            continue
        scale = min(1.0, wall / total)
        cur = end - wall
        for name in PHASE_ORDER + tuple(k for k in phases if k not in PHASE_ORDER):
            dur = float(phases.get(name, 0.0)) * scale
            if dur > 0:
                spans.append((name, cur, cur + dur))
                cur += dur
    return spans


def reduce(trace_dir: str, marks: dict, records, rehearsal: bool = False) -> dict | None:
    """The traced window: ``window_s`` between the profiler's start and
    stop as the server's host clock read them, ``busy_s`` inside it, the
    top operations, the modules, and the idle gaps by host span."""
    path = find_xplane(trace_dir)
    if path is None or not marks or "start" not in marks or "stop" not in marks:
        return None
    ev = read_events(path, rehearsal)
    ops = ev["ops"]
    # The trace's clock starts when the profiler does (my chip run, PR 24:
    # device and host events alike count nanoseconds from the session's
    # start): 0 is the moment start_trace returned, to within its tail.
    wall0 = marks["start"][1] * 1e-9
    t0 = 0.0
    t1 = (marks["stop"][0] - marks["start"][1]) * 1e-9
    busy_s, busy = busy_seconds(ops, t0, t1)
    spans = flight_spans(records, lambda wall: wall - wall0)
    idle = attribute(gaps(busy, t0, t1), spans)
    mods: dict[str, list[float]] = {}
    for name, s, d in ev["modules"]:
        if t0 <= s < t1:
            mods.setdefault(name, []).append(d)
    return {
        "window_s": t1 - t0, "busy_s": busy_s, "ops": len(ops),
        "device_ops": top_ops(ops), "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
        "modules": {k: {"count": len(v), "seconds": sum(v)} for k, v in mods.items()},
        "planes": ev["planes"], "xplane_bytes": os.path.getsize(path),
    }
