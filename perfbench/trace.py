"""From a profiler trace to numbers, on the trace's own clock.  One xplane
holds the device plane (op and module events), the program's spans (the
``/host:CPU`` events ``sched/<name>`` with the stat ``batch``, written by
kubernetes_tpu/framework/tracing.py) and the slice's two ends (the
launcher's ``perfbench/slice_start`` and ``perfbench/slice_end`` events),
all in nanoseconds from the session's start.  So the slice, the device's
busy time as the union of the intervals in which an operation ran, the idle
gaps by the innermost program span open at the time, and the pods whose
passes fell inside the slice are read from it alone: no host clock is laid
over it.  (The one thing taken from the flight records is how long a whole
pass of as many pods lasts, for a pass the slice's end cut.)"""

from __future__ import annotations

import glob
import os
import statistics

NESTING = ("while", "conditional", "call")  # events that enclose other ops
PREFIX = "sched/"
NO_SPAN = "(no span)"
MARK_START = "perfbench/slice_start"  # perfbench/launcher.py writes them around the slice
MARK_END = "perfbench/slice_end"


def op_name(raw: str) -> str:
    """An op event's name is the op's, or (for some) its whole HLO line:
    keep what stands before `` = ``, without the ``%``."""
    return raw.split(" = ", 1)[0].lstrip("%")[:64]


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_events(path: str, rehearsal: bool = False, data=None) -> dict:
    """{"ops": [(name, start_s, dur_s)], "modules": [...], "spans": [(name,
    start_s, end_s, batch)], "pass_pods": {dispatch start_s: pods}, "slice":
    (t0, t1) or None, "host_events": n, "planes": [names], "device_plane":
    whether there is one} from the first
    device plane (one chip per cell) and the host plane.  On the CPU there
    is no device plane; a rehearsal reads the CPU client's executor
    threads instead, to exercise this code, and the result says so."""
    if data is None:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
    planes = list(data.planes)
    names = [p.name for p in planes]
    device = sorted((p for p in planes if p.name.startswith("/device:")
                     and "CUSTOM" not in p.name.upper()), key=lambda p: p.name)
    ops: list[tuple[str, float, float]] = []
    modules: list[tuple[str, float, float]] = []
    spans: list[tuple] = []
    pass_pods: dict[float, int] = {}
    marks: dict[str, float] = {}
    host_events = 0
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            cpu_ops = rehearsal and not device and line.name.startswith("tf_XLAPjRtCpuClient")
            for e in line.events:
                host_events += 1
                name = e.name
                if name.startswith(PREFIX):
                    stats = dict(e.stats)
                    s = e.start_ns * 1e-9
                    short = name[len(PREFIX):].split("#", 1)[0]
                    spans.append((short, s, s + e.duration_ns * 1e-9, stats.get("batch")))
                    if short == "pass/dispatch" and stats.get("pods") is not None:
                        pass_pods[s] = int(stats["pods"])
                elif name == MARK_START:
                    marks["t0"] = (e.start_ns + e.duration_ns) * 1e-9
                elif name == MARK_END:
                    marks["t1"] = e.start_ns * 1e-9
                elif cpu_ops and e.duration_ns > 0 and "::" not in name:
                    ops.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    if device:
        short: dict[str, str] = {}  # millions of events, some hundreds of names
        for line in device[0].lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    raw = e.name
                    name = short.get(raw)
                    if name is None:
                        name = short[raw] = op_name(raw)
                    ops.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
            elif line.name == "XLA Modules":
                modules.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
    ends = (marks["t0"], marks["t1"]) if len(marks) == 2 and marks["t1"] > marks["t0"] else None
    return {"ops": ops, "modules": modules, "spans": spans, "pass_pods": pass_pods,
            "slice": ends, "host_events": host_events, "planes": names, "device_plane": bool(device)}


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


def innermost(gap_list, spans) -> dict[str, float]:
    """Seconds of the gaps by the innermost span open at the time: of the
    spans covering an instant, the one that started last (the shortest
    where two started together).  What no span covers goes to NO_SPAN."""
    cuts = set()
    for g0, g1 in gap_list:
        cuts.update((g0, g1))
    for sp in spans:
        cuts.update((sp[1], sp[2]))
    cuts = sorted(cuts)
    by_start = sorted(spans, key=lambda sp: sp[1])
    totals: dict[str, float] = {}
    active: list[tuple] = []
    nxt = gi = 0
    for lo, hi in zip(cuts, cuts[1:]):
        while gi < len(gap_list) and gap_list[gi][1] <= lo:
            gi += 1
        if gi == len(gap_list):
            break
        if not (gap_list[gi][0] <= lo and hi <= gap_list[gi][1]):
            continue
        while nxt < len(by_start) and by_start[nxt][1] <= lo:
            active.append(by_start[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > lo]
        name = max(active, key=lambda sp: (sp[1], -sp[2]))[0] if active else NO_SPAN
        totals[name] = totals.get(name, 0.0) + (hi - lo)
    return totals


def clip(spans, t0: float, t1: float) -> list[tuple]:
    return [(n, max(s, t0), min(e, t1), b) for n, s, e, b in spans if e > t0 and s < t1]


def top_ops(ops, n: int = 10) -> list[list]:
    totals: dict[str, float] = {}
    for name, _, d in ops:
        if name.startswith(NESTING):
            continue
        totals[name] = totals.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def _record_span_ns(rec: dict, name: str):
    """[(start, end)] of a record's spans called ``name``, in the serving
    process's nanoseconds (the record's ``t0_ns`` plus the span's offset)."""
    t0 = int(rec.get("t0_ns", 0))
    return [(t0 + int(sp[1]) * 1000, t0 + int(sp[1] + sp[2]) * 1000)
            for sp in rec.get("spans") or () if sp[0] == name]


def whole_pass_seconds(records) -> dict[int, float]:
    """{pods: median seconds} of a whole pass of that many pods, from
    ``pass/dispatch``'s start to ``pass/fetch_wait``'s end over the flight
    records: passes are fetched in the order they were dispatched, so the
    k-th fetch ends the k-th dispatch (a predispatched pass starts in the
    record before the one that fetches it), and the pods are those of the
    record that fetches."""
    waiting: list[int] = []
    found: dict[int, list[float]] = {}
    for rec in sorted(records, key=lambda r: r.get("seq", 0)):
        events = [(s, 0, e) for s, e in _record_span_ns(rec, "pass/fetch_wait")]
        events += [(s, 1, e) for s, e in _record_span_ns(rec, "pass/dispatch")]
        for s, kind, e in sorted(events):
            if kind:
                waiting.append(s)
            elif waiting:
                found.setdefault(int(rec.get("pods", 0)), []).append((e - waiting.pop(0)) * 1e-9)
    return {pods: statistics.median(v) for pods, v in found.items()}


def pods_in_slice(spans, pass_pods: dict, t0: float, t1: float, records=()) -> dict | None:
    """Pods whose pass ran inside [t0, t1): a pass dispatched and fetched
    inside counts whole; one the slice cut counts by the share of its
    interval (``pass/dispatch``'s start to ``pass/fetch_wait``'s end) that
    lies inside, its whole length being what whole passes of as many pods
    took (inside the slice where it holds one, else over ``records``).
    None where a cut pass has no such measure, or no pass is inside."""
    dispatched = sorted(s for n, s, _, _ in spans if n == "pass/dispatch" and t0 <= s < t1)
    fetched = sorted((e, s, b) for n, s, e, b in spans if n == "pass/fetch_wait" and t0 < e <= t1)
    by_bid = {r.get("bid"): int(r.get("pods", 0)) for r in records}
    whole: list[tuple[int, float]] = []
    cut: list[tuple[int, float]] = []  # (pods, seconds inside)
    waiting = list(dispatched)
    for e, s, bid in fetched:
        if waiting and waiting[0] <= s:
            d = waiting.pop(0)
            if d in pass_pods:
                whole.append((pass_pods[d], e - d))
        elif by_bid.get(bid):
            cut.append((by_bid[bid], e - t0))  # in flight when the slice began
    cut += [(pass_pods[d], t1 - d) for d in waiting if d in pass_pods]  # in flight at its end
    if not whole and not cut:
        return None
    lengths: dict[int, list[float]] = {}
    for pods, secs in whole:
        lengths.setdefault(pods, []).append(secs)
    known = {pods: statistics.median(v) for pods, v in lengths.items()}
    from_records = None
    total = float(sum(pods for pods, _ in whole))
    passes = float(len(whole))
    for pods, inside in cut:
        if pods not in known:
            if from_records is None:
                from_records = whole_pass_seconds(records)
            if pods not in from_records:
                return None
            known[pods] = from_records[pods]
        share = min(1.0, inside / known[pods]) if known[pods] > 0 else 0.0
        total += pods * share
        passes += share
    return {"pods": total, "passes": passes, "whole": len(whole), "cut": len(cut)}


def reduce(trace_dir: str, records=(), rehearsal: bool = False) -> dict | None:
    """The traced slice, between the launcher's two marks: ``window_s``,
    ``busy_s`` inside it, the top operations, the modules, the idle
    seconds by innermost program span, the device seconds of its passes
    and the pods they decided.  ``records``: the window's flight records
    closed before the stop began (see ``pods_in_slice``)."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    ev = read_events(path, rehearsal)
    if ev["slice"] is None:
        return None
    t0, t1 = ev["slice"]
    ops = ev["ops"]
    busy_s, busy = busy_seconds(ops, t0, t1)
    gap_list = gaps(busy, t0, t1)
    idle = innermost(gap_list, clip(ev["spans"], t0, t1))
    idle_s = sum(b - a for a, b in gap_list)
    mods: dict[str, list[float]] = {}
    for name, s, d in ev["modules"]:
        if t0 <= s < t1:
            mods.setdefault(name, []).append(d)
    # a pass's device time: its module events, and for a pass the slice cut
    # (no module event: it never ended) the ops it got to run
    device_s = sum(b - a for a, b in union(
        [(max(s, t0), min(s + d, t1)) for _, s, d in ev["modules"]] + busy))
    inside = pods_in_slice(ev["spans"], ev["pass_pods"], t0, t1, records)
    in_slice = [op for op in ops if t0 <= op[1] < t1]
    return {
        "window_s": t1 - t0, "busy_s": busy_s, "idle_s": idle_s, "ops": len(in_slice),
        "device_events": len(ops) + len(ev["modules"]),
        "host_events": ev["host_events"], "span_events": len(ev["spans"]),
        "device_ops": top_ops(in_slice),
        "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
        "idle_named_share": 100.0 * (idle_s - idle.get(NO_SPAN, 0.0)) / idle_s if idle_s > 0 else None,
        "modules": {k: {"count": len(v), "seconds": sum(v)} for k, v in mods.items()},
        # each program as it ran, for the timeline's reader: [seconds from the slice's start, seconds]
        "module_events": [[round(s - t0, 6), round(d, 6)] for _, s, d in sorted(
            ev["modules"], key=lambda m: m[1]) if s < t1 and s + d > t0][:32],
        "pass_device_s": device_s,
        "pods_in_slice": inside["pods"] if inside else None,
        "passes_in_slice": inside,
        "planes": ev["planes"], "device_plane": ev["device_plane"], "xplane_bytes": os.path.getsize(path),
    }
