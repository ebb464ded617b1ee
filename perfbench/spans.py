#!/usr/bin/env python3
"""python3 perfbench/spans.py <a traced run's out dir> [--rehearsal] [--json]

The shared clock, used.  A ``--trace 1`` run leaves an xplane in
``<out dir>/trace``.  The program's span primitive
(kubernetes_tpu/framework/tracing.py) writes every span into it as the
host-plane event ``sched/<name>`` with the stat ``batch``, in nanoseconds
from the session's start like the device plane's op events, and
``engine/pass_.py``'s named scopes put the stage (``pass/eval/<plugin>``,
``pass/conflict``, ``pass/commit``, ``pass/tail``) into the metadata of
each instruction of the pass's program, whose HLO the profiler keeps in
the xplane beside the device plane's op events.  So this reads both planes
on the trace's own clock, with no wall-clock mapping and no flight record,
and prints

(a) the slice's idle seconds by the innermost program span open at the time,
(b) the device's op seconds by ``pass/*`` stage,
(c) ``drain_overlapped_share``: of the time inside ``pipeline/drain`` (and
    its ``drain/*`` children), the share during which a device op ran.

Imported by nothing the harness runs.  The slice is the one the harness
judged (``trace.window_s`` of the run's timeline.json, from 0) where that
file is there, else from the first event to the last.  ``--rehearsal``
reads the CPU client's executor threads in place of a device plane, as
``trace.read_events`` does, to exercise the code: not a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import trace  # noqa: E402

PREFIX = "sched/"
NO_SPAN = "(no span)"
NO_SCOPE = "(no scope)"
# a plugin's scope is CamelCase; a lower-case word after pass/eval/ is a primitive
STAGE = re.compile(r"pass/(?:eval(?:/[A-Z][A-Za-z0-9]+)?|conflict|commit|tail)")
DRAIN = re.compile(r"pipeline/drain$|drain/")


def stage_of(op_name: str) -> str:
    """The stage an instruction serves, from its metadata's op_name: the
    fused tail where it is named at all (its scan runs the same step),
    else the innermost (longest) ``pass/*`` scope."""
    found = STAGE.findall(op_name)
    if not found:
        return NO_SCOPE
    if "pass/tail" in found:
        return "pass/tail"
    return max(found, key=lambda s: (s.count("/"), len(s)))


def pb_fields(buf):
    """(field number, wire type, value) over one protobuf message: varints
    as ints, length-delimited fields as slices of ``buf``.  jax's
    ProfileData shows an event's own stats and not its metadata's, which
    is where the profiler keeps each program's HLO, so the few fields
    needed are read off the wire here (xplane.proto, hlo.proto)."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            if b < 0x80:
                return v
            shift += 7

    while i < n:
        key = varint()
        num, wt = key >> 3, key & 7
        if wt == 0:
            yield num, wt, varint()
        elif wt == 2:
            ln = varint()
            yield num, wt, buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            ln = 8 if wt == 1 else 4
            yield num, wt, buf[i:i + ln]
            i += ln
        else:
            raise ValueError(f"protobuf wire type {wt}")


def _sub(buf, num):
    return (v for n, wt, v in pb_fields(buf) if n == num and wt == 2)


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """{instruction name: metadata.op_name} of a serialized HloProto
    (hlo_module=1 / computations=3 / instructions=2 / name=1, metadata=7 /
    op_name=2)."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for inst in _sub(comp, 2):
                name = op_name = None
                for n, wt, v in pb_fields(inst):
                    if n == 1 and wt == 2:
                        name = bytes(v).decode("utf-8", "replace")
                    elif n == 7 and wt == 2:
                        for w in _sub(v, 2):
                            op_name = bytes(w).decode("utf-8", "replace")
                if name and op_name:
                    out[name] = op_name
    return out


def module_stages(xspace) -> dict[str, dict[str, str]]:
    """{program (an ``XLA Modules`` event's name): {instruction: stage}}
    from the ``Hlo Proto`` stat the profiler keeps on each program's event
    metadata (XSpace.planes=1 / event_metadata=4, stat_metadata=5)."""
    out = {}
    for plane in _sub(xspace, 1):
        stat_names, metas = {}, []
        for n, wt, entry in pb_fields(plane):
            if wt != 2 or n not in (4, 5):
                continue
            for value in _sub(entry, 2):
                if n == 4:
                    metas.append(value)
                    continue
                sid = sname = None
                for m, _, x in pb_fields(value):
                    if m == 1:
                        sid = x
                    elif m == 2:
                        sname = bytes(x).decode("utf-8", "replace")
                stat_names[sid] = sname
        for em in metas:
            name = proto = None
            for m, wt, x in pb_fields(em):
                if m == 2 and wt == 2:
                    name = bytes(x).decode("utf-8", "replace")
                elif m == 5 and wt == 2:
                    st = {k: v for k, _, v in pb_fields(x)}
                    if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                        proto = st[6]
            if name and proto is not None:
                out[name] = {inst: stage_of(op) for inst, op in hlo_op_names(proto).items()}
    return out


def read_planes(path: str, rehearsal: bool = False) -> dict:
    """{"spans": [(name, start_s, end_s, batch)], "ops": [(name, start_s,
    dur_s, stage)], "host_events": n, "programs": {name: instructions
    that carry a pass/* scope}} on the trace's clock.  A device op's stage
    is its instruction's in the program (``XLA Modules`` event) that
    encloses it."""
    import bisect

    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    stages = module_stages(memoryview(raw))
    data = ProfileData.from_serialized_xspace(raw)
    spans, ops, host_events = [], [], 0
    planes = list(data.planes)
    device = sorted((p for p in planes if p.name.startswith("/device:")
                     and "CUSTOM" not in p.name.upper()), key=lambda p: p.name)
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
                    spans.append((name[len(PREFIX):].split("#", 1)[0], s,
                                  s + e.duration_ns * 1e-9, stats.get("batch")))
                elif cpu_ops and e.duration_ns > 0 and "::" not in name:
                    ops.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9, stage_of(name)))
    if device:
        lines = {line.name: line for line in device[0].lines}
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in lines["XLA Modules"].events) if "XLA Modules" in lines else []
        starts = [m[0] for m in modules]
        for e in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            name = trace.op_name(e.name)
            k = bisect.bisect_right(starts, e.start_ns) - 1
            table = stages.get(modules[k][2], {}) if k >= 0 and e.start_ns < modules[k][1] else {}
            ops.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                        table.get(name, NO_SCOPE)))
    programs = {name: sum(1 for st in table.values() if st != NO_SCOPE)
                for name, table in stages.items()}
    return {"spans": spans, "ops": ops, "host_events": host_events, "programs": programs}


def innermost(gap_list, spans) -> dict[str, float]:
    """Seconds of the gaps by the innermost span open at the time: of the
    spans covering an instant, the one that started last (the shortest
    where two started together).  What no span covers goes to NO_SPAN."""
    cuts = set()
    for g0, g1 in gap_list:
        cuts.update((g0, g1))
    for _, s, e, _ in spans:
        cuts.update((s, e))
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


def overlap_s(a, b) -> float:
    """Seconds in both of two disjoint sorted interval lists."""
    total, j = 0.0, 0
    for a0, a1 in a:
        while j < len(b) and b[j][1] <= a0:
            j += 1
        k = j
        while k < len(b) and b[k][0] < a1:
            total += max(0.0, min(a1, b[k][1]) - max(a0, b[k][0]))
            k += 1
    return total


def analyse(out_dir: str, rehearsal: bool = False) -> dict:
    path = trace.find_xplane(os.path.join(out_dir, "trace"))
    if path is None:
        raise SystemExit(f"spans: no xplane under {out_dir}/trace")
    ev = read_planes(path, rehearsal)
    spans, ops = ev["spans"], ev["ops"]
    t0 = t1 = None
    try:
        with open(os.path.join(out_dir, "timeline.json"), encoding="utf-8") as f:
            t0, t1 = 0.0, float(json.load(f)["trace"]["window_s"])
    except (OSError, KeyError, ValueError, TypeError):
        pass
    if t1 is None:
        starts = [s for _, s, _, _ in spans] + [s for _, s, _, _ in ops]
        ends = [e for _, _, e, _ in spans] + [s + d for _, s, d, _ in ops]
        if not starts:
            raise SystemExit("spans: the trace holds neither a span nor a device op")
        t0, t1 = min(starts), max(ends)
    busy_s, busy = trace.busy_seconds([(n, s, d) for n, s, d, _ in ops], t0, t1)
    gap_list = trace.gaps(busy, t0, t1)
    clipped = [(n, max(s, t0), min(e, t1), b) for n, s, e, b in spans if e > t0 and s < t1]
    idle = innermost(gap_list, clipped)
    idle_s = sum(b - a for a, b in gap_list)
    stages: dict[str, float] = {}
    for name, s, d, stage in ops:
        if t0 <= s < t1 and not name.startswith(trace.NESTING):
            stages[stage] = stages.get(stage, 0.0) + d
    drain = trace.union((s, e) for n, s, e, _ in clipped if DRAIN.match(n))
    drain_s = sum(b - a for a, b in drain)
    names: dict[str, int] = {}
    for n, _, _, _ in clipped:
        names[n] = names.get(n, 0) + 1
    return {
        "xplane": path, "xplane_bytes": os.path.getsize(path),
        "host_events": ev["host_events"], "span_events": len(spans), "device_ops": len(ops),
        "window_s": t1 - t0, "busy_s": busy_s, "idle_s": idle_s,
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_named_share": 100.0 * (idle_s - idle.get(NO_SPAN, 0.0)) / idle_s if idle_s else None,
        "device_s_by_stage": dict(sorted(stages.items(), key=lambda kv: -kv[1])),
        "drain_s": drain_s,
        "drain_overlapped_share": 100.0 * overlap_s(drain, busy) / drain_s if drain_s else None,
        # instructions with a pass/* scope in each traced program's HLO: 0
        # for the pass means its executable was compiled before the scopes
        # were there (the persistent cache's key ignores op metadata)
        "scoped_instructions": ev["programs"],
        "spans_in_slice": dict(sorted(names.items())),
        "batches_in_slice": len({b for _, _, _, b in clipped if b is not None}),
    }


def render(r: dict) -> str:
    out = [f"slice {r['window_s']:.3f} s: device busy {r['busy_s']:.4f} s, idle {r['idle_s']:.4f} s; "
           f"{r['span_events']} sched/* events of {r['host_events']} host events, "
           f"{r['device_ops']} device op events, xplane {r['xplane_bytes']} bytes",
           "(a) idle seconds by innermost program span"]
    out += [f"    {name:<28} {secs:10.4f}" for name, secs in r["idle_by_span"].items()]
    if r["idle_named_share"] is not None:
        out.append(f"    on a named span: {r['idle_named_share']:.2f}% of the idle seconds")
    out.append("(b) device op seconds by stage")
    out += [f"    {name:<44} {secs:10.5f}" for name, secs in r["device_s_by_stage"].items()]
    out += [f"    program {name}: {n} instructions carry a pass/* scope" + (
        "" if n else " (an executable from before the scopes, or not the pass)")
        for name, n in sorted(r["scoped_instructions"].items())]
    share = r["drain_overlapped_share"]
    out.append(f"(c) drain_overlapped_share: " + (
        f"{share:.2f}% of {r['drain_s']:.4f} s inside pipeline/drain" if share is not None
        else "no drain span in the slice"))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--json", action="store_true", help="print the numbers as one JSON object")
    args = ap.parse_args(argv)
    r = analyse(args.out_dir, args.rehearsal)
    print(json.dumps(r) if args.json else render(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
