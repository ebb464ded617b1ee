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

The harness reduces the same xplane with the same functions
(``perfbench/trace.py``: ``read_events``, ``innermost``); what is only
here is the stage of each device op, read out of the programs' HLO.  The
slice is the one the harness judged, between the launcher's two marks in
the trace, else from the first event to the last.  ``--rehearsal``
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
    """``trace.read_events`` of the xplane, with each device op's stage
    beside it ("ops": [(name, start_s, dur_s, stage)]) and "programs":
    {name: instructions that carry a pass/* scope}.  A device op's stage
    is its instruction's in the program (``XLA Modules`` event) that
    encloses it."""
    import bisect

    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    stages = module_stages(memoryview(raw))
    ev = trace.read_events(path, rehearsal, data=ProfileData.from_serialized_xspace(raw))
    modules = sorted((s, s + d, name) for name, s, d in ev["modules"])
    starts = [m[0] for m in modules]
    ops = []
    for name, s, d in ev["ops"]:
        k = bisect.bisect_right(starts, s) - 1
        table = stages.get(modules[k][2], {}) if k >= 0 and s < modules[k][1] else {}
        ops.append((name, s, d, table.get(name, stage_of(name) if not modules else NO_SCOPE)))
    ev["ops"] = ops
    ev["programs"] = {name: sum(1 for st in table.values() if st != NO_SCOPE)
                      for name, table in stages.items()}
    return ev


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
    if ev["slice"] is not None:
        t0, t1 = ev["slice"]
    else:
        starts = [s for _, s, _, _ in spans] + [s for _, s, _, _ in ops]
        ends = [e for _, _, e, _ in spans] + [s + d for _, s, d, _ in ops]
        if not starts:
            raise SystemExit("spans: the trace holds neither a span nor a device op")
        t0, t1 = min(starts), max(ends)
    busy_s, busy = trace.busy_seconds([(n, s, d) for n, s, d, _ in ops], t0, t1)
    gap_list = trace.gaps(busy, t0, t1)
    clipped = trace.clip(spans, t0, t1)
    idle = trace.innermost(gap_list, clipped)
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
        "idle_named_share": 100.0 * ((idle_s - idle.get(trace.NO_SPAN, 0.0)) / idle_s) if idle_s else None,
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
