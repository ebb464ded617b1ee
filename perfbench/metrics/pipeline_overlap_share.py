"""layer: commit and drain (engine/pipeline.py).  source: program_span
(flight records' overlap block).  moves: pods_per_s.  Share of the batch
loop's serial stage time that depth-2 pipelining took off the wall clock:
sum of saved_s over sum of serial_s.  (The issue's drain_overlapped_share
needs a drain's start time on the trace's clock, which no record carries
yet; see PERF.md.)"""


def read(ctx):
    serial = sum(float(r.get("overlap", {}).get("serial_s", 0.0)) for r in ctx.records)
    saved = sum(float(r.get("overlap", {}).get("saved_s", 0.0)) for r in ctx.records)
    return 100.0 * saved / serial if serial > 0 and saved > 0 else None
