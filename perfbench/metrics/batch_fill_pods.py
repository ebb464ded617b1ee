"""layer: batch forming (sidecar/speculate.py, queue.py).  source:
program_counter (flight records).  moves: decision_p50_ms.  Mean pods per
device batch in the window."""


def read(ctx):
    return ctx.pods() / len(ctx.records) if ctx.records else None
