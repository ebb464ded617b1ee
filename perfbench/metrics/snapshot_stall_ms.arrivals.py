"""layer: checkpoints (scheduler.maybe_snapshot).  source: program_span
(flight records' snapshot phase).  moves: decision_p50_ms.  Sum of
checkpoint time inside the window; 0 when none ran."""


def read(ctx):
    return ctx.phase_s("snapshot") * 1e3 if ctx.records else None
