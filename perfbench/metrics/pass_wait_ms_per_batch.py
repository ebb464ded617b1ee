"""layer: device pass, as the host waits for it.  source: program_span
(flight records' device phase: dispatch to fetched result, a host clock).
moves: decision_p50_ms."""


def read(ctx):
    return ctx.phase_s("device") / len(ctx.records) * 1e3 if ctx.records else None
