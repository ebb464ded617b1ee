"""layer: featurize and pack (engine/features.py, engine/packing.py).
source: program_span (flight records' featurize and packing phases, host
clocks inside the server).  moves: decision_p50_ms."""


def read(ctx):
    pods = ctx.pods()
    secs = ctx.phase_s("featurize") + ctx.phase_s("packing")
    return secs / pods * 1e6 if pods and secs > 0 else None
