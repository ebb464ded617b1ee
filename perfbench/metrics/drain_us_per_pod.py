"""layer: commit and drain (engine/pipeline.py, scheduler.py).  source:
program_span (flight records' commit, predispatch and drain phases, host
clocks inside the server).  moves: pods_per_s."""


def read(ctx):
    pods = ctx.pods()
    secs = ctx.phase_s("commit") + ctx.phase_s("drain") + ctx.phase_s("predispatch")
    return secs / pods * 1e6 if pods and secs > 0 else None
