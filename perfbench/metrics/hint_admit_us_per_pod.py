"""layer: batch forming (sidecar/speculate.py `_admit_hints`).  source:
program_span (scheduler_phase_duration_seconds{phase="hints/admit"}, a host
clock inside the server).  moves: pods_per_s.  Seconds the server spent
admitting hints into the queue (the pool's sort, the stale-hint filter with
the pods built from a dict, the enqueue), per pod of the window.  Since PR 38
the top-up parse runs before the span opens, so this is admission alone; a
program without the `admit/*` children books that parse here too and
reports nothing."""

from perfbench import spanread

KEY = 'scheduler_phase_duration_seconds_sum{phase="hints/admit"}'


def read(ctx):
    if spanread.seconds(ctx.window_records, "admit/sort") is None:
        return None
    pods = ctx.window_pods()  # the counter runs over the whole window
    secs = ctx.delta(KEY)
    return secs / pods * 1e6 if pods and KEY in ctx.after else None
