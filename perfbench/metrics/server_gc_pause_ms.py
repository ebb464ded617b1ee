"""layer: commit and drain.  source: program_counter
(scheduler_gc_pause_seconds_total, from the serving process's
gc.callbacks hook).  moves: pods_per_s.  Milliseconds of the window the
server spent inside collector runs."""


def read(ctx):
    key = "scheduler_gc_pause_seconds_total"
    return ctx.delta(key) * 1e3 if key in ctx.after else None
