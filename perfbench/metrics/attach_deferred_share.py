"""layer: device pass (engine/pass_.py's deferral; counted in scheduler.py
where a batch's picks come back).  source: program_counter
(scheduler_deferred_pods_total: pods a chunk sent to the strict tail).
moves: pods_per_s.  The window's delta of the counter over the window's
pods, in percent: in a cell whose pods carry a claim of a driver with an
attach limit, what settling a node's shared budget costs in strict-tail
work (a later chunk-mate that lands where an earlier one's volumes did is
deferred).  A program without the counter reports nothing."""

KEY = "scheduler_deferred_pods_total"


def read(ctx):
    pods = ctx.window_pods()  # the counter runs over the whole window
    if KEY not in ctx.after or KEY not in ctx.before or not pods:
        return None
    return 100.0 * ctx.delta(KEY) / pods
