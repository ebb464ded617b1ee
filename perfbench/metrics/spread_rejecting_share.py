"""layer: device pass (engine/pass_.py; counted in scheduler.py where a
batch's fetched fail masks are settled).  source: program_counter
(scheduler_pass_filter_rejecting_pods_total{plugin="PodTopologySpread"}:
pods for which the spread filter ruled out at least one node that every
earlier filter had let through, counted once a pod).  moves: pods_per_s.
The window's delta of the counter over the window's pods, in percent: an
engagement reading, like pack_width, that says whether the constraint
bites in this cell, not a goal.  A program without the counter reports
nothing."""

KEY = 'scheduler_pass_filter_rejecting_pods_total{plugin="PodTopologySpread"}'


def read(ctx):
    pods = ctx.window_pods()  # the counter runs over the whole window
    if KEY not in ctx.after or not pods:
        return None
    return 100.0 * ctx.delta(KEY) / pods
