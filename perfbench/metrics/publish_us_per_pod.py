"""layer: wire and hints.  source: program_span (the `spec/publish` span,
through scheduler_phase_duration_seconds{phase="spec/publish"}: it runs
between batches, so no flight record holds it).  moves: pods_per_s.  After
each batch: outcomes cached with their dependency sets, decisions built
into a push frame and written to the subscriber."""


def read(ctx):
    key = 'scheduler_phase_duration_seconds_sum{phase="spec/publish"}'
    pods = ctx.window_pods()  # the counter runs over the whole window
    if key not in ctx.after or not pods:
        return None
    return ctx.delta(key) / pods * 1e6
