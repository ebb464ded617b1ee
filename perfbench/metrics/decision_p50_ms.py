"""Median over every pod due in the window of (answer received - time the
pod was due).  source: host_clock (the client's).  layer: end to end."""


def read(ctx):
    w = ctx.window
    if not w.due_t:
        return None
    return ctx.percentile([a - d for a, d in zip(w.answer_t, w.due_t)], 50) * 1e3
