"""Every pod answered with a node in the window over the window's whole
length, from the first hint frame sent to the last answer received.
source: host_clock (the client's).  layer: end to end."""


def read(ctx):
    w = ctx.window
    return w.bound / w.seconds if w.seconds > 0 and w.bound else None
