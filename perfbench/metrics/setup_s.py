"""Process start to the first measured frame: server start, device
initialisation, compilation or cache load, the cluster's nodes, the
initial pods and the warm-up.  source: host_clock.  layer: end to end."""


def read(ctx):
    return ctx.setup_s
