"""layer: device.  source: device_trace.  moves: pods_per_s.  1 minus the
union of device-op intervals over the traced window."""


def read(ctx):
    tr = ctx.trace
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
