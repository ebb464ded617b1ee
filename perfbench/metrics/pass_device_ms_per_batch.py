"""layer: device pass (engine/pass_.py).  source: device_trace.  moves:
pods_per_s.  Device time of the traced window's programs (XLA modules) per
pass: all module time over the number of times the most-run module ran
(one pass a batch; the pass is nearly all of the time)."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["modules"]:
        return None
    passes = max(m["count"] for m in tr["modules"].values())
    secs = sum(m["seconds"] for m in tr["modules"].values())
    return secs / passes * 1e3 if passes and secs > 0 else None
