"""layer: device pass (engine/pass_.py).  source: device_trace.  moves:
pods_per_s.  Device time a pod decided: the device seconds of the traced
slice's programs (their module events, and for a pass the slice cut the
ops it got to run: trace.reduce's pass_device_s) over the pods whose
passes ran inside it, a cut pass counting by the share of it that lies
inside (trace.pods_in_slice).  Counted by pods and never by steps or ops,
so a scan with fewer, fatter steps is measured against the same work.
Beside 1e6 / pods_per_s it says who sets the pace."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr.get("pods_in_slice") or tr["pass_device_s"] <= 0:
        return None
    return tr["pass_device_s"] / tr["pods_in_slice"] * 1e6
