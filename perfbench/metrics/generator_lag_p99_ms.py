"""layer: load generator (benchmark).  source: host_clock (the client's).
moves: decision_p50_ms.  99th percentile of how late the generator
itself ran: each time the asking loop slept until a pod's due time, how
long after it the loop woke.  (Time the loop spends blocked in a wire call
is the system's, and is in the latencies.)  A loop that never had to wait
for a due time has no sample, and the run says so on an earlier line."""


def read(ctx):
    lag = ctx.window.lag_s
    return ctx.percentile(lag, 99) * 1e3 if lag else None
