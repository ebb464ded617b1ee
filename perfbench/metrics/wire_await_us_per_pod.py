"""layer: plugin emulation (the client's pace, seen from sidecar/server.py).
source: program_counter (scheduler_wire_await_seconds_total: the `wire/await`
spans of served connections, from the top of the read loop until a frame's
header has arrived, each counted once its frame is dispatched, so the
window's closing scrape holds none of the client's time after the window).
moves: pods_per_s.  Microseconds a pod of the window the server waited for
the client's next frame."""

KEY = "scheduler_wire_await_seconds_total"


def read(ctx):
    pods = ctx.window_pods()
    return ctx.delta(KEY) / pods * 1e6 if pods and KEY in ctx.after else None
