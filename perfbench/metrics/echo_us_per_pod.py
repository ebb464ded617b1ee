"""layer: wire and hints (sidecar/server.py AddObject of a bound Pod,
sidecar/speculate.py note_add).  source: host_clock (loops.Window.echo_s
over echo_objects: every pod goes back bound the moment it is answered, as
one posted AddObject frame, and this is the loop's own time in making and
posting them; the sidecar's part runs under the loop and the next pass).
moves: pods_per_s.  Microseconds a pod: the bind echo's price.  A cell
whose configuration states no echo reports nothing."""


def read(ctx):
    w = ctx.window
    n = getattr(w, "echo_objects", 0)
    return getattr(w, "echo_s", 0.0) / n * 1e6 if n else None
