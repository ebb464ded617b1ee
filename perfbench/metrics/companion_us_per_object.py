"""layer: wire and hints (sidecar/server.py AddObject, sidecar/speculate.py
note_add).  source: host_clock (loops.Window.companion_s over
companion_objects: the pods' companion objects sent inside the window as
prebuilt, pipelined AddObject frames before each backlog's hint frame, and
the seconds until the last was acknowledged).  moves: pods_per_s.
Microseconds an object: what ingesting a claim or a volume costs the
served path.  A cell without companions reports nothing."""


def read(ctx):
    w = ctx.window
    n = getattr(w, "companion_objects", 0)
    return getattr(w, "companion_s", 0.0) / n * 1e6 if n else None
