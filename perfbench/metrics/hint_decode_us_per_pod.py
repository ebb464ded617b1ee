"""layer: wire and hints (sidecar/server.py, sidecar/speculate.py).
source: program_span (scheduler_phase_duration_seconds{phase="hint_decode"},
a host clock inside the server).  moves: pods_per_s.  Seconds the server
spent parsing hint frames and building pods from them, per pod scheduled."""


def read(ctx):
    pods = ctx.window_pods()  # the counter runs over the whole window
    secs = ctx.delta('scheduler_phase_duration_seconds_sum{phase="hint_decode"}')
    return secs / pods * 1e6 if pods and secs > 0 else None
