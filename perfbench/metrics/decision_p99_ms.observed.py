"""layer: checkpoints (scheduler.maybe_snapshot), as the client feels them.
source: host_clock (the client's).  moves: decision_p50_ms (the arrivals
cell's judged latency).  99th percentile over every pod due in the window of
(answer received - time the pod was due).  Observed, not judged: it is the
window's last checkpoint stall plus about four batch cycles, and its spread
from run to run (PERF.md section 2) is past what a bound may cover."""


def read(ctx):
    w = ctx.window
    if not w.due_t:
        return None
    return ctx.percentile([a - d for a, d in zip(w.answer_t, w.due_t)], 99) * 1e3
