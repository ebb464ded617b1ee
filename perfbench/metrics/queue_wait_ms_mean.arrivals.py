"""layer: batch forming.  source: program_counter (flight records'
queue_wait: pop time minus the time the server first held the pod, hint
frame or request, summed at pop onto the record of the call that popped:
a prefetched batch's on the one before its own).  moves: decision_p50_ms.
Mean over the pods popped in the window's calls."""


def read(ctx):
    waits = [r["queue_wait"] for r in ctx.records if r.get("queue_wait")]
    pods = sum(w["pods"] for w in waits)
    return sum(w["sum_ms"] for w in waits) / pods if pods else None
