"""layer: commit and drain.  source: program_span (the `drain/apply`
span: finish_binding, taint eviction, queue.done, bound accounting, the
Scheduled event and the SLI sample of every staged bind, after the
barrier).  moves: pods_per_s."""

from perfbench import spanread


def read(ctx):
    return spanread.per_pod_us(ctx, "drain/apply")
