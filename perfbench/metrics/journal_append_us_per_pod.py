"""layer: journal.  source: program_span (the `drain/journal_append`
span: every bind of the group serialised and written, fsync deferred).
moves: pods_per_s."""

from perfbench import spanread


def read(ctx):
    return spanread.per_pod_us(ctx, "drain/journal_append")
