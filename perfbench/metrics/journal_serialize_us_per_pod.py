"""layer: journal.  source: program_span (`serialize_us` accumulated on
the `drain/journal_append` span: serialize.to_dict(pod) alone, two clock
reads a pod).  moves: pods_per_s.  The part of the append that is not the
journal's."""

from perfbench import spanread


def read(ctx):
    us, pods = spanread.stat(ctx.records, "drain/journal_append", "serialize_us"), ctx.pods()
    return us / pods if us is not None and pods else None
