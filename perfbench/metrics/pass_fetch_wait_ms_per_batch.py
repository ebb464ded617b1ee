"""layer: device pass.  source: program_span (the `pass/fetch_wait` span:
the host blocked in block_until_ready on the pass's result tree, after
the async host copies were started).  moves: pods_per_s.  How long the
host truly waits on the chip, a batch; the flight `device` phase beside it
runs from dispatch to fetched and holds whatever the host did meanwhile."""

from perfbench import spanread


def read(ctx):
    return spanread.per_batch_ms(ctx, "pass/fetch_wait")
