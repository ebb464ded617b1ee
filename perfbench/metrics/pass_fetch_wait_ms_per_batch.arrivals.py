"""layer: device pass.  source: program_span (the `pass/fetch_wait` span).
moves: decision_p50_ms.  The host blocked on the chip, a batch, in the
cell where a batch waits out its own pass."""

from perfbench import spanread


def read(ctx):
    return spanread.per_batch_ms(ctx, "pass/fetch_wait")
