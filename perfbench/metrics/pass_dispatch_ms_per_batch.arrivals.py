"""layer: device pass.  source: program_span (the `pass/dispatch` span:
batch invariants, state flush, one device_put, the jitted call returning).
moves: decision_p50_ms.  The host's share of the fixed cost per batch
before the chip has anything to do."""

from perfbench import spanread


def read(ctx):
    return spanread.per_batch_ms(ctx, "pass/dispatch")
