"""layer: featurize and pack (engine/packing.py).  source: program_span
(the `batch/pack` span: the conflict-aware chunk packer planning the
batch's classes and reordering its rows; it runs only where a batch's
plugins write what chunk-mates read, so a cell without such pods reports
nothing).  moves: pods_per_s."""

from perfbench import spanread


def read(ctx):
    return spanread.per_pod_us(ctx, "batch/pack")
