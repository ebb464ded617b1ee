"""layer: featurize and pack (engine/features.py).  source: program_span
(`batch/featurize`, and `batch/prefetch` where the next batch was
featurized under the device's pass: host clocks inside the server).
moves: pods_per_s.  Microseconds of featurization a pod of the slice's
batches: pods that differ only in the name of their claim share one
featurization, and this says whether they do (138 on the program that
featurized each by itself; 5 in basic_5kn).  A program without the span
primitive reports nothing."""

from perfbench import spanread


def read(ctx):
    parts = [spanread.seconds(ctx.records, name) for name in ("batch/featurize", "batch/prefetch")]
    pods = ctx.pods()
    if not pods or all(p is None for p in parts):
        return None
    return sum(p for p in parts if p is not None) / pods * 1e6
