"""layer: checkpoints.  source: program_span (`snapshot/collect`,
`snapshot/encode`, `snapshot/write`).  moves: decision_p50_ms.  Of a
checkpoint's time, the share spent walking the store and encoding it
(host CPU) and not writing and syncing it.  Silent in a window with no
checkpoint."""

from perfbench import spanread


def read(ctx):
    parts = [spanread.seconds(ctx.records, "snapshot/" + n) for n in ("collect", "encode", "write")]
    if any(p is None for p in parts) or sum(parts) <= 0:
        return None
    return 100.0 * (parts[0] + parts[1]) / sum(parts)
