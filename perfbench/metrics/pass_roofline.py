"""layer: device pass (engine/pass_.py).  source: device_trace.  moves:
pods_per_s.  The pass's share of its roofline, bound by HBM bandwidth: the
bytes the traced passes have to move (peaks.pass_bytes, from the cell's
shapes alone: one pass of the window's mean batch, times the passes traced)
over the chip's peak bytes/s, over the device time of the traced window's
programs.  The issue's pass_hbm_share under the name the contract gives a
kernel's roofline share."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["modules"] or not ctx.records:
        return None
    passes = max(m["count"] for m in tr["modules"].values())
    secs = sum(m["seconds"] for m in tr["modules"].values())
    if not passes or secs <= 0:
        return None
    mean_pods = round(ctx.pods() / len(ctx.records))
    need = passes * ctx.peaks.pass_bytes(ctx.config, mean_pods)
    least = need / ctx.peaks.peak(ctx.device["kind"], "hbm_bytes_per_s")
    return 100.0 * least / secs
