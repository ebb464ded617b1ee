"""layer: device pass (engine/pass_.py).  source: device_trace.  moves:
pods_per_s.  The pass's share of its roofline, bound by HBM bandwidth: the
bytes the traced passes have to move (peaks.pass_bytes, from the cell's
shapes alone: what one pass of the window's mean batch moves, a pod, times
the pods whose passes ran inside the slice, a cut pass counting by the
share of it inside) over the chip's peak bytes/s, over the device time of
the slice's programs.  Where the slice holds whole passes of the mean size
that is the passes traced times one pass's bytes.  The issue's
pass_hbm_share under the name the contract gives a kernel's roofline
share."""


def read(ctx):
    tr = ctx.trace
    if not tr or not ctx.records or not tr.get("pods_in_slice") or not tr.get("device_plane"):
        return None  # a rehearsal's CPU threads are no device, and have no roofline
    secs = tr["pass_device_s"]
    mean_pods = round(ctx.pods() / len(ctx.records))
    if secs <= 0 or mean_pods <= 0:
        return None
    need = tr["pods_in_slice"] * ctx.peaks.pass_bytes(ctx.config, mean_pods) / mean_pods
    least = need / ctx.peaks.peak(ctx.device["kind"], "hbm_bytes_per_s")
    return 100.0 * least / secs
