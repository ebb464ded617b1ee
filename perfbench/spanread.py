"""What the new per-layer readers share: sums over the ``spans`` list the
program's span primitive (kubernetes_tpu/framework/tracing.py) leaves on
each flight record, ``[name, start_us, dur_us, parent, {sub-times}?]``.
A program without the primitive leaves no list, and every function here
then returns None, so a reader built on it reports nothing there."""

from __future__ import annotations


def seconds(records, name: str):
    """Seconds spent in spans called ``name`` over the records, or None
    where no record holds one."""
    total, found = 0.0, False
    for r in records:
        for sp in r.get("spans") or ():
            if sp[0] == name:
                total += sp[2] * 1e-6
                found = True
    return total if found else None


def stat(records, name: str, key: str):
    """Sum of an accumulated sub-time ``key`` over spans called ``name``."""
    total, found = 0.0, False
    for r in records:
        for sp in r.get("spans") or ():
            if sp[0] == name and len(sp) > 4 and key in sp[4]:
                total += sp[4][key]
                found = True
    return total if found else None


def per_pod_us(ctx, name: str):
    secs, pods = seconds(ctx.records, name), ctx.pods()
    return secs / pods * 1e6 if secs is not None and pods else None


def per_batch_ms(ctx, name: str):
    secs = seconds(ctx.records, name)
    return secs / len(ctx.records) * 1e3 if secs is not None and ctx.records else None
