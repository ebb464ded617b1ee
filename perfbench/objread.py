"""What the three ``object_*_us_per_object`` readers share: a stage's
seconds of ``scheduler_object_add_seconds_total`` over the objects
``scheduler_objects_added_total`` counted, both over the window.  A program
without the counters, or a window that added no object, gives None."""

from __future__ import annotations

ADDED = "scheduler_objects_added_total{"


def per_object_us(ctx, stage: str):
    key = f'scheduler_object_add_seconds_total{{stage="{stage}"}}'
    objects = sum(ctx.delta(k) for k in ctx.after if k.startswith(ADDED))
    return ctx.delta(key) / objects * 1e6 if objects > 0 and key in ctx.after else None
