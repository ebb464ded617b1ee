"""layer: wire and hints (sidecar/server.py `_add_object`).  source:
program_counter (scheduler_object_add_seconds_total{stage="scope"} over
scheduler_objects_added_total summed over kinds).  moves: pods_per_s.
Microseconds an AddObject spent in its scope stage, over the objects added
in the window (a cell's companions and bind echoes)."""

from perfbench import objread


def read(ctx):
    return objread.per_object_us(ctx, "scope")
