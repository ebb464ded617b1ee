"""layer: featurize and pack (engine/packing.py).  source: program_counter
(scheduler_chunk_pack_width: the chunk width the last pack plan chose).
moves: pods_per_s.  The configured --chunk-size where the packer found
the batch's conflict classes a place each; 1 where one class is as large
as the batch and the pass falls back to the strictly ordered scan.  A
process whose packer never ran has no width to report."""


def read(ctx):
    v = ctx.after.get("scheduler_chunk_pack_width")
    return v if v else None
