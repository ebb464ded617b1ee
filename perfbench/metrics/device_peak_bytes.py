"""layer: device.  source: program_counter (the allocator's
peak_bytes_in_use, through scheduler_device_memory_bytes).  moves:
pods_per_s."""


def read(ctx):
    v = ctx.after.get('scheduler_device_memory_bytes{kind="peak_bytes_in_use"}')
    return v if v else None
