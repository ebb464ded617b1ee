"""layer: device pass (kubernetes_tpu/snapshot.py holds the table, the pass
reads and writes it).  source: program_counter
(scheduler_csi_claims{kind="shared"} at the window's close: claims that two
or more known pods reference, each of which holds a row of per-claim,
per-node knowledge on the host and on the device).  moves: pods_per_s.
An engagement reading: a claim that one pod uses is held as a per-node
count and takes no row, so in a cell whose pods each have a claim of their
own it reads 0 from the first pod to the last, and with device_peak_bytes
that is the proof that nothing grows with the volumes the cluster has
seen.  A program without the gauge reports nothing."""

KEY = 'scheduler_csi_claims{kind="shared"}'


def read(ctx):
    return ctx.after.get(KEY)
