"""layer: journal (journal.py `_write_group`).  source: program_span (the
`drain/journal_write` span: a group's one write + flush, between
`drain/journal_append` and `drain/journal_fsync` under `pipeline/drain`).
moves: pods_per_s."""

from perfbench import spanread


def read(ctx):
    return spanread.per_batch_ms(ctx, "drain/journal_write")
