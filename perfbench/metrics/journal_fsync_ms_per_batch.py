"""layer: journal (journal.py).  source: program_span (flight records'
journal.fsync_s).  moves: pods_per_s."""


def read(ctx):
    recs = [r for r in ctx.records if "journal" in r]
    if not recs:
        return None
    return sum(float(r["journal"]["fsync_s"]) for r in recs) / len(recs) * 1e3
