"""A per-layer metric that only the rehearsal adds: requests released."""


def read(run):
    return float(sum(1 for r in run.recs if not r.midlife))
