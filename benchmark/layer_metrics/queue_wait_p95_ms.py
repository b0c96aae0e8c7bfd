"""``RequestResult.queue_wait_s`` (visible to admitted), 95th percentile over
the requests that finished inside the window."""
from benchmark import reduce


def read(run):
    waits = [r.queue_wait_s for r in run.recs
             if not r.midlife and r.queue_wait_s is not None
             and r.finished is not None and r.finished < run.t_end]
    return 1e3 * reduce.percentile(waits, 0.95) if waits else None
