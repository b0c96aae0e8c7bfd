"""How late the generator ran: release time minus due time, 99th percentile
over the requests due inside the window. Releases happen in the engine's own
poll, once a tick, so this is at most a tick unless the host is starved."""
from benchmark import reduce


def read(run):
    late = [r.released - r.due for r in run.recs
            if not r.midlife and run.t_open <= r.due < run.t_end]
    return 1e3 * reduce.percentile(late, 0.99) if late else None
