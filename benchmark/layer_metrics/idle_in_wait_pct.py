"""Share of the traced window in which the device ran nothing while the
engine was in no phase: between two ticks, waiting for a request. The four
``idle_in_*`` add up to ``device_idle_pct`` of the same run."""
from benchmark import phases


def read(run):
    shares = phases.idle_shares(run)
    return shares[phases.WAIT] if shares else None
