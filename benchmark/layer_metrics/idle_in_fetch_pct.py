"""Share of the traced window in which the device ran nothing while the
engine was in ``fetch``, waiting for the tick's tokens: the device had
finished, or not begun, and the readback was on its way. The four
``idle_in_*`` add up to ``device_idle_pct`` of the same run."""
from benchmark import phases


def read(run):
    shares = phases.idle_shares(run)
    return shares[phases.FETCH] if shares else None
