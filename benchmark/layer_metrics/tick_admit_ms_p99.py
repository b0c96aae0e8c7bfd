"""``ingest`` + ``sweep`` + ``admit`` of a tick (the poll, the control sweep
and the admission loop with its prefix match and LRU eviction), 99th
percentile over the window's fetched ticks that end before the profiler
starts."""
from benchmark import phases


def read(run):
    return phases.percentile_ms(run, ("ingest", "sweep", "admit"), 0.99)
