"""``emit`` + ``account`` of a tick: the token loop with its callbacks,
retirements and SLO observations, then the pool gauges and the flight
record; median over the window's fetched ticks that end before the profiler
starts."""
from benchmark import phases


def read(run):
    return phases.percentile_ms(run, ("emit", "account"), 0.5)
