"""Median length of the ticks that carried no prompt chunk."""
from benchmark import reduce, ticks


def read(run):
    d = ticks.durations_ms(run, mixed=False)
    return reduce.percentile(d, 0.5) if d else None
