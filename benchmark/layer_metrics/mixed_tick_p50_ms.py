"""Median length of the ticks that carried a prompt chunk."""
from benchmark import reduce, ticks


def read(run):
    d = ticks.durations_ms(run, mixed=True)
    return reduce.percentile(d, 0.5) if d else None
