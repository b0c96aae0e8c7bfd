"""Live (decoding) slots per executed tick over the slots, from the flight
recorder's ``occupancy``; prefilling slots do not count."""
from benchmark import ticks


def read(run):
    if not run.flight:
        return None
    sp = ticks.spans(run.flight, run.t_open, run.t_end)
    return 100.0 * sum(s[3] for s in sp) / (len(sp) * run.slots) if sp else None
