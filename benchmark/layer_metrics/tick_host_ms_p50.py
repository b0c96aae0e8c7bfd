"""The host's own part of a tick: every phase of the engine's tick but
``fetch`` (the device's run and the readback), per executed tick that
fetched; median over the window's ticks that end before the profiler
starts."""
from benchmark import phases


def read(run):
    return phases.percentile_ms(run, None, 0.5, but=("fetch",))
