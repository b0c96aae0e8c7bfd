"""Peak device memory on the fullest chip, after the window."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
