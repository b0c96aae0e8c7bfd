"""Programs JAX compiled, or fetched from its cache, inside the window."""


def read(run):
    return float(sum(1 for s in run.compile_stamps
                     if run.t_open <= s < run.t_end))
