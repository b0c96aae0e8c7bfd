"""Tick programs the engine built inside the window: the start-up record's
``startup:program`` spans that began in it. ``compiles_in_window``'s count
from inside the program, where each has a name, a bucket and a tick. Read
by ``setup_unseen_s.py``'s ``parts``."""


def read(run):
    p = run.cell.spec.load_module(
        "layer_metrics", "setup_unseen_s.py").parts(run)
    return None if p is None else float(p["in_window"])
