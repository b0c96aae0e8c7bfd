"""The process before it has an engine to build: the start-up record's
``startup:import`` (the process's own start to the end of the program's
import, JAX's inside it) and ``startup:backend`` (the program's first
device query; ~0 where the harness asked first) spans before the window.
Read by ``setup_unseen_s.py``'s ``parts``."""


def read(run):
    p = run.cell.spec.load_module(
        "layer_metrics", "setup_unseen_s.py").parts(run)
    return None if p is None else p["import"]
