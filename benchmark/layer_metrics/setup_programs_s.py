"""The tick programs built before the window: the seconds JAX reports for
tracing, lowering and compiling each, or fetching it from the persistent
cache (``trace_s + lower_s + compile_s + fetch_s``), summed over the
start-up record's ``startup:program`` spans that closed before the window.
Read by ``setup_unseen_s.py``'s ``parts``."""


def read(run):
    p = run.cell.spec.load_module(
        "layer_metrics", "setup_unseen_s.py").parts(run)
    return None if p is None else p["programs"]
