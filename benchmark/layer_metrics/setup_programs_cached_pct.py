"""Share of the tick programs built before the window that came from the
persistent compile cache (``from_cache`` of the start-up record's
``startup:program`` spans): 100 on a warm run, 0 on the first. The cache's
state, read. Nothing where no program was built before the window. Read by
``setup_unseen_s.py``'s ``parts``."""


def read(run):
    p = run.cell.spec.load_module(
        "layer_metrics", "setup_unseen_s.py").parts(run)
    if p is None or not p["built"]:
        return None
    return 100.0 * p["from_cache"] / p["built"]
