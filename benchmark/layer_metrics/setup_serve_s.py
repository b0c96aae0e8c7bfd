"""The warm-up ticks' and the pre-fill's running: the start-up record's
``startup:serve`` spans before the window (the warm-up calls whole, the
window's own call up to its opening) less what the programs built inside
them took (``setup_programs_s``) and the ``startup:tables`` spans. Read by
``setup_unseen_s.py``'s ``parts``."""


def read(run):
    p = run.cell.spec.load_module(
        "layer_metrics", "setup_unseen_s.py").parts(run)
    return None if p is None else p["serve"]
