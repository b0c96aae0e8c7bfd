"""The engine built: the start-up record's ``startup:params`` (the weights
drawn and re-laid, waited for) and ``startup:engine`` (the flags held, the
model read, the pools allocated, the jits declared) spans before the
window. Read by ``setup_unseen_s.py``'s ``parts``."""


def read(run):
    p = run.cell.spec.load_module(
        "layer_metrics", "setup_unseen_s.py").parts(run)
    return None if p is None else p["engine"]
