"""Device milliseconds a decode tick (no chunk tokens) spends in both ends of
the model (scopes ``embed``: the token rows' gather; and ``head``: the final
norm, the logits on one row a slot, the float32 convert, the sampler), over
such ticks of the traced window. An operation goes to a kind of tick by its
program's table and to a part by its scope (``benchmark/parts.py``)."""
from benchmark import parts


def read(run):
    return parts.ms_tick(run, parts.DEC, "head")
