"""Device milliseconds a decode tick (no chunk tokens) spends in the
attentions' projections (scopes ``attn_in``: pre-norm, q/k/v or the latent
products, QK-norm, rotary, head packing; and ``attn_out``: ``wo`` /
``latent_out``, the residual add), over such ticks of the traced window. An
operation goes to a kind of tick by its program's table and to a part by its
scope (``benchmark/parts.py``)."""
from benchmark import parts


def read(run):
    return parts.ms_tick(run, parts.DEC, "proj")
