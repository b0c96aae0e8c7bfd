"""Device milliseconds a tick that carries a prompt chunk spends in the chunk
rows' attention (scope ``attn_chunk``: ``flash_fwd`` or the latent kernel
over the chunking slot's gathered view), the prompt side of a tick's
prompt/generation split, over such ticks of the traced window. An operation
goes to a kind of tick by its program's table and to a part by its scope
(``benchmark/parts.py``)."""
from benchmark import parts


def read(run):
    return parts.ms_tick(run, parts.MIX, "attn_chunk")
