"""Device milliseconds a decode tick (no chunk tokens) spends in the decode
rows' attention (scopes ``attn_cache``: the new rows' write into the pool,
one write for all of a tick's rows; and ``attn_decode``: the paged decode
kernel, the merge and unpacking round it), over such ticks of the traced
window. An operation goes to a kind of tick by its program's table and to a
part by its scope (``benchmark/parts.py``)."""
from benchmark import parts


def read(run):
    return parts.ms_tick(run, parts.DEC, "attn_decode")
