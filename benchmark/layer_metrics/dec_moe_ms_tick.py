"""Device milliseconds a decode tick (no chunk tokens) spends in the routed
experts (scopes ``route``: router, choice, sort / gather / weigh / scatter-
add, the counters, zero-compute experts; and ``experts``:
``moe_grouped_matmul``, both products), over such ticks of the traced
window. An operation goes to a kind of tick by its program's table and to a
part by its scope (``benchmark/parts.py``)."""
from benchmark import parts


def read(run):
    return parts.ms_tick(run, parts.DEC, "moe")
