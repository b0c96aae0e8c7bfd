"""Device time of the grouped expert product (device events named
``moe_grouped_matmul``: the fused gate/up product and the down product of
``ops/pallas_moe.py``) per decode tick, all expert layers, over the decode
ticks of the traced window."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "moe_grouped_matmul")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
