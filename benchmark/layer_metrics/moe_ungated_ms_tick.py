"""Device time of the ungated grouped expert product (device events named
``moe_ungated_matmul``: the matrix in with relu squared and the matrix out of
``ops/pallas_moe.py``, experts in a latent) per decode tick, all expert
layers, over the decode ticks of the traced window."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "moe_ungated_matmul")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
