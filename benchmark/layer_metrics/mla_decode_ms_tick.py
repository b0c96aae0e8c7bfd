"""Device time of the paged latent decode attention (device events named
``mla_decode_paged``) per decode tick, all layers, over the decode ticks of
the traced window."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "mla_decode_paged")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
