"""Device time of the paged decode attention per decode tick (all layers),
over the decode ticks of the traced window."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "flash_decode_paged")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
