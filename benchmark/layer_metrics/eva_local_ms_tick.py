"""Device time of the EVA layers' exact-rows call per decode tick (all layers),
over the decode ticks of the traced window: the events of the kernel named
``eva_local_decode`` (``ops/pallas_decode.py``: the paged decode body under an
aligned lower edge). Nothing to read (None) where the program launches no such
kernel."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "eva_local_decode")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
