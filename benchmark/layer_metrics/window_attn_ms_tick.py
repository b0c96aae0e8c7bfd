"""Device time of the sliding-window layers' paged decode attention per decode
tick (all window layers), over the decode ticks of the traced window: the
events of the kernel named ``window_decode_paged``. Nothing to read (None)
where the program launches no such kernel."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "window_decode_paged")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
