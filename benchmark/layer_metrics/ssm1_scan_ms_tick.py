"""Device time of the Mamba-1 layers' in-place step (device events named
``ssm1_scan``, ``ops/pallas_ssm.py``) per decode tick, all Mamba-1 layers,
over the decode ticks of the traced window. None where the trace holds no
such event (a program without the kernel)."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "ssm1_scan")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
