"""Device time of the state-space layers' in-place decode step (device
events named ``ssm_decode_update``, ``ops/pallas_ssm.py``) per decode tick,
all state-space layers, over the decode ticks of the traced window."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "ssm_decode_update")
    return 1e3 * k["seconds"] / k["ticks"] if k else None
