"""The Mamba-1 decode step's share of its roofline in decode ticks. It reads
and writes every live slot's state once (``kernel_costs/ssm1_scan.py``) and is
bound by memory: the least time is those bytes over the chip's peak bytes per
second (6 operations a state element over the peak FLOP/s are less, and the
larger of the two is taken)."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "ssm1_scan")
    if not k or not k["seconds"] or not run.peaks:
        return None
    least = max(k["bytes"] / run.peaks["hbm_bytes_per_s"],
                k["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
