"""The paged decode attention's share of its roofline in decode ticks. At one
query row a slot it is bound by memory: the least time is the bytes its cost
function counts over the chip's peak bytes per second (the operations over
the peak FLOP/s are less, and the larger of the two is taken)."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "flash_decode_paged")
    if not k or not k["seconds"] or not run.peaks:
        return None
    least = max(k["bytes"] / run.peaks["hbm_bytes_per_s"],
                k["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
