"""The EVA layers' exact-rows call's share of its roofline in decode ticks: the
larger of the bytes its cost function counts (each live slot's rows of its own
aligned window up to its row, once) over the chip's peak bytes per second and
its operations over the peak FLOP/s, over the kernel's device time. At one
query row a slot it is bound by memory."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "eva_local_decode")
    if not k or not k["seconds"] or not k["bytes"] or not run.peaks:
        return None
    least = max(k["bytes"] / run.peaks["hbm_bytes_per_s"],
                k["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
