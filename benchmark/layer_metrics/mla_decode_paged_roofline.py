"""The paged latent decode attention's share of its roofline in decode
ticks: the larger of the bytes its cost function counts over the chip's peak
bytes per second and its operations over the peak FLOP/s, over the device
time of its events. With every head reading the same row the kernel sits
near the ridge (242 operations a byte at 128 heads against the chip's 240),
so which of the two bounds it swings with the contexts."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "mla_decode_paged")
    if not k or not k["seconds"] or not run.peaks:
        return None
    least = max(k["bytes"] / run.peaks["hbm_bytes_per_s"],
                k["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
