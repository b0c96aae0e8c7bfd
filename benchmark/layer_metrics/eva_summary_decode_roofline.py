"""The EVA layers' summary-rows call's share of its roofline in decode ticks:
the larger of the bytes its cost function counts (each live slot's summary rows
of the windows closed before its row, once) over the chip's peak bytes per
second and its operations over the peak FLOP/s, over the kernel's device time.
None where no slot had a closed window (nothing was needed: not 0 over 0)."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "eva_summary_decode")
    if not k or not k["seconds"] or not k["bytes"] or not run.peaks:
        return None
    least = max(k["bytes"] / run.peaks["hbm_bytes_per_s"],
                k["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
