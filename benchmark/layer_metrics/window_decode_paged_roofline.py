"""The sliding-window layers' paged decode attention's share of its roofline in
decode ticks: the bytes its cost function counts (each live slot's last
``window`` keys and values once) over the chip's peak bytes per second, over
the kernel's device time. It reads low by construction: a grid step holds
several blocks (256 tokens at this configuration's shapes) of which the window
covers half on average, and a step's fixed cost is a large part of a call that
walks one or two steps a slot. The number is the finding."""
from benchmark import kernels


def read(run):
    k = kernels.in_decode_ticks(run, "window_decode_paged")
    if not k or not k["seconds"] or not run.peaks:
        return None
    least = max(k["bytes"] / run.peaks["hbm_bytes_per_s"],
                k["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
