"""The ungated grouped expert product's share of its roofline in decode
ticks. Its device events are named ``moe_ungated_matmul``; its cost comes
from each decode tick's flight record (``experts_touched``, ``expert_pairs``)
through ``kernel_costs/moe_ungated_matmul.py``: the two matrices of the
experts touched once and the pairs' rows, against 4 x latent x width
operations a pair. At a few rows an expert it is bound by memory."""
from benchmark import kernels, ticks

KERNEL = "moe_ungated_matmul"


def read(run):
    k = kernels.in_decode_ticks(run, KERNEL)
    if not k or not k["seconds"] or not run.peaks:
        return None
    call = run.cell.adapter().kernel_call(run.cell.config, KERNEL)
    costs = run.cell.spec.load_module("kernel_costs", KERNEL + ".py")
    off = run.trace["offset_s"]
    w0, w1 = run.trace["t0"] - off, run.trace["t1"] - off
    starts = {s[0] for s in ticks.spans(run.flight, w0, w1)
              if s[2] == 0 and s[3] > 0 and s[1] <= w1}
    need_bytes = need_flops = 0.0
    for rec in run.flight:
        if rec.get("t_s") in starts and "expert_pairs" in rec:
            c = costs.cost(experts_touched=rec["experts_touched"],
                           pairs=rec["expert_pairs"], **call[0])
            need_bytes += c["bytes"]
            need_flops += c["flops"]
    if not need_bytes:
        return None
    least = max(need_bytes / run.peaks["hbm_bytes_per_s"],
                need_flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
