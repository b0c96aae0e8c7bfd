"""What the operations outside the two kernels take of the memory's pace in
decode ticks: the bytes ``kernel_costs/mixer_rest.py`` says a tick needs
(every weight outside the experts once, the rows, the conv tails, the logits)
over ``mixer_rest_ms_tick``'s seconds times the chip's peak bytes per second
(the operations over the peak FLOP/s are less, and the larger is taken). Says
whether sixty-odd small operations at a row a slot run at the memory's pace or
at the launches'."""
from benchmark import ticks


def read(run):
    rest = run.cell.spec.load_module("layer_metrics", "mixer_rest_ms_tick.py")
    k = rest.in_decode_ticks(run)
    call = run.cell.adapter().kernel_call(run.cell.config, "mixer_rest")
    if not k or not k["seconds"] or not run.peaks or call is None:
        return None
    costs = run.cell.spec.load_module("kernel_costs", "mixer_rest.py")
    need_bytes = need_flops = 0.0
    for a, _, _, _ in k["spans"]:
        c = costs.cost(contexts=ticks.live_contexts(run.recs, a), q_rows=1,
                       **call[0])
        need_bytes += c["bytes"] * call[1]
        need_flops += c["flops"] * call[1]
    least = max(need_bytes / run.peaks["hbm_bytes_per_s"],
                need_flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
