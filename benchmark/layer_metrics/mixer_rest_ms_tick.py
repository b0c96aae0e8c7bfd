"""Device time per decode tick of every operation that is neither the grouped
expert product (``moe_grouped_matmul*``) nor the paged decode attention
(``flash_decode_paged*``), over the decode ticks of the traced window: the
conv mixers, the attention layers' projections and norms, the dense FFNs, the
routers and the experts' sorting and weighing, the pools' writes, the head and
the sampling. As XLA fusions they have no names of their own to be read by."""
from benchmark import ticks, trace_reduce

NAMED = ("moe_grouped_matmul", "flash_decode_paged")


def in_decode_ticks(run):
    """Seconds of the unnamed leaf operations that start inside decode ticks
    of the traced window, those ticks' spans and their count; None where
    there is no trace or no decode tick in it."""
    tr = run.trace
    if not tr or not run.flight or "offset_s" not in tr:
        return None
    off = tr["offset_s"]
    w0, w1 = tr["t0"] - off, tr["t1"] - off          # on the host's clock
    spans = [s for s in ticks.spans(run.flight, w0, w1)
             if s[2] == 0 and s[3] > 0 and s[1] <= w1]
    if not spans:
        return None
    n_dev = max(tr["devices"], 1)
    events = sorted((s - off, d) for ev in tr["events"].values()
                    for name, s, d in trace_reduce.leaves(ev)
                    if not any(k in name for k in NAMED))
    if not events:
        return None
    seconds, i = 0.0, 0
    for a, b, _, _ in spans:
        while i < len(events) and events[i][0] < a:
            i += 1
        while i < len(events) and events[i][0] < b:
            seconds += events[i][1] / n_dev
            i += 1
    return {"seconds": seconds, "ticks": len(spans), "spans": spans}


def read(run):
    k = in_decode_ticks(run)
    return 1e3 * k["seconds"] / k["ticks"] if k else None
