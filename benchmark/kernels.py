"""A kernel's device time in the decode ticks of the traced window, and the
bytes its cost function says those calls needed."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import ticks


def in_decode_ticks(run, kernel: str) -> Optional[Dict[str, Any]]:
    """Seconds of ``kernel``'s events that start inside decode ticks (no
    chunk tokens) of the traced window, the count of those ticks, and the
    cost of their calls: the configuration's family says what the cost
    function wants of the model and how many calls a tick makes
    (``adapters/<family>.py`` ``kernel_call``). None where there is no
    trace, no such event, or the family never launches the kernel."""
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
                    for name, s, d in ev if kernel in name)
    if not events:
        return None
    call = run.cell.adapter().kernel_call(run.cell.config, kernel)
    if call is None:
        return None
    of_model, calls_a_tick = call
    costs = run.cell.spec.load_module("kernel_costs", kernel + ".py")
    seconds = need_bytes = need_flops = 0.0
    i = 0
    for a, b, _, _ in spans:
        while i < len(events) and events[i][0] < a:
            i += 1
        while i < len(events) and events[i][0] < b:
            seconds += events[i][1] / n_dev
            i += 1
        c = costs.cost(contexts=ticks.live_contexts(run.recs, a), q_rows=1,
                       **of_model)
        need_bytes += c["bytes"] * calls_a_tick
        need_flops += c["flops"] * calls_a_tick
    return {"seconds": seconds, "ticks": len(spans), "bytes": need_bytes,
            "flops": need_flops}
