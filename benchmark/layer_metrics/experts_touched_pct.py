"""Held experts that got at least one row, over the experts held, in the
window's decode ticks (summed over the expert layers): the flight records'
``experts_touched``. It decides the weight bytes a decode tick streams."""


def decode_records(run):
    """The window's decode-tick records (no chunk tokens, a live slot) of a
    program that counts its expert layers' rows; [] otherwise."""
    return [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and "expert_pairs" in r and not r.get("chunk_tokens")
            and r.get("occupancy")]


def read(run):
    recs = decode_records(run)
    call = run.cell.adapter().kernel_call(run.cell.config,
                                          "moe_grouped_matmul")
    if not recs or call is None:
        return None
    held = call[0]["experts_held"] * call[1]      # a layer's x expert layers
    return 100.0 * sum(r["experts_touched"] for r in recs) / (len(recs) * held)
