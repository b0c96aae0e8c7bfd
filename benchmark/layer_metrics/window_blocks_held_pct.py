"""Window-layer blocks the slots hold over what their window tables would hold
had no block been given back, summed over the window's decode ticks: the flight
records' ``window_blocks_held`` over ``window_blocks_full`` (the full layers'
mapped entries, a block for every ``block`` tokens of every slot's length).
Bounded by the window whatever the lengths: a program that kept every block
reads 100. None where the records carry no such field (a program without
window layers)."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and r.get("window_blocks_full") and not r.get("chunk_tokens")
            and r.get("occupancy")]
    if not recs:
        return None
    return 100.0 * sum(r["window_blocks_held"] for r in recs) \
        / sum(r["window_blocks_full"] for r in recs)
