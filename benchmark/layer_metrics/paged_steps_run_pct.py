"""Grid steps the paged decode kernels ran over the steps of the whole slots x
table rectangle, a layer, summed over the window's decode ticks: the flight
records' ``kv_steps_run`` over ``kv_steps_grid``. The kernels walk a list of
the (slot, step) pairs that hold a live token, so this is the share of the
rectangle the slots' lengths fill: 100 with every slot at capacity."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and r.get("kv_steps_grid") and not r.get("chunk_tokens")
            and r.get("occupancy")]
    if not recs:
        return None
    return 100.0 * sum(r["kv_steps_run"] for r in recs) \
        / sum(r["kv_steps_grid"] for r in recs)
