"""Recurrent states the Mamba-1 layers wrote over the states the live slots
hold, in the window's decode ticks: the flight records'
``ssm_states_advanced`` over ``occupancy`` x the Mamba-1 layers (the calls a
tick the family's adapter gives for ``ssm1_scan``). 100 for a sound program
(every live slot's state of every layer, once); over it for one that rewrites
idle slots' states, under it for one that skips a slot. None where the
records carry no such field or the family never launches the kernel."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and "ssm_states_advanced" in r and not r.get("chunk_tokens")
            and r.get("occupancy")]
    call = run.cell.adapter().kernel_call(run.cell.config, "ssm1_scan")
    if not recs or call is None:
        return None
    return 100.0 * sum(r["ssm_states_advanced"] for r in recs) \
        / (call[1] * sum(r["occupancy"] for r in recs))
