"""The fullest held expert's rows over the mean rows of a touched expert,
in the window's decode ticks (the sum of the ticks' ``expert_rows_max`` over
the sum of their ``expert_pairs / experts_touched``): the tick's straggler.
1 where every touched expert has the same load."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and r.get("experts_touched") and not r.get("chunk_tokens")
            and r.get("occupancy")]
    if not recs:
        return None
    mean = sum(r["expert_pairs"] / r["experts_touched"] for r in recs)
    return sum(r["expert_rows_max"] for r in recs) / mean
