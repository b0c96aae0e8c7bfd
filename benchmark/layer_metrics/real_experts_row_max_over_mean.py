"""The decision that asks for the most expert compute against the mean
decision, in the window's decode ticks: the sum of the flight records'
``real_row_max`` (the most routed, weight-bearing experts one row chose in one
layer of the tick) over the sum of their mean ((``routed_pairs`` -
``zero_pairs``) / ``routed_rows``). The straggler that zero-compute experts
create: where a deployment exchanges rows between holders, the token with the
most real experts sets the step's time. 1 where every decision runs the same
number (a router with no zero-compute experts)."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and r.get("routed_rows") and not r.get("chunk_tokens")
            and r.get("occupancy") and r["routed_pairs"] > r["zero_pairs"]]
    if not recs:
        return None
    mean = sum((r["routed_pairs"] - r["zero_pairs"]) / r["routed_rows"]
               for r in recs)
    return sum(r["real_row_max"] for r in recs) / mean
