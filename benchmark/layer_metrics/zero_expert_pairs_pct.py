"""Share of the router's chosen (row, expert) pairs that fell on zero-compute
experts, in the window's decode ticks: 100 x the sum of the flight records'
``zero_pairs`` over the sum of their ``routed_pairs``. Such a pair costs no
expert's weights and no matmul, only one weighted add of the row. About a third
where the router's scores are even over its width. A reading of the router,
not a thing to optimise: ``better: lower`` in the benchmark file is a
convention."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and r.get("routed_pairs") and not r.get("chunk_tokens")
            and r.get("occupancy")]
    if not recs:
        return None
    return 100.0 * sum(r["zero_pairs"] for r in recs) \
        / sum(r["routed_pairs"] for r in recs)
