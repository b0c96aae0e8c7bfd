"""Share of the rows the tick programs computed that carried a token: the
flight records' ``rows_useful`` (the sum of ``n_vec``) over ``rows_computed``
(slots x the Tq bucket), summed over the window's ticks."""
from benchmark import phases


def read(run):
    r = phases.rows(run)
    return 100.0 * r[1] / r[0] if r else None
