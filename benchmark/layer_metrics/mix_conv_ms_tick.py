"""Device milliseconds a tick that carries a prompt chunk spends in the gated
short convolutions, whole (scope ``conv``: ``W_in``, the gates, the taps,
the tails' read and write, ``W_out``), over such ticks of the traced window.
An operation goes to a kind of tick by its program's table and to a part by
its scope (``benchmark/parts.py``)."""
from benchmark import parts


def read(run):
    return parts.ms_tick(run, parts.MIX, "conv")
