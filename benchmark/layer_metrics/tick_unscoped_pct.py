"""Share of the leaf device seconds in the traced window's ticks that belong to
no part of the model: operations no tick program's table holds, operations a
table holds without a scope, and names two programs put to different parts.
The guard on every ``dec_*`` / ``mix_*`` metric (``benchmark/parts.py``)."""
from benchmark import parts


def read(run):
    return parts.unscoped_pct(run)
