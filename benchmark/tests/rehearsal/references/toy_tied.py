"""The plain reference of the made-up ``toy_tied`` family: the
``llama_dense`` block with the output head tied to the embedding,
``logits = rms(x) @ embed.T``. It exists to show that a family is added with
files alone, and that ``correct`` goes through the family's own reference:
the dense program can serve it, the dense reference gets it wrong.

It builds on the dense family's reference file and imports nothing else.
"""

from __future__ import annotations

from benchmark.references import llama_dense as dense

Widths = dense.Widths
CONTROLS = dense.CONTROLS


def init_weights(seed, w):
    """The dense family's leaves of this seed, without a head of its own."""
    weights = dict(dense.init_weights(seed, w))
    del weights["wout"]
    return weights


def logits_at(weights, w, tokens, rows, *, quant=None, pad_to=1024):
    return dense.logits_at({**weights, "wout": weights["embed"].T}, w,
                           tokens, rows, quant=quant, pad_to=pad_to)
