"""The ``toy_tied`` family's way into the engine: the dense family's, with
the embedding handed to the program as its output head."""

from __future__ import annotations

import types

from benchmark.adapters import llama_dense as dense

kernel_call = dense.kernel_call


def build(config, serving_flags, seed, device, reference):
    def with_head(seed, w):
        weights = reference.init_weights(seed, w)
        return {**weights, "wout": weights["embed"].T}

    return dense.build(config, serving_flags, seed, device,
                       types.SimpleNamespace(Widths=reference.Widths,
                                             init_weights=with_head))
