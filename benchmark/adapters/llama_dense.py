"""The ``llama_dense`` family's way into the engine: the model's flags, the
published widths and the reference's weights handed to the program, the
engine that was built held against the configuration file, and what a
kernel's cost function wants of this configuration.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError


def head_size(config: Dict[str, Any]) -> int:
    return int(config.get("head_dim", config["hidden_size"]
                          // config["num_attention_heads"]))


def model_flags(config: Dict[str, Any]) -> List[str]:
    return [
        "--model-dim", str(config["hidden_size"]),
        "--heads", str(config["num_attention_heads"]),
        "--kv-heads", str(config["num_key_value_heads"]),
        "--vocab-size", str(config["vocab_size"]),
        "--n-layers", str(config["num_hidden_layers"]),
    ]


def engine_params(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat leaves, packed as the program's block takes
    them."""
    per_layer = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3", "w2")
    return {"embed": weights["embed"], "ln_f": weights["ln_f"],
            "wout": weights["wout"],
            "layers": {n: weights[n] for n in per_layer}}


@contextlib.contextmanager
def published_model(config: Dict[str, Any], seed: int, reference):
    """``build_serve_engine`` takes neither a feed-forward width, a rotary
    base or a norm epsilon (the CLI derives the first from the hidden size
    and leaves the others at the model's defaults) nor weights (it draws its
    own, leaf by leaf in float32: 12.8 GB at its peak for 6.6 GB of Yi-6B
    weights, my chip run, PR 23). Until it does (Open question in PERF.md),
    the published values go into the ``TransformerConfig`` the CLI builds, at
    the one place it builds it, and the reference's weights, made in one
    jitted call, take the place of ``init_params``' where the CLI calls
    it."""
    import tree_attention_tpu.models as models
    from tree_attention_tpu import cli

    config_fn, init_fn = cli._transformer_config, models.init_params

    def with_published(cfg):
        return dataclasses.replace(
            config_fn(cfg), d_ff=int(config["intermediate_size"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]))

    def benchmark_weights(key, tcfg):
        del key, tcfg                   # the seed is in the flags
        return engine_params(
            reference.init_weights(seed, reference.Widths.of(config)))

    cli._transformer_config = with_published
    models.init_params = benchmark_weights
    try:
        yield
    finally:
        cli._transformer_config = config_fn
        models.init_params = init_fn


def build(config: Dict[str, Any], serving_flags: List[str], seed: int,
          device: str, reference):
    """The engine of ``serving_flags`` (the harness's: slots, lengths,
    cache, seed, ``device`` among them) serving this configuration with the
    reference's weights of ``seed``. Returns ``(setup, server)``."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    del device                          # one chip: the flags place the model
    cfg = parse_args(serving_flags + model_flags(config))
    with published_model(config, seed, reference):
        setup = cli.build_serve_engine(cfg, None)
    t = setup.tcfg
    got = (t.d_model, t.d_ff, t.n_heads, t.n_kv_heads, t.d_head, t.n_layers,
           t.vocab_size, t.rope_theta, t.norm_eps)
    want = (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            head_size(config), config["num_hidden_layers"],
            config["vocab_size"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]))
    if got != want:
        raise SpecError(f"the engine was built at {got}, the configuration "
                        f"file says {want}")
    return setup, setup.make_engine()


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own (contexts, query rows),
    and how many calls a tick makes; None for a kernel this family never
    launches."""
    if kernel != "flash_decode_paged":
        return None
    return ({"heads": int(config["num_attention_heads"]),
             "kv_heads": int(config["num_key_value_heads"]),
             "head": head_size(config), "dtype_bytes": 2},
            int(config["num_hidden_layers"]))
