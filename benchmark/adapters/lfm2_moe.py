"""The ``lfm2_moe`` family's way into the engine: the model handed to
``cli.build_serve_engine`` as data (the configuration file itself) with the
reference's weights re-packed as the program's layer loop takes them, the
engine that was built held against the configuration file, and what a kernel's
cost function wants of this configuration. No function of the program is
swapped.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError

_MIXERS = {"conv": "conv", "full_attention": "attention"}


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's loop takes them: a stack a
    kind of part, the expert layers under ``layers``, the published taps
    ``(hidden, taps)`` turned to ``(taps, hidden)`` (a tap a row of lanes),
    and no ``wout``: the head is the embedding."""
    del w
    out = {"embed": weights["embed"], "ln_f": weights["ln_f"]}
    for name, to in (("attn", "attn"), ("dense", "dense"), ("moe", "layers")):
        if name in weights:
            out[to] = weights[name]
    if "conv" in weights:
        out["conv"] = dict(weights["conv"],
                           w_conv=weights["conv"]["w_conv"].transpose(0, 2, 1))
    return out


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    ex = t.moe
    return {
        "hidden_size": t.d_model, "intermediate_size": t.d_ff,
        "num_hidden_layers": t.n_layers, "layer_types": list(
            t.layer_types or ()),
        "num_attention_heads": t.n_heads, "num_key_value_heads": t.n_kv_heads,
        "head_dim": t.d_head, "vocab_size": t.vocab_size,
        "conv_L_cache": t.conv_taps, "qk_norm": t.qk_norm,
        "tied_head": t.tied_head, "cache_kind": t.cache_kind,
        "attention_layers": t.cache_layers, "conv_layers": t.conv_layers,
        "latent": t.mla is not None,
        "num_experts": ex.n_experts if ex else 0,
        "experts_held": ex.held if ex else 0,
        "num_experts_per_tok": ex.per_token if ex else 0,
        "moe_intermediate_size": ex.width if ex else 0,
        "num_dense_layers": ex.first_dense if ex else t.n_layers,
        "scoring": ex.scoring if ex else None,
        "corrected_choice": bool(ex and ex.corrected),
        "norm_topk_prob": bool(ex and ex.renorm),
        "routed_scaling_factor": float(ex.scale) if ex else 1.0,
        "shared_width": ex.shared_width if ex else 0,
        "n_group": ex.n_groups if ex else 1,
        "zero_expert_num": ex.n_zero if ex else 0,
        "rope_theta": float(t.rope_theta), "norm_eps": float(t.norm_eps),
    }


def wanted(config: Dict[str, Any]) -> Dict[str, Any]:
    types = [_MIXERS[t] for t in config["layer_types"]]
    heads, hidden = int(config["num_attention_heads"]), int(
        config["hidden_size"])
    dep = config["deployment"]
    if int(dep["experts_total"]) != int(config["num_experts"]) \
            or int(dep["expert_share"]) != 0:
        raise SpecError("this family's cut holds every expert of a layer")
    return {
        **{k: int(config[k]) for k in (
            "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "conv_L_cache", "num_experts", "num_experts_per_tok",
            "moe_intermediate_size", "num_dense_layers")},
        "layer_types": types, "head_dim": hidden // heads,
        "qk_norm": True, "tied_head": True, "cache_kind": "hybrid",
        "attention_layers": types.count("attention"),
        "conv_layers": types.count("conv"), "latent": False,
        "experts_held": int(config["num_experts"]),
        "scoring": "sigmoid",
        "corrected_choice": bool(config["use_expert_bias"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "shared_width": 0, "n_group": 1, "zero_expert_num": 0,
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["norm_eps"]),
    }


def _hold_to_file(model, config: Dict[str, Any]) -> None:
    """SpecError unless ``model`` (a ``TransformerConfig``) is the model
    the configuration file describes."""
    try:
        got = built_as(model)
    except AttributeError as e:         # a model without the layers' fields
        raise SpecError(f"this program's model cannot express the "
                        f"{config['family']} family's layers: {e}") from None
    want = wanted(config)
    if got != want:
        diff = {k: (got.get(k), want.get(k))
                for k in sorted(set(got) | set(want))
                if got.get(k) != want.get(k)}
        raise SpecError(f"the engine was built otherwise than the "
                        f"configuration file says (built, file): {diff}")


def build(config: Dict[str, Any], serving_flags: List[str], seed: int,
          device: str, reference):
    """The engine of ``serving_flags`` (the harness's: slots, lengths,
    cache, seed, ``device`` among them) serving this configuration with the
    reference's weights of ``seed``. Returns ``(setup, server)``. A program
    that cannot express the layers is refused at once, before a weight is
    drawn: its own reading of the file fails or comes out otherwise (one
    that knows no ``num_experts`` reads a dense model)."""
    del device                          # one chip: the flags place the model
    try:
        from tree_attention_tpu import cli
        from tree_attention_tpu.models.transformer import model_from_config
        from tree_attention_tpu.utils.config import parse_args

        model = model_from_config(config)
    except (ImportError, KeyError, TypeError, ValueError) as e:
        raise SpecError(f"this program cannot read the {config['family']} "
                        f"family's model as data: {e!r}") from None
    _hold_to_file(model, config)
    cfg = parse_args(serving_flags)
    w = reference.Widths.of(config)
    params = engine_params(reference.init_weights(seed, w), w)
    setup = cli.build_serve_engine(cfg, None, model=config, params=params)
    del params
    _hold_to_file(setup.tcfg, config)
    server = setup.make_engine()
    cache, s = server.cache, config["serving"]
    want = wanted(config)
    # The program lays heads of fewer than 128 lanes side by side, as many
    # as divide both the lanes and the KV heads, and a block's two tail
    # rows likewise (its own rule, no option; PERF.md, PR 33).
    heads, head = int(config["num_key_value_heads"]), want["head_dim"]
    pack = math.gcd(128 // head, heads) if 128 % head == 0 else 1
    kv = (want["attention_layers"], cache.k.shape[1], heads // pack,
          int(s["kv_block"]), head * pack)
    tail = (want["conv_layers"], cache.k.shape[1],
            2 * int(config["hidden_size"]))
    if cache.k.shape != kv or cache.v.shape != kv or cache.tail.shape != tail:
        raise SpecError(
            f"the pools are K {cache.k.shape}, V {cache.v.shape}, tail "
            f"{cache.tail.shape}; the file says {kv[0]} K/V layers of "
            f"{heads} heads of {head} ({pack} a row of lanes: {kv[1:]}) "
            f"and {tail[0]} tail layers of 2 rows of "
            f"{config['hidden_size']} a block ({tail[1:]}) under one N")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes; None for a kernel this family never launches."""
    types = [_MIXERS[t] for t in config["layer_types"]]
    heads, hidden = int(config["num_attention_heads"]), int(
        config["hidden_size"])
    n_moe = int(config["num_hidden_layers"]) - int(config["num_dense_layers"])
    if kernel == "flash_decode_paged":
        return ({"heads": heads,
                 "kv_heads": int(config["num_key_value_heads"]),
                 "head": hidden // heads, "dtype_bytes": 2},
                types.count("attention"))
    if kernel == "moe_grouped_matmul":
        return ({"hidden": hidden,
                 "width": int(config["moe_intermediate_size"]),
                 "experts_held": int(config["num_experts"]),
                 "dtype_bytes": 2}, n_moe)
    if kernel == "mixer_rest":
        return ({"hidden": hidden, "heads": heads,
                 "kv_heads": int(config["num_key_value_heads"]),
                 "head": hidden // heads,
                 "conv_layers": types.count("conv"),
                 "attention_layers": types.count("attention"),
                 "taps": int(config["conv_L_cache"]),
                 "dense_layers": int(config["num_dense_layers"]),
                 "dense_width": int(config["intermediate_size"]),
                 "expert_layers": n_moe,
                 "experts": int(config["num_experts"]),
                 "vocab": int(config["vocab_size"]),
                 "dtype_bytes": 2}, 1)
    return None
