"""The ``exaone_moe`` family's way into the engine: the model handed to
``cli.build_serve_engine`` as data (the configuration file itself) with the
reference's weights re-packed as the program's layer loop takes them, the
engine that was built held against the configuration file (its two pools
among the rest), and what a kernel's cost function wants of this
configuration. No function of the program is swapped.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError

_MIXERS = {"sliding_attention": "window", "full_attention": "attention"}


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's loop takes them: a stack a
    kind of part (``attn``: the full layers' attention, ``wattn``: the
    window layers'), the expert layers under ``layers``."""
    del w
    out = {n: weights[n] for n in ("embed", "ln_f", "wout")}
    for name, to in (("attn", "attn"), ("wattn", "wattn"),
                     ("dense", "dense"), ("moe", "layers")):
        if name in weights:
            out[to] = weights[name]
    return out


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    ex = t.moe
    return {
        "hidden_size": t.d_model, "intermediate_size": t.d_ff,
        "num_hidden_layers": t.n_layers,
        "layer_types": list(t.layer_types or ()),
        "sliding_window": t.window, "rotary": sorted(t.rotary),
        "num_attention_heads": t.n_heads, "num_key_value_heads": t.n_kv_heads,
        "head_dim": t.d_head, "vocab_size": t.vocab_size,
        "qk_norm": t.qk_norm, "tied_head": t.tied_head,
        "cache_kind": t.cache_kind, "full_layers": t.cache_layers,
        "window_layers": t.window_layers, "latent": t.mla is not None,
        "experts_total": ex.n_experts if ex else 0,
        "experts_held": ex.held if ex else 0,
        "experts_first": ex.held_first if ex else 0,
        "num_experts_per_tok": ex.per_token if ex else 0,
        "moe_intermediate_size": ex.width if ex else 0,
        "first_k_dense_replace": ex.first_dense if ex else t.n_layers,
        "scoring": ex.scoring if ex else None,
        "corrected_choice": bool(ex and ex.corrected),
        "norm_topk_prob": bool(ex and ex.renorm),
        "scale_renormed": bool(ex and ex.renorm_scaled),
        "routed_scaling_factor": float(ex.scale) if ex else 1.0,
        "shared_width": ex.shared_width if ex else 0,
        "n_group": ex.n_groups if ex else 1,
        "rope_theta": float(t.rope_theta), "norm_eps": float(t.norm_eps),
    }


def wanted(config: Dict[str, Any]) -> Dict[str, Any]:
    types = [_MIXERS[t] for t in config["layer_types"]]
    dep, block = config["deployment"], config["block"]
    said = block.get("rotary_layers", "all")
    rotary = ["attention", "window"] if said == "all" else sorted(
        _MIXERS[t] for t in ([said] if isinstance(said, str) else said))
    held = int(config["num_experts"])
    return {
        **{k: int(config[k]) for k in (
            "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "sliding_window", "num_experts_per_tok",
            "moe_intermediate_size", "first_k_dense_replace")},
        "layer_types": types, "rotary": rotary,
        "qk_norm": bool(block.get("qk_norm")), "tied_head": False,
        "cache_kind": "window", "full_layers": types.count("attention"),
        "window_layers": types.count("window"), "latent": False,
        "experts_total": int(dep["experts_total"]), "experts_held": held,
        "experts_first": int(dep["expert_share"]) * held,
        "scoring": "sigmoid",
        "corrected_choice": bool(block.get("corrected_choice")),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "scale_renormed": bool(block.get("scale_renormed")),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "shared_width": int(config["num_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "n_group": 1,
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
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
    drawn: its own reading of the file fails (one that knows no
    ``sliding_attention`` layer refuses the name)."""
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
    cache, s, want = server.cache, config["serving"], wanted(config)
    # The two pools the file says: the full layers' by the tokens the slots
    # may hold, the window layers' a constant of blocks a slot.
    nb = -(-int(s["cache_len"]) // int(s["kv_block"]))
    bound = -(-(want["sliding_window"] + int(s["prefill_chunk"]))
              // int(s["kv_block"])) + 1
    row = (want["num_key_value_heads"], int(s["kv_block"]), want["head_dim"])
    full = (want["full_layers"], int(s["slots"]) * nb) + row
    win = (want["window_layers"], int(s["slots"]) * (bound + 1)) + row
    got = tuple(getattr(cache, n, None) for n in ("k", "v", "wk", "wv"))
    if any(a is None for a in got) or got[0].shape != full \
            or got[1].shape != full or got[2].shape != win \
            or got[3].shape != win \
            or cache.wtable.shape != cache.table.shape:
        raise SpecError(
            f"the pools are {[getattr(a, 'shape', None) for a in got]}; the "
            f"file says K and V {full} for the {full[0]} full layers and "
            f"{win} for the {win[0]} window layers ({bound} + 1 blocks a "
            f"slot), under two tables of one width")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes; None for a kernel this family never launches."""
    types = [_MIXERS[t] for t in config["layer_types"]]
    attn = {"heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head": int(config["head_dim"]), "dtype_bytes": 2}
    if kernel == "flash_decode_paged":
        return attn, types.count("attention")
    if kernel == "window_decode_paged":
        return (dict(attn, window=int(config["sliding_window"])),
                types.count("window"))
    if kernel == "moe_grouped_matmul":
        return ({"hidden": int(config["hidden_size"]),
                 "width": int(config["moe_intermediate_size"]),
                 "experts_held": int(config["num_experts"]),
                 "dtype_bytes": 2},
                int(config["num_hidden_layers"])
                - int(config["first_k_dense_replace"]))
    return None
