"""The ``nemotron_h`` family's way into the engine: the model handed to
``cli.build_serve_engine`` as data (the configuration file itself) with the
reference's weights re-packed as the program's layer loop takes them, the
engine that was built held against the configuration file (its three pools
among the rest), and what a kernel's cost function wants of this
configuration. No function of the program is swapped.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.

The kernels' names. An ungated expert layer's two products run under
``moe_ungated_matmul`` (``ops/pallas_moe.py``): ``kernel_costs/
moe_grouped_matmul.py`` counts three matrices of ``hidden x width`` an
expert, a gated layer's, so no time or roofline metric of THAT name lists
this family's cell. ``kernel_call`` still answers for ``moe_grouped_matmul``,
for the one reader that asks the held count under that name
(``experts_touched_pct``: ``experts_held`` x calls a tick).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError

_MIXERS = {"M": "ssm", "*": "attention"}
_FFNS = {"E": "expert"}


def layers_of(pattern: str) -> Tuple[List[str], List[str]]:
    """The published pattern as the program's layers: a mixer part opens a
    layer, an ``E`` right after it is that layer's feed-forward half."""
    mixers: List[str] = []
    ffns: List[str] = []
    for ch in pattern:
        if ch in _MIXERS:
            mixers.append(_MIXERS[ch])
            ffns.append("none")
        elif ch in _FFNS and ffns and ffns[-1] == "none":
            ffns[-1] = _FFNS[ch]
        else:
            raise SpecError(f"hybrid_override_pattern {pattern!r}: {ch!r} "
                            f"is no part this family's cut is made of")
    return mixers, ffns


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's loop takes them: a stack a
    kind of part (``ssm``, ``attn``; the LatentMoE layers under
    ``layers``), each part's own pre-norm under the name the loop reads it
    by (``ln1`` a mixer's, ``ln2`` a feed-forward half's), the published
    taps ``(conv_dim, taps)`` turned to ``(taps, conv_dim)`` (a tap a row of
    lanes)."""
    del w
    out = {n: weights[n] for n in ("embed", "ln_f", "wout")}
    for name, to, ln in (("ssm", "ssm", "ln1"), ("attn", "attn", "ln1"),
                         ("moe", "layers", "ln2")):
        if name in weights:
            part = dict(weights[name])
            part[ln] = part.pop("ln")
            if name == "ssm":
                part["conv_w"] = part["conv_w"].transpose(0, 2, 1)
            out[to] = part
    return out


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    ex, sm = t.moe, t.ssm
    return {
        "hidden_size": t.d_model, "layers": t.n_layers,
        "layer_types": list(t.layer_types or ()),
        "ffn_types": list(t.ffn_kinds), "rotary": sorted(t.rotary),
        "num_attention_heads": t.n_heads, "num_key_value_heads": t.n_kv_heads,
        "head_dim": t.d_head, "vocab_size": t.vocab_size,
        "qk_norm": t.qk_norm, "tied_head": t.tied_head,
        "cache_kind": t.cache_kind, "attention_layers": t.cache_layers,
        "ssm_layers": t.ssm_layers, "latent_attention": t.mla is not None,
        "mamba_num_heads": sm.n_heads, "mamba_head_dim": sm.d_head,
        "n_groups": sm.n_groups, "ssm_state_size": sm.d_state,
        "conv_kernel": sm.taps, "chunk_size": sm.chunk,
        "experts_total": ex.n_experts, "experts_held": ex.held,
        "experts_first": ex.held_first,
        "num_experts_per_tok": ex.per_token,
        "moe_intermediate_size": ex.width, "moe_latent_size": ex.latent,
        "gated": ex.gated, "shared_width": ex.shared_width,
        "scoring": ex.scoring, "corrected_choice": ex.corrected,
        "norm_topk_prob": ex.renorm, "scale_renormed": ex.renorm_scaled,
        "routed_scaling_factor": float(ex.scale), "n_group": ex.n_groups,
        "zero_expert_num": ex.n_zero, "norm_eps": float(t.norm_eps),
    }


def wanted(config: Dict[str, Any]) -> Dict[str, Any]:
    mixers, ffns = layers_of(config["hybrid_override_pattern"])
    dep, block = config["deployment"], config["block"]
    held = int(config["n_routed_experts"])
    return {
        **{k: int(config[k]) for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "vocab_size", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
            "num_experts_per_tok", "moe_intermediate_size",
            "moe_latent_size")},
        "layers": len(mixers), "layer_types": mixers, "ffn_types": ffns,
        "rotary": [], "qk_norm": False, "tied_head": False,
        "cache_kind": "state", "attention_layers": mixers.count("attention"),
        "ssm_layers": mixers.count("ssm"), "latent_attention": False,
        "experts_total": int(dep["experts_total"]), "experts_held": held,
        "experts_first": int(dep["expert_share"]) * held,
        "gated": False,
        "shared_width": int(config["moe_shared_expert_intermediate_size"]),
        "scoring": "sigmoid",
        "corrected_choice": bool(block.get("corrected_choice")),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "scale_renormed": bool(block.get("scale_renormed")),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "n_group": 1, "zero_expert_num": 0,
        "norm_eps": float(config["layer_norm_epsilon"]),
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
    that cannot read the family is refused at once, before a weight is
    drawn: its own reading of the file fails (one that knows no
    ``moe_shared_expert_intermediate_size`` refuses the key by name; one
    that knows no ``hybrid_override_pattern`` builds another model, which
    ``_hold_to_file`` refuses)."""
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
    # The three pools the file says: the attention layers' K and V by the
    # tokens the slots may hold, and an array a slot for the state-space
    # layers: the state in float32 (heads laid `pack` a row of 128 lanes,
    # the program's own rule: StateSpace.pack) and the conv tail.
    slots = int(s["slots"])
    nb = -(-int(s["cache_len"]) // int(s["kv_block"]))
    kv = (want["attention_layers"], slots * nb, want["num_key_value_heads"],
          int(s["kv_block"]), want["head_dim"])
    H, P, N = (want["mamba_num_heads"], want["mamba_head_dim"],
               want["ssm_state_size"])
    conv_dim = H * P + 2 * want["n_groups"] * N
    # (a slot's conv rows side by side on the lanes: the program's rule)
    tail = (want["ssm_layers"], slots, (want["conv_kernel"] - 1) * conv_dim)
    got = tuple(getattr(cache, n, None)
                for n in ("k", "v", "ssm_state", "ssm_tail"))
    if any(a is None for a in got) or got[0].shape != kv \
            or got[1].shape != kv or got[3].shape != tail \
            or got[2].shape[:2] != tail[:2] \
            or got[2].size != tail[0] * slots * H * P * N \
            or str(got[2].dtype) != "float32":
        raise SpecError(
            f"the pools are {[(getattr(a, 'shape', None), str(getattr(a, 'dtype', None))) for a in got]}; "
            f"the file says K and V {kv} for the {kv[0]} attention layer(s), "
            f"a float32 state of {H} x {P} x {N} a slot for each of the "
            f"{tail[0]} state-space layers over {slots} slots, and their "
            f"conv tails {tail}")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes; None for a kernel this family never launches."""
    mixers, ffns = layers_of(config["hybrid_override_pattern"])
    n_moe = ffns.count("expert")
    held = int(config["n_routed_experts"])
    if kernel == "flash_decode_paged":
        return ({"heads": int(config["num_attention_heads"]),
                 "kv_heads": int(config["num_key_value_heads"]),
                 "head": int(config["head_dim"]), "dtype_bytes": 2},
                mixers.count("attention"))
    if kernel == "ssm_decode_update":
        return ({"heads": int(config["mamba_num_heads"]),
                 "head": int(config["mamba_head_dim"]),
                 "state": int(config["ssm_state_size"]),
                 "groups": int(config["n_groups"]), "state_bytes": 4},
                mixers.count("ssm"))
    if kernel == "moe_ungated_matmul":
        return ({"latent": int(config["moe_latent_size"]),
                 "width": int(config["moe_intermediate_size"]),
                 "experts_held": held, "dtype_bytes": 2}, n_moe)
    if kernel == "moe_grouped_matmul":
        # For the held count alone (the module's docstring): the products
        # run under the other name.
        return ({"hidden": int(config["moe_latent_size"]),
                 "width": int(config["moe_intermediate_size"]),
                 "experts_held": held, "dtype_bytes": 2}, n_moe)
    return None
