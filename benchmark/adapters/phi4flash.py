"""The ``phi4flash`` family's way into the engine: the model handed to
``cli.build_serve_engine`` as data (the configuration file itself) with the
reference's weights re-packed as the program's layer loop takes them, the
engine that was built held against the configuration file (every width, the
split of the depth, its six pools), and what a kernel's cost function wants
of this configuration. No function of the program is swapped.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError

_KINDS = {"mamba": "ssm1", "swa": "window", "full": "attention",
          "gmu": "gmu", "cross": "cross"}
_ASSUMED = {"mamba_expand": 2, "mamba_d_state": 16, "mamba_d_conv": 4}


def _norm(part: Dict[str, Any], name: str) -> Dict[str, Any]:
    return {name: part["ln_g"], name + "_b": part["ln_b"]}


def _attention(part: Dict[str, Any], w, own_kv: bool) -> Dict[str, Any]:
    """An attention stack's leaves under the loop's names: ``W_qkv`` cut
    into ``wq`` / ``wk`` / ``wv`` (the engine lays them out-major as one
    again, ``served_layout``; a cross layer holds ``wq`` alone)."""
    q, kv = w.heads * w.head, w.kv_heads * w.head
    out = {**_norm(part, "ln1"), "wq": part["w_qkv"][..., :q],
           "bqkv": part["b_qkv"], "wo": part["w_o"], "bo": part["b_o"],
           "lam": part["lam"], "sub_ln": part["sub_g"]}
    if own_kv:
        out.update(wk=part["w_qkv"][..., q:q + kv],
                   wv=part["w_qkv"][..., q + kv:])
    return out


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's loop takes them: a stack a
    kind of part (``ssm1``, ``wattn``, ``attn``, ``gmu``, ``xattn``,
    ``dense``), each part's LayerNorm under the name the loop reads it by
    (``ln1`` a mixer's, ``ln2`` the MLP's, gain and bias), the published
    taps ``(inner, taps)`` turned to ``(taps, inner)`` (a tap a row of
    lanes), ``A_log`` ``(inner, N)`` turned to the state pool's ``(N,
    inner)``, the MLP's one ``[gate | up]`` matrix cut into the loop's
    ``w1`` and ``w3``."""
    m, mlp = weights["mamba"], weights["mlp"]
    return {
        "embed": weights["embed"], **_norm(weights["ln_f"], "ln_f"),
        "ssm1": {**_norm(m, "ln1"),
                 **{n: m[n] for n in ("w_in", "conv_b", "w_x", "w_dt",
                                      "dt_bias", "D", "w_out")},
                 "conv_w": m["conv_w"].transpose(0, 2, 1),
                 "A_log": m["A_log"].transpose(0, 2, 1)},
        "wattn": _attention(weights["swa"], w, True),
        "attn": _attention(weights["full"], w, True),
        "xattn": _attention(weights["cross"], w, False),
        "gmu": {**_norm(weights["gmu"], "ln1"),
                "w_in": weights["gmu"]["w_in"],
                "w_out": weights["gmu"]["w_out"]},
        "dense": {**_norm(mlp, "ln2"), "w1": mlp["w1"][..., :w.ffn],
                  "w3": mlp["w1"][..., w.ffn:], "w2": mlp["w2"]},
    }


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    sm = t.ssm1
    return {
        "hidden_size": t.d_model, "intermediate_size": t.d_ff,
        "num_attention_heads": t.n_heads,
        "num_key_value_heads": t.n_kv_heads, "head_dim": t.d_head,
        "vocab_size": t.vocab_size, "sliding_window": t.window,
        "mamba_d_inner": sm.inner, "mamba_d_state": sm.d_state,
        "mamba_d_conv": sm.taps, "mamba_dt_rank": sm.dt_rank,
        "layers": t.n_layers, "layer_types": list(t.layer_types or ()),
        "ffn_types": list(t.ffn_kinds), "rotary": sorted(t.rotary),
        "norm": t.norm, "norm_eps": float(t.norm_eps),
        "diff_attn": t.diff_attn, "attn_bias": t.attn_bias,
        "qk_norm": t.qk_norm, "tied_head": t.tied_head,
        "cache_kind": t.cache_kind, "shared_layers": t.cache_layers,
        "window_layers": t.window_layers, "ssm_layers": t.ssm_layers,
        "row_cut": t.row_cut, "kv_pack": t.kv_pack,
        "latent_attention": t.mla is not None, "experts": t.moe is not None,
    }


def wanted(config: Dict[str, Any], reference) -> Dict[str, Any]:
    w = reference.Widths.of(config)
    kinds = [_KINDS[w.kind(l)] for l in range(w.layers)]
    return {
        "hidden_size": w.hidden, "intermediate_size": w.ffn,
        "num_attention_heads": w.heads, "num_key_value_heads": w.kv_heads,
        "head_dim": w.head, "vocab_size": w.vocab,
        "sliding_window": w.window, "mamba_d_inner": w.inner,
        "mamba_d_state": w.state, "mamba_d_conv": w.taps,
        "mamba_dt_rank": w.dt_rank, "layers": w.layers,
        "layer_types": kinds, "ffn_types": ["dense"] * w.layers,
        "rotary": [], "norm": "layer", "norm_eps": w.norm_eps,
        "diff_attn": True, "attn_bias": True, "qk_norm": False,
        "tied_head": True, "cache_kind": "state_window", "shared_layers": 1,
        "window_layers": kinds.count("window"),
        "ssm_layers": kinds.count("ssm1"),
        "row_cut": kinds.index("attention") + 1, "kv_pack": 2,
        "latent_attention": False, "experts": False,
    }


def _hold_to_file(model, config: Dict[str, Any], reference) -> None:
    """SpecError unless ``model`` (a ``TransformerConfig``) is the model
    the configuration file describes."""
    try:
        got = built_as(model)
    except AttributeError as e:         # a model without the layers' fields
        raise SpecError(f"this program's model cannot express the "
                        f"{config['family']} family's layers: {e}") from None
    want = wanted(config, reference)
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
    drawn: one that knows no ``model_type`` ``phi4flash`` builds a dense
    rotary model with no Mamba-1 widths, which ``_hold_to_file`` refuses."""
    del device                          # one chip: the flags place the model
    try:
        from tree_attention_tpu import cli
        from tree_attention_tpu.models.transformer import model_from_config
        from tree_attention_tpu.utils.config import parse_args

        model = model_from_config(config)
    except (ImportError, KeyError, TypeError, ValueError) as e:
        raise SpecError(f"this program cannot read the {config['family']} "
                        f"family's model as data: {e!r}") from None
    _hold_to_file(model, config, reference)
    cfg = parse_args(serving_flags)
    w = reference.Widths.of(config)
    params = engine_params(reference.init_weights(seed, w), w)
    setup = cli.build_serve_engine(cfg, None, model=config, params=params)
    del params
    _hold_to_file(setup.tcfg, config, reference)
    server = setup.make_engine()
    cache, s = server.cache, config["serving"]
    # The six pools the file says: the ONE shared layer's K and V by the
    # tokens the slots may hold, the window layers' by the window's bounded
    # blocks, every pool a PAIR of heads a row; and an array a slot for
    # every Mamba-1 layer: the state in float32, (d_state, inner), and the
    # conv tail.
    slots, blk = int(s["slots"]), int(s["kv_block"])
    nb = -(-int(s["cache_len"]) // blk)
    row = (w.kv_heads // 2, blk, 2 * w.head)
    per_slot = -(-(w.window + int(s["prefill_chunk"])) // blk) + 1
    n_ssm, n_win = w.count("mamba"), w.count("swa")
    want = {"k": (1, slots * nb) + row, "v": (1, slots * nb) + row,
            "ssm_state": (n_ssm, slots, w.state, w.inner),
            "ssm_tail": (n_ssm, slots, (w.taps - 1) * w.inner)}
    got = {n: getattr(cache, n, None)
           for n in ("k", "v", "wk", "wv", "ssm_state", "ssm_tail")}
    shapes = {n: getattr(a, "shape", None) for n, a in got.items()}
    wk = shapes["wk"]
    if any(a is None for a in got.values()) \
            or any(shapes[n] != s_ for n, s_ in want.items()) \
            or shapes["wv"] != wk or wk[0] != n_win or wk[2:] != row \
            or not slots * per_slot <= wk[1] <= slots * (per_slot + 2) \
            or str(got["ssm_state"].dtype) != "float32":
        raise SpecError(
            f"the pools are {shapes} (state "
            f"{getattr(got['ssm_state'], 'dtype', None)}); the file says "
            f"{want}, the {n_win} window layers' K and V of {per_slot} to "
            f"{per_slot + 2} blocks a slot of rows {row}, and a float32 "
            f"state")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes; None for a kernel this family never launches. The paged kernels
    run at PAIRS of KV heads: 10 rows of 128 lanes under 40 query heads.
    ``flash_decode_paged``: the shared layer and the seven cross layers,
    each streaming a live slot's rows once; ``window_decode_paged``: the
    eight window layers; ``ssm1_scan``: the nine Mamba-1 layers."""
    n = int(config["num_hidden_layers"])
    heads = int(config["num_attention_heads"])
    paged = {"heads": heads,
             "kv_heads": int(config["num_key_value_heads"]) // 2,
             "head": 2 * int(config["hidden_size"]) // heads,
             "dtype_bytes": 2}
    a = {**_ASSUMED, **(config.get("assumed") or {})}
    if kernel == "flash_decode_paged":
        return paged, 1 + (n // 2 - 2) // 2
    if kernel == "window_decode_paged":
        return {**paged, "window": int(config["sliding_window"])}, n // 4
    if kernel == "ssm1_scan":
        return ({"channels": int(a["mamba_expand"])
                 * int(config["hidden_size"]),
                 "state": int(a["mamba_d_state"]), "state_bytes": 4},
                n // 4 + 1)
    return None
