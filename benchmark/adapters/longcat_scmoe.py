"""The ``longcat_scmoe`` family's way into the engine: the model handed to
``cli.build_serve_engine`` as data (the configuration file itself) with the
reference's weights re-packed as the program's block takes them, the engine
that was built held against the configuration file, and what a kernel's cost
function wants of this configuration. No function of the program is swapped.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError

_SAME = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
         "num_layers", "num_attention_heads", "vocab_size", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "n_routed_experts", "zero_expert_num", "moe_topk")


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's block takes them: one stack
    of layers, each sublayer's leaves named apart under ``sub``, and the
    published ``wkvb`` ``(kv_rank, heads x [k_nope | v])`` cut into the two
    per-head maps the absorbed order multiplies by (``wkb`` ``(heads, nope,
    kv_rank)``, ``wvb`` ``(heads, kv_rank, v_head)``)."""

    def sublayer(st):
        st = dict(st)
        wkvb = st.pop("wkvb")                  # (L, kv_rank, H * (nope + v))
        kvb = wkvb.reshape(wkvb.shape[0], w.kv_rank, w.heads,
                           w.nope + w.v_head)
        return {**st, "wkb": kvb[..., :w.nope].transpose(0, 2, 3, 1),
                "wvb": kvb[..., w.nope:].transpose(0, 2, 1, 3)}

    layers = dict(weights["layers"],
                  sub=[sublayer(st) for st in weights["layers"]["sub"]])
    return {"embed": weights["embed"], "ln_f": weights["ln_f"],
            "wout": weights["wout"], "layers": layers}


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    la, ex = t.mla, t.moe
    if la is None or ex is None:
        return {"block": "dense"}
    return {
        "hidden_size": t.d_model, "ffn_hidden_size": t.d_ff,
        "expert_ffn_hidden_size": ex.width, "num_layers": t.n_layers,
        "num_attention_heads": t.n_heads, "vocab_size": t.vocab_size,
        "q_lora_rank": la.q_rank, "kv_lora_rank": la.kv_rank,
        "qk_nope_head_dim": la.nope, "qk_rope_head_dim": la.rope,
        "v_head_dim": la.v_head,
        "q_scale": round(float(la.q_scale), 6),
        "kv_scale": round(float(la.kv_scale), 6),
        "rope_scaling": la.yarn,
        "n_routed_experts": ex.held, "experts_total": ex.n_routed,
        "expert_first": ex.held_first, "zero_expert_num": ex.n_zero,
        "moe_topk": ex.per_token,
        "routed_scaling_factor": float(ex.scale),
        "norm_topk_prob": ex.renorm, "corrected_choice": ex.corrected,
        "shared_width": ex.shared_width, "n_group": ex.n_groups,
        "first_dense": ex.first_dense,
        "sublayers": t.sublayers, "routed_branch": list(ex.branch or ()),
        "rope_theta": float(t.rope_theta), "rms_norm_eps": float(t.norm_eps),
    }


def wanted(config: Dict[str, Any]) -> Dict[str, Any]:
    dep, block = config["deployment"], config["block"]
    hidden = int(config["hidden_size"])
    return {
        **{k: int(config[k]) for k in _SAME},
        "q_scale": round((hidden / int(config["q_lora_rank"])) ** 0.5
                         if config["mla_scale_q_lora"] else 1.0, 6),
        "kv_scale": round((hidden / int(config["kv_lora_rank"])) ** 0.5
                          if config["mla_scale_kv_lora"] else 1.0, 6),
        "rope_scaling": None,
        "experts_total": int(dep["experts_total"]),
        "expert_first": int(dep["expert_share"])
        * int(config["n_routed_experts"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "norm_topk_prob": bool(config["assumed"]["norm_topk_prob"]),
        "corrected_choice": bool(block["corrected_choice"]),
        "shared_width": 0, "n_group": 1, "first_dense": 0,
        "sublayers": int(block["sublayers"]),
        "routed_branch": [int(j) for j in block["routed_branch"]],
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
    }


def _hold_to_file(model, config: Dict[str, Any]) -> None:
    """SpecError unless ``model`` (a ``TransformerConfig``) is the block
    the configuration file describes."""
    try:
        got = built_as(model)
    except AttributeError as e:         # a model without the block's fields
        raise SpecError(f"this program's model cannot express the "
                        f"{config['family']} family's block: {e}") from None
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
    that cannot express the block is refused at once, before a weight is
    drawn: its own reading of the file fails or comes out otherwise."""
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
    pool = server.cache.kv
    row = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    lanes = -(-row // 128) * 128
    depth = int(config["num_layers"]) * int(config["block"]["sublayers"])
    s = config["serving"]
    if pool.ndim != 4 or pool.shape[0] != depth \
            or pool.shape[2:] != (int(s["kv_block"]), lanes):
        raise SpecError(f"the latent pool is {pool.shape}, the file says "
                        f"{depth} layers of blocks of {s['kv_block']} rows "
                        f"of {row} values on {lanes} lanes")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes; None for a kernel this family never launches."""
    layers = int(config["num_layers"])
    if kernel == "mla_decode_paged":
        row = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
        return ({"heads": int(config["num_attention_heads"]),
                 "rank": int(config["kv_lora_rank"]), "row": row,
                 "dtype_bytes": 2},
                layers * int(config["block"]["sublayers"]))
    if kernel == "moe_grouped_matmul":
        return ({"hidden": int(config["hidden_size"]),
                 "width": int(config["expert_ffn_hidden_size"]),
                 "experts_held": int(config["n_routed_experts"]),
                 "dtype_bytes": 2}, layers)
    return None
