"""The ``deepseek_mla_moe`` family's way into the engine: the model handed
to ``cli.build_serve_engine`` as data (the configuration file itself) with the
reference's weights re-packed as the program's block takes them, the engine
that was built held against the configuration file, and what a kernel's cost
function wants of this configuration. No function of the program is swapped.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError

_ATTENTION = ("ln1", "ln2", "wqa", "q_ln", "wqb", "wkva", "kv_ln", "wo")
_DENSE = ("w1", "w3", "w2")
_EXPERTS = ("router", "we1", "we3", "we2", "ws1", "ws3", "ws2")


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's block takes them: the two
    stacks under ``dense`` and ``layers``, and the published ``wkvb``
    ``(kv_rank, heads x [k_nope | v])`` cut into the two per-head maps the
    absorbed order multiplies by (``wkb`` ``(heads, nope, kv_rank)``,
    ``wvb`` ``(heads, kv_rank, v_head)``)."""

    def stack(st, more):
        kvb = st["wkvb"].reshape(st["wkvb"].shape[0], w.kv_rank, w.heads,
                                 w.nope + w.v_head)
        return {**{n: st[n] for n in _ATTENTION + more},
                "wkb": kvb[..., :w.nope].transpose(0, 2, 3, 1),
                "wvb": kvb[..., w.nope:].transpose(0, 2, 1, 3)}

    return {"embed": weights["embed"], "ln_f": weights["ln_f"],
            "wout": weights["wout"],
            "dense": stack(weights["dense"], _DENSE),
            "layers": stack(weights["moe"], _EXPERTS)}


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    la, ex = t.mla, t.moe
    if la is None or ex is None:
        return {"block": "dense"}
    y = la.yarn
    return {
        "hidden_size": t.d_model, "intermediate_size": t.d_ff,
        "num_attention_heads": t.n_heads, "num_hidden_layers": t.n_layers,
        "vocab_size": t.vocab_size, "q_lora_rank": la.q_rank,
        "kv_lora_rank": la.kv_rank, "qk_nope_head_dim": la.nope,
        "qk_rope_head_dim": la.rope, "v_head_dim": la.v_head,
        "n_routed_experts": ex.held,
        "experts_total": ex.n_experts, "expert_first": ex.held_first,
        "num_experts_per_tok": ex.per_token, "n_group": ex.n_groups,
        "topk_group": ex.top_groups,
        "routed_scaling_factor": float(ex.scale),
        "norm_topk_prob": ex.renorm,
        "moe_intermediate_size": ex.width, "shared_width": ex.shared_width,
        "first_k_dense_replace": ex.first_dense,
        "rope_theta": float(t.rope_theta), "rms_norm_eps": float(t.norm_eps),
        "rope_scaling": None if y is None else {
            "factor": float(y.factor),
            "original_max_position_embeddings": y.original_len,
            "beta_fast": float(y.beta_fast), "beta_slow": float(y.beta_slow),
            "mscale": float(y.mscale),
            "mscale_all_dim": float(y.mscale_all_dim)},
    }


def wanted(config: Dict[str, Any]) -> Dict[str, Any]:
    dep = config["deployment"]
    rs = config["rope_scaling"]
    same = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_hidden_layers", "vocab_size", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "moe_intermediate_size",
            "first_k_dense_replace")
    return {
        **{k: config[k] for k in same},
        "experts_total": int(dep["experts_total"]),
        "expert_first": int(dep["expert_share"])
        * int(config["n_routed_experts"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "shared_width": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "rope_scaling": {
            k: (int(rs[k]) if k == "original_max_position_embeddings"
                else float(rs[k]))
            for k in ("factor", "original_max_position_embeddings",
                      "beta_fast", "beta_slow", "mscale", "mscale_all_dim")},
    }


def build(config: Dict[str, Any], serving_flags: List[str], seed: int,
          device: str, reference):
    """The engine of ``serving_flags`` (the harness's: slots, lengths,
    cache, seed, ``device`` among them) serving this configuration with the
    reference's weights of ``seed``. Returns ``(setup, server)``."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    del device                          # one chip: the flags place the model
    if not hasattr(cli, "load_model_config"):
        raise SpecError(
            "this program takes no model as data (no --model-config, no "
            "model= on cli.build_serve_engine): it cannot serve the "
            f"{config['family']} family")
    cfg = parse_args(serving_flags)
    w = reference.Widths.of(config)
    params = engine_params(reference.init_weights(seed, w), w)
    setup = cli.build_serve_engine(cfg, None, model=config, params=params)
    del params
    got, want = built_as(setup.tcfg), wanted(config)
    if got != want:
        diff = {k: (got.get(k), want.get(k))
                for k in sorted(set(got) | set(want))
                if got.get(k) != want.get(k)}
        raise SpecError(f"the engine was built otherwise than the "
                        f"configuration file says (built, file): {diff}")
    server = setup.make_engine()
    pool = server.cache.kv
    row = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    lanes = -(-row // 128) * 128
    if pool.ndim != 4 or pool.shape[0] != want["num_hidden_layers"] \
            or pool.shape[-1] != lanes:
        raise SpecError(f"the latent pool is {pool.shape}, the file says one "
                        f"row of {row} values on {lanes} lanes a token a "
                        f"layer")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes; None for a kernel this family never launches."""
    layers = int(config["num_hidden_layers"])
    if kernel == "mla_decode_paged":
        row = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
        return ({"heads": int(config["num_attention_heads"]),
                 "rank": int(config["kv_lora_rank"]), "row": row,
                 "dtype_bytes": 2}, layers)
    if kernel == "moe_grouped_matmul":
        return ({"hidden": int(config["hidden_size"]),
                 "width": int(config["moe_intermediate_size"]),
                 "experts_held": int(config["n_routed_experts"]),
                 "dtype_bytes": 2},
                layers - int(config["first_k_dense_replace"]))
    return None
