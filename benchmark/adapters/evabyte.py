"""The ``evabyte`` family's way into the engine: the model handed to
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

# The rules only the modelling code says (the file's ``block`` group): the
# one value of each that program and reference build.
RULES = {"summary_key": "weighted_plus_mu", "summary_logits_scaled": False,
         "summary_after_rotary": True, "window_rule": "aligned"}


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's loop takes them: the
    attention leaves with ``phi`` and ``mu`` under ``eva``, the SwiGLU under
    ``dense``, each on a leading axis of the layers. A norm's leaf is the
    published parameter ``g``; the program's holds the GAIN, so where the
    file says ``norm_add_unit_offset`` the one is added here, in float32
    (``TransformerConfig.norm_offset``)."""
    one = 1.0 if w.norm_offset else 0.0
    layers = weights["layers"]
    return {
        "embed": weights["embed"], "wout": weights["wout"],
        "ln_f": one + weights["ln_f"],
        "eva": {"ln1": one + layers["ln1"],
                **{n: layers[n] for n in
                   ("wq", "wk", "wv", "wo", "phi", "mu")}},
        "dense": {"ln2": one + layers["ln2"],
                  **{n: layers[n] for n in ("w1", "w3", "w2")}},
    }


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    return {
        "hidden_size": t.d_model, "intermediate_size": t.d_ff,
        "num_hidden_layers": t.n_layers,
        "layer_types": sorted(set(t.layer_types or ())),
        "eva_layers": t.eva_layers,
        "window_size": t.window, "chunk_size": t.chunk,
        "window_rule": t.window_rule,
        "num_attention_heads": t.n_heads, "num_key_value_heads": t.n_kv_heads,
        "head_dim": t.d_head, "vocab_size": t.vocab_size,
        "num_pred_heads": t.pred_heads,
        "norm_add_unit_offset": t.norm_offset,
        "rotary": sorted(t.rotary), "qk_norm": t.qk_norm,
        "tied_head": t.tied_head, "cache_kind": t.cache_kind,
        "latent": t.mla is not None, "experts": t.moe is not None,
        "rope_theta": float(t.rope_theta), "norm_eps": float(t.norm_eps),
    }


def wanted(config: Dict[str, Any]) -> Dict[str, Any]:
    block = config.get("block") or {}
    for key, built in RULES.items():
        if block.get(key) != built:
            raise SpecError(
                f"block.{key} {block.get(key)!r}: the file of this family "
                f"says {built!r}, the one reading built")
    heads = int(config["num_attention_heads"])
    return {
        **{k: int(config[k]) for k in (
            "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "window_size", "chunk_size", "num_pred_heads")},
        "head_dim": int(config.get("head_dim")
                        or int(config["hidden_size"]) // heads),
        "layer_types": ["eva"],
        "eva_layers": int(config["num_hidden_layers"]),
        "window_rule": block["window_rule"],
        "norm_add_unit_offset": bool(config["norm_add_unit_offset"]),
        "rotary": ["attention", "window"], "qk_norm": False,
        "tied_head": bool(config.get("tie_word_embeddings")),
        "cache_kind": "eva", "latent": False, "experts": False,
        "rope_theta": float(config["rope_theta"]),
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


def pools(config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """The two pools and two tables the file says (``serving``), by the
    cache's field names: the exact rows' pool a constant of blocks a slot
    (``ceil((window + prefill_chunk) / kv_block) + 1``, and one more), the
    summary rows' one block of ``kv_block`` rows for every ``kv_block x
    chunk`` positions of every slot."""
    s, want = config["serving"], wanted(config)
    block, slots = int(s["kv_block"]), int(s["slots"])
    nb = -(-int(s["cache_len"]) // block)
    nb_sum = -(-nb // want["chunk_size"])
    bound = -(-(want["window_size"] + int(s["prefill_chunk"])) // block) + 1
    row = (want["num_key_value_heads"], block, want["head_dim"])
    local = (want["eva_layers"], slots * (bound + 1)) + row
    summary = (want["eva_layers"], slots * nb_sum) + row
    return {"k": summary, "v": summary, "wk": local, "wv": local,
            "table": (slots, nb_sum), "wtable": (slots, nb)}


def build(config: Dict[str, Any], serving_flags: List[str], seed: int,
          device: str, reference):
    """The engine of ``serving_flags`` (the harness's: slots, lengths,
    cache, seed, ``device`` among them) serving this configuration with the
    reference's weights of ``seed``. Returns ``(setup, server)``. A program
    that cannot express the layers is refused at once, before a weight is
    drawn: one that knows no ``attention_class`` reads the file as a dense
    rotary model, and what it built is held to the file and fails."""
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
    want = pools(config)
    got = {n: getattr(getattr(server.cache, n, None), "shape", None)
           for n in want}
    if got != want:
        raise SpecError(
            f"the pools and tables are {got}; the file says {want}: the "
            f"summary rows' K and V under the first table, the exact rows' "
            f"under the second")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes; None for a kernel this family never launches."""
    if kernel not in ("eva_local_decode", "eva_summary_decode"):
        return None
    heads = int(config["num_attention_heads"])
    return ({"heads": heads,
             "kv_heads": int(config["num_key_value_heads"]),
             "head": int(config.get("head_dim")
                         or int(config["hidden_size"]) // heads),
             "dtype_bytes": 2, "window": int(config["window_size"]),
             "chunk": int(config["chunk_size"])},
            int(config["num_hidden_layers"]))
