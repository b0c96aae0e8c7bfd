"""The ``falcon_h1`` family's way into the engine: the model handed to
``cli.build_serve_engine`` as data (the configuration file itself) with the
reference's weights re-packed as the program's layer loop takes them, the
engine that was built held against the configuration file (every width,
every multiplier, its four pools), and what a kernel's cost function wants of
this configuration. No function of the program is swapped.

An adapter may import the program; the harness finds it by the family's
name (``references/README.md``). It gives ``build`` and ``kernel_call``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

from benchmark.spec import SpecError

_MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")
_WIDTHS = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "mamba_n_heads",
    "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
    "mamba_chunk_size")


def engine_params(weights: Dict[str, Any], w) -> Dict[str, Any]:
    """The reference's leaves as the program's loop takes them: a stack a
    kind of part, every one ``layers`` deep (a layer is an attention layer,
    a state-space layer and a dense feed-forward half at once): ``attn``
    with the layer's ONE pre-norm (``ln1``), ``ssm`` with the published
    taps ``(conv_dim, taps)`` turned to ``(taps, conv_dim)`` (a tap a row of
    lanes), ``dense`` with the MLP's gate, up and down matrices under the
    loop's names and its pre-norm (``ln2``)."""
    del w
    lay = weights["layers"]
    return {
        **{n: weights[n] for n in ("embed", "ln_f", "wout")},
        "attn": {n: lay[n] for n in ("ln1", "wq", "wk", "wv", "wo")},
        "ssm": {**{n: lay[n] for n in ("w_in", "conv_b", "dt_bias", "A_log",
                                       "D", "norm", "w_out")},
                "conv_w": lay["conv_w"].transpose(0, 2, 1)},
        "dense": {"ln2": lay["ln2"], "w1": lay["wg"], "w3": lay["wu"],
                  "w2": lay["wd"]},
    }


def _floats(v):
    return [float(x) for x in v] if isinstance(v, (list, tuple)) \
        else float(v)


def built_as(t) -> Dict[str, Any]:
    """What the engine's model says of itself, in the file's keys."""
    sm, mup = t.ssm, t.mup
    return {
        "hidden_size": t.d_model, "intermediate_size": t.d_ff,
        "num_attention_heads": t.n_heads,
        "num_key_value_heads": t.n_kv_heads, "head_dim": t.d_head,
        "vocab_size": t.vocab_size, "mamba_n_heads": sm.n_heads,
        "mamba_d_head": sm.d_head, "mamba_n_groups": sm.n_groups,
        "mamba_d_state": sm.d_state, "mamba_d_conv": sm.taps,
        "mamba_chunk_size": sm.chunk, "mamba_d_ssm": sm.inner,
        "layers": t.n_layers, "layer_types": list(t.layer_types or ()),
        "ffn_types": list(t.ffn_kinds), "rotary": sorted(t.rotary),
        "rope_theta": float(t.rope_theta), "norm_eps": float(t.norm_eps),
        "qk_norm": t.qk_norm, "tied_head": t.tied_head,
        "cache_kind": t.cache_kind, "attention_layers": t.cache_layers,
        "ssm_layers": t.ssm_layers, "latent_attention": t.mla is not None,
        "experts": t.moe is not None,
        **{k: _floats(getattr(mup, k)) for k in _MULTIPLIERS},
    }


def wanted(config: Dict[str, Any]) -> Dict[str, Any]:
    n = int(config["num_hidden_layers"])
    return {
        **{k: int(config[k]) for k in _WIDTHS},
        "mamba_d_ssm": int(config["mamba_d_ssm"]),
        "layers": n, "layer_types": ["parallel"] * n,
        "ffn_types": ["dense"] * n, "rotary": ["attention", "window"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "qk_norm": False, "tied_head": False, "cache_kind": "state",
        "attention_layers": n, "ssm_layers": n, "latent_attention": False,
        "experts": False,
        **{k: _floats(config[k]) for k in _MULTIPLIERS},
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
    drawn: one that knows no ``model_type`` ``falcon_h1`` builds a dense
    model with no state-space widths and no multipliers, which
    ``_hold_to_file`` refuses."""
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
    # The four pools the file says: every layer's K and V by the tokens the
    # slots may hold, and an array a slot for every layer's state: the state
    # in float32 (heads side by side on a row of 128 lanes where they are
    # narrower, the program's own rule, StateSpace.pack: a head of 128 fills
    # a row, so at the published widths a state is (32, 256, 128)) and the
    # conv tail.
    slots, n = int(s["slots"]), want["layers"]
    nb = -(-int(s["cache_len"]) // int(s["kv_block"]))
    kv = (n, slots * nb, want["num_key_value_heads"], int(s["kv_block"]),
          want["head_dim"])
    H, P, N = (want["mamba_n_heads"], want["mamba_d_head"],
               want["mamba_d_state"])
    conv_dim = H * P + 2 * want["mamba_n_groups"] * N
    pack = math.gcd(128 // P, H // want["mamba_n_groups"]) \
        if 128 % P == 0 else 1
    state = (n, slots, H // pack, N, pack * P)
    tail = (n, slots, (want["mamba_d_conv"] - 1) * conv_dim)
    got = tuple(getattr(cache, name, None)
                for name in ("k", "v", "ssm_state", "ssm_tail"))
    if any(a is None for a in got) \
            or [a.shape for a in got] != [kv, kv, state, tail] \
            or str(got[2].dtype) != "float32":
        raise SpecError(
            f"the pools are {[(getattr(a, 'shape', None), str(getattr(a, 'dtype', None))) for a in got]}; "
            f"the file says K and V {kv} (every one of the {n} layers), a "
            f"float32 state {state} ({H} heads x {N} x {P} a slot a layer) "
            f"and the conv tails {tail}")
    return setup, server


def kernel_call(config: Dict[str, Any], kernel: str
                ) -> Optional[Tuple[Dict[str, Any], int]]:
    """The keyword arguments ``kernel_costs/<kernel>.py``'s ``cost`` wants
    for this configuration beside the tick's own, and how many calls a tick
    makes (every layer launches both kernels); None for a kernel this
    family never launches."""
    n = int(config["num_hidden_layers"])
    if kernel == "flash_decode_paged":
        return ({"heads": int(config["num_attention_heads"]),
                 "kv_heads": int(config["num_key_value_heads"]),
                 "head": int(config["head_dim"]), "dtype_bytes": 2}, n)
    if kernel == "ssm_decode_update":
        return ({"heads": int(config["mamba_n_heads"]),
                 "head": int(config["mamba_d_head"]),
                 "state": int(config["mamba_d_state"]),
                 "groups": int(config["mamba_n_groups"]),
                 "state_bytes": 4}, n)
    return None
