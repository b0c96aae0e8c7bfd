"""The plain reference of the ``longcat_scmoe`` family: a shortcut-connected
double layer (two latent-attention sublayers, two dense SwiGLU FFNs, and one
routed branch that leaves after the first attention and comes back after the
second FFN) whose router also scores zero-compute (identity) experts and takes
its top by bias-corrected scores. Latent (MLA) attention in the EXPANDED,
published order; float32 at the highest matmul precision; no kernels, no cache,
no batching. What every family's file gives is in ``README.md`` beside this
file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
nothing of the harness.

Equations (LongCat-Flash,
https://huggingface.co/meituan-longcat/LongCat-Flash-Omni), one layer ``i``,
residual ``x``, every norm an RMSNorm:

- attention, sublayer ``j``: ``c_q = s_q · rms(h W_qa)``, ``s_q = (hidden /
  q_rank)^1/2`` (``mla_scale_q_lora``); ``q = c_q W_qb`` -> heads of ``[q_nope |
  q_rope]``; ``[c_kv | k_rope] = h W_kva``; ``c_kv = s_kv · rms(c_kv)``, ``s_kv =
  (hidden / kv_rank)^1/2`` (``mla_scale_kv_lora``; ``k_rope`` is not scaled);
  ``k_rope = rope(k_rope)``, one for all heads; per head ``[k_nope | v] = c_kv
  W_kvb``; scores ``(q_nope·k_nope + rope(q_rope)·k_rope) · (nope + rope)^-1/2``,
  causal softmax, ``o = concat(Σ p v) W_o``. Rotary is plain (base
  ``rope_theta``, no scaling).
- the layer: ``a = x + Attn_0(rms(x))``; ``h = rms(a)``; ``m = MoE(h)``; ``b = a
  + FFN_0(h)``; ``c = b + Attn_1(rms(b))``; ``d = c + FFN_1(rms(c))``; ``out = d
  + m``.
- the routed branch: ``scores = softmax(h W_r)`` over the routed experts and,
  after them, ``zero_expert_num`` identity experts; the ``moe_topk`` are those
  with the largest ``scores + bias`` (ties to the lowest index), weighted by
  their own ``scores`` (not renormed) times ``routed_scaling_factor``; ``m = Σ
  w_e SwiGLU_e(h)`` over the chosen routed experts ``+ (Σ w_e) h`` over the
  chosen identity ones.

Departures, each noted where it is made: (1) rotary pairs are taken as halves
``(i, i + d/2)``, not de-interleaved: with seeded weights a relabelling of the
columns of ``W_qb`` and ``W_kva``. (2) The chip's share: of the routed experts
only ``[held_first, held_first + held)`` are held; the sum runs over the chosen
experts that are held, what the others would add is left out, and that partial
sum goes on. The identity experts have no weights and act where the row lives:
every share computes them for its own rows. (3) The vocabulary is the rows
held.

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands and the cached latent rows to int8 (symmetric, per row / per output
channel / per token), the precision below the bf16 the configuration states;
``"latent_int8"`` rounds the cached rows alone; ``"router_bf16"`` computes the
router's logits from bfloat16 operands.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
CONTROLS = ("int8", "latent_int8", "router_bf16")


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    hidden: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_head: int
    q_scale: float
    kv_scale: float
    ffn: int
    routed: int           # routed experts of the whole layer
    zero: int             # identity experts, ids routed .. routed + zero - 1
    held: int
    held_first: int
    per_tok: int
    route_scale: float
    expert_ffn: int
    rope_theta: float
    norm_eps: float
    dtype: str
    embed_std: float
    bias_std: float

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        dep = config.get("deployment") or {}
        seeded = config["assumed"]["seeded_scales"]
        if config.get("zero_expert_type", "identity") != "identity":
            raise ValueError("zero-compute experts other than identity")
        hidden, held = int(config["hidden_size"]), int(config["n_routed_experts"])
        q_rank, kv_rank = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
        return cls(
            vocab=int(config["vocab_size"]), hidden=hidden,
            layers=int(config["num_layers"]),
            heads=int(config["num_attention_heads"]),
            q_rank=q_rank, kv_rank=kv_rank,
            nope=int(config["qk_nope_head_dim"]),
            rope=int(config["qk_rope_head_dim"]),
            v_head=int(config["v_head_dim"]),
            q_scale=(hidden / q_rank) ** 0.5
            if config["mla_scale_q_lora"] else 1.0,
            kv_scale=(hidden / kv_rank) ** 0.5
            if config["mla_scale_kv_lora"] else 1.0,
            ffn=int(config["ffn_hidden_size"]),
            routed=int(dep.get("experts_total", held)),
            zero=int(config["zero_expert_num"]),
            held=held, held_first=int(dep.get("expert_share", 0)) * held,
            per_tok=int(config["moe_topk"]),
            route_scale=float(config["routed_scaling_factor"]),
            expert_ffn=int(config["expert_ffn_hidden_size"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            dtype=str(config["torch_dtype"]),
            embed_std=float(seeded["embedding_std"]),
            bias_std=float(seeded["router_bias_std"]),
        )


# -- weights -----------------------------------------------------------------


def _leaf(key, shape, stddev: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w",))
def _init_weights(seed: jax.Array, w: Widths) -> Dict[str, Any]:
    dtype = jnp.dtype(w.dtype)
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(seed), 3)
    D, H = w.hidden, w.heads
    res_std = 0.02 / (2 * w.layers) ** 0.5

    def leaves(key, shapes):
        ks = jax.random.split(key, len(shapes))
        return {n: _leaf(k, shape, sd, dtype)
                for k, (n, (shape, sd)) in zip(ks, shapes.items())}

    def sublayer(key):
        return {
            "ln1": jnp.ones((D,), jnp.float32),
            "q_ln": jnp.ones((w.q_rank,), jnp.float32),
            "kv_ln": jnp.ones((w.kv_rank,), jnp.float32),
            "ln2": jnp.ones((D,), jnp.float32),
            **leaves(key, {
                "wqa": ((D, w.q_rank), 0.02),
                "wqb": ((w.q_rank, H * (w.nope + w.rope)), 0.02),
                "wkva": ((D, w.kv_rank + w.rope), 0.02),
                "wkvb": ((w.kv_rank, H * (w.nope + w.v_head)), 0.02),
                "wo": ((H * w.v_head, D), res_std),
                "w1": ((D, w.ffn), 0.02), "w3": ((D, w.ffn), 0.02),
                "w2": ((w.ffn, D), res_std)})}

    def layer(key):
        k_s, k_r, k_b, k_e = jax.random.split(key, 4)

        def one_expert(k):
            return leaves(k, {"we1": ((D, w.expert_ffn), 0.02),
                              "we3": ((D, w.expert_ffn), 0.02),
                              "we2": ((w.expert_ffn, D), res_std)})

        # Layer by layer and expert by expert: the float32 draw of a
        # stacked tensor never exists whole.
        return {"sub": [sublayer(k) for k in jax.random.split(k_s, 2)],
                "router": _leaf(k_r, (D, w.routed + w.zero), 0.02, dtype),
                "router_bias": _leaf(k_b, (w.routed + w.zero,), w.bias_std,
                                     jnp.float32),
                **lax.map(one_expert, jax.random.split(k_e, w.held))}

    return {
        "embed": _leaf(k_embed, (w.vocab, D), w.embed_std, dtype),
        "layers": lax.map(layer, jax.random.split(k_layers, w.layers)),
        "ln_f": jnp.ones((D,), jnp.float32),
        "wout": _leaf(k_out, (D, w.vocab), 0.02, dtype),
    }


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call. Normal, std 0.02, the router included (its logits on a normed
    input of width 6,144 then have a standard deviation of ~1.6, so routing
    is not flat); the projections that write the residual stream (``wo``,
    ``w2``, ``we2``) scaled by ``(2 * layers) ** -0.5``; norms at one. Two
    scales are the configuration file's (``assumed.seeded_scales``): the
    embedding's (at one the residual stream has unit rms where the first
    identity expert adds ``6 w h`` of a unit-rms ``h`` to it) and the
    router's correction bias's (float32, never zero). ``layers`` is one
    stack on a leading layer axis: under ``sub`` the two sublayers'
    attention, norm and dense-FFN leaves, each sublayer's named apart (a
    list of two), the held experts of a layer on a second axis. Shapes are the published ones: ``wkvb`` is
    ``(kv_rank, heads * (nope + v_head))``, per head ``[k_nope | v]``."""
    return _init_weights(jnp.uint32(int(seed) % (2 ** 32)), w)


# -- the forward pass --------------------------------------------------------


def _fake_int8(x: jax.Array, axis: int) -> jax.Array:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _mm(x: jax.Array, wt: jax.Array, quant: Optional[str]) -> jax.Array:
    wt = wt.astype(jnp.float32)
    if quant == "int8":
        x, wt = _fake_int8(x, -1), _fake_int8(wt, 0)
    return jnp.matmul(x, wt, precision=HIGHEST)


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x: jax.Array, w: Widths) -> jax.Array:
    """``x`` is (T, ..., d); position t is row t. Pairs are halves
    (departure 1)."""
    T, half = x.shape[0], x.shape[-1] // 2
    freqs = w.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, p, *, w: Widths, quant: Optional[str] = None):
    """One sublayer's attention of the normed rows ``h`` ``(T, hidden)``,
    expanded: every token's per-head keys and values are made from its
    latent."""
    T, H = h.shape[0], w.heads
    c_q = w.q_scale * _rms(_mm(h, p["wqa"], quant), p["q_ln"], w.norm_eps)
    q = _mm(c_q, p["wqb"], quant).reshape(T, H, w.nope + w.rope)
    q = jnp.concatenate(
        [q[..., :w.nope], _rope(q[..., w.nope:], w)], axis=-1)
    kva = _mm(h, p["wkva"], quant)
    c_kv = w.kv_scale * _rms(kva[:, :w.kv_rank], p["kv_ln"], w.norm_eps)
    k_rope = _rope(kva[:, w.kv_rank:], w)
    if quant in ("int8", "latent_int8"):     # the cached row, per token
        row = _fake_int8(jnp.concatenate([c_kv, k_rope], -1), -1)
        c_kv, k_rope = row[:, :w.kv_rank], row[:, w.kv_rank:]
    kv = _mm(c_kv, p["wkvb"], quant).reshape(T, H, w.nope + w.v_head)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = (w.nope + w.rope) ** -0.5

    def one_head(args):
        qh, kvh = args               # (T, nope + rope), (T, nope + v_head)
        kh = jnp.concatenate([kvh[:, :w.nope], k_rope], axis=-1)
        s = jnp.einsum("td,sd->ts", qh, kh, precision=HIGHEST) * scale
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", pr, kvh[:, w.nope:],
                          precision=HIGHEST)

    o = lax.map(one_head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2).reshape(T, H * w.v_head)
    return _mm(o, p["wo"], quant)


def _swiglu(h, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


def route(scores: jax.Array, bias: jax.Array,
          w: Widths) -> Tuple[jax.Array, jax.Array]:
    """The ``per_tok`` experts with the largest ``scores + bias`` of
    ``scores`` (T, routed + zero), the lower index first among equals, and
    their weights: their own scores times the scale."""
    order = jnp.argsort(-(scores + bias), axis=-1, stable=True)
    idx = order[:, :w.per_tok]
    return idx, jnp.take_along_axis(scores, idx, axis=-1) * w.route_scale


def router_scores(h, router, quant):
    if quant == "router_bf16":
        logits = jnp.matmul(h.astype(jnp.bfloat16), router.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
    else:
        logits = _mm(h, router, quant)
    return jax.nn.softmax(logits, axis=-1)


def routed_branch(h, p, *, w: Widths, quant: Optional[str] = None,
                  held_first: Optional[int] = None,
                  held: Optional[int] = None, identity: bool = True,
                  layer=None):
    """The routed branch's output for normed rows ``h``: the held experts'
    part of the routed sum (departure 2), a plain loop over them, one
    expert's weights in float32 at a time, plus the rows' chosen identity
    experts (``identity`` False leaves them out: a share counted beside
    another's). ``p`` holds one layer's leaves; with ``layer`` its ``we*``
    are the whole stack's and an expert is cut out of it by (layer,
    expert)."""
    held_first = w.held_first if held_first is None else held_first
    held = w.held if held is None else held
    idx, weight = route(router_scores(h, p["router"], quant),
                        p["router_bias"], w)

    def of(name, e):
        return p[name][e] if layer is None else p[name][layer, e]

    def one_expert(y, e):
        # The weight of expert e for each row: its score where chosen, else 0.
        we = jnp.sum(jnp.where(idx == held_first + e, weight, 0.0), axis=-1)
        out = _swiglu(h, of("we1", e), of("we3", e), of("we2", e), quant)
        return y + we[:, None] * out, None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(held))
    if identity:
        y = y + jnp.sum(jnp.where(idx >= w.routed, weight, 0.0),
                        axis=-1)[:, None] * h
    return y


def layer_parts(x, p, *, w: Widths, quant: Optional[str] = None,
                layer=None) -> Dict[str, jax.Array]:
    """One double layer, every named intermediate of the equations above
    (``p["sub"]`` holds the two sublayers' leaves)."""
    s0, s1 = p["sub"]
    a = x + attention(_rms(x, s0["ln1"], w.norm_eps), s0, w=w, quant=quant)
    h = _rms(a, s0["ln2"], w.norm_eps)
    m = routed_branch(h, p, w=w, quant=quant, layer=layer)
    b = a + _swiglu(h, s0["w1"], s0["w3"], s0["w2"], quant)
    c = b + attention(_rms(b, s1["ln1"], w.norm_eps), s1, w=w, quant=quant)
    d = c + _swiglu(_rms(c, s1["ln2"], w.norm_eps),
                    s1["w1"], s1["w3"], s1["w2"], quant)
    return {"a": a, "h": h, "m": m, "b": b, "c": c, "d": d, "out": d + m}


_EXPERT_LEAVES = ("we1", "we3", "we2")


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _layer(x, stack, l, *, w: Widths, quant: Optional[str]):
    p = {n: (a if n in _EXPERT_LEAVES else
             jax.tree.map(lambda t: t[l], a)) for n, a in stack.items()}
    return layer_parts(x, p, w=w, quant=quant, layer=l)["out"]


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, wout, *, w: Widths, quant: Optional[str]):
    return _mm(_rms(x, ln_f, w.norm_eps), wout, quant)


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 512) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    so that few shapes compile; causal attention keeps the padding out of
    every row that is read, and a row's experts are its own."""
    T = len(tokens)
    padded = -(-T // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:T] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for l in range(w.layers):
        x = _layer(x, weights["layers"], jnp.int32(l), w=w, quant=quant)
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["wout"],
                w=w, quant=quant)
    return np.asarray(out)
