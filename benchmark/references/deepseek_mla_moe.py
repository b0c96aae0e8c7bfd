"""The plain reference of the ``deepseek_mla_moe`` family: latent (MLA)
attention in the EXPANDED, published order, a leading dense SwiGLU layer, then
layers of routed experts with shared experts, in float32 at the highest
matmul precision, with no kernels, no cache and no batching. What every
family's file gives is in ``README.md`` beside this file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
nothing of the harness.

Equations (DeepSeek-V2, https://huggingface.co/deepseek-ai/DeepSeek-V2):

- attention, every layer: ``c_q = rms(x W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_rope]``; ``[c_kv | k_rope] = x W_kva``; ``c_kv = rms(c_kv)``;
  ``k_rope = rope(k_rope)``, one for all heads; per head ``[k_nope | v] = c_kv
  W_kvb``; scores ``(q_nope·k_nope + rope(q_rope)·k_rope) · s``, causal
  softmax, ``o = concat(Σ p v) W_o``. Rotary is YaRN (per-frequency blend of
  the plain and the interpolated frequency by a linear ramp between the two
  correction dimensions), ``s = (nope + rope)^-1/2 · yarn_mscale(factor,
  mscale_all_dim)^2``, cos and sin scaled by ``yarn_mscale(factor, mscale) /
  yarn_mscale(factor, mscale_all_dim)``.
- layer 0 (``first_k_dense_replace``): dense SwiGLU. Later layers: ``scores =
  softmax(x W_g)`` over all routed experts; group-limited greedy (a group's
  score is its best expert's, keep the best ``topk_group`` groups, then the
  top ``num_experts_per_tok`` among their experts; ties to the lowest index);
  weights are those scores times ``routed_scaling_factor`` (not renormed);
  ``y = Σ w_i SwiGLU_i(x) + SwiGLU_shared(x)``.

Departures, each noted where it is made: (1) rotary pairs are taken as halves
``(i, i + d/2)``, not de-interleaved ``(2i, 2i+1)`` as the published code does:
with seeded weights a relabelling of the columns of ``W_qb`` and ``W_kva``.
(2) The chip's share: of the router's experts only ``[held_first, held_first
+ held)`` are held; the sum runs over the chosen experts that are held, what
the others would add is left out, and that partial sum goes on. (3) The
vocabulary is the rows held.

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands and the cached latent rows to int8 (symmetric, per row / per output
channel / per token), the precision below the bf16 the configuration states;
``"latent_int8"`` rounds the cached rows alone; ``"router_bf16"`` computes the
router's logits from bfloat16 operands.
"""

from __future__ import annotations

import dataclasses
import functools
import math
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
    first_dense: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_head: int
    ffn: int
    experts: int          # the router's width
    held: int
    held_first: int
    per_tok: int
    groups: int
    top_groups: int
    route_scale: float
    expert_ffn: int
    shared_ffn: int
    rope_theta: float
    yarn: Tuple[float, ...]   # factor, original length, beta_fast, beta_slow,
                              # mscale, mscale_all_dim
    norm_eps: float
    dtype: str

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        dep = config.get("deployment") or {}
        rs = config["rope_scaling"]
        held = int(config["n_routed_experts"])
        return cls(
            vocab=int(config["vocab_size"]),
            hidden=int(config["hidden_size"]),
            layers=int(config["num_hidden_layers"]),
            first_dense=int(config["first_k_dense_replace"]),
            heads=int(config["num_attention_heads"]),
            q_rank=int(config["q_lora_rank"]),
            kv_rank=int(config["kv_lora_rank"]),
            nope=int(config["qk_nope_head_dim"]),
            rope=int(config["qk_rope_head_dim"]),
            v_head=int(config["v_head_dim"]),
            ffn=int(config["intermediate_size"]),
            experts=int(dep.get("experts_total", held)),
            held=held,
            held_first=int(dep.get("expert_share", 0)) * held,
            per_tok=int(config["num_experts_per_tok"]),
            groups=int(config["n_group"]),
            top_groups=int(config["topk_group"]),
            route_scale=float(config["routed_scaling_factor"]),
            expert_ffn=int(config["moe_intermediate_size"]),
            shared_ffn=int(config["n_shared_experts"])
            * int(config["moe_intermediate_size"]),
            rope_theta=float(config["rope_theta"]),
            yarn=(float(rs["factor"]),
                  float(rs["original_max_position_embeddings"]),
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale"]), float(rs["mscale_all_dim"])),
            norm_eps=float(config["rms_norm_eps"]),
            dtype=str(config["torch_dtype"]),
        )


# -- weights -----------------------------------------------------------------


def _leaf(key, shape, stddev: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


def _attention_shapes(w: Widths, res_std: float):
    D, H = w.hidden, w.heads
    return {
        "wqa": ((D, w.q_rank), 0.02),
        "wqb": ((w.q_rank, H * (w.nope + w.rope)), 0.02),
        "wkva": ((D, w.kv_rank + w.rope), 0.02),
        "wkvb": ((w.kv_rank, H * (w.nope + w.v_head)), 0.02),
        "wo": ((H * w.v_head, D), res_std),
    }


def _norms(w: Widths) -> Dict[str, jax.Array]:
    return {"ln1": jnp.ones((w.hidden,), jnp.float32),
            "q_ln": jnp.ones((w.q_rank,), jnp.float32),
            "kv_ln": jnp.ones((w.kv_rank,), jnp.float32),
            "ln2": jnp.ones((w.hidden,), jnp.float32)}


@functools.partial(jax.jit, static_argnames=("w",))
def _init_weights(seed: jax.Array, w: Widths) -> Dict[str, Any]:
    dtype = jnp.dtype(w.dtype)
    k_embed, k_dense, k_moe, k_out = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    D = w.hidden
    res_std = 0.02 / (2 * w.layers) ** 0.5

    def leaves(key, shapes):
        ks = jax.random.split(key, len(shapes))
        return {n: _leaf(k, shape, sd, dtype)
                for k, (n, (shape, sd)) in zip(ks, shapes.items())}

    def dense_layer(key):
        return {**_norms(w), **leaves(key, {
            **_attention_shapes(w, res_std),
            "w1": ((D, w.ffn), 0.02), "w3": ((D, w.ffn), 0.02),
            "w2": ((w.ffn, D), res_std)})}

    def expert_layer(key):
        k_a, k_e = jax.random.split(key)

        def one_expert(k):
            return leaves(k, {"we1": ((D, w.expert_ffn), 0.02),
                              "we3": ((D, w.expert_ffn), 0.02),
                              "we2": ((w.expert_ffn, D), res_std)})

        # Expert by expert and layer by layer: the float32 draw of a stacked
        # tensor never exists whole, the peak is the weights themselves.
        return {**_norms(w), **leaves(k_a, {
            **_attention_shapes(w, res_std),
            "router": ((D, w.experts), 0.02),
            "ws1": ((D, w.shared_ffn), 0.02),
            "ws3": ((D, w.shared_ffn), 0.02),
            "ws2": ((w.shared_ffn, D), res_std)}),
            **lax.map(one_expert, jax.random.split(k_e, w.held))}

    return {
        "embed": _leaf(k_embed, (w.vocab, D), 0.02, dtype),
        "dense": lax.map(dense_layer,
                         jax.random.split(k_dense, w.first_dense)),
        "moe": lax.map(expert_layer,
                       jax.random.split(k_moe, w.layers - w.first_dense)),
        "ln_f": jnp.ones((D,), jnp.float32),
        "wout": _leaf(k_out, (D, w.vocab), 0.02, dtype),
    }


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: normal, std 0.02, the router included (its logits on a normed
    input of width 5,120 then have a standard deviation of ~1.4, so routing
    is not flat); the projections that write the residual stream (``wo``,
    ``w2``, ``we2``, ``ws2``) scaled by ``(2 * layers) ** -0.5``; norms at
    one. ``dense`` and ``moe`` are the two stacks, each on a leading layer
    axis, the experts of a layer on a second. Shapes are the published
    ones: ``wkvb`` is ``(kv_rank, heads * (nope + v_head))``, per head
    ``[k_nope | v]``."""
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


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(w: Widths) -> np.ndarray:
    """The ``rope // 2`` rotary frequencies, as the published
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    factor, orig, beta_fast, beta_slow, _, _ = w.yarn
    dim, base = w.rope, w.rope_theta
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def _rope(x: jax.Array, w: Widths) -> jax.Array:
    """``x`` is (T, ..., d); position t is row t. Pairs are halves
    (departure 1)."""
    factor, _, _, _, mscale, mscale_all = w.yarn
    amp = _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all)
    T, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(w))
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(w: Widths) -> float:
    return (w.nope + w.rope) ** -0.5 \
        * _yarn_mscale(w.yarn[0], w.yarn[5]) ** 2


def _attention(x, p, *, w: Widths, quant: Optional[str]):
    T, H = x.shape[0], w.heads
    h = _rms(x, p["ln1"], w.norm_eps)
    c_q = _rms(_mm(h, p["wqa"], quant), p["q_ln"], w.norm_eps)
    q = _mm(c_q, p["wqb"], quant).reshape(T, H, w.nope + w.rope)
    q = jnp.concatenate(
        [q[..., :w.nope], _rope(q[..., w.nope:], w)], axis=-1)
    kva = _mm(h, p["wkva"], quant)
    c_kv = _rms(kva[:, :w.kv_rank], p["kv_ln"], w.norm_eps)
    k_rope = _rope(kva[:, w.kv_rank:], w)
    if quant in ("int8", "latent_int8"):     # the cached row, per token
        row = _fake_int8(jnp.concatenate([c_kv, k_rope], -1), -1)
        c_kv, k_rope = row[:, :w.kv_rank], row[:, w.kv_rank:]
    kv = _mm(c_kv, p["wkvb"], quant).reshape(T, H, w.nope + w.v_head)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = softmax_scale(w)

    def one_head(args):
        qh, kvh = args               # (T, nope + rope), (T, nope + v_head)
        kh = jnp.concatenate([kvh[:, :w.nope], k_rope], axis=-1)
        s = jnp.einsum("td,sd->ts", qh, kh, precision=HIGHEST) * scale
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", pr, kvh[:, w.nope:],
                          precision=HIGHEST)

    o = lax.map(one_head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2).reshape(T, H * w.v_head)
    return x + _mm(o, p["wo"], quant)


def _swiglu(h, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


def route(scores: jax.Array, w: Widths) -> Tuple[jax.Array, jax.Array]:
    """Group-limited greedy over ``scores`` (T, experts): the chosen experts
    (T, per_tok) and their weights. ``lax.top_k`` puts the lower index
    first among equals."""
    T = scores.shape[0]
    per = w.experts // w.groups
    best = scores.reshape(T, w.groups, per).max(axis=-1)
    _, top_g = lax.top_k(best, w.top_groups)
    keep = jnp.zeros((T, w.groups), bool).at[
        jnp.arange(T)[:, None], top_g].set(True)
    masked = jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)
    weight, idx = lax.top_k(masked, w.per_tok)
    return idx, weight * w.route_scale


def router_scores(h, router, quant):
    if quant == "router_bf16":
        logits = jnp.matmul(h.astype(jnp.bfloat16), router.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
    else:
        logits = _mm(h, router, quant)
    return jax.nn.softmax(logits, axis=-1)


def expert_ffn(h, p, *, w: Widths, quant: Optional[str],
               held_first: Optional[int] = None, held: Optional[int] = None,
               shared: bool = True, layer=None):
    """The expert layer's output for normed rows ``h``: the held experts'
    part of the routed sum (departure 2), a plain loop over them, one
    expert's weights in float32 at a time, plus the shared experts. ``p``
    holds one layer's leaves; with ``layer`` its ``we*`` are the whole
    stack's and an expert is cut out of it by (layer, expert)."""
    held_first = w.held_first if held_first is None else held_first
    held = w.held if held is None else held
    idx, weight = route(router_scores(h, p["router"], quant), w)

    def of(name, e):
        return p[name][e] if layer is None else p[name][layer, e]

    def one_expert(y, e):
        # The weight of expert e for each row: its score where chosen, else 0.
        we = jnp.sum(jnp.where(idx == held_first + e, weight, 0.0), axis=-1)
        out = _swiglu(h, of("we1", e), of("we3", e), of("we2", e), quant)
        return y + we[:, None] * out, None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(held))
    if shared:
        y = y + _swiglu(h, p["ws1"], p["ws3"], p["ws2"], quant)
    return y


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _dense_layer(x, p, *, w: Widths, quant: Optional[str]):
    x = _attention(x, p, w=w, quant=quant)
    h = _rms(x, p["ln2"], w.norm_eps)
    return x + _swiglu(h, p["w1"], p["w3"], p["w2"], quant)


_EXPERT_LEAVES = ("we1", "we3", "we2")


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _expert_layer(x, stack, l, *, w: Widths, quant: Optional[str]):
    p = {n: (a if n in _EXPERT_LEAVES else a[l]) for n, a in stack.items()}
    x = _attention(x, p, w=w, quant=quant)
    return x + expert_ffn(_rms(x, p["ln2"], w.norm_eps), p, w=w, quant=quant,
                          layer=l)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, wout, *, w: Widths, quant: Optional[str]):
    return _mm(_rms(x, ln_f, w.norm_eps), wout, quant)


def _layer_of(stack: Dict[str, Any], l: int) -> Dict[str, Any]:
    return {n: a[l] for n, a in stack.items()}


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
    for l in range(w.first_dense):
        x = _dense_layer(x, _layer_of(weights["dense"], l), w=w, quant=quant)
    for l in range(w.layers - w.first_dense):
        x = _expert_layer(x, weights["moe"], jnp.int32(l), w=w, quant=quant)
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["wout"],
                w=w, quant=quant)
    return np.asarray(out)
