"""The plain reference of the ``lfm2_moe`` family: a decoder whose layers are
of two kinds of mixer (a gated short convolution, or rotary grouped-query
attention with an RMSNorm over each query and key head) over two kinds of
feed-forward half (a dense SwiGLU in the leading layers, then routed experts
under a sigmoid router with a per-expert correction of the choice), the head
tied to the embedding. The full forward pass over one sequence: the
convolution a plain shifted sum, attention over the whole sequence, every
expert a loop; float32 at the highest matmul precision; no kernels, no cache,
no batching. What every family's file gives is in ``README.md`` beside this
file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
nothing of the harness.

Equations (LFM2-8B-A1B, https://huggingface.co/LiquidAI/LFM2-8B-A1B,
``model_type`` ``lfm2_moe``), one layer ``l``, residual ``x``, every norm an
RMSNorm with a learned gain and ``norm_eps``:

- mixer, ``h = rms(x; operator_norm)``, by ``layer_types[l]``:
  - ``conv`` (``conv_L_cache`` 3 taps, no bias): ``[b | c | u] = h W_in``
    (hidden -> 3 x hidden, split in that order); ``z = b * u``; ``s_t =
    sum_k w_k * z_{t-2+k}``, k = 0..2, a depthwise causal convolution, ``z``
    before the sequence's start zero; ``y = (c * s) W_out``. No activation.
  - ``full_attention``: ``q = h W_q`` -> heads of ``head``, ``k = h W_k``,
    ``v = h W_v`` -> KV heads (query head ``i`` reads KV head ``i // (heads /
    kv_heads)``); ``q = rms_head(q; q_layernorm)``, ``k = rms_head(k;
    k_layernorm)`` (over each head's values, one gain of ``head`` a layer)
    before the rotary embedding (base ``rope_theta``, every dimension, no
    scaling); causal softmax at ``head^-1/2``; ``y = concat(heads) W_o``.
  - ``x = x + y``.
- feed-forward, ``g = rms(x; ffn_norm)``: layers below ``num_dense_layers``
  ``x += W_2(silu(W_1 g) * W_3 g)``; the others ``s = sigmoid(g W_r)`` over
  ``num_experts``; the ``num_experts_per_tok`` chosen are those with the
  largest ``s + bias`` (``use_expert_bias``; ties to the lowest index), their
  weights the uncorrected ``s`` of the chosen divided by their sum
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``x += sum_e w_e
  SwiGLU_e(g)``. No shared expert, no groups.
- ends: logits ``= rms(x; embedding_norm) E^T``, ``E`` the embedding.

Departures, each noted where it is made: (1) rotary pairs are taken as halves
``(i, i + head/2)``, not de-interleaved: with seeded weights a relabelling of
the columns of ``W_q`` and ``W_k`` (and of the gains' entries). (2) The sum
of the chosen scores is divided with the published ``1e-6`` added to it; the
program adds ``1e-20``. Four sigmoid scores that were chosen sum to 2-3.5, so
the two differ by 3-5 parts in 10^7 of a weight: under float32's own rounding
of the sum, and four orders under the tolerances the tests hold. (3) The
head is tied (``tie_word_embeddings``, which the source's ``config.json`` does
not state; the count of parameters bears it out). (4) Depth is the layers the
file keeps (its first ``num_hidden_layers`` of ``layer_types``).

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands, the cached keys and values and the cached ``z`` rows to int8
(symmetric, per row / per output channel / per token), the precision below
the bf16 the configuration states; ``"state_int8"`` rounds the cached rows
alone (K, V and ``z``); ``"router_bf16"`` computes the router's logits from
bfloat16 operands.
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
CONTROLS = ("int8", "state_int8", "router_bf16")
_MIXERS = {"conv": "conv", "full_attention": "attention"}


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    hidden: int
    layer_types: Tuple[str, ...]      # "conv" | "attention", one a layer
    heads: int
    kv_heads: int
    head: int
    taps: int
    ffn: int
    n_dense: int
    experts: int
    per_tok: int
    expert_ffn: int
    route_scale: float
    renorm: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    scales: Tuple[Tuple[str, float], ...]   # assumed.seeded_scales, sorted

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    def scale(self, name: str) -> float:
        return dict(self.scales)[name]

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        heads = int(config["num_attention_heads"])
        hidden = int(config["hidden_size"])
        layers = int(config["num_hidden_layers"])
        types = tuple(_MIXERS[t] for t in config["layer_types"])
        if len(types) != layers:
            raise ValueError(f"{len(types)} layer_types for {layers} layers")
        if config.get("conv_bias") or not config.get("use_expert_bias"):
            raise ValueError("conv_bias / no use_expert_bias: not this family")
        return cls(
            vocab=int(config["vocab_size"]), hidden=hidden,
            layer_types=types, heads=heads,
            kv_heads=int(config["num_key_value_heads"]),
            head=int(config.get("head_dim") or hidden // heads),
            taps=int(config["conv_L_cache"]),
            ffn=int(config["intermediate_size"]),
            n_dense=int(config["num_dense_layers"]),
            experts=int(config["num_experts"]),
            per_tok=int(config["num_experts_per_tok"]),
            expert_ffn=int(config["moe_intermediate_size"]),
            route_scale=float(config["routed_scaling_factor"]),
            renorm=bool(config["norm_topk_prob"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["norm_eps"]),
            dtype=str(config["torch_dtype"]),
            scales=tuple(sorted(
                (k, float(v))
                for k, v in config["assumed"]["seeded_scales"].items())),
        )


# -- weights -----------------------------------------------------------------


def _leaf(key, shape, stddev: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w",))
def _init_weights(seed: jax.Array, w: Widths) -> Dict[str, Any]:
    dtype = jnp.dtype(w.dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    D = w.hidden
    ones = lambda n: jnp.ones((n,), jnp.float32)   # noqa: E731

    def leaves(key, shapes):
        kk = jax.random.split(key, len(shapes))
        return {n: _leaf(k, shape, sd, dtype)
                for k, (n, (shape, sd)) in zip(kk, shapes.items())}

    def conv(key):
        return {"ln1": ones(D), **leaves(key, {
            "w_in": ((D, 3 * D), 0.02),
            "w_conv": ((D, w.taps), w.taps ** -0.5),
            "w_out": ((D, D), w.scale("conv_out_std"))})}

    def attn(key):
        k_w, k_q, k_k = jax.random.split(key, 3)
        gain = lambda k: w.scale("qk_gain_mean") + w.scale(   # noqa: E731
            "qk_gain_std") * jax.random.normal(k, (w.head,), jnp.float32)
        return {"ln1": ones(D), "q_ln": gain(k_q), "k_ln": gain(k_k),
                **leaves(k_w, {
                    "wq": ((D, w.heads * w.head), 0.02),
                    "wk": ((D, w.kv_heads * w.head), 0.02),
                    "wv": ((D, w.kv_heads * w.head), 0.02),
                    "wo": ((w.heads * w.head, D), w.scale("attn_out_std"))})}

    def dense(key):
        return {"ln2": ones(D), **leaves(key, {
            "w1": ((D, w.ffn), 0.02), "w3": ((D, w.ffn), 0.02),
            "w2": ((w.ffn, D), w.scale("dense_down_std"))})}

    def moe(key):
        k_r, k_b, k_e = jax.random.split(key, 3)

        def one_expert(k):
            return leaves(k, {
                "we1": ((D, w.expert_ffn), 0.02),
                "we3": ((D, w.expert_ffn), 0.02),
                "we2": ((w.expert_ffn, D), w.scale("expert_down_std"))})

        # Layer by layer and expert by expert: the float32 draw of a
        # stacked tensor never exists whole.
        return {"ln2": ones(D),
                "router": _leaf(k_r, (D, w.experts), 0.02, dtype),
                "router_bias": _leaf(k_b, (w.experts,),
                                     w.scale("router_bias_std"), jnp.float32),
                **lax.map(one_expert, jax.random.split(k_e, w.experts))}

    n_conv = w.layer_types.count("conv")
    out = {"embed": _leaf(ks[0], (w.vocab, D), w.scale("embedding_std"),
                          dtype),
           "ln_f": ones(D)}
    for name, make, n, k in (("conv", conv, n_conv, ks[1]),
                             ("attn", attn, w.layers - n_conv, ks[2]),
                             ("dense", dense, min(w.n_dense, w.layers), ks[3]),
                             ("moe", moe, max(w.layers - w.n_dense, 0),
                              ks[4])):
        if n:
            out[name] = lax.map(make, jax.random.split(k, n))
    return out


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: a stack a kind of part (``conv``, ``attn``: the mixers, on a
    leading axis of that kind's layers in depth order; ``dense``, ``moe``:
    the feed-forward halves, likewise), ``embed`` and ``ln_f``; no ``wout``
    (departure 3). Normal; every projection INTO a part at std 0.02 (the
    router too: its logits on a normed input of width 2,048 then have a
    standard deviation of ~0.9 and the sigmoid scores spread over 0.2-0.8);
    the taps at ``taps^-1/2`` (the convolution keeps ``z``'s scale); the
    norms' gains at one. The scales that decide how much a part adds to the
    residual it joins are the configuration file's
    (``assumed.seeded_scales``, with the reckoning that chose them): the
    embedding's, each kind of part's projection back onto the residual, the
    QK-norm gains' mean and spread (never one: a gain that were dropped
    would show) and the router's correction bias's (float32). Shapes are
    the published ones: ``w_conv`` is ``(hidden, taps)``, tap ``k`` on
    ``z_{t-2+k}``."""
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


def _cached(rows: jax.Array, quant: Optional[str]) -> jax.Array:
    """A row as the cache would hand it back: per token in int8 under the
    controls that round the cache."""
    return _fake_int8(rows, -1) if quant in ("int8", "state_int8") else rows


def _rope(x: jax.Array, w: Widths) -> jax.Array:
    """``x`` is (T, heads, d); position t is row t. Pairs are halves
    (departure 1)."""
    T, half = x.shape[0], x.shape[-1] // 2
    freqs = w.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def conv_mixer(h, p, *, w: Widths, quant: Optional[str] = None):
    """The gated short convolution of the normed rows ``h`` ``(T, hidden)``:
    the convolution as a plain sum of shifted copies of ``z``."""
    D, T = w.hidden, h.shape[0]
    bcu = _mm(h, p["w_in"], quant)
    b, c, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
    z = _cached(b * u, quant)
    taps = p["w_conv"].astype(jnp.float32)                # (hidden, taps)
    zpad = jnp.concatenate([jnp.zeros((w.taps - 1, D), z.dtype), z])
    s = sum(taps[:, k] * zpad[k:k + T] for k in range(w.taps))
    return _mm(c * s, p["w_out"], quant)


def attention(h, p, *, w: Widths, quant: Optional[str] = None):
    """Rotary grouped-query attention of the normed rows ``h``, the norm
    over each query and key head before the rotation, over the whole
    sequence."""
    T, H, G = h.shape[0], w.heads, w.heads // w.kv_heads
    q = _mm(h, p["wq"], quant).reshape(T, H, w.head)
    k = _mm(h, p["wk"], quant).reshape(T, w.kv_heads, w.head)
    v = _mm(h, p["wv"], quant).reshape(T, w.kv_heads, w.head)
    q = _rope(_rms(q, p["q_ln"], w.norm_eps), w)
    k = _cached(_rope(_rms(k, p["k_ln"], w.norm_eps), w), quant)
    v = _cached(v, quant)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def one_head(args):
        qh, i = args
        s = jnp.einsum("td,sd->ts", qh, k[:, i // G],
                       precision=HIGHEST) * w.head ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", pr, v[:, i // G], precision=HIGHEST)

    o = lax.map(one_head, (q.transpose(1, 0, 2), jnp.arange(H)))
    return _mm(o.transpose(1, 0, 2).reshape(T, H * w.head), p["wo"], quant)


def _swiglu(h, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


def router_scores(h, router, quant):
    if quant == "router_bf16":
        logits = jnp.matmul(h.astype(jnp.bfloat16),
                            router.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
    else:
        logits = _mm(h, router, quant)
    return jax.nn.sigmoid(logits)


def route(scores: jax.Array, bias: jax.Array,
          w: Widths) -> Tuple[jax.Array, jax.Array]:
    """The ``per_tok`` experts with the largest ``scores + bias`` of
    ``scores`` (T, experts), the lower index first among equals, and their
    weights: their own scores over their sum (departure 2), times the
    scale."""
    order = jnp.argsort(-(scores + bias), axis=-1, stable=True)
    idx = order[:, :w.per_tok]
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if w.renorm:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-6)
    return idx, wt * w.route_scale


def experts_ffn(h, p, *, w: Widths, quant: Optional[str] = None, layer=None):
    """The routed experts' sum for normed rows ``h``: a plain loop over the
    experts, one expert's weights in float32 at a time. ``p`` holds one
    layer's leaves; with ``layer`` its ``we*`` are the whole stack's and an
    expert is cut out of it by (layer, expert)."""
    idx, weight = route(router_scores(h, p["router"], quant),
                        p["router_bias"], w)

    def of(name, e):
        return p[name][e] if layer is None else p[name][layer, e]

    def one_expert(y, e):
        we = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)
        out = _swiglu(h, of("we1", e), of("we3", e), of("we2", e), quant)
        return y + we[:, None] * out, None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(w.experts))
    return y


_EXPERT_LEAVES = ("we1", "we3", "we2")


@functools.partial(jax.jit, static_argnames=("w", "quant", "kind"))
def _mixer(x, stack, i, *, w: Widths, quant: Optional[str], kind: str):
    p = jax.tree.map(lambda t: t[i], stack)
    h = _rms(x, p["ln1"], w.norm_eps)
    fn = conv_mixer if kind == "conv" else attention
    return x + fn(h, p, w=w, quant=quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _dense_ffn(x, stack, i, *, w: Widths, quant: Optional[str]):
    p = jax.tree.map(lambda t: t[i], stack)
    return x + _swiglu(_rms(x, p["ln2"], w.norm_eps),
                       p["w1"], p["w3"], p["w2"], quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _moe_ffn(x, stack, i, *, w: Widths, quant: Optional[str]):
    p = {n: (a if n in _EXPERT_LEAVES else a[i]) for n, a in stack.items()}
    return x + experts_ffn(_rms(x, p["ln2"], w.norm_eps), p, w=w,
                           quant=quant, layer=i)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, embed, *, w: Widths, quant: Optional[str]):
    return _mm(_rms(x, ln_f, w.norm_eps), embed.T, quant)


def residuals(weights: Dict[str, Any], w: Widths, x: jax.Array, *,
              quant: Optional[str] = None):
    """The residual after every half layer, from the embedded rows ``x``:
    yields ``(layer, "mixer" | "ffn", x)`` in depth order."""
    seen = {"conv": 0, "attention": 0}
    for l, kind in enumerate(w.layer_types):
        stack = weights["conv" if kind == "conv" else "attn"]
        x = _mixer(x, stack, jnp.int32(seen[kind]), w=w, quant=quant,
                   kind=kind)
        seen[kind] += 1
        yield l, "mixer", x
        if l < w.n_dense:
            x = _dense_ffn(x, weights["dense"], jnp.int32(l), w=w,
                           quant=quant)
        else:
            x = _moe_ffn(x, weights["moe"], jnp.int32(l - w.n_dense), w=w,
                         quant=quant)
        yield l, "ffn", x


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 512) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    so that few shapes compile; the convolution and the attention are
    causal, so the padding reaches no row that is read, and a row's experts
    are its own."""
    T = len(tokens)
    padded = -(-T // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:T] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for _, _, x in residuals(weights, w, x, quant=quant):
        pass
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["embed"],
                w=w, quant=quant)
    return np.asarray(out)
