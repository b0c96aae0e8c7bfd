"""The plain reference of the ``llama_dense`` family: a decoder-only
transformer's forward pass in float32 at the highest matmul precision, with
no kernels, no cache and no batching. What every family's file gives is in
``README.md`` beside this file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
takes nothing the program made. The weights are the benchmark's:
:func:`init_weights` makes them from the seed, once for the program to serve
and, after the program is freed, again for the reference.

The architecture is the one this family's configurations publish (Llama-style:
RMSNorm before each block, rotary embedding on split halves, grouped-query
causal attention scaled by 1/sqrt(head size), SwiGLU, untied output head).

``quant="int8"`` (or ``"fp8"``, e4m3) is the control of the ``correct`` gate,
not a reference: the same forward with every matmul's operands and the
cached keys and values rounded to that type (symmetric, scaled per row / per
output channel / per token and head), the precision below the bf16 the
configurations state.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
CONTROLS = ("int8", "fp8")      # the lower precisions ``quant`` takes


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head: int
    ffn: int
    rope_theta: float
    norm_eps: float
    dtype: str

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        heads = int(config["num_attention_heads"])
        return cls(
            vocab=int(config["vocab_size"]),
            hidden=int(config["hidden_size"]),
            layers=int(config["num_hidden_layers"]),
            heads=heads,
            kv_heads=int(config["num_key_value_heads"]),
            head=int(config.get("head_dim",
                                int(config["hidden_size"]) // heads)),
            ffn=int(config["intermediate_size"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            dtype=str(config["torch_dtype"]),
        )


def _leaf(key, shape, stddev: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w",))
def _init_weights(seed: jax.Array, w: Widths) -> Dict[str, Any]:
    dtype = jnp.dtype(w.dtype)
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(seed), 3)
    L, D = w.layers, w.hidden
    std = 0.02
    res_std = std / (2 * L) ** 0.5
    q_dim, kv_dim = w.heads * w.head, w.kv_heads * w.head
    shapes = {
        "wq": ((D, q_dim), std), "wk": ((D, kv_dim), std),
        "wv": ((D, kv_dim), std), "wo": ((q_dim, D), res_std),
        "w1": ((D, w.ffn), std), "w3": ((D, w.ffn), std),
        "w2": ((w.ffn, D), res_std),
    }

    def one_layer(key):
        ks = jax.random.split(key, len(shapes))
        return {n: _leaf(k, shape, sd, dtype)
                for k, (n, (shape, sd)) in zip(ks, shapes.items())}

    # Layer by layer, so that the float32 draw of a stacked tensor never
    # exists whole: the peak is the weights themselves.
    layers = lax.map(one_layer, jax.random.split(k_layers, L))
    return {
        "embed": _leaf(k_embed, (w.vocab, D), std, dtype),
        "ln1": jnp.ones((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
        **layers,
        "ln_f": jnp.ones((D,), jnp.float32),
        "wout": _leaf(k_out, (D, w.vocab), std, dtype),
    }


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: normal, std 0.02; the two projections that write the residual
    stream (``wo``, ``w2``) scaled by ``(2 * layers) ** -0.5``; norms at one.
    Per-layer tensors are stacked on a leading layer axis. Drawn in float32
    and rounded once to the served type. The benchmark hands these to the
    program as its model and, once the program is freed, makes them again
    for the reference: the same seed gives the same values."""
    return _init_weights(jnp.uint32(int(seed) % (2 ** 32)), w)


def _fake_int8(x: jax.Array, axis: int) -> jax.Array:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _fake_fp8(x: jax.Array, axis: int) -> jax.Array:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_FAKE = {"int8": _fake_int8, "fp8": _fake_fp8}


def _mm(x: jax.Array, wt: jax.Array, quant: Optional[str]) -> jax.Array:
    wt = wt.astype(jnp.float32)
    if quant is not None:
        x, wt = _FAKE[quant](x, -1), _FAKE[quant](wt, 0)
    return jnp.matmul(x, wt, precision=HIGHEST)


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """``x`` is (T, H, d); position t is row t."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _layer(x, ln1, wq, wk, wv, wo, ln2, w1, w3, w2, *, w: Widths,
           quant: Optional[str]):
    T = x.shape[0]
    h = _rms(x, ln1, w.norm_eps)
    q = _rope(_mm(h, wq, quant).reshape(T, w.heads, w.head), w.rope_theta)
    k = _rope(_mm(h, wk, quant).reshape(T, w.kv_heads, w.head), w.rope_theta)
    v = _mm(h, wv, quant).reshape(T, w.kv_heads, w.head)
    if quant is not None:
        k, v = _FAKE[quant](k, -1), _FAKE[quant](v, -1)
    group = w.heads // w.kv_heads
    causal = jnp.tril(jnp.ones((T, T), bool))

    def one_kv_head(args):
        qg, kh, vh = args            # (G, T, d), (T, d), (T, d)
        s = jnp.einsum("gtd,sd->gts", qg, kh, precision=HIGHEST)
        s = jnp.where(causal, s * (w.head ** -0.5), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gts,sd->gtd", p, vh, precision=HIGHEST)

    qg = q.reshape(T, w.kv_heads, group, w.head).transpose(1, 2, 0, 3)
    o = lax.map(one_kv_head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(T, w.heads * w.head)
    x = x + _mm(o, wo, quant)
    h = _rms(x, ln2, w.norm_eps)
    return x + _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2,
                   quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, wout, *, w: Widths, quant: Optional[str]):
    return _mm(_rms(x, ln_f, w.norm_eps), wout, quant)


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 1024) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    so that few shapes compile; causal attention keeps the padding out of
    every row that is read."""
    T = len(tokens)
    padded = -(-T // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:T] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for l in range(w.layers):
        x = _layer(x, *(weights[n][l] for n in (
            "ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3", "w2")),
            w=w, quant=quant)
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["wout"],
                w=w, quant=quant)
    return np.asarray(out)
