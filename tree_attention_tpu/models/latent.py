"""Latent attention (MLA) and YaRN rotary frequencies.

A latent-attention layer caches ONE row a token a layer for all of its heads:
``[c_kv (kv_rank) | k_rope (rope)]``, the normed key/value latent (times
``LatentAttention.kv_scale`` where the model scales it) and the one rotary key
every head shares. Per-head keys and values are up-projections of
``c_kv``. Two orders of the same arithmetic:

- **expanded** (the published order): ``[k_nope | v] = c_kv · W_kvb`` for every
  cached token, then ordinary attention with head size ``nope + rope`` for
  q·k and ``v_head`` for p·v;
- **absorbed** (what the cache is for): ``q_lat = q_nope · W_kvb,k^T`` once
  per query, scores ``q_lat·c_kv + q_rope·k_rope`` against the cached rows as
  they lie, ``out_lat = Σ p·c_kv``, then ``out = out_lat · W_kvb,v`` once per
  query. Attention against the cache is multi-query attention with one KV
  "head" of width ``kv_rank + rope`` whose first ``kv_rank`` lanes are also
  the values.

:func:`latent_qkv` gives the query in absorbed form and the new cache row;
:func:`latent_attention` runs the absorbed form against a paged pool of such
rows (the block-table kernel ``mla_decode_paged`` on TPU, a gather
elsewhere), for decode rows and chunk rows alike. The published order lives
in the benchmark's plain reference.

Rotary pairs are taken as halves (the repo's :func:`~.transformer.rope`), not
de-interleaved as the published code does; with weights drawn from a seed that
relabels columns of ``W_qb`` and ``W_kva``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tree_attention_tpu.models.transformer import (
    LATENT_SERVED,
    LatentAttention,
    TransformerConfig,
    YarnRope,
    times_out_major,
    rms_norm,
)

Params = Dict[str, Any]


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(dim: int, theta: float,
                     yarn: Optional[YarnRope]) -> jax.Array:
    """The ``dim // 2`` rotary frequencies. Under YaRN each is a blend of
    the plain frequency and the one interpolated by ``factor``: a linear
    ramp over the frequency index, from all-plain below the correction
    dimension of ``beta_fast`` rotations over the original length to
    all-interpolated above that of ``beta_slow``."""
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return plain

    def correction_dim(rotations: float) -> float:
        return dim * math.log(
            yarn.original_len / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    return plain / yarn.factor * ramp + plain * (1 - ramp)


def rope_amplitude(yarn: Optional[YarnRope]) -> float:
    """What YaRN multiplies cos and sin by (1 where ``mscale`` equals
    ``mscale_all_dim``)."""
    if yarn is None:
        return 1.0
    return _yarn_mscale(yarn.factor, yarn.mscale) \
        / _yarn_mscale(yarn.factor, yarn.mscale_all_dim)


def softmax_scale(la: LatentAttention) -> float:
    """``(nope + rope)^-1/2``, times YaRN's ``mscale_all_dim`` term
    squared."""
    s = (la.nope + la.rope) ** -0.5
    if la.yarn is not None:
        s *= _yarn_mscale(la.yarn.factor, la.yarn.mscale_all_dim) ** 2
    return s


def rope_halves(x: jax.Array, positions: jax.Array, freqs: jax.Array,
                amplitude: float = 1.0) -> jax.Array:
    """Rotate ``x`` ``(B, T, ..., d)`` by ``positions`` ``(B, T)``: pairs
    are (i, i + d/2)."""
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (B, T, d/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def latent_qkv(p: Params, h: jax.Array, positions: jax.Array,
               cfg: TransformerConfig) -> Tuple[jax.Array, jax.Array]:
    """From the normed residual ``h`` ``(B, T, D)``: the new cache rows
    ``(B, 1, T, row)`` (``[c_kv | k_rope | 0 pad]``, the pool's layout) and
    the absorbed query ``(B, H, T, row)`` (``[q_lat | q_rope | 0 pad]``)."""
    la = cfg.mla
    B, T, _ = h.shape
    H = cfg.n_heads
    freqs = rope_frequencies(la.rope, cfg.rope_theta, la.yarn)
    amp = rope_amplitude(la.yarn)

    def gain(ln, scale):   # a scaled latent: the scale rides the norm's gain
        return ln if scale == 1.0 else ln * scale

    c_q = h
    if la.q_rank:
        c_q = rms_norm(h @ p["wqa"], gain(p["q_ln"], la.q_scale),
                       cfg.norm_eps)
    if LATENT_SERVED in p:
        # The reshape to heads waits behind a barrier: folded into the
        # product, the compiler for the chip first slices the layer's whole
        # ``wqb_t`` out of its stack into fast memory and multiplies from
        # there, two passes of the weight's length; left flat, the product's
        # own fusion reads the stack's rows where they lie.
        q = lax.optimization_barrier(times_out_major(c_q, p[LATENT_SERVED]))
    else:
        q = c_q @ p["wqb"]
    q = q.reshape(B, T, H, la.nope + la.rope)
    q_nope, q_rope = q[..., :la.nope], q[..., la.nope:]
    q_rope = rope_halves(q_rope, positions, freqs, amp)
    kva = h @ p["wkva"]                                   # (B, T, rank+rope)
    c_kv = rms_norm(kva[..., :la.kv_rank], gain(p["kv_ln"], la.kv_scale),
                    cfg.norm_eps)
    k_rope = rope_halves(kva[..., la.kv_rank:], positions, freqs, amp)
    q_lat = jnp.einsum("bthn,hnc->bthc", q_nope, p["wkb"])

    def row_of(latent_part, rope_part):  # [latent | rotary | 0 pad]
        row = jnp.concatenate([latent_part, rope_part], axis=-1)
        return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, la.row_pad)])

    rows = row_of(c_kv, k_rope)[:, None]
    q_abs = row_of(q_lat, q_rope).transpose(0, 2, 1, 3)
    return rows, q_abs


def latent_out(p: Params, out_lat: jax.Array) -> jax.Array:
    """``out_lat`` ``(B, H, T, kv_rank)`` through the value up-projection
    and the output projection: ``(B, T, D)``."""
    B, H, T, _ = out_lat.shape
    o = jnp.einsum("bhtc,hcv->bthv", out_lat, p["wvb"])
    return o.reshape(B, T, -1) @ p["wo"]


def latent_attention_reference(
    q: jax.Array, pool: jax.Array, block_table: jax.Array, *,
    q_offset: jax.Array, scale: float, rank: int,
) -> Tuple[jax.Array, jax.Array]:
    """The kernel's contract in plain ``jax.numpy``: gather each slot's
    logical rows, mask, softmax in float32."""
    B, H, Tq, W = q.shape
    N = pool.shape[0]
    rows = pool[jnp.clip(block_table, 0, N - 1)].reshape(B, -1, W)
    s = jnp.einsum("bhtw,bsw->bhts", q.astype(jnp.float32),
                   rows.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * scale
    col = jnp.arange(rows.shape[1], dtype=jnp.int32)
    pos = q_offset[:, None] + jnp.arange(Tq, dtype=jnp.int32)
    ok = col[None, None, :] <= pos[:, :, None]                # (B, Tq, S)
    s = jnp.where(ok[:, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bhts,bsc->bhtc", e / jnp.where(l > 0, l, 1.0),
                     rows[..., :rank].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    lse = jnp.where(l[..., 0] > 0, m[..., 0] + jnp.log(
        jnp.where(l[..., 0] > 0, l[..., 0], 1.0)), -jnp.inf)
    return out.astype(q.dtype), lse


def latent_attention(
    q: jax.Array, pool: jax.Array, block_table: jax.Array, *,
    q_offset: jax.Array, cfg: TransformerConfig, step_plan=None,
) -> Tuple[jax.Array, jax.Array]:
    """Absorbed attention of ``q`` ``(B, H, Tq, row)`` against the paged
    latent pool ``(N, block, row)``: the block-table kernel on TPU (handed
    ``step_plan``, its work list, where the caller built it for every
    layer), the gathered reference elsewhere. Returns ``(out_lat, lse)``."""
    from tree_attention_tpu.ops import _on_tpu, _pallas_available
    from tree_attention_tpu.ops.decode import _account_dispatch

    la = cfg.mla
    kw = dict(q_offset=q_offset, scale=softmax_scale(la), rank=la.kv_rank)
    kv_tokens = block_table.shape[1] * pool.shape[1]
    if _on_tpu(q) and _pallas_available():
        from tree_attention_tpu.ops.pallas_decode import (
            attention_pallas_mla_paged,
        )

        _account_dispatch("mla_paged_decode", kv_tokens)
        return attention_pallas_mla_paged(
            q, pool, block_table, step_plan=step_plan, **kw)
    _account_dispatch("mla_paged_reference", kv_tokens)
    return latent_attention_reference(q, pool, block_table, **kw)


def init_latent_layer(key: jax.Array, cfg: TransformerConfig,
                      res_std: float) -> Params:
    """One layer's attention leaves, each drawn in float32 and rounded
    to the served type on its own."""
    la, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std
                ).astype(cfg.dtype)

    out = {
        "wqb": normal(ks[1], (la.q_rank or D, H * (la.nope + la.rope)), 0.02),
        "wkva": normal(ks[2], (D, la.kv_rank + la.rope), 0.02),
        "kv_ln": jnp.ones((la.kv_rank,), jnp.float32),
        "wkb": normal(ks[3], (H, la.nope, la.kv_rank), 0.02),
        "wvb": normal(ks[4], (H, la.kv_rank, la.v_head), 0.02),
        "wo": normal(ks[5], (H * la.v_head, D), res_std),
    }
    if la.q_rank:
        out["wqa"] = normal(ks[0], (D, la.q_rank), 0.02)
        out["q_ln"] = jnp.ones((la.q_rank,), jnp.float32)
    return out
