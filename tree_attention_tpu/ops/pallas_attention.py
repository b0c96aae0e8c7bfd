"""Pallas TPU flash-attention forward kernel emitting ``(out, lse)``.

This is the real version of what the reference's ``flash_res_lse`` only
simulates (``/root/reference/model.py:60-83`` materialises the full score
matrix; its README TODO at ``README.md:21`` admits flash attention was never
integrated). Here the score matrix never exists: the kernel streams KV tiles
through VMEM, maintains the online-softmax state ``(m, l, acc)`` in scratch
across the (sequential) KV grid dimension, and writes ``out = acc/l`` and
``lse = m + log l`` once per Q tile.

TPU mapping:

- Both matmuls (QKᵀ and P·V) hit the MXU with ``preferred_element_type=f32``;
  tiles default to 128×512×head_dim.
- Grid ``(B·Hq, Tq/bq, Tk/bk)``; the last dim iterates sequentially on TPU,
  which is what lets scratch carry the running softmax state.
- GQA is native: the K/V BlockSpec index map folds the query head down to its
  KV head (no KV replication in HBM or VMEM).
- Causal shard offsets arrive via SMEM scalars (they are traced values inside
  ``shard_map``); fully-masked causal tiles skip both matmuls via ``pl.when``.
- ``interpret=True`` runs the same kernel on CPU for cluster-free tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tree_attention_tpu.ops.block_utils import (
    LANES as _LANES,
    NEG_INF,
    culled_ki,
    mask_scores,
    matmul_precision,
    static_offsets,
    tile_live,
)


def _lane_bcast(x, n):
    """Widen a lane-replicated ``(bq, LANES)`` state vector to ``(bq, n)``.

    Every lane of ``x`` holds the same value, so slicing narrows and tiling
    widens without changing semantics. The multiple-of-LANES paths stay
    lane-aligned on the VPU; the ``n < LANES`` / non-multiple paths still
    produce a sub-128-lane vector and pay its relayout (reachable only
    with narrow heads or sub-128 test tiles, not the product shapes)."""
    L = x.shape[-1]
    if n == L:
        return x
    if n < L:
        return x[:, :n]
    if n % L == 0:
        return jnp.tile(x, (1, n // L))
    return jnp.tile(x, (1, -(-n // L)))[:, :n]


def _flash_fwd_kernel(
    offs_ref,  # SMEM (2, B): per-batch [q_offset | kv_offset] columns —
               # ragged prefill gives every batch row its own global position
    q_ref,     # VMEM (1, bq, D)
    k_ref,     # VMEM (1, bk, D)
    v_ref,     # VMEM (1, bk, D)
    out_ref,   # VMEM (1, bq, D)
    lse_ref,   # VMEM (1, bq, LANES) — lse broadcast across lanes (TPU tiling
               # requires a 128-multiple trailing dim; host slices lane 0)
    m_scr,     # VMEM (bq, LANES) f32
    l_scr,     # VMEM (bq, LANES) f32
    acc_scr,   # VMEM (bq, D) f32
    *,
    scale: float,
    causal: bool,
    tk: int,
    block_q: int,
    block_k: int,
    n_q_heads: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    b = pl.program_id(0) // n_q_heads  # grid dim 0 runs over B·Hq
    q_offset = offs_ref[0, b]
    kv_offset = offs_ref[1, b]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(tile_live(qi, ki, block_q, block_k, q_offset, kv_offset, causal))
    def _compute():
        # Operands stay in their native dtype (bf16 hits the MXU's fast
        # path; casting to f32 first would quarter matmul throughput) with
        # f32 accumulation via preferred_element_type.
        s = lax.dot_general(
            q_ref[0],
            k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(q_ref.dtype, k_ref.dtype),
        ) * scale  # (bq, bk) f32

        # Ragged-tail + causal masking; interior tiles skip it entirely.
        s = mask_scores(
            s, qi, ki, block_q, block_k, q_offset, kv_offset, tk, causal
        )

        # Softmax state math stays LANE-REPLICATED at (bq, LANES)
        # throughout: narrow (bq, 1) intermediates force a VPU lane
        # relayout per op, and with ~8 state ops per KV step that overhead
        # measured ~19% of step time at 512/1024 tiles (44.0% -> 54.0%
        # MFU, r5 race vs the JAX-bundled kernel, which keeps state at
        # (bq, 128) for the same reason). Two narrow (bq, 1) reductions
        # necessarily remain — the row max and the row sum of p — each
        # broadcast back to lane width once.
        m_prev = m_scr[...]  # (bq, LANES)
        l_prev = l_scr[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_blk)          # (bq, LANES)
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
        p = jnp.exp(s - _lane_bcast(m_safe, s.shape[-1]))  # masked cols -> 0
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # P is cast to V's dtype for the second MXU matmul (the FA2 trick:
        # probabilities are in [0,1] so bf16 relative error stays small) and
        # accumulated in f32. When Tk is ragged the last tile's trailing V
        # rows are unspecified garbage (no host padding; interpret mode
        # NaN-poisons them) — p's masked columns are exactly 0, but 0·NaN is
        # NaN, so those rows must be zeroed. Static no-op for divisible Tk.
        v_tile = v_ref[0]
        if tk % block_k:
            row_ok = (
                ki * block_k
                + lax.broadcasted_iota(jnp.int32, v_tile.shape, 0)
            ) < tk
            v_tile = jnp.where(row_ok, v_tile, 0)
        acc_scr[...] = acc_scr[...] * _lane_bcast(
            alpha, acc_scr.shape[-1]
        ) + lax.dot_general(
            p.astype(v_ref.dtype), v_tile,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(v_ref.dtype, v_ref.dtype),
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        m = m_scr[...]  # (bq, LANES), lane-replicated
        l = l_scr[...]
        empty = l <= 0.0
        l_safe = jnp.where(empty, 1.0, l)
        D_acc = acc_scr.shape[-1]
        out_ref[0] = (
            jnp.where(
                _lane_bcast(empty, D_acc), 0.0,
                acc_scr[...] / _lane_bcast(l_safe, D_acc),
            )
        ).astype(out_ref.dtype)
        lse = jnp.where(
            empty, NEG_INF, jnp.where(m == NEG_INF, 0.0, m) + jnp.log(l_safe)
        )
        lse_ref[0] = lse




def attention_pallas_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    block_size: int = 512,
    block_q: int = 256,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Raw (non-differentiable) Pallas forward. Same contract as the jnp impls.

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere —
    the same kernel code path is what CI exercises on CPU.

    When ``causal`` and both offsets are compile-time integers (the unsharded
    path), causally dead KV tiles are culled at the grid level: their index
    maps repeat the last live block, so the pipeline elides the DMA — up to
    ~2× less HBM traffic for the bottom-right-aligned training shape. Traced
    offsets (``shard_map``) keep the ``pl.when`` compute skip only. Offsets
    become part of the compile key only in the static case, so a loop over
    *varying* integer offsets should pass them as arrays.

    ``q_offset`` / ``kv_offset`` may also be ``(B,)`` vectors (the ragged
    prefill shape: each batch row is a cache slot at its own position);
    per-batch offsets ride SMEM like the decode kernel's, with the
    ``pl.when`` compute skip per batch row (no grid culling — the grid is
    shared across rows).
    """
    cull = (
        (int(q_offset), int(kv_offset))
        if causal and static_offsets(q_offset, kv_offset)
        else None
    )
    return _attention_pallas_fwd(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset,
        kv_offset=kv_offset, block_size=block_size, block_q=block_q,
        interpret=interpret, cull=cull,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_size", "block_q", "interpret", "cull"
    ),
)
def _attention_pallas_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: Optional[float],
    q_offset,
    kv_offset,
    block_size: int,
    block_q: int,
    interpret: Optional[bool],
    cull: Optional[Tuple[int, int]],
) -> Tuple[jax.Array, jax.Array]:
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    G = Hq // Hkv
    s = (D ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    if Tk == 0:
        return jnp.zeros_like(q), jnp.full((B, Hq, Tq), NEG_INF, jnp.float32)

    bq = min(block_q, max(Tq, 8))
    bk = min(block_size, max(Tk, _LANES))

    # No host-side padding: Pallas handles ragged last blocks itself, and an
    # explicit jnp.pad copies the ENTIRE Q/K/V every call whenever the length
    # is not a block multiple (measured as the difference between 27% and 92%
    # of HBM roofline on the 64000-token decode; same physics here).
    qp = q.reshape(B * Hq, Tq, D)
    kp = k.reshape(B * Hkv, Tk, D)
    vp = v.reshape(B * Hkv, Tk, D)
    n_q, n_k = -(-Tq // bq), -(-Tk // bk)
    tq_pad = n_q * bq

    from tree_attention_tpu.ops.block_utils import offsets_smem

    offs = offsets_smem(q_offset, kv_offset, B)

    grid = (B * Hq, n_q, n_k)

    def kv_index(bh, qi, ki):
        b, hq = bh // Hq, bh % Hq
        return (b * Hkv + hq // G, culled_ki(qi, ki, cull, bq, bk, n_k), 0)

    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel,
            scale=s, causal=causal, tk=Tk, block_q=bq, block_k=bk,
            n_q_heads=Hq,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, tq_pad, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, tq_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        # Batch-head and Q-tile dims are independent; only the KV dim is
        # sequential (scratch carries the online-softmax state across it).
        # Declaring that lets Mosaic split the parallel dims across cores on
        # megacore parts (v5p/v4); no-op on single-core chips (v5e).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(offs, qp, kp, vp)

    out = out[:, :Tq].reshape(B, Hq, Tq, D)
    lse = lse[:, :Tq, 0].reshape(B, Hq, Tq)
    return out, lse
