"""Shared KV-blocking and mask helpers for the blockwise forward/backward.

The forward (``reference.attention_blockwise``) and the flash backward
(``vjp.attention_bwd_blockwise``) must mask and pad *identically* or gradients
silently diverge from the forward — so the logic lives once, here.
"""

from __future__ import annotations

import numbers
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

# The one definition of the masking sentinel and the TPU vector lane width —
# every impl must mask with the same -inf and tile to the same lane count.
NEG_INF = float("-inf")
LANES = 128


def matmul_precision(*dtypes):
    """Contraction precision for the ops-layer matmuls, by operand dtype.

    bf16 operands need nothing: the MXU multiplies them exactly and
    ``preferred_element_type=f32`` accumulates in f32 — that is already the
    best bf16 inputs can get, and requesting HIGHEST instead makes XLA upcast
    to a multi-pass f32 contraction (~4x slower) and Mosaic reject the matmul
    outright ("Bad lhs type").

    Anything else (f32/f16/f64) must pin HIGHEST: the default matmul
    precision may silently lower the contraction to a single bf16 pass
    (observed ~5e-3 relative logit error on both the TPU MXU and, for some
    contraction layouts, the CPU backend) — unacceptable in an
    exact-attention library whose merge currency is an f32 lse.
    """
    from jax import lax

    if all(jnp.dtype(d) == jnp.bfloat16 for d in dtypes):
        return None
    return lax.Precision.HIGHEST


def pad_to_block(x: jax.Array, dim: int, block: int) -> jax.Array:
    """Zero-pad ``dim`` up to a multiple of ``block``."""
    pad = (-x.shape[dim]) % block
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[dim] = (0, pad)
    return jnp.pad(x, widths)


def split_kv_blocks(
    k: jax.Array, v: jax.Array, block: int
) -> Tuple[jax.Array, jax.Array, int, int]:
    """Reshape (B, Hkv, Tk, D) K/V into the (num_blocks, B, Hkv, blk, D) scan
    layout, padding the tail block. Returns (kb, vb, num_blocks, blk)."""
    B, Hkv, Tk, D = k.shape
    blk = min(block, Tk)
    kp = pad_to_block(k, 2, blk)
    vp = pad_to_block(v, 2, blk)
    num_blocks = kp.shape[2] // blk
    kb = kp.reshape(B, Hkv, num_blocks, blk, D).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(B, Hkv, num_blocks, blk, D).transpose(2, 0, 1, 3, 4)
    return kb, vb, num_blocks, blk


def tile_geometry(qi, ki, block_q: int, block_k: int, q_offset, kv_offset):
    """Per-tile global positions for the Pallas kernels (rows = Q, cols = K).

    Returns ``(row_pos, col_idx, col_pos)`` in **broadcast form** —
    ``row_pos`` is ``(block_q, 1)``, ``col_idx``/``col_pos`` are
    ``(1, block_k)`` — so a mask like ``row_pos >= col_pos`` materialises
    one ``(block_q, block_k)`` compare instead of two full-tile i32 iotas
    first (~4 VPU passes down to ~1; measured 2026-07-31, the full-tile
    form cost the 4k causal fwd kernel several percent and an attempted
    ``lax.cond`` skip cost 45%). Forward and both backward kernels must use
    this one definition or their masks diverge.
    """
    q_start = qi * block_q
    k_start = ki * block_k
    row_pos = q_offset + q_start + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )
    col_idx = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    col_pos = kv_offset + col_idx
    return row_pos, col_idx, col_pos


def tile_live(qi, ki, block_q: int, block_k: int, q_offset, kv_offset,
              causal: bool):
    """Whether a (Q-tile, KV-tile) pair has any visible entry under causality:
    live iff the most-visible corner (last row, first col) is unmasked."""
    if not causal:
        return True
    return (q_offset + qi * block_q + block_q - 1) >= (kv_offset + ki * block_k)


def mask_scores(s, qi, ki, block_q: int, block_k: int, q_offset, kv_offset,
                tk: int, causal: bool):
    """Ragged-tail + causal masking for a ``(block_q, block_k)`` score tile.

    Static no-op for non-causal divisible shapes. Built from the broadcast
    geometry (see :func:`tile_geometry`): the mask is one broadcast compare
    + select, not full-tile iota materialisation. (A ``lax.cond``
    interior-tile skip was tried and REGRESSED the 4k causal fwd kernel 45%
    on v5e — Mosaic's vector-operand branch join costs more than the mask
    it saves — and VMEM-OOM'd the bwd kernels at 16k; don't reintroduce
    it.) One definition shared by the fwd and both bwd kernels.
    """
    needs_ragged = tk % block_k != 0
    if not causal and not needs_ragged:
        return s
    row_pos, col_idx, col_pos = tile_geometry(
        qi, ki, block_q, block_k, q_offset, kv_offset
    )
    if needs_ragged and causal:
        valid = (col_idx < tk) & (row_pos >= col_pos)
    elif causal:
        valid = row_pos >= col_pos
    else:
        valid = jnp.broadcast_to(col_idx < tk, s.shape)
    return jnp.where(valid, s, NEG_INF)


def offsets_smem(q_offset, kv_offset, batch: int) -> jax.Array:
    """(2, B) int32 SMEM operand: per-batch [q_offset | kv_offset] rows.

    Scalars broadcast to every batch row; a ``(B,)`` vector gives each row
    (cache slot) its own global position — the ragged-batch contract shared
    by every offset-taking Pallas kernel (a kernel with batch-major grid
    dim 0 indexes column ``program_id(0) // heads_per_batch``)."""
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (batch,))
    kv_off = jnp.broadcast_to(jnp.asarray(kv_offset, jnp.int32), (batch,))
    return jnp.stack([q_off, kv_off])


def static_offsets(q_offset, kv_offset) -> bool:
    """Whether both causal shard offsets are compile-time integers.

    True on the unsharded path (offsets are literals); False inside
    ``shard_map``, where at least one offset is a traced ``axis_index``
    product. Static offsets let the Pallas index maps cull causally dead
    tiles at the *grid* level — dead iterations map to the block the next
    live step will need (block 0 for trailing-dead ``culled_ki``, the
    first live block for leading-dead ``culled_qi``), so Pallas's
    revisiting pipeline elides the repeats and the dead time prefetches —
    instead of only skipping their compute via ``pl.when``.
    """
    return isinstance(q_offset, numbers.Integral) and isinstance(
        kv_offset, numbers.Integral
    )


def causal_last_live_k(qi, block_q: int, block_k: int, q_offset: int,
                       kv_offset: int, n_k: int):
    """Last causally live KV-tile index for Q tile ``qi`` (static offsets).

    Derived from :func:`tile_live`: live iff
    ``q_offset + qi·bq + bq − 1 >= kv_offset + ki·bk``. Clamped to
    ``[0, n_k−1]``; a fully-masked Q row clamps to 0 (its compute is skipped
    either way, the clamp just keeps the index in range).
    """
    hi = (q_offset - kv_offset + qi * block_q + block_q - 1) // block_k
    return jnp.clip(hi, 0, n_k - 1)


def causal_first_live_q(ki, block_q: int, block_k: int, q_offset: int,
                        kv_offset: int, n_q: int):
    """First causally live Q-tile index for KV tile ``ki`` (static offsets).

    The ceil counterpart of :func:`causal_last_live_k`, clamped to
    ``[0, n_q−1]``.
    """
    lo = -((q_offset + block_q - 1 - kv_offset - ki * block_k) // block_q)
    return jnp.clip(lo, 0, n_q - 1)


def culled_ki(qi, ki, cull, block_q: int, block_k: int, n_k: int):
    """KV-tile index with grid-level causal culling (index-map side).

    ``cull`` is ``(q_offset, kv_offset)`` as ints or None. Dead tiles past
    the diagonal all map to block **0** — the first block the NEXT Q row
    needs — so the row's dead grid steps (which run in ~no time; their
    compute is gated off by ``pl.when(tile_live(...))``) become prefetch
    time for the next row instead of a cold-fetch bubble at its first live
    step. One DMA fires on the diagonal→0 transition; the remaining dead
    steps and the next row's ``ki=0`` step reuse the resident block (the
    Pallas revisiting pipeline elides repeats). Clamping dead tiles to the
    row's last live block instead (the pre-r5 scheme) elides their DMA too
    but leaves the next row starting cold — measured as most of a ~9%
    fwd-MFU gap vs the JAX-bundled kernel, whose causal ``kv_index_map``
    uses this same prefetch-zero trick. The one definition shared by the
    fwd and dQ kernels — they must cull identically or diverge silently.
    """
    if cull is None:
        return ki
    live = ki <= causal_last_live_k(
        qi, block_q, block_k, cull[0], cull[1], n_k
    )
    return jnp.where(live, ki, 0)


def culled_qi(ki, qi, cull, block_q: int, block_k: int, n_q: int):
    """Q-tile index with grid-level causal culling (dKV mirror of
    :func:`culled_ki`): dead tiles *before* the diagonal repeat the first
    live block of their segment."""
    if cull is None:
        return qi
    return jnp.maximum(
        qi, causal_first_live_q(ki, block_q, block_k, cull[0], cull[1], n_q)
    )


class AlignedWindow(NamedTuple):
    """A window that is a BLOCK of positions: a row at ``t`` sees ``[w0,
    t]``, ``w0 = (t // window) * window`` (the last ``window`` positions
    only at a window's last row)."""

    window: int


class ChunkSummaries(NamedTuple):
    """Columns that are not positions but one row a ``chunk`` of positions
    (column ``c`` stands for ``[c * chunk, (c + 1) * chunk)``): a row at
    ``t`` sees the chunks of every window closed before its own, ``c * chunk
    < (t // window) * window``. The partner of :class:`AlignedWindow`."""

    window: int
    chunk: int


# What ``window=`` may say, everywhere it is taken: an int is the sliding
# rule, a row at ``t`` sees ``(t - window, t]``.
WindowRule = Union[int, AlignedWindow, ChunkSummaries]


def window_low(window: "WindowRule", q_pos):
    """The lowest POSITION a row at ``q_pos`` sees under a position rule
    (sliding or aligned), not clipped at 0."""
    if isinstance(window, AlignedWindow):
        return q_pos - q_pos % window.window
    return q_pos - (window - 1)


def summaries_seen(window: ChunkSummaries, q_pos):
    """Summary rows a row at ``q_pos`` sees: the chunks under its window."""
    return (q_pos - q_pos % window.window) // window.chunk


def window_visible(window: "WindowRule", q_pos, k_pos):
    """Whether a row at ``q_pos`` sees column ``k_pos`` under ``window``,
    beside the causal term (which every rule keeps: a summary's column
    index lies under its row's position too). Compares, a remainder and a
    product only, so that a kernel body can say it."""
    if isinstance(window, ChunkSummaries):
        return k_pos * window.chunk < q_pos - q_pos % window.window
    if isinstance(window, AlignedWindow):
        return k_pos >= window_low(window, q_pos)
    return k_pos > q_pos - window


def tile_mask(
    tq: int,
    blk: int,
    blk_idx,
    tk: int,
    q_offset,
    kv_offset,
    causal: bool,
    window: Optional[WindowRule] = None,
) -> jax.Array:
    """(tq, blk) visibility mask for one KV tile.

    Combines the ragged-tail range check (padded keys beyond ``tk`` are
    invalid) with cross-shard causality: query global position
    ``q_offset + row`` sees key global position ``kv_offset + start + col``
    iff q_pos >= k_pos; with ``window`` also only iff the rule says so
    (:func:`window_visible`; an int: ``k_pos > q_pos - window``, a
    sliding-window layer: a row sees its last ``window`` positions, itself
    included).
    """
    start = blk_idx * blk
    local_col = start + lax.broadcasted_iota(jnp.int32, (tq, blk), 1)
    valid = local_col < tk
    if causal:
        q_pos = q_offset + lax.broadcasted_iota(jnp.int32, (tq, blk), 0)
        valid = valid & (q_pos >= kv_offset + local_col)
        if window is not None:
            valid = valid & window_visible(
                window, q_pos, kv_offset + local_col)
    return valid
