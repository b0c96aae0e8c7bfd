"""Pure-jnp reference attention kernels emitting ``(out, lse)``.

These are the numerics anchor of the framework and the CPU fallback path. The
kernel contract — every attention impl returns the attention output *and* the
logsumexp of the scaled logits per query row — is the spine of the tree merge,
mirroring the reference's ``flash_res_lse`` (``/root/reference/model.py:60-83``)
but fixing its three confirmed bugs:

1. The contraction runs over the *sequence* axis (the reference's layout
   mismatch made it attend over the head axis, ``model.py:74`` with
   ``model.py:51-53`` layouts).
2. ``lse`` is the logsumexp of the **scaled logits**, not of post-softmax
   probabilities (``model.py:80``), which is what the safe-softmax merge
   requires.
3. Causal masking uses ``-inf`` before the softmax, not ``tril`` zeroing
   (``model.py:76``), and supports cross-shard offsets so a sequence-sharded
   KV block knows its global position.

Two implementations share one contract:

- :func:`attention_naive` — materialises the score matrix; the readable
  oracle for tests (small shapes only).
- :func:`attention_blockwise` — ``lax.scan`` over KV blocks with an online
  softmax (running max / sum / accumulator), O(block) memory; the
  any-backend fallback with the same access pattern as the Pallas kernel.

Shapes (TPU-friendly, head-major so the trailing two dims tile onto the MXU):

- ``q``: ``(B, Hq, Tq, D)``
- ``k``, ``v``: ``(B, Hkv, Tk, D)`` with ``Hq % Hkv == 0`` (GQA/MQA)
- returns ``out``: ``(B, Hq, Tq, D)`` (q's dtype), ``lse``: ``(B, Hq, Tq)``
  float32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tree_attention_tpu.ops.block_utils import (  # noqa: F401  (canonical home)
    NEG_INF,
    WindowRule,
    matmul_precision,
    window_visible,
)


def _default_scale(head_dim: int, scale: Optional[float]) -> float:
    return (head_dim ** -0.5) if scale is None else scale


def _causal_mask(
    q_len: int, k_len: int, q_offset, k_offset
) -> jax.Array:
    """Visibility mask: query at global position i sees key at global j iff i >= j.

    ``q_offset``/``k_offset`` are the global positions of the first local
    query/key row — this is how a sequence-sharded KV block expresses causality
    against replicated or sharded Q (the reference never faced this: its causal
    path is dead code, ``model.py:100``).
    """
    q_pos = q_offset + lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
    k_pos = k_offset + lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
    return q_pos >= k_pos


def finalize(out_unnormalized: jax.Array, m: jax.Array, l: jax.Array, out_dtype) -> Tuple[jax.Array, jax.Array]:
    """Turn running (acc, max, sum) online-softmax state into (out, lse).

    Rows that saw no visible key (``m == -inf`` / ``l == 0``) produce zero
    output and ``lse == -inf`` so a later :func:`merge_partials` treats the
    shard as contributing nothing — the identity of the safe-softmax monoid.
    """
    empty = l <= 0.0
    safe_l = jnp.where(empty, 1.0, l)
    out = out_unnormalized / safe_l[..., None]
    out = jnp.where(empty[..., None], 0.0, out)
    lse = jnp.where(empty, NEG_INF, m + jnp.log(safe_l))
    return out.astype(out_dtype), lse.astype(jnp.float32)


def attention_naive(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    tree_mask: Optional[jax.Array] = None,
    window: Optional[WindowRule] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Materialised-scores attention. Oracle implementation for tests.

    GQA is grouped, not expanded: query head ``h`` reads KV head ``h // G``
    through a reshape (``(B, Hkv, G, Tq, D)``) and grouped einsums, so KV is
    never replicated in memory — the same mapping the Pallas kernel's
    BlockSpec index does in VMEM. That keeps this path viable for big GQA
    decode caches, not just as a test oracle.

    ``window`` (with ``causal``): a row at global position ``t`` sees the
    keys at ``(t - window, t]`` only, a sliding-window layer's rule; or one
    of ``block_utils``' other rules (:class:`~.block_utils.AlignedWindow`,
    :class:`~.block_utils.ChunkSummaries`).
    """
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    if window is not None and (tree_mask is not None or not causal):
        raise ValueError("window requires causal=True and no tree_mask")
    G = Hq // Hkv
    s = _default_scale(D, scale)

    if Tk == 0:  # empty shard contributes the safe-softmax identity
        return (
            jnp.zeros_like(q),
            jnp.full((B, Hq, Tq), NEG_INF, jnp.float32),
        )

    qg = q.reshape(B, Hkv, G, Tq, D)
    # See matmul_precision: non-bf16 operands must not be silently lowered
    # to a single bf16 pass (MXU on TPU, and observed on the CPU backend for
    # some contraction layouts) — unacceptable in the oracle; bf16 operands
    # already multiply exactly into f32 and keep the MXU fast path.
    logits = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32,
        precision=matmul_precision(qg.dtype, k.dtype),
    ) * s
    if tree_mask is not None:
        # Tree-window rule (see attention_blockwise): visible below the
        # window, per the packed ancestor mask inside it, never past it.
        if not causal:
            raise ValueError("tree_mask requires causal=True")
        rel = (
            kv_offset + lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
            - q_offset
        )
        taken = jnp.take_along_axis(
            tree_mask,
            jnp.broadcast_to(jnp.clip(rel, 0, Tq - 1)[None], (B, Tq, Tk)),
            axis=2,
        )
        mask = (rel < 0)[None] | ((rel < Tq)[None] & taken)
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    elif causal:
        mask = _causal_mask(Tq, Tk, q_offset, kv_offset)
        if window is not None:
            mask &= window_visible(
                window,
                q_offset + lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0),
                kv_offset + lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1))
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)

    m = jnp.max(logits, axis=-1)
    # exp(-inf - -inf) would be nan; fully-masked rows get m := 0 so that
    # exp(-inf - 0) = 0 and the row drops out cleanly.
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    # The value contraction runs in full f32 (p carries real f32 precision
    # from the exp) — this is the oracle; perf paths do the FA2 p-downcast
    # trick instead.
    acc = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32),
        precision=matmul_precision(jnp.float32),
    )
    return finalize(
        acc.reshape(B, Hq, Tq, D),
        m.reshape(B, Hq, Tq),
        l.reshape(B, Hq, Tq),
        q.dtype,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_size", "window"))
def attention_blockwise(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    block_size: int = 512,
    tree_mask: Optional[jax.Array] = None,
    window: Optional[WindowRule] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Online-softmax attention: ``lax.scan`` over KV blocks, O(block) memory.

    Same math the Pallas kernel performs on-chip; usable on any backend. This
    is what the reference's ``flash_res_lse`` *claims* to be ("simulates flash
    attention", ``model.py:62``) but isn't — it materialises the full score
    matrix.

    GQA runs against *unexpanded* KV: query heads are folded into a group axis
    (``bghqd,bhkd->bghqk``) so KV memory stays ``Hkv``-sized — the point of
    grouped-query attention for big KV caches.

    ``tree_mask`` (a ``(B, Tq, Tq)`` bool array, requires ``causal=True``
    and a scalar ``q_offset``) switches the **window rule** of speculative
    tree verification (SpecInfer, arXiv:2305.09781) on: the Tq query rows
    are packed draft-tree nodes occupying KV positions ``[q_offset,
    q_offset + Tq)``, and query row ``i`` sees KV position ``p`` iff
    ``p < q_offset`` (the committed history) or ``p`` lies in the window
    with ``tree_mask[b, i, p - q_offset]`` set (an ancestor of ``i`` — or
    ``i`` itself). A lower-triangular mask reproduces plain causal
    masking bit-for-bit (same visibility sets, same arithmetic).

    ``window`` (requires ``causal``, no ``tree_mask``): the sliding-window
    rule, a row at global position ``t`` sees ``(t - window, t]``, or one of
    ``block_utils``' other rules (``tile_mask``).
    """
    B, Hq, Tq, D = q.shape
    Hkv = k.shape[1]
    if window is not None and (tree_mask is not None or not causal):
        raise ValueError("window requires causal=True and no tree_mask")
    if Hq % Hkv != 0:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    G = Hq // Hkv
    Tk = k.shape[2]
    s = _default_scale(D, scale)
    if tree_mask is not None:
        if not causal:
            raise ValueError("tree_mask requires causal=True")
        if tree_mask.shape != (B, Tq, Tq):
            raise ValueError(
                f"tree_mask must be (B, Tq, Tq) = {(B, Tq, Tq)}, got "
                f"{tree_mask.shape}"
            )

    if Tk == 0:  # empty shard contributes the safe-softmax identity
        return (
            jnp.zeros_like(q),
            jnp.full((B, Hq, Tq), NEG_INF, jnp.float32),
        )

    from tree_attention_tpu.ops.block_utils import split_kv_blocks, tile_mask

    qf = (q.astype(jnp.float32) * s).reshape(B, Hkv, G, Tq, D)
    kb, vb, num_blocks, blk = split_kv_blocks(k, v, block_size)

    m0 = jnp.full((B, Hkv, G, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Tq), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, Tq, D), jnp.float32)

    def compute(carry, inputs):
        m_prev, l_prev, acc = carry
        blk_idx, k_blk, v_blk = inputs
        logits = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qf, k_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(jnp.float32),
        )
        if tree_mask is None:
            valid = tile_mask(Tq, blk, blk_idx, Tk, q_offset, kv_offset,
                              causal, window)
            logits = jnp.where(valid[None, None, None], logits, NEG_INF)
        else:
            # Tree-window rule: below the window everything is visible,
            # inside it the packed ancestor mask decides, past it nothing
            # is (the plain causal rule is the lower-triangular special
            # case). ``rel`` is the KV position relative to the window
            # start q_offset.
            col = blk_idx * blk + lax.broadcasted_iota(
                jnp.int32, (Tq, blk), 1
            )
            rel = kv_offset + col - q_offset  # (Tq, blk)
            taken = jnp.take_along_axis(
                tree_mask,
                jnp.broadcast_to(
                    jnp.clip(rel, 0, Tq - 1)[None], (B, Tq, blk)
                ),
                axis=2,
            )
            valid = (col < Tk)[None] & (
                (rel < 0)[None] | ((rel < Tq)[None] & taken)
            )
            logits = jnp.where(valid[:, None, None], logits, NEG_INF)

        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(jnp.where(jnp.isneginf(m_prev), NEG_INF, m_prev - m_safe))
        p = jnp.exp(logits - m_safe[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32),
            precision=matmul_precision(jnp.float32),
        )
        return m_new, l_new, acc_new

    def body(carry, inputs):
        if not causal:
            return compute(carry, inputs), None
        # Skip fully-masked blocks: a block is live iff its most visible
        # pairing (last query row, first key column) is unmasked. This makes
        # causal work proportional to live tiles — the property the zigzag
        # layout balances across shards (the Pallas kernels skip via
        # pl.when; this is the same cull for the jnp fallback).
        blk_idx = inputs[0]
        live = (q_offset + Tq - 1) >= (kv_offset + blk_idx * blk)
        return lax.cond(live, compute, lambda c, _: c, carry, inputs), None

    idxs = jnp.arange(num_blocks)
    (m, l, acc), _ = lax.scan(body, (m0, l0, acc0), (idxs, kb, vb))
    out, lse = finalize(acc, m, l, q.dtype)
    return out.reshape(B, Hq, Tq, D), lse.reshape(B, Hq, Tq)


def merge_partials(outs: jax.Array, lses: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard ``(out, lse)`` partials along a leading stacked axis.

    The local-device form of the tree reduction: given ``outs`` of shape
    ``(S, ..., D)`` and ``lses`` of shape ``(S, ...)`` from S KV shards,
    recombine into the exact global softmax via the safe-softmax monoid:
    ``m = max_i lse_i; num = Σ out_i · e^{lse_i − m}; den = Σ e^{lse_i − m}``.

    This is what the reference's three allreduces compute across ranks
    (``model.py:108,114-115``) — here as a pure function, reusable both in
    tests and inside the split-KV decode kernel. Its second caller on the
    serving path joins two partials of ONE layer on one chip: an EVA
    layer's exact rows of the open window and its summary rows of the
    closed ones (``models/hybrid.py`` ``eva_mixer``), one softmax over both.
    """
    m = jnp.max(lses, axis=0)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w = jnp.exp(lses - m_safe[None])
    den = jnp.sum(w, axis=0)
    num = jnp.sum(outs.astype(jnp.float32) * w[..., None], axis=0)
    return finalize_merge(num, den, m, outs.dtype)


def finalize_merge(
    num: jax.Array, den: jax.Array, m: jax.Array, out_dtype
) -> Tuple[jax.Array, jax.Array]:
    """Normalise reduced safe-softmax state into ``(out, lse)``.

    The ONE definition of the merge epilogue — rows with no visible keys
    (``den <= 0``) emit 0 / −inf — shared by :func:`merge_partials`, the
    tree merge (``parallel/tree.py``), and both ring paths
    (``parallel/ring.py``), so the families' numerics cannot diverge.
    """
    empty = den <= 0.0
    den_safe = jnp.where(empty, 1.0, den)
    out = jnp.where(empty[..., None], 0.0, num / den_safe[..., None])
    lse = jnp.where(empty, NEG_INF, m + jnp.log(den_safe))
    return out.astype(out_dtype), lse.astype(jnp.float32)
