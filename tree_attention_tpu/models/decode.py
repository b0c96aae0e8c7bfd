"""Autoregressive decoding: sharded KV cache + prefill/step/generate.

BASELINE.json config 4 ("GQA decode: 1-token Q against 256k-token sharded KV
cache") is the inference shape the reference gestures at but never builds — its
driver decodes one token against freshly random KV and discards the result
(``/root/reference/model.py:129-155``). This module provides the real thing:

- :class:`KVCache` — a pytree of per-layer K/V buffers ``(L, B, Hkv, Tmax, D)``
  plus a traced per-slot ``length`` vector ``(B,)``. Under a mesh the buffers
  are **sequence-sharded** (``P(None, data, model, seq, None)``), so a
  256k-token cache lives as Tmax/N-token shards — context capacity scales
  with the mesh, the point of tree attention.
- :func:`forward_step` — one model step over ``Tq`` new tokens per slot:
  writes each slot's K/V rows at that slot's own ``[length[i], length[i]+Tq)``
  (a vmapped dynamic-update over batch) and attends causally against the
  whole buffer. Static shapes throughout (``length`` is data, not shape):
  one compilation serves every step AND every mixture of per-slot lengths —
  the property continuous batching (:mod:`tree_attention_tpu.serving`)
  is built on. Prefill is the same function with the prompt as one big step.
  With ``n_tokens`` (a per-slot ``(B,)`` valid-count vector) the step goes
  **mixed-Tq**: slot ``i`` consumes only its first ``n_tokens[i]`` rows of
  the padded ``(B, Tq)`` token matrix — the shape a stall-free serving tick
  needs, where decode slots (one token) and prefill chunks (up to ``Tq``
  tokens) share ONE compiled program.
- :func:`forward_packed_step` — the same step over only the rows that carry
  a token: a compact chunk group ``(C, Tq)`` beside one decode row a slot,
  ``C·Tq + S`` rows where the padded matrix has ``S·Tq``. Both run ONE layer
  body (:func:`_step_layers`) over groups of rows (:class:`_RowGroup`: a
  group is a table and lengths); the serving tick with a prompt chunk.
- :func:`generate` — prefill + ``lax.scan`` of single-token steps, greedy or
  temperature sampling, donate-friendly (all slots in lockstep — the
  equal-lengths special case of the ragged machinery).

Masking needs no separate "valid length" machinery: slot ``i``'s query ``j``
sits at global position ``length[i] + j`` and the causal rule
``q_pos >= k_pos`` already hides every cache row ``>= length[i]`` (they are
that slot's future) — per-row offsets, same online-softmax monoid. Cache
attention routes through :func:`tree_decode
<tree_attention_tpu.parallel.tree.tree_decode>` on a sequence-parallel mesh
(replicated Q, one pmax + one fused psum) and through :func:`flash_decode
<tree_attention_tpu.ops.decode.flash_decode>` (split-KV) on a single device —
both take the per-slot ``(B,)`` ``q_position``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tree_attention_tpu import obs
from tree_attention_tpu.obs import scopes
from tree_attention_tpu.models.transformer import (
    Params,
    TransformerConfig,
    _unheads,
    _mlp_block,
    embed,
    gqa_qkv,
    norm_rows,
    rms_norm,
    unembed,
)

# Cache observability. forward_step is normally jitted (generate() scans
# it), so these count traces/dispatches; the capacity gauge is a point
# value either way. Execution-true generated-token totals live in the CLI
# generate loop.
_CACHE_CAPACITY = obs.gauge(
    "kv_cache_capacity_tokens",
    "capacity of the most recently allocated KV cache (tokens)",
)
_CACHE_ALLOCS = obs.counter(
    "kv_cache_allocs_total",
    "KV cache allocations",
    labels=("sharded",),
)
_STEP_DISPATCH = obs.counter(
    "forward_step_dispatch_total",
    "forward_step dispatches by cache kind (trace-time under jit)",
    labels=("cache",),
)
_CACHE_QUANTIZE = obs.counter(
    "kv_cache_quantize_total",
    "whole-cache int8 quantizations (quantize-after-prefill)",
)
from tree_attention_tpu.ops.block_utils import AlignedWindow, ChunkSummaries
from tree_attention_tpu.ops.decode import flash_decode
from tree_attention_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_SEQ,
    prune_axes,
)
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("models.decode")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Per-layer KV buffers ``(L, B, Hkv, Tmax, D)`` and per-slot lengths."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # (B,) int32 — tokens written so far, per slot

    @property
    def capacity(self) -> int:
        return self.k.shape[3]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Paged KV: one block pool, per-slot block tables (PagedAttention).

    The contiguous :class:`KVCache` pins capacity at ``B × Tmax`` whether
    slots are full or empty; the paged layout (vLLM's PagedAttention,
    arXiv:2309.06180) stores KV in a single pool of ``N`` fixed-size
    blocks — ``k``/``v`` are ``(L, N, Hkv, block, D)`` — and each slot is
    a **block table** row: ``table[i, j]`` names the physical pool block
    holding slot ``i``'s tokens ``[j·block, (j+1)·block)``. Slot capacity
    is logical (``NB · block`` via the table width); physical blocks are
    allocated on demand by the host-side allocator
    (:mod:`tree_attention_tpu.serving.block_pool`), so total memory is
    ``N`` blocks regardless of slot count, and two slots may map the SAME
    physical block (copy-free shared prefixes — a radix-cache hit is a
    table write, not a gather). Unwritten table entries must stay at a
    valid pool index (0): the causal mask hides every position past
    ``length[i]``, so a garbage block is never *visible*, but the gather
    and the Pallas index maps still dereference it.

    :func:`forward_step` never slices a replicated pool by layer: it
    carries ``k``/``v`` whole through the layer loop and reaches layer
    ``l`` by offset, ``table + l·N`` into the ``(L·N, Hkv, block, D)``
    view — a bitcast, so the pool that enters a tick, the kernels'
    operand and the pool that leaves are one buffer (see
    :func:`_paged_pool_write` for what that asks of the write, and
    :func:`_pool_write` for the two granules it is made at).
    """

    k: jax.Array       # (L, N, Hkv, block, D) pool
    v: jax.Array       # (L, N, Hkv, block, D) pool
    table: jax.Array   # (B, NB) int32 — physical block per logical block
    length: jax.Array  # (B,) int32 — tokens written so far, per slot

    @property
    def capacity(self) -> int:
        return self.table.shape[1] * self.k.shape[3]

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def blocks(self) -> int:
        return self.k.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedLatentCache:
    """The paged cache of a latent-attention model: ONE pool of
    ``[c_kv | k_rope | pad]`` rows, ``(L, N, block, row)``, under the
    same block tables, lengths, allocator and radix tree as
    :class:`PagedKVCache`. A row serves every head as key and (its first
    ``kv_rank`` lanes) as value, so there is no second pool to hold it
    again, and no head axis. ``row`` is a multiple of the chip's 128 lanes
    (``LatentAttention.row_pad`` zero lanes after the values): at 576 the
    TPU compiler copied the whole pool into a layout of its own before
    every launch of the kernel (PERF.md, PR 27). It rides
    :func:`forward_step`'s layer loops as carry and is written by the one
    :func:`_pool_write`, which sees it as ``(L, N, 1, block, row)``
    (a bitcast)."""

    kv: jax.Array      # (L, N, block, row) pool
    table: jax.Array   # (B, NB) int32 — physical block per logical block
    length: jax.Array  # (B,) int32 — tokens written so far, per slot

    @property
    def capacity(self) -> int:
        return self.table.shape[1] * self.kv.shape[2]

    @property
    def block(self) -> int:
        return self.kv.shape[2]

    @property
    def blocks(self) -> int:
        return self.kv.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedHybridCache:
    """The paged cache of a model whose layers are of several kinds: K/V
    pools for its rotary-GQA attention layers, as :class:`PagedKVCache`
    holds them but for heads of fewer than 128 lanes laid side by side
    (``TransformerConfig.kv_pack``): ``(attention layers, N, Hkv / p,
    block, p x D)``; and beside them a ``tail`` pool ``(conv layers, N, 2 x
    hidden)`` for its gated short-convolution layers, a block's two rows
    side by side on the lanes, under the SAME block ids and the SAME table.
    (As ``(N, 2, hidden)`` the compiler tiles the pair of rows on its own
    and every flat view of the pool is a copy of it: PERF.md, PR 33.)

    A conv layer's state at position ``p`` is its last two gated inputs
    ``z_{p-1}``, ``z_{p-2}``, whatever the context. Block ``j``'s tail
    entry holds, for every conv layer, the ``z`` of the two highest
    positions written into the block so far, position ``q`` in half ``q %
    2`` of the row (a block is even, so of any two neighbouring positions
    each has a half of its own): a row at ``p`` reads ``z_{p-1}`` and ``z_{p-2}``
    through the table entries of the blocks that hold those positions
    (zero before position 0) and writes its own ``z_p`` over ``z_{p-2}``.
    So a FULL block's tail is final, and it is exactly the state the
    position after the block needs: a prefix hit of whole blocks stays a
    table update, a fork's copy of its partial block
    (:func:`copy_pool_block`) carries the tail with it, a freed slot needs
    no reset, and the allocator, the radix tree and the engine's per-slot
    lists hold nothing for it. What the tail cannot do is roll back: a
    draft that is rejected has overwritten it.

    A model with expert layers under rotary-GQA attention and no conv
    layer is served from this cache too, its tail pool of depth 0."""

    k: jax.Array       # (attention layers, N, Hkv / p, block, p x D) pool
    v: jax.Array       # (attention layers, N, Hkv / p, block, p x D) pool
    tail: jax.Array    # (conv layers, N, 2 x hidden) pool
    table: jax.Array   # (B, NB) int32 — physical block per logical block
    length: jax.Array  # (B,) int32 — tokens written so far, per slot

    @property
    def capacity(self) -> int:
        return self.table.shape[1] * self.k.shape[3]

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def blocks(self) -> int:
        return self.k.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedWindowCache:
    """The paged cache of a model whose attention layers are of two kinds:
    layers that see their whole context, whose K/V rows lie in the pools
    ``k`` / ``v`` ``(full layers, N, Hkv, block, D)`` under ``table`` as
    :class:`PagedKVCache`'s do, and sliding-window layers (a row at ``t``
    sees ``(t - window, t]``), whose rows lie in pools of their own, ``wk``
    / ``wv`` ``(window layers, Nw, Hkv, block, D)``, under a SECOND table
    ``wtable`` of the same width and block size.

    ``wtable[i, j]`` names the window-pool block that holds slot ``i``'s
    tokens ``[j * block, (j + 1) * block)`` for every window layer, as long
    as some row still to be computed for the slot can see one of them;
    behind the slot's window the entry names no block of the slot's (it is
    0, as an unwritten entry is) and the block has gone back to the window
    pool's allocator (``serving/block_pool.py`` ``WindowBlocks``). So what
    a slot holds for a window layer does not grow with its length: at most
    ``ceil((window + chunk) / block) + 1`` blocks while a chunk is written,
    ``ceil(window / block) + 1`` in decode. The kernels never read behind
    the window: a window layer's work list starts at the step that holds
    the lowest visible position (``ops/pallas_decode.py`` ``paged_plan``
    with ``window``) and the mask hides the rest of that step. One
    ``length`` serves both tables: a token is a row in every layer.

    A model of EVA layers (``cfg.cache_kind == "eva"``) is served from the
    same two pools under another row rule, EVERY layer in both: ``wk`` /
    ``wv`` hold its exact rows, a token a row, for as long as the slot's
    ALIGNED window is open (``wtable`` as above; behind ``w0 = (t // window)
    * window`` the blocks go back, a whole window's at once), and ``k`` /
    ``v`` hold one SUMMARY row for every ``chunk`` positions, written when
    the chunk's last position is and kept for the request's life. Row ``c``
    of a slot's summaries is row ``c % block`` of block ``table[i, c //
    block]``: a block of ``table`` spans ``block * chunk`` positions, so
    that table is ``chunk`` times narrower than ``wtable``."""

    k: jax.Array        # (full layers, N, Hkv, block, D) pool
    v: jax.Array        # (full layers, N, Hkv, block, D) pool
    wk: jax.Array       # (window layers, Nw, Hkv, block, D) pool
    wv: jax.Array       # (window layers, Nw, Hkv, block, D) pool
    table: jax.Array    # (B, NB) int32: the full layers' blocks
    wtable: jax.Array   # (B, NB) int32: the window layers' blocks
    length: jax.Array   # (B,) int32: tokens written so far, per slot

    @property
    def capacity(self) -> int:
        # The table with an entry for every ``block`` positions.
        return self.wtable.shape[1] * self.k.shape[3]

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def blocks(self) -> int:
        return self.k.shape[1]

    @property
    def window_blocks(self) -> int:
        return self.wk.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedStateCache:
    """The cache of a model whose mixers are state-space layers beside a
    few attention layers, or both side by side in every layer (a
    ``"parallel"`` layer is an attention layer AND a state-space layer:
    the two depths are then the model's): the attention layers' K/V rows
    in paged pools under the block table, as :class:`PagedKVCache` holds
    them, AND a recurrent state a slot for every state-space layer, which
    no table indexes: ``ssm_state`` ``(ssm layers, slots) + StateSpace.state_shape``
    float32 and ``ssm_tail`` ``(ssm layers, slots, (taps - 1) x
    conv_dim)``, the last pre-activation rows of the mixer's short
    convolution, a slot's rows side by side on the lanes (as ``(slots, taps
    - 1, conv_dim)`` the compiler tiles the three rows on its own and copies
    the pool to and from the layout its scatter wants, in every tick
    program: the hybrid pool's tails taught the same, PERF.md, PR 33).

    Every token rewrites a layer's state whole, so a state has no block
    structure and nothing of it can be shared, restored or rolled back: a
    prefix hit, a fork and a rejected draft would each need the state as it
    was at another position, and are refused by this cache kind's name
    (``"state"``). Three rules keep the engine's life simple
    (``models/hybrid.py`` ``ssm_mixer``): a member whose first position is
    0 starts from a zero state and a zero tail, so a reused slot needs no
    reset; a row past a member's valid count leaves the state bit for bit;
    a slot with no row in a program is neither read nor written."""

    k: jax.Array          # (attention layers, N, Hkv, block, D) pool
    v: jax.Array          # (attention layers, N, Hkv, block, D) pool
    ssm_state: jax.Array  # (ssm layers, slots, H / pack, d_state, pack x P)
    ssm_tail: jax.Array   # (ssm layers, slots, (taps - 1) x conv_dim)
    table: jax.Array      # (B, NB) int32: physical block per logical block
    length: jax.Array     # (B,) int32: tokens written so far, per slot

    @property
    def capacity(self) -> int:
        return self.table.shape[1] * self.k.shape[3]

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def blocks(self) -> int:
        return self.k.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedStateWindowCache:
    """The cache of a decoder that feeds a second decoder (``cfg.cache_kind``
    ``"state_window"``): a state AND a window at once. What
    :class:`PagedWindowCache` holds, the sliding-window layers' rows in
    ``wk`` / ``wv`` under ``wtable`` (a bounded number of blocks a slot) and
    the full-context rows in ``k`` / ``v`` under ``table``; and what
    :class:`PagedStateCache` holds, a recurrent state and a conv tail a slot
    for every Mamba-1 layer: ``ssm_state`` ``(ssm1 layers, slots, d_state,
    inner)`` float32 (``Mamba1.state_shape``) and ``ssm_tail`` ``(ssm1
    layers, slots, (taps - 1) x inner)``.

    ``k`` / ``v`` are ONE layer deep: the shared full-attention layer writes
    a token's row and every cross layer above it reads the same rows again
    (no ``W_k``, no ``W_v``, no write), so a token costs one layer's rows
    however many layers attend to it. Every K/V pool lays a PAIR of
    neighbouring heads side by side on a row (``(layers, N, Hkv / 2, block,
    2 x D)``): differential attention's value pair (``models/hybrid.py``
    ``diff_branch``). The state pool's three rules hold as they are (a
    member at position 0 starts from zero, a row past the valid count
    leaves state and tail bit for bit, a slot with no row is neither read
    nor written), and nothing of a state can be shared, restored or rolled
    back."""

    k: jax.Array          # (1, N, Hkv / 2, block, 2 x D): the shared rows
    v: jax.Array          # (1, N, Hkv / 2, block, 2 x D)
    wk: jax.Array         # (window layers, Nw, Hkv / 2, block, 2 x D)
    wv: jax.Array         # (window layers, Nw, Hkv / 2, block, 2 x D)
    ssm_state: jax.Array  # (ssm1 layers, slots, d_state, inner) float32
    ssm_tail: jax.Array   # (ssm1 layers, slots, (taps - 1) x inner)
    table: jax.Array      # (B, NB) int32: the shared layer's blocks
    wtable: jax.Array     # (B, NB) int32: the window layers' blocks
    length: jax.Array     # (B,) int32: tokens written so far, per slot

    @property
    def capacity(self) -> int:
        return self.table.shape[1] * self.k.shape[3]

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def blocks(self) -> int:
        return self.k.shape[1]

    @property
    def window_blocks(self) -> int:
        return self.wk.shape[1]


# The caches with a second table, and those with a state a slot.
_TWO_TABLES = (PagedWindowCache, PagedStateWindowCache)
_SLOT_STATES = (PagedStateCache, PagedStateWindowCache)


def cache_pools(cache) -> Dict[str, jax.Array]:
    """A paged cache's block pools by field name, every one ``(L, N, ...)``
    with the block on axis 1: what a block copy, a leak check or a byte
    count has to visit, whatever the model caches. (A
    :class:`PagedWindowCache`'s window pools are indexed by another
    allocator's ids: :func:`window_pools`.)"""
    if isinstance(cache, PagedLatentCache):
        return {"kv": cache.kv}
    pools = {"k": cache.k, "v": cache.v}
    if isinstance(cache, PagedQuantKVCache):
        pools.update(k_scale=cache.k_scale, v_scale=cache.v_scale)
    if isinstance(cache, PagedHybridCache):
        pools.update(tail=cache.tail)
    return pools


def cache_token_bytes(cache) -> int:
    """Bytes one cached token takes over all layers, read from the arrays
    (what a block holds whatever its tokens is not counted: the scales of
    an int8 pool, a conv layer's tail; :func:`cache_block_fixed_bytes`)."""
    if isinstance(cache, PagedLatentCache):
        return int(cache.kv.shape[0] * cache.kv.shape[3]
                   * cache.kv.dtype.itemsize)
    k = cache.k  # (L, B|N, Hkv, T|block, D)
    return int(2 * k.shape[0] * k.shape[2] * k.shape[4] * k.dtype.itemsize)


def window_pools(cache) -> Dict[str, jax.Array]:
    """The pools under a cache's SECOND table (``wtable``), by field name:
    a :class:`PagedWindowCache`'s (or a :class:`PagedStateWindowCache`'s)
    window layers' K and V; none for every other cache."""
    if isinstance(cache, _TWO_TABLES):
        return {"wk": cache.wk, "wv": cache.wv}
    return {}


def cache_block_fixed_bytes(cache) -> int:
    """Bytes a pool block takes beside its tokens' rows: the conv layers'
    two-row tails of a :class:`PagedHybridCache`, 0 for every other
    cache."""
    if not isinstance(cache, PagedHybridCache):
        return 0
    t = cache.tail  # (conv layers, N, 2 x hidden)
    return int(t.shape[0] * t.shape[2] * t.dtype.itemsize)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedQuantKVCache:
    """int8 paged KV: int8 block pools + per-BLOCK scale scalars.

    Scales ride the POOL (``(L, N, Hkv)`` — one float per layer, physical
    block, and KV head), not the slot (ISSUE 13): a published block
    carries everything needed to dequantize it, so int8 blocks share
    through the radix tree exactly like exact blocks — roughly doubling
    effective pool capacity at the same device bytes. The
    quantize-after-prefill contract becomes per block: each prompt
    block's scale is the absmax of ITS rows at final-chunk quantization
    (:func:`quantize_paged_blocks`), and decode rows appended later
    quantize under the slot's **anchor** scale — the scale of the block
    holding the slot's last pre-write row — which every block the write
    *enters* (first row) inherits. All rows of a block are therefore
    quantized under the block's own current scale, whichever slot wrote
    them, and dequantization (per-block scalar, commuting out of the
    score matmul — the property that keeps the int8-MXU q8q kernel's
    post-matmul rescale a scalar multiply) is always consistent.
    """

    k: jax.Array        # (L, N, Hkv, block, D) int8 pool
    v: jax.Array        # (L, N, Hkv, block, D) int8 pool
    k_scale: jax.Array  # (L, N, Hkv) float32 — per POOL block
    v_scale: jax.Array  # (L, N, Hkv) float32 — per POOL block
    table: jax.Array    # (B, NB) int32
    length: jax.Array   # (B,) int32

    @property
    def capacity(self) -> int:
        return self.table.shape[1] * self.k.shape[3]

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def blocks(self) -> int:
        return self.k.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantKVCache:
    """int8 per-layer KV buffers with frozen per-channel scales.

    The quantize-after-prefill shape: the prompt is prefilled in the model
    dtype, :func:`quantize_cache` converts the filled buffers once (scales =
    per-channel absmax of the prefix), and subsequent decode steps append
    new rows quantized under those *frozen* scales (outliers clamp to
    ±127). Halves the KV bytes the decode step streams — the step's entire
    cost at long context — at int8 quantization error. The exact
    :class:`KVCache` stays the default.
    """

    k: jax.Array        # (L, B, Hkv, Tmax, D) int8
    v: jax.Array        # (L, B, Hkv, Tmax, D) int8
    k_scale: jax.Array  # (L, B, Hkv, 1, D) float32
    v_scale: jax.Array  # (L, B, Hkv, 1, D) float32
    length: jax.Array   # (B,) int32 — per slot

    @property
    def capacity(self) -> int:
        return self.k.shape[3]


def quantize_cache(cache: KVCache) -> QuantKVCache:
    """Per-channel int8 quantization of a (typically just-prefilled) cache.

    Scales come from the filled prefix only — unwritten capacity rows are
    zeros and must not shrink the scale; rows appended later clamp to the
    prefix's range (attention values live in the prompt's activation
    distribution, so the clamp is rare in practice — measured by the
    long-horizon drift test in ``tests/test_decode.py``).

    Degenerate case (ADVICE r2): a channel that is *all-zero across the
    prefill prefix* gets the contract's fallback scale of 1.0
    (:func:`quantize_symmetric_int8`), so rows appended later quantize as
    ``round(x)`` — sub-0.5 magnitudes collapse to 0 (absolute error ≤ 0.5,
    relative error up to 100%). This is deliberate: no frozen scale can be
    right for a channel the prefix carried no information about, and the
    1.0 fallback bounds the *absolute* error where a tiny epsilon scale
    would instead clamp ordinary activations to ~0 (unbounded relative
    error the other way). Channels that are zero over a real prompt are
    almost always dead (projection rows ~0), where any scale is exact.
    """

    from tree_attention_tpu.ops.pallas_decode import quantize_symmetric_int8

    _CACHE_QUANTIZE.inc()
    k_q, k_s = quantize_symmetric_int8(cache.k, axis=3)  # over tokens
    v_q, v_s = quantize_symmetric_int8(cache.v, axis=3)
    return QuantKVCache(
        k=k_q, v=v_q, k_scale=k_s, v_scale=v_s, length=cache.length
    )


def _quantize_rows(rows: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize new (B, Hkv, Tq, D) rows under one layer's frozen scale."""
    return jnp.clip(
        jnp.round(rows.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)


def quantize_paged_blocks(
    k: jax.Array, v: jax.Array, block: int, valid: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-BLOCK symmetric int8 quantization of a just-prefilled B=1 cache.

    ``k``/``v`` are ``(L, 1, Hkv, T, D)`` exact rows, ``valid`` the token
    count (rows at ``>= valid`` must already be zeroed by the caller —
    they quantize to 0 under any scale, and a zero block takes the
    contract's fallback scale of 1.0 exactly like
    :func:`quantize_symmetric_int8`'s zero channels). ``T`` pads up to a
    whole number of ``block``-token spans; the scale of span ``j`` is
    ``absmax`` over that span's valid rows and ALL channels — one scalar
    per ``(layer, block, head)``, the granularity that lets a scale ride
    the pool next to its block and commute out of the score matmul
    (:class:`PagedQuantKVCache`). Returns ``(k_q, v_q, k_scale,
    v_scale)`` with int8 rows shaped like the (padded) inputs and scales
    ``(L, nb, Hkv)``.
    """
    del valid  # rows past it are pre-zeroed; absmax ignores them
    L, B, Hkv, T, D = k.shape
    nb = -(-T // block)
    pad = nb * block - T

    def one(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        xf = x.astype(jnp.float32)[:, 0]  # (L, Hkv, T, D)
        if pad:
            xf = jnp.pad(xf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        xb = xf.reshape(L, Hkv, nb, block, D)
        amax = jnp.max(jnp.abs(xb), axis=(3, 4))  # (L, Hkv, nb)
        scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
        q = jnp.clip(
            jnp.round(xb / scale[:, :, :, None, None]), -127, 127
        ).astype(jnp.int8)
        q = q.reshape(L, Hkv, nb * block, D)[:, :, :T]
        return q[:, None], jnp.moveaxis(scale, 1, 2)  # (L,1,Hkv,T,D), (L,nb,Hkv)

    (k_q, k_s) = one(k)
    (v_q, v_s) = one(v)
    return k_q, v_q, k_s, v_s


def gather_kv_blocks(
    pool_k: jax.Array,
    pool_v: jax.Array,
    ids: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> Tuple[jax.Array, ...]:
    """The demote gather (ISSUE 13): stack pool blocks ``ids`` for ONE
    batched D2H fetch — ``(nb, L, Hkv, block, D)`` K and V rows, plus
    ``(nb, L, Hkv)`` per-block scale scalars for an int8 pool. Padded
    ``ids`` entries clip to block 0; the host pool ignores their rows
    (the id bucket bounds compiles, exactly like the prefix gathers)."""
    idx = jnp.clip(ids, 0, pool_k.shape[1] - 1)
    out = [
        jnp.moveaxis(pool_k[:, idx], 1, 0),
        jnp.moveaxis(pool_v[:, idx], 1, 0),
    ]
    if k_scale is not None:
        out.append(jnp.moveaxis(k_scale[:, idx], 1, 0))
        out.append(jnp.moveaxis(v_scale[:, idx], 1, 0))
    return tuple(out)


def scatter_kv_blocks(
    pool_k: jax.Array,
    pool_v: jax.Array,
    ids: jax.Array,
    k_rows: jax.Array,
    v_rows: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    ks_rows: Optional[jax.Array] = None,
    vs_rows: Optional[jax.Array] = None,
) -> Tuple[jax.Array, ...]:
    """The restore scatter (ISSUE 13): land one H2D batch of host-tier
    blocks into freshly allocated pool rows ``ids`` (padded entries
    point past the pool and DROP). ``k_rows``/``v_rows`` are the
    ``(nb, L, Hkv, block, D)`` staged host bytes; int8 pools also take
    their per-block scale scalars. Donated by the engine: one dispatch
    restores a whole matched path. Returns the updated pool arrays
    (+ scale arrays when quantized)."""
    out = [
        pool_k.at[:, ids].set(jnp.moveaxis(k_rows, 0, 1), mode="drop"),
        pool_v.at[:, ids].set(jnp.moveaxis(v_rows, 0, 1), mode="drop"),
    ]
    if k_scale is not None:
        out.append(
            k_scale.at[:, ids].set(jnp.moveaxis(ks_rows, 0, 1),
                                   mode="drop")
        )
        out.append(
            v_scale.at[:, ids].set(jnp.moveaxis(vs_rows, 0, 1),
                                   mode="drop")
        )
    return tuple(out)


def copy_pool_block(cache, src: jax.Array, dst: jax.Array,
                    wsrc: Optional[jax.Array] = None,
                    wdst: Optional[jax.Array] = None):
    """The copy-on-write fork's ONE device copy (ISSUE 15): duplicate
    pool block ``src`` into freshly allocated block ``dst`` — K and V
    rows, plus the per-block scale scalars under int8, so the copy is
    self-contained whichever tier quantization runs at. Full ancestor
    blocks are SHARED by refcount (zero bytes); only the partial tail
    block a forked branch will append into needs its own copy, and this
    is that copy. ``src == dst`` degenerates to an identical-bytes
    self-write (the engine's no-partial-tail arc reuses one compiled
    program that way). Works on every paged cache (:func:`cache_pools`);
    ``wsrc`` / ``wdst`` name the same copy in the pools under a second
    table (:func:`window_pools`), whose ids are another allocator's."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    new = {
        name: pool.at[:, dst].set(pool[:, src])
        for name, pool in cache_pools(cache).items()
    }
    if wsrc is not None:
        wsrc = jnp.asarray(wsrc, jnp.int32)
        wdst = jnp.asarray(wdst, jnp.int32)
        new.update({
            name: pool.at[:, wdst].set(pool[:, wsrc])
            for name, pool in window_pools(cache).items()
        })
    return dataclasses.replace(cache, **new)


def insert_dequant_prefix(
    staging: KVCache,
    pool_k: jax.Array,
    pool_v: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    ids: jax.Array,
    matched: jax.Array,
) -> KVCache:
    """Dequantize matched int8 pool blocks into the B=1 staging cache.

    The int8 paged hit path (ISSUE 13): the slot references the matched
    int8 blocks IN PLACE through its table, but the suffix's exact
    staged prefill needs the prefix as activations-grade rows — this
    places ``matched`` dequantized tokens (``int8 · per-block scale``)
    at positions ``[0, matched)`` of staging slot 0 and sets its length
    (one donated gather, whatever the match). Re-quantizing these rows at
    final chunk reproduces the original int8 bytes exactly (absmax/127
    scaling round-trips int8 code points), so shared blocks never need
    rewriting.
    """
    nb = ids.shape[0]
    block = pool_k.shape[3]
    span = nb * block
    matched = jnp.asarray(matched, jnp.int32)
    idx = jnp.clip(ids, 0, pool_k.shape[1] - 1)

    def place(buf: jax.Array, pool: jax.Array, scale: jax.Array):
        rows = pool[:, idx]                       # (L, nb, Hkv, blk, D)
        s = scale[:, idx]                         # (L, nb, Hkv)
        rows = rows.astype(jnp.float32) * s[:, :, :, None, None]
        rows = jnp.moveaxis(rows, 1, 2)           # (L, Hkv, nb, blk, D)
        L, Hkv = rows.shape[0], rows.shape[1]
        rows = rows.reshape(L, Hkv, span, rows.shape[-1])
        cur = buf[:, 0]                           # (L, Hkv, cap, D)
        window = lax.dynamic_slice_in_dim(cur, 0, span, axis=2)
        valid = (
            jnp.arange(span, dtype=jnp.int32) < matched
        )[None, None, :, None]
        merged = jnp.where(valid, rows.astype(buf.dtype), window)
        cur = lax.dynamic_update_slice_in_dim(cur, merged, 0, axis=2)
        return cur[:, None]

    return KVCache(
        k=place(staging.k, pool_k, k_scale),
        v=place(staging.v, pool_v, v_scale),
        length=jnp.full_like(staging.length, matched),
    )


def init_cache(
    cfg: TransformerConfig,
    batch_size: int,
    max_len: int,
    *,
    mesh: Optional[Mesh] = None,
    data_axis: Optional[str] = AXIS_DATA,
    seq_axis: str = AXIS_SEQ,
    model_axis: Optional[str] = AXIS_MODEL,
) -> KVCache:
    """Allocate an empty cache; sequence-sharded over ``mesh`` when given."""
    shape = (cfg.cache_layers, batch_size, cfg.n_kv_heads, max_len, cfg.d_head)
    if mesh is not None:
        ax = prune_axes(
            mesh, {"data": data_axis, "seq": seq_axis, "model": model_axis}
        )
        spec = P(None, ax["data"], ax["model"], ax["seq"], None)
        if max_len % max(mesh.shape.get(seq_axis, 1), 1):
            raise ValueError(
                f"cache capacity {max_len} must divide over "
                f"{mesh.shape.get(seq_axis, 1)} '{seq_axis}' shards"
            )
        sharding = NamedSharding(mesh, spec)
        zeros = jax.jit(
            lambda: jnp.zeros(shape, cfg.dtype), out_shardings=sharding
        )
        k = zeros()
        v = zeros()
    else:
        k = jnp.zeros(shape, cfg.dtype)
        v = jnp.zeros(shape, cfg.dtype)
    if obs.REGISTRY.enabled:
        _CACHE_CAPACITY.set(max_len)
        _CACHE_ALLOCS.labels(sharded=str(mesh is not None).lower()).inc()
    return KVCache(k=k, v=v, length=jnp.zeros((batch_size,), jnp.int32))


def init_paged_cache(
    cfg: TransformerConfig,
    batch_size: int,
    max_len: int,
    blocks: int,
    *,
    block: int = 64,
    mesh: Optional[Mesh] = None,
    quantize: bool = False,
    kv_shard: str = "replicated",
    seq_axis: str = AXIS_SEQ,
    window_blocks: Optional[int] = None,
) -> Union[PagedKVCache, PagedQuantKVCache, PagedLatentCache,
           PagedHybridCache, "PagedWindowCache", "PagedStateCache",
           "PagedStateWindowCache"]:
    """Allocate a paged cache: one ``blocks``-block pool + empty tables,
    of the kind the model caches (``cfg.cache_kind``).

    ``max_len`` is the logical per-slot capacity (rounded up to a whole
    number of blocks — the table width); ``blocks`` is the POOL capacity
    shared by every slot, which may be far less than
    ``batch_size × max_len`` tokens (the point of paging). Under a mesh
    ``kv_shard`` picks the pool placement:

    - ``"replicated"`` (compat default): every device holds the whole
      pool — table entries place blocks at arbitrary token offsets, so no
      static sharding of the TOKEN axis can stay aligned with a sequence
      shard, and capacity is capped by one device's memory.
    - ``"seq"`` (ISSUE 18): shard the BLOCK axis instead — blocks are the
      unit of placement, not token ranges, so the arbitrary-offset
      argument above does not apply to them. Shard ``s`` of ``W`` owns
      global block ids ``[s·N/W, (s+1)·N/W)`` (``blocks`` must divide by
      the ``seq_axis`` size; callers round up), tables stay replicated
      with GLOBAL ids, and pool bytes per device drop to ``1/W`` — max
      servable context finally scales WITH the mesh. Int8 per-block
      scales shard with their pool slice. Attention runs the
      shard_map'd tree-monoid merge
      (:func:`~tree_attention_tpu.parallel.tree.paged_tree_decode`).

    ``quantize`` allocates int8 pools with per-slot unit scales — the
    same empty-cache fallback :func:`quantize_cache` produces, so a
    paged and a contiguous int8 server start bit-identical.

    ``window_blocks``: the capacity of the window layers' pool of a model
    with sliding-window layers, or of the exact rows' pool of a model of
    EVA layers (:class:`PagedWindowCache`), which no other model has.
    """
    if block < 1 or block & (block - 1):
        raise ValueError(f"kv block must be a power of two, got {block}")
    if blocks < 1:
        raise ValueError(f"paged pool needs >= 1 block, got {blocks}")
    if kv_shard not in ("replicated", "seq"):
        raise ValueError(
            f"kv_shard must be 'replicated' or 'seq', got {kv_shard!r}"
        )
    seq_sharded = kv_shard == "seq" and mesh is not None
    if seq_sharded:
        n_sh = max(mesh.shape.get(seq_axis, 1), 1)
        if blocks % n_sh:
            raise ValueError(
                f"kv_shard='seq': pool of {blocks} blocks must divide "
                f"over {n_sh} '{seq_axis}' shards — round the pool up"
            )
    nb = -(-max_len // block)
    if cfg.mla is not None:
        if quantize:
            raise ValueError(
                "int8 latent rows are not built: a latent-attention "
                "model's pool is served exact")
        if seq_sharded:
            raise ValueError(
                "a sequence-sharded latent pool (kv_shard='seq') is not "
                "built: the tree merge has no latent kernel")
        shape = (cfg.cache_layers, blocks, block, cfg.mla.row)
        pool = (
            jax.jit(lambda: jnp.zeros(shape, cfg.dtype),
                    out_shardings=NamedSharding(mesh, P()))()
            if mesh is not None else jnp.zeros(shape, cfg.dtype)
        )
        return PagedLatentCache(
            kv=pool, table=jnp.zeros((batch_size, nb), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
        )
    shape = (cfg.cache_layers, blocks, cfg.n_kv_heads, block, cfg.d_head)
    if cfg.cache_kind in ("window", "eva"):
        kind = cfg.cache_kind
        if quantize:
            raise ValueError(
                f"int8 rows under two tables are not built: the {kind} "
                f"pool is served exact")
        if seq_sharded:
            raise ValueError(
                f"a sequence-sharded {kind} pool (kv_shard='seq') is not "
                f"built: the tree merge has no lower edge")
        if not window_blocks or window_blocks < 1:
            raise ValueError(
                f"a model with sliding-window or EVA layers needs the "
                f"window pool's capacity (window_blocks), got "
                f"{window_blocks}")
        shapes = (shape, shape) + 2 * ((
            cfg.window_layers or cfg.eva_layers, window_blocks,
            cfg.n_kv_heads, block, cfg.d_head),)
        # An EVA layer's first table indexes summary rows, one a chunk.
        nb_first = -(-nb // cfg.chunk) if kind == "eva" else nb
        k, v, wk, wv = (
            jax.jit(lambda: tuple(jnp.zeros(s, cfg.dtype) for s in shapes),
                    out_shardings=NamedSharding(mesh, P()))()
            if mesh is not None
            else tuple(jnp.zeros(s, cfg.dtype) for s in shapes)
        )
        return PagedWindowCache(
            k=k, v=v, wk=wk, wv=wv,
            table=jnp.zeros((batch_size, nb_first), jnp.int32),
            wtable=jnp.zeros((batch_size, nb), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
        )
    if cfg.cache_kind == "state_window":
        if quantize or seq_sharded:
            raise ValueError(
                "int8 rows or a sequence-sharded pool (kv_shard='seq') "
                "beside a recurrent state and window blocks are not built: "
                "the state_window pool is served exact, replicated")
        if not window_blocks or window_blocks < 1:
            raise ValueError(
                f"a model with sliding-window layers needs the window "
                f"pool's capacity (window_blocks), got {window_blocks}")
        sm, p = cfg.ssm1, cfg.kv_pack
        row = (cfg.n_kv_heads // p, block, cfg.d_head * p)
        shapes = (
            ((cfg.cache_layers, blocks) + row, cfg.dtype),
            ((cfg.cache_layers, blocks) + row, cfg.dtype),
            ((cfg.window_layers, window_blocks) + row, cfg.dtype),
            ((cfg.window_layers, window_blocks) + row, cfg.dtype),
            ((cfg.ssm_layers, batch_size) + sm.state_shape, jnp.float32),
            ((cfg.ssm_layers, batch_size, (sm.taps - 1) * sm.inner),
             cfg.dtype))
        make = lambda: tuple(jnp.zeros(s, d) for s, d in shapes)  # noqa: E731
        k, v, wk, wv, state, tail = (
            jax.jit(make, out_shardings=NamedSharding(mesh, P()))()
            if mesh is not None else make())
        return PagedStateWindowCache(
            k=k, v=v, wk=wk, wv=wv, ssm_state=state, ssm_tail=tail,
            table=jnp.zeros((batch_size, nb), jnp.int32),
            wtable=jnp.zeros((batch_size, nb), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
        )
    if cfg.cache_kind == "state":
        if quantize:
            raise ValueError(
                "int8 rows beside a recurrent state are not built: the "
                "state pool is served exact")
        if seq_sharded:
            raise ValueError(
                "a sequence-sharded state pool (kv_shard='seq') is not "
                "built: a slot's state is one array, not blocks")
        sm = cfg.ssm
        shapes = (
            (shape, cfg.dtype), (shape, cfg.dtype),
            ((cfg.ssm_layers, batch_size) + sm.state_shape, jnp.float32),
            ((cfg.ssm_layers, batch_size, (sm.taps - 1) * sm.conv_dim),
             cfg.dtype))
        make = lambda: tuple(jnp.zeros(s, d) for s, d in shapes)  # noqa: E731
        k, v, state, tail = (
            jax.jit(make, out_shardings=NamedSharding(mesh, P()))()
            if mesh is not None else make())
        return PagedStateCache(
            k=k, v=v, ssm_state=state, ssm_tail=tail,
            table=jnp.zeros((batch_size, nb), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
        )
    if cfg.cache_kind == "hybrid":
        if quantize:
            raise ValueError(
                "int8 rows beside conv tails are not built: the hybrid "
                "pool is served exact")
        if seq_sharded:
            raise ValueError(
                "a sequence-sharded hybrid pool (kv_shard='seq') is not "
                "built: the conv tails are read through the whole table")
        if block % 2:
            raise ValueError(
                f"a conv layer's state is a two-row tail of a block: the "
                f"block ({block}) is even")
        kv = (cfg.cache_layers, blocks, cfg.n_kv_heads // cfg.kv_pack,
              block, cfg.d_head * cfg.kv_pack)
        shapes = (kv, kv, (cfg.conv_layers, blocks, 2 * cfg.d_model))
        k, v, tail = (
            jax.jit(lambda: tuple(jnp.zeros(s, cfg.dtype) for s in shapes),
                    out_shardings=NamedSharding(mesh, P()))()
            if mesh is not None
            else tuple(jnp.zeros(s, cfg.dtype) for s in shapes)
        )
        return PagedHybridCache(
            k=k, v=v, tail=tail,
            table=jnp.zeros((batch_size, nb), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
        )
    dtype = jnp.int8 if quantize else cfg.dtype
    sscale = None
    if mesh is not None:
        pool_p = P(None, seq_axis) if seq_sharded else P()
        sharding = NamedSharding(mesh, pool_p)
        zeros = jax.jit(
            lambda: jnp.zeros(shape, dtype), out_shardings=sharding
        )
        k = zeros()
        v = zeros()
        if quantize:
            sscale = NamedSharding(
                mesh, P(None, seq_axis) if seq_sharded else P()
            )
    else:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
    table = jnp.zeros((batch_size, nb), jnp.int32)
    length = jnp.zeros((batch_size,), jnp.int32)
    if obs.REGISTRY.enabled:
        _CACHE_CAPACITY.set(nb * block)
        _CACHE_ALLOCS.labels(sharded=str(mesh is not None).lower()).inc()
    if quantize:
        sshape = (cfg.cache_layers, blocks, cfg.n_kv_heads)
        ones = (
            jax.jit(lambda: jnp.ones(sshape, jnp.float32),
                    out_shardings=sscale)
            if sscale is not None
            else lambda: jnp.ones(sshape, jnp.float32)
        )
        return PagedQuantKVCache(
            k=k, v=v,
            # Per-BLOCK scale scalars (see the class docstring). Two
            # distinct buffers: the engine's donating steps may not
            # alias k_scale and v_scale. Unit scales = the empty-cache
            # fallback, same as quantize_symmetric_int8's zero-channel
            # contract.
            k_scale=ones(),
            v_scale=ones(),
            table=table, length=length,
        )
    return PagedKVCache(k=k, v=v, table=table, length=length)


def _paged_pool_write(
    pool: jax.Array,
    rows: jax.Array,
    table: jax.Array,
    start: jax.Array,
    n: jax.Array,
    layer: Union[int, jax.Array],
) -> jax.Array:
    """Write each slot's new token rows into layer ``layer`` of the pool.

    The paged mixed-Tq step's one write: ``pool`` is the WHOLE pool
    ``(L, N, Hkv, block, D)``, ``rows`` one layer's ``(B, Hkv, Tq, D)``,
    ``start``/``n`` per-slot ``(B,)`` vectors. Token ``j`` of slot ``i``
    (valid iff ``j < n[i]``) lands at physical block
    ``table[i, (start[i]+j)//block]`` row ``(start[i]+j) % block`` of that
    layer; invalid rows DROP, so the paged write needs none of the
    contiguous path's clamp-and-shift machinery — ragged and near-capacity
    cases fall out of the drop semantics.

    It moves whole BLOCKS: the ``Tq`` rows of a slot touch at most
    ``(Tq + block - 2)//block + 1`` consecutive logical blocks; each is
    read, overlaid with the rows that fall in it, and scattered back to
    ``layer·N + pb`` of the pool viewed as ``(L·N, Hkv, block, D)`` — a
    bitcast. A block no valid row falls in (an idle slot, padding, past
    capacity, a table entry outside the pool) is sent to index ``L·N``
    and dropped. Distinct slots never share a *writable* block (shared
    prefix blocks sit below ``start``, and only whole blocks are shared),
    so no two entries name one block and a block's other rows come back
    as they were.

    Why it is shaped as it is (ISSUE 25; the compiled tick is the
    criterion, ``tests/test_chip_compile.py``):

    - Indexing the pool's block and row dims apart (``pool.at[pb, :, off]``,
      the parent's write) made XLA's TPU compiler hold the pool in the
      scatter's preferred layout (block, row, head, D) while the Pallas
      kernels pin the default one: it copied the pool between the two in
      every layer and tick. A scatter that indexes the major dim alone
      wants no other layout, so the entry pool, the loop's carry, the
      kernels' operand and the output stay ONE buffer. (Not the view
      ``(L·N, Hkv·block·D)``: under the (8, 128) tiling that is no
      bitcast, and XLA rebuilt the pool round it.)
    - One ``D``-row per (slot, head, token), into ``(L·N·Hkv·block, D)``,
      compiles as clean but costs ~70 ns a row on the chip, dropped rows
      included: 18 ms of Yi-6B's mixed tick and 73 ms of Mistral-7B's
      (PERF.md section 6, PR 25). A block is 64-128 KB and a chunk tick
      moves 5 a slot.
    - The drop index lies past the WHOLE flat pool: the old per-layer
      sentinel ``N`` is block 0 of layer 1 there.
    """
    L, N, Hkv, block, D = pool.shape
    B, _, Tq, _ = rows.shape
    NB = table.shape[1]
    nblk = (Tq + block - 2) // block + 1  # blocks Tq consecutive rows touch
    lb = (start // block)[:, None] + jnp.arange(nblk, dtype=jnp.int32)
    pb = jnp.take_along_axis(table, jnp.clip(lb, 0, NB - 1), axis=1)
    # Token position of every row of every touched block, and the new row
    # (if any) that belongs there.
    pos = lb[:, :, None] * block + jnp.arange(block, dtype=jnp.int32)
    j = pos - start[:, None, None]  # (B, nblk, block)
    take = (
        (j >= 0) & (j < n[:, None, None])
        # Over-capacity safety: the contiguous path RAISES on overflow
        # eagerly; under jit this mask keeps a buggy caller's overflow
        # from landing in another slot's pool block through the clipped
        # table index above.
        & (pos < NB * block)
    )
    # A table entry outside the pool would land in another LAYER.
    live = take.any(axis=-1) & (pb >= 0) & (pb < N)
    flat = pool.reshape(L * N, Hkv, block, D)
    old = flat[layer * N + jnp.clip(pb, 0, N - 1)]  # (B, nblk, Hkv, block, D)
    new = jnp.take_along_axis(
        rows.astype(pool.dtype),
        jnp.clip(j, 0, Tq - 1).reshape(B, 1, nblk * block, 1),
        axis=2,
    ).reshape(B, Hkv, nblk, block, D)
    merged = jnp.where(
        take[:, :, None, :, None], jnp.swapaxes(new, 1, 2), old
    )
    idx = jnp.where(live, layer * N + pb, L * N)
    flat = flat.at[idx.reshape(-1)].set(
        merged.reshape(B * nblk, Hkv, block, D), mode="drop"
    )
    return flat.reshape(pool.shape)


def pool_write_path(tq: int) -> str:
    """Which of the paged pool's two writes a group of ``tq`` rows a slot
    takes: ``"row"`` (:func:`~tree_attention_tpu.ops.pallas_decode.paged_row_write`:
    the sublane tile that holds a slot's one new row, in one kernel for
    every pool of the layer) where a TPU serves a group of one row a slot,
    ``"block"`` (:func:`_paged_pool_write`) for a chunk group, a verify or
    tree tick, and off the TPU. One algorithm whose granule follows the row
    count: decided from what the program observes, the same for every
    model."""
    from tree_attention_tpu.ops import _on_tpu, _pallas_available

    return "row" if tq == 1 and _on_tpu() and _pallas_available() \
        else "block"


def _row_targets(table: jax.Array, start: jax.Array, n: jax.Array,
                 blocks: int, block: int) -> Tuple[jax.Array, jax.Array]:
    """``(block, row)`` a slot's ONE new token lands in, through its table
    of ``blocks``-block layers: what :func:`_paged_pool_write` works out for
    a group of one row a slot, with block -1 for the rows it drops (a slot
    with ``n`` 0, a position at or past ``NB * block``, a table entry
    outside ``[0, blocks)``). The same for every layer, so a step program
    works it out once before its layer loop (:func:`_plan_groups`)."""
    NB = table.shape[1]
    pb = jnp.take_along_axis(
        table, jnp.clip(start // block, 0, NB - 1)[:, None], axis=1)[:, 0]
    live = (n > 0) & (start < NB * block) & (pb >= 0) & (pb < blocks)
    return jnp.where(live, pb, -1), start % block


def _pool_write(
    pools: Tuple[jax.Array, ...],
    rows: Tuple[jax.Array, ...],
    table: jax.Array,
    start: jax.Array,
    n: jax.Array,
    layer: Union[int, jax.Array],
    at: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, ...]:
    """A group's new rows into layer ``layer`` of every pool of that layer
    (K and V; the one latent pool), by the path :func:`pool_write_path`
    names. Both leave the same bits: the row path is handed the ONE block
    and row a slot's token lands in (:func:`_row_targets`; ``at``, where
    the step worked them out before its layer loop) and no block for the
    rows the block path drops."""
    if pool_write_path(rows[0].shape[2]) == "block":
        return tuple(
            _paged_pool_write(p, r, table, start, n, layer)
            for p, r in zip(pools, rows))
    from tree_attention_tpu.ops.pallas_decode import paged_row_write

    L, N, Hkv, block, D = pools[0].shape
    ids, off = at if at is not None else _row_targets(
        table, start, n, N, block)
    out = paged_row_write(
        tuple(p.reshape(L * N, Hkv, block, D) for p in pools),
        tuple(r.astype(p.dtype) for p, r in zip(pools, rows)),
        ids, off, layer * N)
    return tuple(o.reshape(p.shape) for o, p in zip(out, pools))


def _pool_write_seq(
    pools: Tuple[jax.Array, ...],
    rows: Tuple[jax.Array, ...],
    table: jax.Array,
    start: jax.Array,
    n: jax.Array,
    *,
    mesh: Mesh,
    seq_axis: str,
) -> Tuple[jax.Array, ...]:
    """:func:`_pool_write` over sequence-SHARDED pools (ISSUE 18).

    Each pool is one layer's ``(N, Hkv, block, D)`` slice sharded on the
    block axis over ``seq_axis`` (a flat ``(L·N)`` view would cut that
    axis by layers, so these pools are not carried whole: the layer loop
    slices them, see :func:`forward_step`); the (replicated) ``table``
    carries GLOBAL block ids. Under ``shard_map`` each shard rebases the
    table to its own id range ``[s·N/W, (s+1)·N/W)`` and points every
    entry it does NOT own at ``N/W``, outside its local pool — which
    either write drops, so the local call writes precisely the rows whose
    blocks live here and drops the rest.
    No collectives: a block is owned by exactly one shard, so the union
    of the local writes IS the replicated write, bit for bit.
    """
    n_sh = mesh.shape[seq_axis]
    n_local = pools[0].shape[0] // n_sh

    def body(pools_l, rows_l, table_l, start_l, n_l):
        s = lax.axis_index(seq_axis)
        loc = table_l - s * n_local
        loc = jnp.where((loc >= 0) & (loc < n_local), loc, n_local)
        out = _pool_write(
            tuple(p[None] for p in pools_l), rows_l, loc, start_l, n_l, 0)
        return tuple(o[0] for o in out)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(seq_axis), P(), P(), P(), P()),
        out_specs=P(seq_axis),
        check_vma=False,
    )(pools, rows, table, start, n)


def paged_insert_slot(
    cache: Union[PagedKVCache, PagedQuantKVCache],
    slot: jax.Array,
    k_rows: jax.Array,
    v_rows: jax.Array,
    plen: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    lo: Union[int, jax.Array] = 0,
) -> Union[PagedKVCache, PagedQuantKVCache]:
    """Place a B=1 prefilled cache's rows into one slot's mapped blocks.

    What int8 staged admission ends with: ``k_rows`` / ``v_rows`` are
    ``(L, 1, Hkv, T, D)`` (the staging cache's rows, possibly already
    int8), token positions ``[lo, plen)`` scatter through the
    slot's table row (``plen``/``lo`` may be traced; rows outside drop),
    the slot's ``length`` becomes ``plen``, and — for a quantized cache —
    the prompt blocks' per-BLOCK scales (``(L, nb, Hkv)``, from
    :func:`quantize_paged_blocks`) land in the pool's scale arrays
    through the same table row. ``lo`` exists for the int8 prefix-hit
    path: the matched prefix's blocks are SHARED (tree-owned, already
    carrying their own scales) and must not be rewritten — ``lo`` is the
    block-aligned matched length, so only the slot's own suffix blocks
    take writes. The caller must have mapped blocks covering
    ``[0, plen)`` in the table first.
    """
    L, _, Hkv, T, D = k_rows.shape
    N, block = cache.blocks, cache.block
    row = lax.dynamic_index_in_dim(cache.table, slot, axis=0, keepdims=False)
    pos = jnp.arange(T, dtype=jnp.int32)
    lb = jnp.clip(pos // block, 0, row.shape[0] - 1)
    lo = jnp.asarray(lo, jnp.int32)
    # Rows below lo (shared prefix blocks), past plen, AND past the
    # slot's logical capacity all drop (same over-capacity safety as
    # _paged_pool_write).
    ok = (pos >= lo) & (pos < plen) & (pos < row.shape[0] * block)
    pb = jnp.where(ok, jnp.take(row, lb), N)  # OOB -> dropped
    off = pos % block

    def put(pool: jax.Array, rows: jax.Array) -> jax.Array:
        vals = jnp.moveaxis(rows[:, 0], 2, 0)  # (T, L, Hkv, D)
        return pool.at[:, pb, :, off, :].set(
            vals.astype(pool.dtype), mode="drop"
        )

    length = lax.dynamic_update_index_in_dim(
        cache.length, jnp.asarray(plen, jnp.int32), slot, axis=0
    )
    if isinstance(cache, PagedQuantKVCache):
        nbk = k_scale.shape[1]
        blocks_idx = jnp.arange(nbk, dtype=jnp.int32)
        blk_ok = (
            (blocks_idx >= lo // block)
            & (blocks_idx * block < plen)
            & (blocks_idx < row.shape[0])
        )
        pb_s = jnp.where(
            blk_ok, jnp.take(row, jnp.clip(blocks_idx, 0,
                                           row.shape[0] - 1)), N
        )
        put_s = lambda buf, new: buf.at[:, pb_s, :].set(new, mode="drop")
        return PagedQuantKVCache(
            k=put(cache.k, k_rows), v=put(cache.v, v_rows),
            k_scale=put_s(cache.k_scale, k_scale),
            v_scale=put_s(cache.v_scale, v_scale),
            table=cache.table, length=length,
        )
    return PagedKVCache(
        k=put(cache.k, k_rows), v=put(cache.v, v_rows),
        table=cache.table, length=length,
    )


def _masked_window_write(
    buf: jax.Array, rows: jax.Array, start: jax.Array, n: jax.Array
) -> jax.Array:
    """Write ``rows[:, :n]`` into ``buf`` at token positions
    ``[start, start + n)``, leaving every other buffer byte untouched.

    One slot's piece of the mixed-Tq step (vmapped over batch): ``buf`` is
    ``(Hkv, Tmax, D)``, ``rows`` ``(Hkv, Tq, D)``, ``start``/``n`` scalars
    with ``start + n <= Tmax`` and ``Tq <= Tmax``. The window offset is
    clamped to ``Tmax - Tq`` (a decode slot near capacity padded to a
    chunk-sized Tq would otherwise clamp INSIDE dynamic_update_slice and
    shift garbage over valid rows); the valid rows are shifted to
    compensate, so they land at their true absolute positions and the
    rest of the window is written back unchanged.
    """
    Tq = rows.shape[1]
    cap = buf.shape[1]
    ws = jnp.clip(start, 0, cap - Tq)
    shift = start - ws  # > 0 only when the window straddles capacity
    window = lax.dynamic_slice_in_dim(buf, ws, Tq, axis=1)
    idx = jnp.arange(Tq, dtype=jnp.int32)
    src = idx - shift  # new-row index that window position idx holds
    gathered = jnp.take(rows, jnp.clip(src, 0, Tq - 1), axis=1)
    keep = (src >= 0) & (src < n)
    merged = jnp.where(keep[None, :, None], gathered, window)
    return lax.dynamic_update_slice_in_dim(buf, merged, ws, axis=1)


class _RowGroup(NamedTuple):
    """One group of a step's rows: ``batch`` cache views of ``tq`` rows each.

    A group is nothing but a table and lengths. Member ``i`` writes its first
    ``n[i]`` rows (all ``tq`` where ``n`` is None) at ``[start[i], start[i] +
    n[i])`` of the view its ``table`` row names (its own buffer row, for a
    contiguous cache) and attends from there. The padded step
    (:func:`forward_step`) is ONE group, every slot with ``Tq`` rows, and its
    arrays are the group's as they lie (``lo`` None). A packed step
    (:func:`forward_packed_step`) lays its groups end to end on one row axis,
    ``(1, R, ...)``, group ``g`` from row ``lo`` on: everything row-wise in a
    layer (norms, projections, the feed-forward half) runs on the ``R`` rows at
    once, so the weights stream once, and only the pool write and attention
    run group by group.
    """

    lo: Optional[int]
    batch: int
    tq: int
    start: jax.Array
    n: Optional[jax.Array]
    table: Optional[jax.Array]
    tree_mask: Optional[jax.Array]
    chunk: bool = False   # a packed step's chunk group (``scopes.ATTN_CHUNK``)
    plan: Any = None      # the paged kernels' work list (:func:`_plan_groups`)
    wtable: Optional[jax.Array] = None  # the window layers' table
    wplan: Any = None     # ... and their work list (a window's steps)
    at: Any = None        # one row a slot: its (block, row) (_row_targets)
    wat: Any = None       # ... under the window layers' table
    slot: Optional[jax.Array] = None  # each member's slot (a per-slot state)
    live: Any = None      # one row a slot: the slots that have one (live_list)
    tail: Any = None      # one row a slot, conv layers: ConvTailPlan

    @property
    def n_valid(self) -> jax.Array:
        if self.n is None:
            return jnp.full((self.batch,), self.tq, jnp.int32)
        return self.n

    @property
    def valid(self) -> jax.Array:
        """``(batch, tq)``: the rows that carry a token."""
        return (jnp.arange(self.tq, dtype=jnp.int32)[None, :]
                < self.n_valid[:, None])

    def take(self, a: jax.Array) -> jax.Array:
        """The group's rows of a step array ``(1, H, R, D)`` as the ops
        layer's ``(batch, H, tq, D)``."""
        if self.lo is None:
            return a
        _, H, _, D = a.shape
        rows = a[0, :, self.lo:self.lo + self.batch * self.tq]
        return rows.reshape(H, self.batch, self.tq, D).transpose(1, 0, 2, 3)


def window_rules(cfg: TransformerConfig) -> Tuple[Any, Any]:
    """The visibility rule (``ops/block_utils.py`` ``WindowRule``, or None)
    of a paged call under a cache's first table and of one under its second:
    a sliding-window layer's window under the second; an EVA layer's summary
    rule under the first and its aligned window under the second."""
    if cfg.cache_kind == "eva":
        return (ChunkSummaries(cfg.window, cfg.chunk),
                AlignedWindow(cfg.window))
    return None, cfg.window or None


def chunks_closed(start, n, chunk: int):
    """``(first, count)`` of the chunks of ``chunk`` positions whose LAST
    position lies among rows ``[start, start + n)``: the summary rows an EVA
    layer writes for those rows, consecutive from ``first``. Arrays of
    numpy's or of jax's (the serve loop counts with it what a tick is due)."""
    return start // chunk, (start + n) // chunk - start // chunk


def _plan_groups(groups: Tuple[_RowGroup, ...], cache: Any,
                 cfg: TransformerConfig) -> Tuple[_RowGroup, ...]:
    """Each group with the paged decode kernels' work list for its rows
    (``ops/pallas_decode.py`` ``PagedPlan``: which steps of which member's
    table hold a position its rows may see, and the table in that order).
    The list follows from the group's lengths and the shapes, not from the
    layer, so a step builds it here, once, and every layer's call is handed
    it shifted to the layer's blocks, as the table is; built inside the
    call it would be built again in every pass of the layer loop. A group the
    kernels will not serve (a chunk of 128 rows or more on the Q-tiled
    kernel) leaves its list unused, and the compiler drops it. A cache
    with a second table (:class:`PagedWindowCache`) gets a second list a
    group, the window layers': two plans a tick, one a kind. A group of one
    row a slot also gets, for the same reason and behind the same barrier,
    the ``(block, row)`` its write lands in (:func:`_row_targets`, under
    either table): a layer's :func:`_pool_write` then adds ``l * N`` inside
    its kernel and the loop's body holds nothing of the write but the
    launch. Over a :class:`PagedStateCache` such a group gets the list of
    the slots that have a row too (``ops/pallas_ssm.py`` ``live_list``);
    over a :class:`PagedStateWindowCache` every group gets the list of its
    members that have one (``ssm1_scan`` walks it at any ``tq``).
    Over a model with conv layers it gets the places of positions ``p``,
    ``p - 1`` and ``p - 2`` in the tail pool (``ops/pallas_conv.py``
    ``conv_tail_plan``), which a conv layer's one launch is handed as they are.
    An EVA model's lists follow its two rules (:func:`window_rules`), and
    its first table's ``(block, row)`` is that of the SUMMARY row the
    slot's token closes, if it closes one (:func:`chunks_closed`)."""
    from tree_attention_tpu.ops.pallas_decode import decode_plan, mla_plan

    rule, wrule = window_rules(cfg)

    def barrier(plan):
        # Behind a barrier: the compiler otherwise clones the cheapest
        # of a short list's operations back into the loop's body.
        out = lax.optimization_barrier(tuple(plan))
        return type(plan)(*out) if hasattr(plan, "_fields") else out

    planned = []
    for g in groups:
        with jax.named_scope(
                scopes.ATTN_CHUNK if g.chunk else scopes.ATTN_DECODE):
            if isinstance(cache, PagedLatentCache):
                plan = mla_plan(g.tq, cache.kv, g.table, g.start)
            else:
                plan = decode_plan(
                    cfg.n_heads, g.tq, cache.k, g.table, g.start,
                    window=rule)
            g = g._replace(plan=barrier(plan))
            if g.wtable is not None:
                g = g._replace(wplan=barrier(decode_plan(
                    cfg.n_heads, g.tq, cache.wk, g.wtable, g.start,
                    window=wrule)))
        if (g.tq == 1 and isinstance(cache, PagedStateCache)) \
                or isinstance(cache, PagedStateWindowCache):
            # The slots a state-space layer's in-place step visits.
            from tree_attention_tpu.ops.pallas_ssm import live_list

            with jax.named_scope(scopes.CONV):
                g = g._replace(live=barrier(live_list(g.n_valid)))
        if pool_write_path(g.tq) == "row":
            with jax.named_scope(scopes.ATTN_CACHE):
                first, n = g.start, g.n_valid
                if cfg.eva_layers:
                    first, n = chunks_closed(first, n, cfg.chunk)
                g = g._replace(at=barrier(_row_targets(
                    g.table, first, n, cache.blocks, cache.block)))
                if g.wtable is not None:
                    g = g._replace(wat=barrier(_row_targets(
                        g.wtable, g.start, g.n_valid, cache.window_blocks,
                        cache.block)))
            if cfg.conv_layers:
                # Where positions p, p-1 and p-2 lie in the tail pool.
                from tree_attention_tpu.ops.pallas_conv import conv_tail_plan

                with jax.named_scope(scopes.CONV):
                    g = g._replace(tail=barrier(conv_tail_plan(
                        g.table, g.start, g.n_valid, cache.blocks,
                        cache.block)))
        planned.append(g)
    return tuple(planned)


def paged_step_tokens(cache: Any, cfg: TransformerConfig,
                      tq: int, window: bool = False) -> Optional[int]:
    """Tokens one grid step of the paged decode kernel takes that serves a
    group of ``tq`` rows a slot against ``cache``; None where no paged
    kernel serves such a group (a contiguous cache; an exact chunk of 128
    rows or more, the Q-tiled kernel's over a gathered view). What the
    serve loop counts a tick's work list with (``kv_steps_run``).
    ``window``: the step of a window layer's call against the window pool
    (the paged kernel at every ``tq``; None for a cache without one). An
    EVA model's call against its first pool, the summary rows', is the
    paged kernel at every ``tq`` too."""
    from tree_attention_tpu.ops.pallas_decode import (
        decode_step_entries, mla_step_entries,
    )
    from tree_attention_tpu.ops.tuning import tpu_kernel_for

    if window:
        if not isinstance(cache, _TWO_TABLES):
            return None
        return cache.block * decode_step_entries(
            cfg.n_heads, tq, cache.wk, cache.wtable.shape[1])
    if isinstance(cache, PagedLatentCache):
        return mla_step_entries(cache.table.shape[1]) * cache.block
    if not isinstance(cache, (PagedKVCache, PagedQuantKVCache,
                              PagedHybridCache, PagedWindowCache,
                              PagedStateCache, PagedStateWindowCache)):
        return None
    if not isinstance(cache, PagedQuantKVCache) and not cfg.eva_layers \
            and tpu_kernel_for(tq) != "pallas_decode":
        return None
    return cache.block * decode_step_entries(
        cfg.n_heads, tq, cache.k, cache.table.shape[1])


def _slots(cache: Any, batch: int) -> Optional[jax.Array]:
    """Every slot's own index, for a cache that holds a state a slot; None
    for every other cache (whose groups are tables and lengths alone)."""
    if not isinstance(cache, _SLOT_STATES):
        return None
    return jnp.arange(batch, dtype=jnp.int32)


def _join_rows(groups: Tuple[_RowGroup, ...], outs) -> jax.Array:
    """The inverse of :meth:`_RowGroup.take` over every group: per-group
    ``(batch, H, tq, D)`` back onto the step's row axis."""
    if groups[0].lo is None:
        return outs[0]
    return jnp.concatenate([
        o.transpose(1, 0, 2, 3).reshape(o.shape[1], -1, o.shape[3])
        for o in outs
    ], axis=1)[None]


@dataclasses.dataclass(frozen=True)
class _Attend:
    """The attention half of a rotary-GQA layer, for one group of a step's
    rows: what :func:`_step_layers` settled once for the step (which cache
    it steps, on which path) and, called, the write of the group's new K/V
    rows and its queries against what the cache then holds. Every layer
    loop whose layers cache K/V rows calls it (:func:`gqa_mixer`): the
    dense block's and the loop over layers of several kinds
    (``models/hybrid.py``)."""

    groups: Tuple["_RowGroup", ...]
    cfg: TransformerConfig
    mesh: Optional[Mesh]
    axes: Dict[str, Optional[str]]
    num_splits: Optional[int]
    quant_kernel: str
    paged: bool
    quant: bool
    carried: bool
    seq_sharded: bool
    hoist_view: bool
    anchors: Any
    scale: Optional[float] = None   # None: the kernels' own, D^-1/2
    # What the layer KIND settles, the one body being built with it
    # (:func:`gqa_mixer`): a sliding-window layer's window (its rows go to,
    # and are read from, the pools under the groups' second table, through
    # the window's work list), and whether queries and keys are rotated.
    window: Any = None
    rotary: bool = True
    # The caller merges this call's partial with another's (an EVA layer):
    # the output comes back as ``(out, lse)``.
    partial: bool = False
    # False: a cross layer's call. The group's rows attend to what another
    # layer wrote into the cache in this step and before; nothing is
    # written and ``k_new`` / ``v_new`` are None.
    write: bool = True

    def __call__(self, gi, q, k_new, v_new, k_cache, v_cache, k_s, v_s,
                 views, l, base):
        """The attention half of a layer for group ``gi`` of the step's
        ``q`` / ``k_new`` / ``v_new``: its new K/V rows into the cache
        (``scopes.ATTN_CACHE``), its queries against what the cache then
        holds (``ATTN_DECODE``; a packed step's chunk group:
        ``ATTN_CHUNK``). Returns the heads' output and the cache arrays."""
        groups, cfg, mesh, axes = self.groups, self.cfg, self.mesh, self.axes
        paged, quant, carried = self.paged, self.quant, self.carried
        seq_sharded, hoist_view = self.seq_sharded, self.hoist_view
        anchors, num_splits = self.anchors, self.num_splits
        quant_kernel = self.quant_kernel
        g = groups[gi]
        if self.window is not None:
            # A window layer's group: the same rows under the second table.
            g = g._replace(table=g.wtable, plan=g.wplan, at=g.wat)
        B, Tq = g.batch, g.tq
        start, n_valid = g.start, g.n_valid
        with jax.named_scope(scopes.ATTN_CACHE):
            if self.write:
                k_new, v_new = g.take(k_new), g.take(v_new)
            k_view = v_view = None
            if hoist_view:
                k_view, v_view = views[2 * gi:2 * gi + 2]
            # Write member i's new rows at its own [start[i], start[i]+Tq):
            # a vmapped dynamic-update over batch (per-slot token offsets).
            # Under a mesh GSPMD turns it into per-shard masked writes on
            # the seq dim. Quantized caches quantize the rows first — under
            # the per-slot frozen scales (contiguous) or the per-block
            # anchor scale (paged; entered blocks inherit it, see above).
            k_deq = v_deq = None
            k_sf = v_sf = None
            if quant and paged:
                anchor_pb, write_pb, entered = anchors[gi]
                # The scales as (blocks, Hkv) rows: every layer's when carried
                # (a bitcast), this layer's (base 0) when scanned.
                hkv = k_s.shape[-1]
                k_sf, v_sf = k_s.reshape(-1, hkv), v_s.reshape(-1, hkv)
                k_anchor = k_sf[base + anchor_pb][:, :, None, None]
                # (B, Hkv, 1, 1)
                v_anchor = v_sf[base + anchor_pb][:, :, None, None]
                k_new = _quantize_rows(k_new, k_anchor)
                v_new = _quantize_rows(v_new, v_anchor)
                vals_k = jnp.broadcast_to(
                    k_anchor[:, None, :, 0, 0], (B, Tq, hkv)
                ).reshape(-1, hkv)
                vals_v = jnp.broadcast_to(
                    v_anchor[:, None, :, 0, 0], (B, Tq, hkv)
                ).reshape(-1, hkv)
                # Rows that enter no block scatter past every layer and drop.
                scale_tgt = jnp.where(
                    entered, base + write_pb, k_sf.shape[0]
                ).reshape(-1)
                k_sf = k_sf.at[scale_tgt].set(vals_k, mode="drop")
                v_sf = v_sf.at[scale_tgt].set(vals_v, mode="drop")
                k_s, v_s = k_sf.reshape(k_s.shape), v_sf.reshape(v_s.shape)
                if hoist_view:
                    # The view holds DEQUANTIZED rows: mirror exactly what
                    # the pool now holds (quantize-then-dequantize), so
                    # attention over the view == attention over the pool.
                    k_deq = (
                        k_new.astype(jnp.float32) * k_anchor
                    ).astype(k_view.dtype)
                    v_deq = (
                        v_new.astype(jnp.float32) * v_anchor
                    ).astype(v_view.dtype)
            elif quant:
                k_new = _quantize_rows(k_new, k_s)
                v_new = _quantize_rows(v_new, v_s)
            if not self.write:
                pass        # a cross layer: this scope stays empty
            elif paged:
                # Paged write, through the block table: valid rows land
                # in their slot's mapped blocks, padded rows drop (K and V
                # in one call: _pool_write). The contiguous path's window
                # clamp machinery is unnecessary here.
                if seq_sharded:
                    k_cache, v_cache = _pool_write_seq(
                        (k_cache, v_cache), (k_new, v_new), g.table, start,
                        n_valid, mesh=mesh, seq_axis=axes["seq"],
                    )
                else:
                    k_cache, v_cache = _pool_write(
                        (k_cache, v_cache), (k_new, v_new), g.table, start,
                        n_valid, l, g.at,
                    )
                if hoist_view:
                    # Mirror the new rows into the hoisted logical view (the
                    # pre-scan gather predates this layer's write) — a cheap
                    # Tq-row window write, vs re-gathering the whole pool.
                    wv = jax.vmap(_masked_window_write, in_axes=(0, 0, 0, 0))
                    mk = k_new if k_deq is None else k_deq
                    mv = v_new if v_deq is None else v_deq
                    k_view = wv(
                        k_view, mk.astype(k_view.dtype), start, n_valid
                    )
                    v_view = wv(
                        v_view, mv.astype(v_view.dtype), start, n_valid
                    )
            elif g.n is None:
                write = jax.vmap(
                    lambda buf, rows, s: lax.dynamic_update_slice_in_dim(
                        buf, rows, s, axis=1
                    )
                )
                k_cache = write(k_cache, k_new.astype(k_cache.dtype), start)
                v_cache = write(v_cache, v_new.astype(v_cache.dtype), start)
            else:
                # Mixed-Tq masked write: only rows < n_tokens[i] may land. A
                # plain Tq-row dynamic-update would (a) write pad garbage the
                # causal mask has to hide until it is overwritten and (b)
                # CLAMP near capacity (dynamic_update_slice semantics), sliding
                # garbage over a decode slot's newest valid rows. Instead:
                # read the Tq-row window at a clamped offset, overlay exactly
                # the valid rows at their true absolute positions, write it
                # back — cache bytes outside [start, start+n) are untouched.
                write = jax.vmap(_masked_window_write, in_axes=(0, 0, 0, 0))
                k_cache = write(
                    k_cache, k_new.astype(k_cache.dtype), start, g.n
                )
                v_cache = write(
                    v_cache, v_new.astype(v_cache.dtype), start, g.n
                )

        with jax.named_scope(
                scopes.ATTN_CHUNK if g.chunk else scopes.ATTN_DECODE):
            q = g.take(q)
            data = axes["data"]
            if data and mesh is not None and B % mesh.shape[data]:
                # A packed group need not divide over the batch axis.
                data = None
            attn_kw = dict(
                q_position=start,
                mesh=mesh,
                data_axis=data,
                seq_axis=axes["seq"],
                model_axis=axes["model"],
                block_size=cfg.attn_block_size,
                tree_mask=g.tree_mask,
            )
            if self.scale is not None:
                attn_kw["scale"] = self.scale
            if self.window is not None:
                attn_kw["window"] = self.window
            if not self.write:
                attn_kw["launch"] = "shared"    # another layer's rows
            ak, av, ak_s, av_s = k_cache, v_cache, k_s, v_s
            if hoist_view:
                ak, av = k_view, v_view
            elif carried:
                # The kernels and the reference gather take a pool and a
                # table: hand them every layer's blocks (a bitcast of the
                # carry) and this layer's addresses.
                ak = k_cache.reshape((-1,) + k_cache.shape[2:])
                av = v_cache.reshape((-1,) + v_cache.shape[2:])
                attn_kw["block_table"] = base + g.table
                if g.plan is not None:
                    attn_kw["step_plan"] = g.plan.shifted(base)
                if quant:
                    ak_s, av_s = k_sf, v_sf
            elif paged:
                attn_kw["block_table"] = g.table
                attn_kw["kv_shard"] = "seq"
            if quant and not (paged and hoist_view):
                out, _ = decode_attention(
                    q, ak, av, k_scale=ak_s, v_scale=av_s,
                    quant_kernel=quant_kernel, **attn_kw,
                )
            else:
                # Exact caches — and the paged-quant DEQUANTIZED view (the
                # off-kernel path; see the hoist_view comment above).
                out, lse = decode_attention(
                    q, ak, av,
                    impl=cfg.attn_impl, num_splits=num_splits, **attn_kw,
                )
                if self.partial:
                    out = (out, lse)
        return out, k_cache, v_cache, k_s, v_s


def gqa_branch(attend: _Attend, layer: Params, h: jax.Array,
               positions: jax.Array, k_cache, v_cache, k_s, v_s, views, l,
               base):
    """A rotary-GQA mixer's branch over every group of the step's rows,
    from rows already normed: projections (:func:`~.transformer.gqa_qkv`),
    each group's rows into the cache and against it (:class:`_Attend`), the
    output projection. Returns what the mixer adds to the residual and the
    cache arrays."""
    cfg, groups = attend.cfg, attend.groups
    with jax.named_scope(scopes.ATTN_IN):
        q, k_new, v_new = gqa_qkv(
            layer, h, positions, cfg, rotary=attend.rotary)
        if cfg.kv_pack > 1:
            q, k_new, v_new = _pack_heads(q, k_new, v_new, cfg)
    outs = []
    for gi in range(len(groups)):
        out, k_cache, v_cache, k_s, v_s = attend(
            gi, q, k_new, v_new, k_cache, v_cache, k_s, v_s, views, l, base,
        )
        outs.append(out)
    with jax.named_scope(scopes.ATTN_DECODE):
        out = _join_rows(groups, outs)
        if cfg.kv_pack > 1:
            out = _unpack_heads(out, cfg)
    with jax.named_scope(scopes.ATTN_OUT):
        y = _unheads(out) @ layer["wo"]
    return y, k_cache, v_cache, k_s, v_s


def gqa_mixer(attend: _Attend, layer: Params, x: jax.Array,
              positions: jax.Array, k_cache, v_cache, k_s, v_s, views, l,
              base):
    """A rotary-GQA mixer as a layer of its own: the norm, the branch
    (:func:`gqa_branch`), the add. Returns the residual with the mixer's
    output added and the cache arrays."""
    with jax.named_scope(scopes.ATTN_IN):
        h = rms_norm(x, layer["ln1"], attend.cfg.norm_eps)
    y, *pools = gqa_branch(
        attend, layer, h, positions, k_cache, v_cache, k_s, v_s, views, l,
        base)
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + y
    return (x, *pools)


def _head_lanes(cfg: TransformerConfig) -> jax.Array:
    """``(heads, kv_pack)`` one-hot: which of a packed row's ``kv_pack``
    spans of ``d_head`` lanes query head ``h`` reads (its KV head's).
    Under differential attention (``cfg.diff_attn``) the packed row is a
    PAIR of KV heads and query head ``h = 2p + σ`` is half ``σ`` of its
    pair: it reads key head ``2j + σ``, half ``h % 2`` of packed head ``j``
    (standard GQA packing would put it in half ``(h // group) % 2``)."""
    if cfg.diff_attn:
        return jax.nn.one_hot(jnp.arange(cfg.n_heads) % 2, 2)
    kv_head = jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)
    return jax.nn.one_hot(kv_head % cfg.kv_pack, cfg.kv_pack)


def _pack_heads(q, k, v, cfg: TransformerConfig):
    """``cfg.kv_pack`` neighbouring KV heads side by side on a row's lanes
    (``(B, Hkv, T, D)`` -> ``(B, Hkv / p, T, p x D)``), and every query
    head's values in its KV head's lanes with zeros beside them: q . k over
    the packed row is the head's own dot product, and query head ``h``
    still reads packed KV head ``h // (group x p)``. ``k`` / ``v`` None (a
    cross layer projects queries only) stay None."""
    p = cfg.kv_pack
    B, _, T, D = q.shape

    def side_by_side(a):
        if a is None:
            return None
        Hkv = a.shape[1]
        return a.reshape(B, Hkv // p, p, T, D).transpose(0, 1, 3, 2, 4) \
            .reshape(B, Hkv // p, T, p * D)

    lanes = _head_lanes(cfg).astype(q.dtype)
    q = (q[:, :, :, None, :] * lanes[None, :, None, :, None]).reshape(
        B, cfg.n_heads, T, p * D)
    return q, side_by_side(k), side_by_side(v)


def _unpack_heads(out: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Of a packed output row ``(B, H, T, p x D)`` the span of the head's
    own KV head: ``(B, H, T, D)``. (Differential attention keeps ALL the
    lanes instead, ``[softmax . v_2j | softmax . v_2j+1]`` is its
    ``a_{p,σ}``: ``models/hybrid.py`` ``diff_branch``.)"""
    B, H, T, _ = out.shape
    out = out.reshape(B, H, T, cfg.kv_pack, cfg.d_head)
    return jnp.einsum("bhtpd,hp->bhtd", out,
                      _head_lanes(cfg).astype(out.dtype))


# The caches a model's own layer loop steps (not the dense block's scan).
_OWN_POOLS = (PagedLatentCache, PagedHybridCache, PagedWindowCache,
              PagedStateCache, PagedStateWindowCache)


def _check_block_cache(cache: Any, cfg: TransformerConfig) -> None:
    """The model's layers and the cache's kind go together."""
    kind = ("latent" if isinstance(cache, PagedLatentCache)
            else "hybrid" if isinstance(cache, PagedHybridCache)
            else "window" if isinstance(cache, PagedWindowCache)
            else "state" if isinstance(cache, PagedStateCache)
            else "state_window" if isinstance(cache, PagedStateWindowCache)
            else "kv")
    if kind == "window" and cfg.cache_kind == "eva":
        kind = "eva"   # the same two pools under the EVA row rule
    if kind != cfg.cache_kind:
        raise ValueError(
            f"this model caches {cfg.cache_kind!r} state "
            f"(TransformerConfig.cache_kind: a latent pool for latent "
            f"attention, the hybrid pool for conv layers or experts under "
            f"rotary GQA, the window pools for sliding-window layers or EVA "
            f"layers, the "
            f"state pool for state-space layers, the state_window pool for "
            f"a decoder that feeds a second decoder, K/V buffers for the dense "
            f"block) and is served "
            f"from the cache init_paged_cache builds for it and no other; "
            f"got {type(cache).__name__}"
        )


def _count_step(cache: Any) -> None:
    """One step program traced (or run eagerly), by the cache it steps."""
    if not obs.REGISTRY.enabled:
        return
    quant = isinstance(cache, (QuantKVCache, PagedQuantKVCache))
    if isinstance(cache, PagedLatentCache):
        kind = "paged_latent"
    elif isinstance(cache, PagedHybridCache):
        kind = "paged_hybrid"
    elif isinstance(cache, PagedWindowCache):
        kind = "paged_window"
    elif isinstance(cache, PagedStateCache):
        kind = "paged_state"
    elif isinstance(cache, PagedStateWindowCache):
        kind = "paged_state_window"
    elif isinstance(cache, (PagedKVCache, PagedQuantKVCache)):
        kind = "paged_quant" if quant else "paged"
    else:
        kind = "quant" if quant else "exact"
    _STEP_DISPATCH.labels(cache=kind).inc()


def _latent_layers(
    params: Params,
    x: jax.Array,
    cache: PagedLatentCache,
    cfg: TransformerConfig,
    positions: jax.Array,
    groups: Tuple[_RowGroup, ...],
    stats: Optional[Dict[str, Any]],
) -> Tuple[jax.Array, jax.Array]:
    """The layer loops of a latent-attention / expert model: the leading
    dense-FFN stack under one scan, the expert stack under another, the
    whole latent pool the carry of both and the layer's blocks reached by
    offset (``table + l·N``, the pool's layer index running through both
    stacks: one for every attention, so ``sublayers`` of them a model
    layer). Where the routed experts are a branch beside the dense FFNs
    (``cfg.moe.branch``), the expert stack's body is the third one below:
    every sublayer's attention and dense FFN in turn, the branch computed
    where it leaves and added where it rejoins. Every row — decode rows
    and chunk rows alike — attends in absorbed form against the pool it
    has just been written to, group by group."""
    from tree_attention_tpu.models.experts import (
        EXPERT_LEAVES, expert_layer, held_counts,
    )
    from tree_attention_tpu.models.latent import (
        latent_attention, latent_out, latent_qkv,
    )

    N = cache.blocks
    valid = groups[0].valid
    if groups[0].lo is not None:
        valid = jnp.concatenate([g.valid.reshape(-1) for g in groups])[None]

    def attend(layer, x, pool, l):
        with jax.named_scope(scopes.ATTN_IN):
            h = rms_norm(x, layer["ln1"], cfg.norm_eps)
            rows, q_abs = latent_qkv(layer, h, positions, cfg)
        outs = []
        for g in groups:
            with jax.named_scope(scopes.ATTN_CACHE):
                pool = _pool_write(
                    (pool[:, :, None],), (g.take(rows),), g.table, g.start,
                    g.n_valid, l, g.at)[0][:, :, 0]
            with jax.named_scope(
                    scopes.ATTN_CHUNK if g.chunk else scopes.ATTN_DECODE):
                out_lat, _ = latent_attention(
                    g.take(q_abs), pool.reshape((-1,) + pool.shape[2:]),
                    l * N + g.table, q_offset=g.start, cfg=cfg,
                    step_plan=g.plan and g.plan.shifted(l * N),
                )
            outs.append(out_lat)
        with jax.named_scope(scopes.ATTN_DECODE):
            out_lat = _join_rows(groups, outs)
        with jax.named_scope(scopes.ATTN_OUT):
            return x + latent_out(layer, out_lat), pool

    def dense_body(carry, xs):
        layer, l = xs
        x, pool = attend(layer, *carry, l)
        with jax.named_scope(scopes.FFN):
            x = x + _mlp_block(
                layer, rms_norm(x, layer["ln2"], cfg.norm_eps))
        return (x, pool), None

    def expert_body(carry, xs):
        layer, l = xs
        x, pool = attend(layer, *carry, l)
        with jax.named_scope(scopes.ROUTE):
            h32 = rms_norm(
                x.astype(jnp.float32), layer["ln2"], cfg.norm_eps)
            h = h32.astype(x.dtype)
        y, chosen = expert_layer(
            layer, h, cfg.moe, router_input=h32,
            experts=experts, first=(l - n_dense) * cfg.moe.held,
        )
        with jax.named_scope(scopes.ROUTE):
            return (x + y, pool), held_counts(chosen, valid, cfg.moe)

    def branch_body(carry, xs):
        layer, i = xs
        x, pool = carry
        leaves, rejoins = cfg.moe.branch
        for j in range(cfg.sublayers):
            sub = layer["sub"][j]
            x, pool = attend(sub, x, pool, i * cfg.sublayers + j)
            with jax.named_scope(scopes.FFN):
                h32 = rms_norm(
                    x.astype(jnp.float32), sub["ln2"], cfg.norm_eps)
                h = h32.astype(x.dtype)
            if j == leaves:
                m, chosen = expert_layer(
                    layer, h, cfg.moe, router_input=h32,
                    experts=experts, first=i * cfg.moe.held,
                )
            with jax.named_scope(scopes.FFN):
                x = x + _mlp_block(sub, h)
            if j == rejoins:
                with jax.named_scope(scopes.ROUTE):
                    x = x + m
        with jax.named_scope(scopes.ROUTE):
            return (x, pool), held_counts(chosen, valid, cfg.moe)

    carry = (x, cache.kv)
    n_dense = cfg.n_dense_layers
    if n_dense:
        carry, _ = lax.scan(dense_body, carry, (
            params["dense"], jnp.arange(n_dense, dtype=jnp.int32)))
    if cfg.n_layers > n_dense:
        # Every layer's experts as ONE stack (a bitcast), a layer's reached
        # by offset like the pool's blocks: scanned as ``xs`` the loop would
        # copy a layer's experts out of the stack before the kernel read
        # one of them.
        stack = params["layers"]
        experts = tuple(
            stack[n].reshape((-1,) + stack[n].shape[2:])
            for n in EXPERT_LEAVES)
        carry, rows = lax.scan(
            expert_body if cfg.moe.branch is None else branch_body, carry, (
                {n: a for n, a in stack.items() if n not in EXPERT_LEAVES},
                jnp.arange(n_dense, cfg.n_layers, dtype=jnp.int32)))
        if stats is not None:
            stats["expert_rows"] = rows
    return carry


def _step_layers(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    groups: Tuple[_RowGroup, ...],
    cache: Any,
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh],
    axes: Dict[str, Optional[str]],
    num_splits: Optional[int],
    quant_kernel: str,
    kv_shard: str,
    stats: Optional[Dict[str, Any]],
    cut: Optional[Tuple[jax.Array, _RowGroup]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Every layer of a step over ``groups`` of rows: THE layer body, which
    the padded step (one group) and the packed step (a chunk group beside a
    decode group) share. ``x`` and ``positions`` lie as :class:`_RowGroup`
    says. ``cut`` (a packed step of a model with ``cfg.row_cut``): the rows
    that go on above the seam, ``(their indices on the row axis, the group
    they make)``; the residual comes back with those rows alone. Returns
    the final residual and the cache's arrays the step rewrote, by field
    name."""
    from tree_attention_tpu.ops import _on_tpu, _pallas_available

    paged = isinstance(cache, (PagedKVCache, PagedQuantKVCache))
    on_kernels = _on_tpu(params["embed"]) and _pallas_available()
    seq_shards = (
        max(mesh.shape.get(axes["seq"] or "", 1), 1)
        if mesh is not None else 1
    )
    seq_sharded = paged and kv_shard == "seq" and seq_shards > 1
    if on_kernels and groups[0].table is not None and not seq_sharded:
        groups = _plan_groups(groups, cache, cfg)
        if cut is not None:
            cut = (cut[0], _plan_groups((cut[1],), cache, cfg)[0])
    if isinstance(cache, PagedLatentCache):
        x, pool = _latent_layers(
            params, x, cache, cfg, positions, groups, stats)
        return x, {"kv": pool}
    if isinstance(cache, (PagedHybridCache, PagedWindowCache,
                          PagedStateCache, PagedStateWindowCache)):
        from tree_attention_tpu.models.hybrid import hybrid_layers

        attend = _Attend(
            groups=groups, cfg=cfg, mesh=mesh, axes=axes,
            num_splits=num_splits, quant_kernel=quant_kernel, paged=True,
            quant=False, carried=True, seq_sharded=False, hoist_view=False,
            anchors=(),
            scale=cfg.d_head ** -0.5 if cfg.kv_pack > 1 else None,
        )
        return hybrid_layers(
            params, x, positions, cache, cfg, attend, stats, cut)
    quant = isinstance(cache, (QuantKVCache, PagedQuantKVCache))

    # Satellite fix (ISSUE 8): off the TPU Pallas kernels — the eager/CPU
    # proxy and interpret-mode runs — a paged step used to re-gather
    # ``pool[table]`` once PER LAYER inside the scan (flash_decode's
    # fallback materialises the logical view per call). Hoist that to ONE
    # gather for the whole step: build each group's logical (L, B, Hkv,
    # NB·block, D) views up front, write each layer's new rows into both the
    # pool (the persistent state) and its view slice (a cheap Tq-row window
    # write), and run the contiguous attention path on the view. Bit-exact
    # with the per-layer gather — identical rows in identical order. On TPU
    # the paged kernels stream blocks in place and this path never runs.
    hoist_view = False
    paged_quant = paged and quant
    if paged:
        # Under a >1-way seq mesh the contiguous view would re-route
        # decode_attention onto the tree-merge branch (the view is
        # replicated, not seq-sharded) — keep the block-table path there.
        if seq_sharded and any(g.tree_mask is not None for g in groups):
            raise ValueError(
                "tree_mask is not supported under kv_shard='seq' "
                "(paged_tree_decode has no window-mask plumbing); use "
                "chain drafts or the replicated pool"
            )
        if seq_sharded:
            # The hoisted contiguous view is a REPLICATED materialisation
            # of the pool — the exact thing kv_shard='seq' exists to
            # avoid. Attention stays on the block-table path, whose
            # sharded dispatch gathers per shard inside shard_map.
            hoist_view = False
        elif paged_quant:
            # Per-block scales (ISSUE 13): on TPU the q8 kernels read
            # them as a block-indexed lane-broadcast operand; everywhere
            # else the whole step runs on a DEQUANTIZED logical view
            # (int8 · per-block scale, built once per step) through the
            # exact attention paths — mesh included, since the view is
            # replicated and the tree merge handles it like a contiguous
            # cache. The pool stays int8 + scales; only attention's
            # operand is dequantized, so CPU and TPU agree to int8
            # quantization-step resolution and the engine's token-parity
            # contracts see one consistent numeric story per topology.
            hoist_view = not on_kernels
        else:
            hoist_view = seq_shards == 1 and not on_kernels
        # Trace time under jit: one line per step-program build.
        log.debug(
            "forward_step: paged%s step (rows %s) on %s",
            " int8" if quant else "",
            " + ".join(f"{g.batch}x{g.tq}" for g in groups),
            "the hoisted reference view" if hoist_view
            else "the block-table dispatch"
            + (" (Pallas kernels)" if on_kernels else " (reference gather)"),
        )
    views0: Tuple[jax.Array, ...] = ()
    if hoist_view:
        def _view(pool: jax.Array, idx: jax.Array,
                  scales: Optional[jax.Array] = None) -> jax.Array:
            rows = jnp.moveaxis(pool[:, idx], 2, 3)  # (L, B, Hkv, NB, blk, D)
            if scales is not None:
                s = jnp.swapaxes(scales[:, idx], 2, 3)  # (L, B, Hkv, NB)
                rows = (
                    rows.astype(jnp.float32) * s[..., None, None]
                ).astype(cfg.dtype)
            L, Bv, Hkv, NB, blk, D = rows.shape
            return rows.reshape(L, Bv, Hkv, NB * blk, D)

        for g in groups:
            idx = jnp.clip(g.table, 0, cache.blocks - 1)  # (B, NB)
            views0 += (
                (_view(cache.k, idx, cache.k_scale),
                 _view(cache.v, idx, cache.v_scale)) if paged_quant
                else (_view(cache.k, idx), _view(cache.v, idx))
            )
    anchors = []
    if paged_quant:
        # The anchor rule (see PagedQuantKVCache): every row this step
        # writes for member i quantizes under the scale of the block
        # holding the member's last pre-write row, and each block the
        # write ENTERS (its first row) inherits that scale — so a
        # block's rows and its pool scale always agree, across decode
        # appends, speculative rollback re-writes, and remapped blocks.
        blk_sz = cache.block
        for g in groups:
            NBt = g.table.shape[1]
            anchor_pb = jnp.clip(
                jnp.take_along_axis(
                    g.table,
                    jnp.clip((g.start - 1) // blk_sz, 0, NBt - 1)[:, None],
                    axis=1,
                )[:, 0],
                0, cache.blocks - 1,
            )  # (B,) physical anchor block per member
            pos_all = g.start[:, None] + jnp.arange(
                g.tq, dtype=jnp.int32)[None, :]
            write_pb = jnp.take_along_axis(
                g.table, jnp.clip(pos_all // blk_sz, 0, NBt - 1), axis=1
            )  # (B, Tq)
            entered = (
                g.valid
                & (pos_all % blk_sz == 0)
                & (pos_all < NBt * blk_sz)
            )
            anchors.append((anchor_pb, write_pb, entered))

    # A replicated paged pool rides the layer loop WHOLE, as loop-carried
    # state, and layer l is addressed by offset — ``table + l·N`` into the
    # ``(L·N, Hkv, block, D)`` view — never by slicing the pool (ISSUE 25).
    # As scanned ``xs``/``ys`` the compiled tick copied each layer's pool
    # out, into the write's layout and back, into a fresh stacked buffer,
    # and that whole buffer into the donated output: about five passes
    # over the pool, for K and for V, to append one row a slot. The
    # sequence-sharded pool still takes that route (its block axis is the
    # sharded one; a flat view would cut it by layers), as do the
    # contiguous caches.
    carried = paged and not seq_sharded

    attend = _Attend(
        groups=groups, cfg=cfg, mesh=mesh, axes=axes, num_splits=num_splits,
        quant_kernel=quant_kernel, paged=paged, quant=quant, carried=carried,
        seq_sharded=seq_sharded, hoist_view=hoist_view, anchors=anchors,
    )

    def body(carry, xs):
        parts = list(xs)
        layer = parts.pop(0)
        l, base = None, 0  # layer l's first block in the flat pool
        k_s = v_s = None
        if carried:
            x, k_cache, v_cache = carry[:3]
            if quant:
                k_s, v_s = carry[3:]
            l = parts.pop(0)
            base = l * cache.blocks
        else:
            x = carry
            k_cache, v_cache = parts[:2]
            parts = parts[2:]
        views = None
        if hoist_view:
            views, parts = parts[:len(views0)], parts[len(views0):]
        if quant and not carried:
            k_s, v_s = parts
        x, k_cache, v_cache, k_s, v_s = gqa_mixer(
            attend, layer, x, positions, k_cache, v_cache, k_s, v_s, views,
            l, base)
        with jax.named_scope(scopes.FFN):
            x = x + _mlp_block(
                layer, rms_norm(x, layer["ln2"], cfg.norm_eps))
        new = (k_cache, v_cache)
        if paged and quant:
            new = new + (k_s, v_s)  # entered blocks' inherited scales
        return ((x,) + new, None) if carried else (x, new)

    xs = (params["layers"],)
    init = x
    if carried:
        init = (x, cache.k, cache.v)
        if quant:
            init = init + (cache.k_scale, cache.v_scale)
        xs = xs + (jnp.arange(cache.k.shape[0], dtype=jnp.int32),)
    else:
        xs = xs + (cache.k, cache.v)
    xs = xs + views0
    if quant and not carried:
        xs = xs + (cache.k_scale, cache.v_scale)
    out_carry, scanned = lax.scan(body, init, xs)
    if carried:
        x, scanned = out_carry[0], out_carry[1:]
    else:
        x = out_carry
    pools = {"k": scanned[0], "v": scanned[1]}
    if paged and quant:
        pools.update(k_scale=scanned[2], v_scale=scanned[3])
    return x, pools


def forward_step(
    params: Params,
    tokens: jax.Array,
    cache: Union[KVCache, QuantKVCache],
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    data_axis: Optional[str] = AXIS_DATA,
    seq_axis: str = AXIS_SEQ,
    model_axis: Optional[str] = AXIS_MODEL,
    num_splits: Optional[int] = None,
    quant_kernel: str = "q8q",
    n_tokens: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    tree_mask: Optional[jax.Array] = None,
    kv_shard: str = "replicated",
    stats: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Union[KVCache, QuantKVCache]]:
    """Run ``Tq`` new tokens through the model against the cache.

    The layer body (:func:`_step_layers`, shared with
    :func:`forward_packed_step`; this step is its one-group case) is
    chosen by the model data: the rotary-GQA block with the dense SwiGLU;
    where ``cfg.mla`` is set, latent attention against a
    :class:`PagedLatentCache` with a leading stack of dense-FFN layers
    and a stack of expert layers (:func:`_latent_layers`); where the
    layers are of several kinds (``cfg.layer_types``: short-convolution
    mixers beside rotary-GQA ones) or expert layers lie under rotary GQA,
    the loop over runs of layers of one kind against a
    :class:`PagedHybridCache` (``models/hybrid.py``). Checks, positions,
    the embedding, the final norm, the head and the length bookkeeping
    are the same code for all.
    ``stats``, if given, is filled with the step's counters as traced
    arrays (read them in the same trace): ``expert_rows`` ``(expert
    layers, held + 1)`` int32, the valid rows routed to each held expert
    and, last, the pairs routed to experts held elsewhere; for a model
    with conv layers ``tail_blocks`` (a scalar), the block tails the
    step wrote over all conv layers.

    ``kv_shard="seq"`` (paged caches under a >1-way ``seq_axis`` mesh
    only — see :func:`init_paged_cache`) declares the pool
    block-sharded: per-layer KV writes and attention both run under
    ``shard_map`` (:func:`_pool_write_seq`,
    :func:`~tree_attention_tpu.parallel.tree.paged_tree_decode` — each
    shard computes flash partials over only its local blocks, merged by
    the 3-collective tree monoid). ``tree_mask`` is not supported there
    (chain speculation is; the engine gates draft trees off).

    Args:
      tokens: ``(B, Tq)`` token ids; row ``i`` occupies global positions
        ``[cache.length[i], cache.length[i] + Tq)`` of its own slot — slots
        need not agree (the ragged-batch shape continuous batching serves).
        ``Tq`` is the prompt length at prefill and 1 in the decode loop —
        both hit the same code path.
      n_tokens: optional per-slot ``(B,)`` valid counts — the **mixed-Tq**
        step a stall-free serving tick runs. Slot ``i`` consumes only its
        first ``n_tokens[i]`` rows of the padded token matrix: exactly
        those K/V rows are written (a masked read-modify-write window —
        rows ``>= n_tokens[i]`` leave the cache untouched, so the buffer
        stays bit-identical to a sequence of exact steps) and ``length``
        advances by ``n_tokens[i]``, not ``Tq``. A slot with ``n == 0``
        rides along inert (nothing written, length frozen). Logits rows at
        ``>= n_tokens[i]`` are pad garbage the caller must ignore (sample
        slot ``i`` from row ``n_tokens[i] - 1``). Values must satisfy
        ``0 <= n_tokens[i]`` and ``length[i] + n_tokens[i] <= capacity``;
        ``Tq`` itself must be ``<= capacity`` (the write window is
        ``Tq`` rows).
      positions: optional per-slot ``(B, Tq)`` TOKEN positions for RoPE —
        the speculative tree-verification shape (SpecInfer,
        arXiv:2305.09781), where packed draft-tree node ``j`` of slot
        ``i`` sits at depth ``depth[j]`` below the committed tip, so its
        rotary position is ``length[i] + depth[j]``, not ``length[i] +
        j``. Defaults to ``length[i] + j`` (the linear contract). KV rows
        still land at buffer positions ``[length[i], length[i] + Tq)`` in
        ROW order — the tree lives in positions and mask, not in the
        buffer layout.
      tree_mask: optional per-slot ``(B, Tq, Tq)`` ancestor-visibility
        mask (requires ``Tq <= 32``): row ``j`` of slot ``i`` attends its
        committed history plus exactly the window rows ``tree_mask[i, j]``
        flags (its draft-tree ancestors and itself), instead of the pure
        causal window rule. A lower-triangular mask reproduces plain
        causal masking bit-for-bit. Not supported on the sequence-sharded
        contiguous tree-decode path (the paged pool is replicated, so
        paged serving under a mesh takes the flash paths and works).

    A replicated paged pool (:class:`PagedKVCache`,
    :class:`PagedQuantKVCache`) rides the layer loop as loop-carried
    state: each layer's rows are written into the whole pool in place
    (:func:`_pool_write`) and attention — the block-table kernels
    on TPU, the hoisted reference view elsewhere — addresses layer ``l``
    through ``table + l·N``. Nothing slices, restacks or copies the pool,
    and the compiled tick must stay so
    (``tests/test_chip_compile.py::test_step_keeps_the_pool_in_place``).
    The sequence-sharded pool and the contiguous caches are scanned
    per layer as ``xs``/``ys``.

    Returns:
      ``logits``: ``(B, Tq, vocab)`` float32; the updated cache
      (``length += Tq``, or ``+= n_tokens`` when given). With a
      :class:`QuantKVCache`, new rows quantize
      under the cache's frozen scales and attention runs the q8 kernels —
      ``quant_kernel`` picks which (``"q8q"`` int8-MXU default, ``"q8"``
      bf16-cast; see :func:`decode_attention`), while ``cfg.attn_impl``
      and ``num_splits`` apply to the exact cache only (the q8 path's
      kernels are split-KV internally).
    """
    axes = prune_axes(
        mesh, {"data": data_axis, "seq": seq_axis, "model": model_axis}
    )

    B, Tq = tokens.shape
    start = cache.length  # (B,) per-slot offsets
    own_pool = isinstance(cache, _OWN_POOLS)
    _check_block_cache(cache, cfg)
    if own_pool and (tree_mask is not None or kv_shard == "seq"):
        raise ValueError(
            f"a {cfg.cache_kind} pool takes no tree_mask (the latent "
            f"kernel has none; a conv tail or a recurrent state cannot roll "
            f"a draft back; a window's freed blocks cannot come back) and "
            f"is not sequence-sharded"
        )
    paged = own_pool or isinstance(cache, (PagedKVCache, PagedQuantKVCache))
    if not paged and n_tokens is not None and Tq > cache.capacity:
        # The masked write is a Tq-row window into the token axis; a window
        # wider than the buffer cannot be placed at any offset.
        raise ValueError(
            f"mixed-Tq step: Tq={Tq} exceeds cache capacity "
            f"{cache.capacity}"
        )
    if not isinstance(start, jax.core.Tracer):
        # Only checkable eagerly: under jit ``length`` is traced and an
        # overflowing write would silently clamp (dynamic_update_slice
        # semantics), corrupting the newest rows — callers sizing their own
        # caches must keep max(length) + Tq <= capacity (generate() does;
        # the serving engine retires slots before their budget can). The
        # max runs in numpy: a jnp reduction here would be silently lifted
        # into any enclosing trace (a concrete cache closed over by a
        # scanned step) and break the isinstance guard.
        import numpy as np

        if n_tokens is None:
            hi = int(np.max(np.asarray(start))) + Tq
        elif not isinstance(n_tokens, jax.core.Tracer):
            # Mixed-Tq: each slot grows by its own count, so the overflow
            # bound is per-slot, not max(length) + Tq. An out-of-range
            # count is just as silent a corrupter: n > Tq advances length
            # past the last written row (stale bytes become visible
            # history), n < 0 rewinds it.
            nt = np.asarray(n_tokens)
            if int(np.min(nt)) < 0 or int(np.max(nt)) > Tq:
                raise ValueError(
                    f"mixed-Tq step: n_tokens must lie in [0, Tq={Tq}], "
                    f"got range [{int(np.min(nt))}, {int(np.max(nt))}]"
                )
            hi = int(np.max(np.asarray(start) + nt))
        else:
            hi = None
        if hi is not None and hi > cache.capacity:
            raise ValueError(
                f"KV cache overflow: writes reach {hi} tokens, "
                f"exceeding capacity {cache.capacity}"
            )
    if positions is None:
        positions = start[:, None] + jnp.arange(Tq, dtype=jnp.int32)
    if kv_shard not in ("replicated", "seq"):
        raise ValueError(
            f"kv_shard must be 'replicated' or 'seq', got {kv_shard!r}"
        )
    if kv_shard == "seq" and not paged:
        raise ValueError(
            "kv_shard='seq' shards the paged block pool; contiguous "
            "caches shard the token axis via the mesh instead"
        )

    with jax.named_scope(scopes.EMBED):
        x = embed(params, tokens, cfg.mup)
    _count_step(cache)
    group = _RowGroup(
        lo=None, batch=B, tq=Tq, start=start, n=n_tokens,
        table=cache.table if paged else None, tree_mask=tree_mask,
        wtable=getattr(cache, "wtable", None),
        slot=_slots(cache, B),
    )
    x, pools = _step_layers(
        params, x, positions, (group,), cache, cfg, mesh=mesh, axes=axes,
        num_splits=num_splits, quant_kernel=quant_kernel, kv_shard=kv_shard,
        stats=stats,
    )
    with jax.named_scope(scopes.HEAD):
        logits = unembed(params, norm_rows(cfg, x, params, "ln_f"), cfg.mup)
    grew = Tq if n_tokens is None else n_tokens
    return logits, dataclasses.replace(cache, length=start + grew, **pools)


def forward_packed_step(
    params: Params,
    chunk_tokens: jax.Array,
    chunk_slot: jax.Array,
    chunk_n: jax.Array,
    tokens: jax.Array,
    n_tokens: jax.Array,
    cache: Union[PagedKVCache, PagedQuantKVCache, PagedLatentCache,
                 PagedHybridCache],
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    data_axis: Optional[str] = AXIS_DATA,
    seq_axis: str = AXIS_SEQ,
    model_axis: Optional[str] = AXIS_MODEL,
    num_splits: Optional[int] = None,
    quant_kernel: str = "q8q",
    kv_shard: str = "replicated",
    stats: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Any]:
    """A step that computes only the rows that carry a token: a compact
    **chunk group** beside **one decode row a slot** (ISSUE 30).

    What :func:`forward_step` does with ``(S, Tq)`` tokens and ``n_tokens``
    when a few slots take a prompt chunk and the rest one token, in ``C·Tq +
    S`` rows where the padded step computes ``S·Tq``:

    - ``chunk_tokens`` ``(C, Tq)``: member ``j`` of the chunk group is slot
      ``chunk_slot[j]``, its first ``chunk_n[j]`` rows are valid. Its cache
      view is the same pool under ``table[chunk_slot[j]]`` from
      ``length[chunk_slot[j]]`` on: a gathered table and length, nothing
      else. A member with ``chunk_n == 0`` is padding: it writes nothing,
      moves no length and no slot samples from it, whatever slot it names.
    - ``tokens`` ``(S,)``, ``n_tokens`` ``(S,)`` in {0, 1}: the decode group,
      the ``Tq = 1`` step of every slot.

    A slot has rows in at most one group (the caller's contract: a chunking
    slot rides the decode group with ``n_tokens == 0``), so the groups'
    writes cannot collide, and no two members with rows name one slot. In a
    layer everything row-wise runs on the ``C·Tq + S`` rows at once, so the
    weights stream once; the pool write and attention run per group through
    the code the padded step runs (:func:`_step_layers`). The head runs on
    ONE row a slot: its last valid chunk row where it is a member with rows,
    its decode row otherwise. Where the model is a decoder that feeds a
    second decoder (``cfg.row_cut``), the rows are cut to that one row a
    slot EARLIER, at the seam: layers ``[0, row_cut)`` run on the ``C·Tq +
    S`` rows, then the residual (and the memory the gated units read) is
    gathered by the head's index and the layers above, the final norm and
    the head run on ``S`` rows, a cross layer's row attending from its own
    position. Exact: nothing above the seam carries anything from one
    position to the next.

    Returns ``logits`` ``(S, vocab)`` float32 (a slot with no row anywhere
    gets its inert decode row's, which the caller ignores) and the cache
    with ``length += n_tokens``, ``length[chunk_slot] += chunk_n``.
    """
    axes = prune_axes(
        mesh, {"data": data_axis, "seq": seq_axis, "model": model_axis}
    )
    own_pool = isinstance(cache, _OWN_POOLS)
    if not (own_pool
            or isinstance(cache, (PagedKVCache, PagedQuantKVCache))):
        raise ValueError(
            "a packed step's groups are views of ONE paged pool; got "
            f"{type(cache).__name__}"
        )
    _check_block_cache(cache, cfg)
    if kv_shard not in ("replicated", "seq") or (
            own_pool and kv_shard == "seq"):
        raise ValueError(
            f"kv_shard must be 'replicated' or 'seq' (a latent, hybrid, "
            f"window or state pool: 'replicated'), got {kv_shard!r}"
        )
    C, Tq = chunk_tokens.shape
    S = cache.table.shape[0]
    length = cache.length
    c_start = length[chunk_slot]
    wtable = getattr(cache, "wtable", None)
    slots = _slots(cache, S)
    groups = (
        _RowGroup(lo=0, batch=C, tq=Tq, start=c_start, n=chunk_n,
                  table=cache.table[chunk_slot], tree_mask=None, chunk=True,
                  wtable=None if wtable is None else wtable[chunk_slot],
                  slot=None if slots is None else chunk_slot),
        _RowGroup(lo=C * Tq, batch=S, tq=1, start=length, n=n_tokens,
                  table=cache.table, tree_mask=None, wtable=wtable,
                  slot=slots),
    )
    c_pos = c_start[:, None] + jnp.arange(Tq, dtype=jnp.int32)
    positions = jnp.concatenate([c_pos.reshape(-1), length])[None]
    with jax.named_scope(scopes.EMBED):
        rows = jnp.concatenate(
            [chunk_tokens.reshape(-1), tokens.reshape(-1)])
        x = embed(params, rows[None], cfg.mup)          # (1, C·Tq + S, D)
    _count_step(cache)

    def sampled_rows():
        # One row a slot: padding members scatter past the last slot and
        # drop.
        return (C * Tq + jnp.arange(S, dtype=jnp.int32)).at[
            jnp.where(chunk_n > 0, chunk_slot, S)
        ].set(
            jnp.arange(C, dtype=jnp.int32) * Tq
            + jnp.maximum(chunk_n - 1, 0),
            mode="drop",
        )

    cut = None
    if cfg.row_cut is not None:
        # The seam: above layer ``row_cut`` only a row some slot samples
        # from goes on, each attending from its own position (a chunk
        # member's last valid row sits at ``length + chunk_n - 1``).
        with jax.named_scope(scopes.ATTN_OUT):
            member = jnp.where(chunk_n > 0, chunk_slot, S)
            at = length.at[member].set(c_start + chunk_n - 1, mode="drop")
            has = n_tokens.at[member].set(1, mode="drop")
            cut = (sampled_rows(), _RowGroup(
                lo=0, batch=S, tq=1, start=at, n=has, table=cache.table,
                tree_mask=None))
    x, pools = _step_layers(
        params, x, positions, groups, cache, cfg, mesh=mesh, axes=axes,
        num_splits=num_splits, quant_kernel=quant_kernel, kv_shard=kv_shard,
        stats=stats, cut=cut,
    )
    with jax.named_scope(scopes.HEAD):
        rows = x[0] if cut is not None else x[0, sampled_rows()]
        logits = unembed(params, norm_rows(cfg, rows, params, "ln_f"),
                         cfg.mup)
    new_len = (length + n_tokens).at[chunk_slot].add(chunk_n)
    return logits, dataclasses.replace(cache, length=new_len, **pools)


def _compact_window_slot(
    buf: jax.Array, start: jax.Array, src: jax.Array, n: jax.Array
) -> jax.Array:
    """One slot's piece of :func:`compact_decode_window` (vmapped over
    batch): ``buf`` is ``(L, Hkv, cap, D)``, ``src`` a ``(W,)`` vector of
    window-relative source rows, ``start``/``n`` scalars. Token position
    ``start + i`` takes the value of ``start + src[i]`` for ``i < n``;
    everything else is written back unchanged (an identity ``src`` with
    ``n = 0`` is a bit-exact no-op). Same clamp-and-shift trick as
    :func:`_masked_window_write` near capacity."""
    W = src.shape[0]
    cap = buf.shape[2]
    ws = jnp.clip(start, 0, cap - W)
    shift = start - ws  # > 0 only when the window straddles capacity
    window = lax.dynamic_slice_in_dim(buf, ws, W, axis=2)
    loc = jnp.arange(W, dtype=jnp.int32)
    rel = loc - shift  # window-relative row this local position holds
    src_loc = shift + jnp.take(src, jnp.clip(rel, 0, W - 1))
    idx = jnp.where((rel >= 0) & (rel < n), src_loc, loc)
    merged = jnp.take(window, jnp.clip(idx, 0, W - 1), axis=2)
    return lax.dynamic_update_slice_in_dim(buf, merged, ws, axis=2)


def compact_decode_window(
    cache: Union[KVCache, QuantKVCache, PagedKVCache, PagedQuantKVCache],
    start: jax.Array,
    src: jax.Array,
    n: jax.Array,
) -> Union[KVCache, QuantKVCache, PagedKVCache, PagedQuantKVCache]:
    """Compact accepted speculative-tree rows to the front of each slot's
    verify window (the device half of a tree-draft commit).

    A tree verify step writes its packed draft nodes at buffer positions
    ``[start[i], start[i] + W)`` in ROW order; the accepted root-path's
    rows are scattered among them. This gathers them contiguous: token
    position ``start[i] + j`` takes the KV bytes of ``start[i] + src[i,
    j]`` for ``j < n[i]`` (``src`` is ascending, so sources are never
    overwritten before being read — and all reads are from the pre-call
    buffer anyway). Slots with ``n[i] = 0`` are untouched; ``length`` is
    NOT modified (the engine rolls it back through the next step's
    ``reset_val``). Linear (chain) drafts never need this — their accepted
    prefix is already contiguous.

    Works on all four cache layouts: contiguous caches permute inside a
    window read-modify-write (mesh-safe — the same vmapped machinery as
    the mixed-Tq write); paged caches gather + re-scatter the few moved
    rows through the block table (int8 rows move verbatim: they were
    quantized under their slot's frozen scales, which do not change).
    """
    B, W = src.shape
    src = jnp.asarray(src, jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    if isinstance(cache, (PagedKVCache, PagedQuantKVCache)):
        table = cache.table
        N, blk = cache.blocks, cache.block
        nb = table.shape[1]
        pos_dst = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
        pos_src = start[:, None] + src
        valid = (
            (jnp.arange(W, dtype=jnp.int32)[None] < n[:, None])
            & (pos_dst < nb * blk)
            & (pos_src < nb * blk)
        )
        pb_src = jnp.clip(
            jnp.take_along_axis(
                table, jnp.clip(pos_src // blk, 0, nb - 1), axis=1
            ), 0, N - 1,
        )  # gather side clamps; garbage rows pair with dropped dsts
        pb_dst = jnp.where(
            valid,
            jnp.take_along_axis(
                table, jnp.clip(pos_dst // blk, 0, nb - 1), axis=1
            ),
            N,  # OOB -> dropped
        )
        sb, so = pb_src.reshape(-1), (pos_src % blk).reshape(-1)
        db, do = pb_dst.reshape(-1), (pos_dst % blk).reshape(-1)

        def perm(pool: jax.Array) -> jax.Array:
            rows = pool[:, sb, :, so, :]  # (B·W, L, Hkv, D)
            return pool.at[:, db, :, do, :].set(
                rows.astype(pool.dtype), mode="drop"
            )

        return dataclasses.replace(
            cache, k=perm(cache.k), v=perm(cache.v)
        )
    move = jax.vmap(_compact_window_slot, in_axes=(1, 0, 0, 0), out_axes=1)
    return dataclasses.replace(
        cache, k=move(cache.k, start, src, n), v=move(cache.v, start, src, n)
    )


def round_cache_len(
    total: int, mesh: Optional[Mesh] = None, seq_axis: str = AXIS_SEQ
) -> int:
    """Cache capacity for ``total`` tokens, rounded up to the mesh's
    seq-shard multiple — the ONE sizing rule :func:`generate` and the
    serving CLI share (a capacity that does not divide over the seq axis
    is rejected by :func:`init_cache`)."""
    shards = mesh.shape.get(seq_axis, 1) if mesh is not None else 1
    return total + (-total) % max(shards, 1)


def _sample(logits: jax.Array, temperature: float, key: Optional[jax.Array]):
    """Greedy when temperature == 0 (static), else categorical."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(
        jnp.int32
    )


def sample_slots(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    keys: jax.Array,
    sample_idx: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Per-slot sampling for the serving tick (ISSUE 15): temperature /
    top-k categorical where ``temperature[i] > 0``, exact argmax where it
    is 0 — value-identical to the greedy path, so temperature-0 slots
    keep every existing parity gate.

    The PRNG discipline is the reproducibility contract: slot ``i``'s
    randomness for its ``j``-th emitted token is
    ``fold_in(keys[i], sample_idx[j])`` — a pure function of the
    REQUEST's key and the token's stream index, independent of tick
    interleaving, chunk mixtures, batch composition, or how many forked
    siblings share the batch. Two serves of the same trace with the same
    seeds therefore sample bit-identically, and a forked sibling (its
    own key) diverges from its parent at exactly the fork point.

    Args:
      logits: ``(S, V)`` last-row logits.
      temperature: ``(S,)`` float32 per-slot temperature (0 = greedy).
      top_k: ``(S,)`` int32 per-slot top-k cutoff (0 = off).
      keys: ``(S, 2)`` uint32 per-slot request keys.
      sample_idx: ``(S,)`` int32 emitted-token index per slot.

    Returns:
      ``(tok, logprob)``: ``(S,)`` int32 sampled ids and ``(S,)`` float32
      UNadjusted model log-probabilities of the chosen tokens (the
      cumulative-logprob input best-of-n selects on — OpenAI semantics:
      model logprob, not temperature-scaled).
    """
    V = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)

    def one(lg, t, k, key, idx):
        sub = jax.random.fold_in(key, idx)
        # Dynamic per-slot top-k: threshold at the k-th largest logit
        # (ties keep every logit >= it); k <= 0 disables the mask.
        srt = jnp.sort(lg)  # ascending
        kk = jnp.clip(k, 1, V)
        thresh = srt[V - kk]
        masked = jnp.where((k > 0) & (lg < thresh), -jnp.inf, lg)
        t_safe = jnp.where(t > 0, t, 1.0)
        return jax.random.categorical(sub, masked / t_safe)

    # The sort + categorical run only when some slot actually samples —
    # an all-greedy tick (the engine default) pays argmax alone, not a
    # discarded O(V log V) per slot on the hot path.
    sampled = lax.cond(
        jnp.any(temperature > 0.0),
        lambda _: jax.vmap(one)(lf, temperature, top_k, keys,
                                sample_idx).astype(jnp.int32),
        lambda _: greedy,
        operand=None,
    )
    tok = jnp.where(temperature > 0.0, sampled, greedy)
    logp = jax.nn.log_softmax(lf, axis=-1)
    lp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    return tok, lp


def sample_rows(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    row_keys: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Per-ROW sampling over a verify-shaped logits bundle (ISSUE 20):
    the row-wise generalization of :func:`sample_slots` for the
    ``(S, Tq, V)`` output of a tree/verify tick. Row ``(i, j)`` samples
    under ``row_keys[i, j]`` — the caller derives each row's key from
    the reproducibility chain (request key, branch index, produced
    stream index), so the key is already final: no index is folded in
    here. Temperature-0 slots take the exact per-row argmax, which is
    bit-identical to the greedy verify path this generalizes.

    Two consumers share this one function:

    - **token-tree sibling decode**: each live branch's deepest row is
      that branch's next sampled token;
    - **stochastic speculative acceptance** (Leviathan et al.,
      arXiv:2211.17192): row ``j``'s sample is the target-model draw
      after the path ending at row ``j`` — accepting a point-mass draft
      iff the draw equals it IS the ratio test, so the committed stream
      is distributed (and, under fixed keys, bit-) identical to
      non-speculative sampling.

    Args:
      logits: ``(S, Tq, V)`` verify-tick logits.
      temperature: ``(S,)`` float32 per-slot temperature (0 = greedy).
      top_k: ``(S,)`` int32 per-slot top-k cutoff (0 = off).
      row_keys: ``(S, Tq, 2)`` uint32 per-row PRNG keys (pre-folded).

    Returns:
      ``(tok, logprob)``: ``(S, Tq)`` int32 sampled ids and ``(S, Tq)``
      float32 UNadjusted model log-probabilities of the chosen tokens.
    """
    V = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)

    def one(lg, t, k, key):
        # Same distribution as sample_slots' inner draw: dynamic top-k
        # threshold (ties keep >=), temperature-scaled categorical.
        srt = jnp.sort(lg)
        kk = jnp.clip(k, 1, V)
        thresh = srt[V - kk]
        masked = jnp.where((k > 0) & (lg < thresh), -jnp.inf, lg)
        t_safe = jnp.where(t > 0, t, 1.0)
        return jax.random.categorical(key, masked / t_safe)

    def rows(lg, t, k, keys):  # (Tq, V) -> (Tq,)
        return jax.vmap(lambda g, kk: one(g, t, k, kk))(lg, keys)

    # All-greedy ticks (temperature 0 everywhere — the spec default)
    # pay the argmax alone, exactly like the pre-sampling verify path.
    sampled = lax.cond(
        jnp.any(temperature > 0.0),
        lambda _: jax.vmap(rows)(lf, temperature, top_k,
                                 row_keys).astype(jnp.int32),
        lambda _: greedy,
        operand=None,
    )
    tok = jnp.where(temperature[:, None] > 0.0, sampled, greedy)
    logp = jax.nn.log_softmax(lf, axis=-1)
    lp = jnp.take_along_axis(logp, tok[..., None], axis=-1)[..., 0]
    return tok, lp


def generate(
    params: Params,
    prompt: jax.Array,
    max_new_tokens: int,
    cfg: TransformerConfig,
    *,
    cache_len: Optional[int] = None,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    data_axis: Optional[str] = AXIS_DATA,
    seq_axis: str = AXIS_SEQ,
    model_axis: Optional[str] = AXIS_MODEL,
    quantize_after_prefill: bool = False,
    quant_kernel: str = "q8q",
) -> jax.Array:
    """Prefill the prompt, then decode ``max_new_tokens`` autoregressively.

    Args:
      prompt: ``(B, Tp)`` token ids.
      cache_len: cache capacity; defaults to ``Tp + max_new_tokens`` rounded up
        to the mesh's seq-shard multiple.
      quantize_after_prefill: prefill exactly, then int8-quantize the cache
        (:func:`quantize_cache`) so every decode step streams half the KV
        bytes. Approximate (per-channel int8); default off.
      quant_kernel: which q8 kernel the quantized steps run (``"q8q"``
        int8-MXU default, ``"q8"`` bf16-cast); ignored for the exact cache.

    Returns:
      ``(B, max_new_tokens)`` sampled token ids.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    B, Tp = prompt.shape
    total = Tp + max_new_tokens
    if cache_len is None:
        cache_len = round_cache_len(total, mesh, seq_axis)
    if cache_len < total:
        raise ValueError(f"cache_len={cache_len} < prompt+new={total}")
    if temperature > 0.0 and key is None:
        raise ValueError("temperature sampling needs a PRNG key")
    key = jax.random.PRNGKey(0) if key is None else key

    kw = dict(
        mesh=mesh, data_axis=data_axis, seq_axis=seq_axis, model_axis=model_axis
    )
    cache = init_cache(cfg, B, cache_len, **kw)
    logits, cache = forward_step(params, prompt, cache, cfg, **kw)
    kw["quant_kernel"] = quant_kernel  # decode steps only; prefill is exact
    if quantize_after_prefill:
        cache = quantize_cache(cache)
    key, sub = jax.random.split(key)
    tok = _sample(logits[:, -1], temperature, sub)

    def body(carry, _):
        cache, tok, key = carry
        logits, cache = forward_step(params, tok[:, None], cache, cfg, **kw)
        key, sub = jax.random.split(key)
        nxt = _sample(logits[:, -1], temperature, sub)
        return (cache, nxt, key), tok

    (_, last, _), toks = lax.scan(
        body, (cache, tok, key), None, length=max_new_tokens - 1
    )
    return jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    q_position=None,
    mesh: Optional[Mesh] = None,
    data_axis: Optional[str] = AXIS_DATA,
    seq_axis: str = AXIS_SEQ,
    model_axis: Optional[str] = AXIS_MODEL,
    impl: str = "auto",
    num_splits: Optional[int] = None,
    block_size: Optional[int] = None,
    quant_kernel: str = "q8q",
    block_table: Optional[jax.Array] = None,
    tree_mask: Optional[jax.Array] = None,
    kv_shard: str = "replicated",
    scale: Optional[float] = None,
    step_plan: Any = None,
    window: Any = None,
    launch: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Op-level decode entry: split-KV on one device, tree merge on a mesh.

    The two are the same algorithm at different granularity (chunks vs
    shards); this picks by topology so callers write one line. This is the
    single home of that dispatch rule — :func:`forward_step` routes through
    it for both the exact and the quantized cache. ``q_position`` may be a
    scalar or a per-slot ``(B,)`` vector (the ragged-batch shape); every
    path — flash_decode, the q8 kernels, and both tree merges — masks each
    row against its own offset. Passing ``k_scale`` /
    ``v_scale`` (with int8 ``k``/``v``) selects the q8 kernels, and
    ``quant_kernel`` picks which: ``"q8q"`` (default) runs scores natively
    int8 × int8 on the MXU — the fastest decode path (measured 92% vs 86%
    of the int8 roofline at 64k ctx) at ~1/254 extra relative logit error —
    and ``"q8"`` keeps the bf16-cast kernel. ``impl`` and ``num_splits``
    apply to the exact path only (the q8 kernels are split-KV internally).
    With ``block_table`` the call is **paged**: ``k``/``v`` are
    ``(N, Hkv, block, D)`` pools and each batch row reads KV through its
    ``(B, NB)`` table row (see :class:`PagedKVCache`); the pool is
    replicated under a mesh, so the tree merge never applies. ``step_plan``
    is the paged kernels' work list where the caller built it for several
    calls (a step's layers: :func:`_plan_groups`). ``window``: a
    sliding-window layer's call (the exact paged path only).
    """
    quant = k_scale is not None
    if window is not None and (quant or block_table is None
                               or kv_shard == "seq"):
        raise ValueError(
            "a sliding window is taken by the exact replicated paged path "
            "only")
    if quant and v_scale is None or (not quant and v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if scale is not None and (quant or kv_shard == "seq" or (
            mesh is not None and block_table is None)):
        raise ValueError(
            "a softmax scale other than D^-1/2 (rows of packed heads) is "
            "taken by the exact single-device paths only")
    ax = prune_axes(
        mesh, {"data": data_axis, "seq": seq_axis, "model": model_axis}
    )
    if block_table is not None:
        # Paged KV: k/v are (N, Hkv, block, D) pools and the table maps
        # each slot's logical blocks to pool rows. With the default
        # REPLICATED pool the flash/Pallas paths serve every topology
        # (blocks land at arbitrary token offsets, so no static sharding
        # of the TOKEN axis aligns with a seq shard). kv_shard="seq"
        # declares the pool BLOCK-sharded instead (ISSUE 18) and routes
        # to the shard_map'd 3-collective tree merge.
        if q_position is None:
            raise ValueError("paged decode needs an explicit q_position")
        if (
            kv_shard == "seq"
            and mesh is not None
            and mesh.shape.get(ax["seq"] or "", 1) > 1
        ):
            if tree_mask is not None:
                raise ValueError(
                    "tree_mask is not supported under kv_shard='seq'; "
                    "use chain drafts or the replicated pool"
                )
            from tree_attention_tpu.parallel.tree import paged_tree_decode

            return paged_tree_decode(
                q, k, v, block_table,
                mesh=mesh, seq_axis=ax["seq"], data_axis=ax["data"],
                head_axis=ax["model"], q_position=q_position,
                k_scale=k_scale, v_scale=v_scale,
            )
        if quant:
            from tree_attention_tpu.ops.pallas_decode import (
                resolve_q8_kernel,
            )

            kernel_fn = resolve_q8_kernel(quant_kernel)
            return kernel_fn(
                q, k, v, k_scale, v_scale, causal=True,
                q_offset=q_position, block_size=block_size,
                block_table=block_table, tree_mask=tree_mask,
                step_plan=step_plan,
            )
        return flash_decode(
            q, k, v, q_position=q_position, num_splits=num_splits,
            block_size=block_size, block_table=block_table,
            tree_mask=tree_mask, scale=scale, step_plan=step_plan,
            window=window, launch=launch,
        )
    if q_position is None:
        q_position = k.shape[2] - q.shape[2]
    if mesh is not None and mesh.shape.get(ax["seq"] or "", 1) > 1:
        if tree_mask is not None:
            # The tree merge has no window-mask plumbing; the serving
            # engine falls back to chain drafts where its step comes
            # here (a replicated exact pool rides the flash paths, which
            # take the mask).
            raise ValueError(
                "tree_mask is not supported on the sequence-sharded "
                "tree-decode path; use a replicated pool (flash "
                "kernels) or linear drafts"
            )
        mesh_kw = dict(
            mesh=mesh,
            seq_axis=ax["seq"],
            data_axis=ax["data"],
            head_axis=ax["model"],
            causal=True,
            q_position=q_position,
            block_size=block_size,
        )
        if quant:
            from tree_attention_tpu.parallel.tree import tree_decode_q8

            return tree_decode_q8(
                q, k, v, k_scale, v_scale, kernel=quant_kernel, **mesh_kw
            )
        from tree_attention_tpu.parallel.tree import tree_decode

        return tree_decode(q, k, v, impl=impl, **mesh_kw)
    if quant:
        from tree_attention_tpu.ops.pallas_decode import resolve_q8_kernel

        # block_size=None resolves inside the wrapper via the q8 tile table
        # (the one home of that default).
        kernel_fn = resolve_q8_kernel(quant_kernel)
        return kernel_fn(
            q, k, v, k_scale, v_scale, causal=True,
            q_offset=q_position, block_size=block_size,
            tree_mask=tree_mask,
        )
    return flash_decode(
        q, k, v, q_position=q_position, num_splits=num_splits,
        block_size=block_size, tree_mask=tree_mask, scale=scale,
    )



