"""Pallas TPU split-KV flash-decode kernel for small-Tq (inference) shapes.

Decode is the reference's entire workload (``/root/reference/model.py:140-145``:
one query token against a 64k-token KV buffer). It is bandwidth-bound — the
chip must stream every KV byte once — so the kernel's only job is to keep the
per-KV-row compute cost below the HBM delivery rate.

Layout (chosen by measurement on v5e; see the design notes below):

- **Q-major scores.** The score tile is ``(r8, block_k)``: packed query rows
  on sublanes (padded to a multiple of 8), KV positions across lanes. The
  QKᵀ matmul is then ``(r8, D) x (D, block_k)`` — ``r8·D·block_k`` MACs, a
  factor ``128/r8`` cheaper than a KV-major layout that pads queries to the
  128-lane width. A KV-major variant measured MXU-bound at ~25% of the HBM
  roofline for MHA decode precisely because of that padding; this layout's
  matmul cost is ~``block_k/16`` MXU cycles per tile against a DMA cost of
  ~``0.6·block_k`` cycles — comfortably DMA-bound.
- **The GQA group rides in the Q tile.** Queries are packed per KV head as
  ``(group × Tq)`` rows, and the grid runs over ``B·Hkv``, so each KV head's
  stream is read exactly **once** regardless of group size. (The Q-tiled
  training kernel instead re-reads KV per query head: measured 12% of
  roofline on GQA-8 decode, 8× the necessary bytes.)
- **Split-KV as the sequential grid dimension.** KV tiles iterate in the
  last grid dimension with the running online-softmax state ``(m, l, acc)``
  in VMEM scratch — the in-kernel mirror of
  :func:`tree_attention_tpu.ops.reference.merge_partials`, so the emitted
  ``(out, lse)`` plugs into the cross-device tree merge unchanged.
- **The paged kernels take fat steps.** A pool block is a few tens of KB a
  head, so a step of one head of one block is all fixed cost: the paged
  grid walks a list of the (slot, step) pairs that hold a live token
  (``paged_step_plan``), and a step takes every KV head of several table
  entries (``_paged_decode_step``; ``ops/tuning.py`` ``paged_decode_step``).
- Causal masking uses global offsets from SMEM (they are traced values
  inside jitted decode steps); tiles whose every KV position is masked skip
  both matmuls via ``pl.when``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tree_attention_tpu import obs
from tree_attention_tpu.ops.block_utils import (
    LANES as _LANES,
    NEG_INF,
    AlignedWindow,
    ChunkSummaries,
    WindowRule,
    matmul_precision,
    offsets_smem as _offsets_smem,
    pad_to_block as _pad_dim,
    window_visible,
)

# The wrappers below are jitted, so their Python bodies run once per
# distinct (shape, config): this counts kernel program BUILDS — a
# recompile storm (e.g. a caller advancing a static q_position per token)
# shows up here as a runaway count. Execution totals live in the host
# loops (bench/harness.py, cli.py).
# ``heads`` and ``entries`` say what one grid step takes of the KV stream: KV
# heads, and table entries of a paged pool (0: no table, the step is a
# ``block_k`` tile of a contiguous buffer).
# Execution-true, unlike the builds: the serve loop adds, tick by tick, what
# the paged kernels' work lists held (``run``: ``paged_step_plan``'s count,
# worked out on the host from the lengths it packed by the same rule,
# ``tuning.paged_live_steps``) and what the whole slots x steps rectangle
# would have (``grid``), over a tick's groups of rows.
PAGED_STEPS = obs.counter(
    "pallas_decode_paged_steps_total",
    "grid steps of the paged decode kernels a layer, summed over ticks: "
    "run (live steps on the work list) and grid (slots x table steps)",
    labels=("steps",),
)
_KERNEL_BUILDS = obs.counter(
    "pallas_decode_kernel_builds_total",
    "flash-decode kernel program builds (one per distinct shape/config), "
    "by the KV heads and table entries a grid step takes (mla_paged: the "
    "query heads packed over the one latent row)",
    labels=("kernel", "heads", "entries"),
)


def _decode_visibility_mask(s, qi, si, *, bq, bk, tq, tk,
                            q_offset, kv_offset, causal, tree_bits=None,
                            window=None):
    """Ragged-tail + causal masking for one (bq, bk) decode score tile —
    the ONE mask definition shared by the bf16-cast and int8-MXU kernels.

    Lane i is KV global position kv_offset + si*bk + i; sublane j is query
    row ((qi*bq + j) % Tq) at global position q_offset + that. Padded rows
    (j >= r) alias a real query's position and compute a duplicate row the
    host slices away. Broadcast form: (bq, 1) row positions vs (1, bk)
    column positions — one broadcast compare, no full-tile iota
    materialisation (see block_utils.mask_scores for why not a lax.cond
    interior skip). Static no-op for non-causal divisible shapes.

    ``tree_bits`` (a ``(bq, 1)`` int32 tile of per-PACKED-row ancestor
    bitmasks; requires ``causal`` and ``tq <= 32``) replaces the plain
    causal rule with the speculative tree-verification window rule
    (SpecInfer, arXiv:2305.09781): the tq query rows occupy KV positions
    ``[q_offset, q_offset + tq)`` of their slot, and row ``j`` sees window
    position ``i`` iff bit ``i`` of its mask is set; positions below the
    window stay visible (committed history), positions past it never are.
    A lower-triangular bitmask reproduces causal masking bit-for-bit.

    ``window`` (requires ``causal``, no ``tree_bits``): a sliding-window
    layer's lower edge, one more compare beside the causal one: row ``j``
    at position ``p`` sees the columns in ``(p - window, p]``, each row of
    a chunk its own. It may also be an aligned window's lower edge (``col
    >= (p // W) * W``) or the summary rule (``col < (p // W) * W / C``,
    the causal term then always true): ``block_utils.window_visible`` is
    the one definition, per row, so that a chunk group whose rows straddle
    a window boundary gives each row its own window.
    """
    needs_ragged = tk % bk != 0
    if tree_bits is not None:
        col_idx = si * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        rel = kv_offset + col_idx - q_offset  # window-relative KV position
        # Per-element logical shift; rel >= tq columns fail the window
        # check regardless, so the clip only keeps the shift in-range.
        bit = jax.lax.shift_right_logical(
            jnp.broadcast_to(tree_bits, (bq, bk)),
            jnp.broadcast_to(jnp.clip(rel, 0, 31), (bq, bk)),
        ) & 1
        valid = (rel < 0) | ((rel < tq) & (bit == 1))
        if needs_ragged:
            valid &= col_idx < tk
        return jnp.where(valid, s, NEG_INF)
    if not (causal or needs_ragged):
        return s
    col_idx = si * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    valid = None
    if needs_ragged:
        valid = col_idx < tk
    if causal:
        q_pos = q_offset + (
            (qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)) % tq
        )
        c = (kv_offset + col_idx) <= q_pos
        if window is not None:
            c &= window_visible(window, q_pos, kv_offset + col_idx)
        valid = c if valid is None else valid & c
    return jnp.where(valid, s, NEG_INF)


def _decode_softmax_fold(s, v_tile, m_scr, l_scr, acc_scr, *, si, bk, tk,
                         v_scale=None):
    """Fold one masked score tile and its V tile into the running
    online-softmax state — shared by the decode kernels. The paged kernels
    fold every head of a step at once: their tiles and state carry a
    leading head dimension, ``(heads, bq, bk)`` against ``(heads, bk, D)``.

    P·V with the FA2 p-downcast (probabilities are in [0,1], bf16 relative
    error stays small), f32 accumulation. When Tk is ragged the last tile's
    trailing V rows are unspecified garbage (Pallas loads the partial block
    unpadded; interpret mode NaN-poisons it) — p's masked columns are
    exactly 0, but 0·NaN = NaN, so those rows must be zeroed. Static no-op
    for divisible shapes.

    ``v_scale`` (a scalar — the per-BLOCK V dequantization scale of a
    paged int8 tile, ISSUE 13) multiplies ``p`` AFTER the running sum
    ``l`` is taken: the softmax normalizer is over the (dequantized)
    scores only, the scale belongs to the V values — ``p·(v_q·s) ==
    (p·s)·v_q``, one scalar multiply on the probability tile instead of
    a per-element dequant of the V stream.
    """
    m_prev = m_scr[..., :1]  # (bq, 1)
    l_prev = l_scr[..., :1]
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
    p = jnp.exp(s - m_safe)  # (bq, bk); masked cols are exactly 0
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    if v_tile.dtype == jnp.int8:
        v_tile = v_tile.astype(jnp.bfloat16)
    lead = tuple(range(s.ndim - 2))  # the paged kernels' heads
    if tk % bk:
        row_ok = (
            si * bk
            + lax.broadcasted_iota(jnp.int32, v_tile.shape, len(lead))
        ) < tk
        v_tile = jnp.where(row_ok, v_tile, 0)
    acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
        p.astype(v_tile.dtype), v_tile,
        dimension_numbers=(((len(lead) + 1,), (len(lead),)), (lead, lead)),
        preferred_element_type=jnp.float32,
        precision=matmul_precision(v_tile.dtype, v_tile.dtype),
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _decode_finalize(out_ref, lse_ref, m_scr, l_scr, acc_scr):
    """Emit (out, lse) from the final online-softmax state — shared by the
    decode kernels. Rows with no visible keys emit 0 / -inf."""
    m = m_scr[..., :1]
    l = l_scr[..., :1]
    empty = l <= 0.0
    l_safe = jnp.where(empty, 1.0, l)
    out_ref[0] = (
        jnp.where(empty, 0.0, acc_scr[...] / l_safe)
    ).astype(out_ref.dtype)
    lse = jnp.where(
        empty, NEG_INF, jnp.where(m == NEG_INF, 0.0, m) + jnp.log(l_safe)
    )
    lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _flash_decode_kernel(
    offs_ref,  # SMEM (2, B): per-batch [q_offset | kv_offset] columns —
               # ragged caches give every batch row its own global position
    *refs,     # q_ref, [tb_ref when tree], k_ref, v_ref, out_ref, lse_ref,
               # m_scr, l_scr, acc_scr:
               #   tb_ref  VMEM (1, bq, LANES) int32 — per-packed-row tree
               #           ancestor bitmasks (lane-broadcast), tree=True only
               #   q_ref   VMEM (1, bq, D) — packed (group × Tq) queries of
               #           one KV head
               #   k/v_ref VMEM (1, bk, D)
               #   out_ref VMEM (1, bq, D)
               #   lse_ref VMEM (1, bq, LANES) — lse broadcast across lanes
               #           (host slices lane 0; TPU tiling wants a
               #           128-multiple trailing dim)
               #   m/l_scr VMEM (bq, LANES) f32 — running max / sum
               #   acc_scr VMEM (bq, D) f32
    scale: float,
    causal: bool,
    tk: int,
    tq: int,
    block_q: int,
    block_k: int,
    n_kv_heads: int,
    tree: bool = False,
):
    if tree:
        q_ref, tb_ref, k_ref, v_ref, out_ref, lse_ref, \
            m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        tb_ref = None
    qi = pl.program_id(1)
    si = pl.program_id(2)
    n_s = pl.num_programs(2)

    b = pl.program_id(0) // n_kv_heads  # grid dim 0 runs over B·Hkv
    q_offset = offs_ref[0, b]
    kv_offset = offs_ref[1, b]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    bq, bk = block_q, block_k

    # Tile liveness: skip both matmuls when every KV position of this tile is
    # invisible — beyond Tk (host padding), or, under causality, past the
    # most visible query row of this Q tile. Packed row j is query index
    # (j % Tq), so the tile's maximum query position is q_offset + Tq - 1.
    live = si * bk < tk
    if causal:
        live &= (kv_offset + si * bk) <= (q_offset + tq - 1)

    @pl.when(live)
    def _compute():
        # Scores (bq, bk): packed queries on sublanes, KV across lanes.
        # Operands stay in their native dtype (bf16 MXU fast path) with f32
        # accumulation; see matmul_precision for the precision contract.
        # int8 K/V (the quantized-cache path) casts to bf16 first — exact
        # for values in [-127, 127], and dot_general rejects mixed dtypes.
        k_tile = k_ref[0]
        if k_tile.dtype == jnp.int8:
            k_tile = k_tile.astype(jnp.bfloat16)
        s = lax.dot_general(
            q_ref[0],
            k_tile,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(q_ref.dtype, k_tile.dtype),
        ) * scale  # (bq, bk) f32

        s = _decode_visibility_mask(
            s, qi, si, bq=bq, bk=bk, tq=tq, tk=tk,
            q_offset=q_offset, kv_offset=kv_offset, causal=causal,
            tree_bits=None if tb_ref is None else tb_ref[0][:, :1],
        )
        _decode_softmax_fold(
            s, v_ref[0], m_scr, l_scr, acc_scr, si=si, bk=bk, tk=tk
        )

    @pl.when(si == n_s - 1)
    def _finalize():
        _decode_finalize(out_ref, lse_ref, m_scr, l_scr, acc_scr)


def _flash_decode_q8q_kernel(
    offs_ref,  # SMEM (2, B): per-batch [q_offset | kv_offset] columns
    *refs,     # q_ref, qs_ref, [tb_ref when tree], k_ref, v_ref, out_ref,
               # lse_ref, m_scr, l_scr, acc_scr:
               #   tb_ref  VMEM (1, bq, LANES) int32 — tree bitmasks
               #   q_ref   VMEM (1, bq, D) int8 — per-row-quantized,
               #           scale-folded Q
               #   qs_ref  VMEM (1, bq, LANES) f32 — per-row Q scales
               #   k/v_ref VMEM (1, bk, D) int8
               #   out_ref VMEM (1, bq, D); lse_ref VMEM (1, bq, LANES)
               #   m/l_scr VMEM (bq, LANES) f32; acc_scr VMEM (bq, D) f32
    causal: bool,
    tk: int,
    tq: int,
    block_q: int,
    block_k: int,
    n_kv_heads: int,
    tree: bool = False,
):
    """The int8-MXU variant of :func:`_flash_decode_kernel`: scores run
    natively int8 x int8 -> int32 (no K dequant cast on the KV stream — the
    bf16-cast kernel's dominant per-tile VPU cost) and are rescaled by the
    per-row Q scale, one (bq, 1)-broadcast multiply. Measured 92.0% of the
    int8 roofline at 64k ctx vs 85.7% for the cast kernel
    (measurements/r3/experiment_q8q.jsonl). Same online-softmax state and
    ``(out, lse)`` contract; the lse is of the dequantized logits, so the
    output plugs into the tree merge unchanged."""
    if tree:
        q_ref, qs_ref, tb_ref, k_ref, v_ref, out_ref, lse_ref, \
            m_scr, l_scr, acc_scr = refs
    else:
        q_ref, qs_ref, k_ref, v_ref, out_ref, lse_ref, \
            m_scr, l_scr, acc_scr = refs
        tb_ref = None
    qi = pl.program_id(1)
    si = pl.program_id(2)
    n_s = pl.num_programs(2)

    b = pl.program_id(0) // n_kv_heads
    q_offset = offs_ref[0, b]
    kv_offset = offs_ref[1, b]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    bq, bk = block_q, block_k

    live = si * bk < tk
    if causal:
        live &= (kv_offset + si * bk) <= (q_offset + tq - 1)

    @pl.when(live)
    def _compute():
        s_i = lax.dot_general(
            q_ref[0],
            k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        s = s_i.astype(jnp.float32) * qs_ref[0][:, :1]  # (bq, bk) f32

        s = _decode_visibility_mask(
            s, qi, si, bq=bq, bk=bk, tq=tq, tk=tk,
            q_offset=q_offset, kv_offset=kv_offset, causal=causal,
            tree_bits=None if tb_ref is None else tb_ref[0][:, :1],
        )
        _decode_softmax_fold(
            s, v_ref[0], m_scr, l_scr, acc_scr, si=si, bk=bk, tk=tk
        )

    @pl.when(si == n_s - 1)
    def _finalize():
        _decode_finalize(out_ref, lse_ref, m_scr, l_scr, acc_scr)


# What a plan's flag word says of its entry: the slot's first entry (the
# online-softmax state starts here), its last (the rows are written out), and
# whether the step holds a token at all (a slot whose rows see nothing still
# gets one entry, which only starts and writes out).
_PLAN_FIRST, _PLAN_LAST, _PLAN_LIVE = 1, 2, 4
_PLAN_PREFETCH = 5  # offsets, table, slot, step, flags (``PagedPlan``)
PLAN_SCOPE = "paged_plan"
# The kernel name of a sliding-window layer's paged call: the one body under
# a name that does not contain a full layer's ("flash_decode_paged"), which
# the benchmark's readers match by substring.
WINDOW_KERNEL = "window_decode_paged"
# ... and of an EVA layer's two calls (``models/hybrid.py`` ``eva_mixer``):
# its exact rows under the aligned lower edge, and its summary rows. Neither
# contains another paged kernel's name: their cost files count other rows.
EVA_LOCAL_KERNEL, EVA_SUMMARY_KERNEL = "eva_local_decode", "eva_summary_decode"


def window_kernel_name(window: WindowRule) -> str:
    """The kernel name of a paged call under ``window``."""
    if isinstance(window, AlignedWindow):
        return EVA_LOCAL_KERNEL
    if isinstance(window, ChunkSummaries):
        return EVA_SUMMARY_KERNEL
    return WINDOW_KERNEL


def paged_step_plan(live: jax.Array, n_steps: int,
                    first: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The paged decode kernels' work list: ``(slot of entry e, step of
    entry e, flags of entry e, number of entries)`` from ``live`` ``(B,)``,
    the steps of each slot's table that hold a token its rows may see
    (``tuning.paged_live_steps``, 0 to ``n_steps``). Slot ``b`` gets entries
    for steps ``0 .. live[b] - 1`` (from ``first[b]`` on where ``first``,
    ``tuning.paged_first_step``, is given: a layer whose rows see a window
    of the context), slots in order and a slot's steps in
    order; a slot with none gets step 0 all the same, without
    ``_PLAN_LIVE``, so that its rows are still written. The lists have the
    static capacity ``B * n_steps`` (every slot full: the rectangle); what
    lies past the count is never visited. The count is the grid's last,
    dynamic, dimension, as ``pallas_moe.tile_plan``'s is."""
    B = live.shape[0]
    live = live.astype(jnp.int32)
    held = jnp.maximum(live, 1)
    ends = jnp.cumsum(held)
    e = jnp.arange(B * n_steps, dtype=jnp.int32)
    # A compare against every slot's end: no loop (a binary search lowers
    # to one, which the compiler would leave inside a layer loop's body).
    slot = jnp.minimum(
        jnp.sum(ends[None, :] <= e[:, None], axis=1, dtype=jnp.int32), B - 1)
    step = jnp.clip(e - (ends - held)[slot], 0, n_steps - 1)
    flags = (
        jnp.where(step == 0, _PLAN_FIRST, 0)
        | jnp.where(step == held[slot] - 1, _PLAN_LAST, 0)
        | jnp.where(live[slot] > 0, _PLAN_LIVE, 0)
    ).astype(jnp.int32)
    if first is not None:
        step = jnp.clip(step + first.astype(jnp.int32)[slot], 0, n_steps - 1)
    return slot, step, flags, ends[-1]


class PagedPlan(NamedTuple):
    """A paged decode call's scalar-prefetch operands and its dynamic grid
    bound: what the call works out from its offsets and its table before it
    reads a block. A layer loop shifts the table by a constant (``l * N +
    table``) and nothing else, so a step program builds one plan a group of
    rows (:func:`decode_plan`, :func:`mla_plan`) and hands every layer's
    call that plan :meth:`shifted` as the layer's table is; a call handed
    none builds its own."""

    offsets: jax.Array  # (2, B): per-slot [q_offset | kv_offset]
    table: jax.Array    # (E * entries,): the block table in list order,
                        # block j of entry e at [e * entries + j], so that a
                        # K/V index map reads its block with one scalar
                        # load, no slot or step to look up first
    slot: jax.Array     # (E,) the work list (``paged_step_plan``)
    step: jax.Array     # (E,)
    flags: jax.Array    # (E,)
    count: jax.Array    # (): the entries in the list

    def shifted(self, base) -> "PagedPlan":
        """The plan of the same call on ``base + block_table``."""
        return self._replace(table=self.table + base)


def paged_plan(q_offset, kv_offset, block_table: jax.Array, *, tq: int,
               entries: int, block: int, causal: bool = True,
               window: Optional[WindowRule] = None) -> PagedPlan:
    """The plan of a paged call of ``tq`` rows a slot against
    ``block_table`` ``(B, NB)`` of blocks of ``block`` tokens, ``entries``
    of them a grid step. With ``window`` (a sliding-window layer) a slot's
    list starts at the step that holds ``max(0, q_offset - window + 1)``,
    the lowest position its first row sees, and so holds one or two steps
    whatever the slot's length. An aligned window's list starts at the step
    that holds the first row's ``w0`` (the lowest of the group's, where its
    rows straddle a boundary); a summary call's list holds the steps under
    the LAST row's ``w0 / chunk`` summary rows and none for a slot whose
    rows have no closed window behind them."""
    from tree_attention_tpu.ops.tuning import paged_rule_steps

    B, NB = block_table.shape
    n_steps = NB // entries
    # Named, so that a compiled program says where its plans are built
    # (``tests/test_chip_compile.py``: not in a layer loop's body).
    with jax.named_scope(PLAN_SCOPE):
        offs = _offsets_smem(q_offset, kv_offset, B)
        if window is not None and not causal:
            raise ValueError("a sliding window requires causal=True")
        if causal:
            first, live = paged_rule_steps(
                offs[0], offs[1], tq, entries * block, n_steps, window,
                jnp.maximum)
        else:
            first, live = None, jnp.full((B,), n_steps, jnp.int32)
        slot, step, flags, count = paged_step_plan(live, n_steps, first)
        at = (slot * NB + step * entries)[:, None] \
            + jnp.arange(entries, dtype=jnp.int32)[None, :]
        table = jnp.asarray(block_table, jnp.int32).reshape(-1)[
            at.reshape(-1)]
    return PagedPlan(offs, table, slot, step, flags, count)


def _plan_operands(plan: Optional[PagedPlan], q_offset, kv_offset,
                   block_table: jax.Array, *, tq: int, entries: int,
                   block: int, causal: bool, window: Optional[WindowRule] = None):
    """``(the five scalar-prefetch operands, the dynamic grid bound)`` of a
    paged call, from the caller's plan or one built here."""
    B, NB = block_table.shape
    if plan is None:
        plan = paged_plan(q_offset, kv_offset, block_table, tq=tq,
                          entries=entries, block=block, causal=causal,
                          window=window)
    elif plan.offsets.shape != (2, B) or plan.table.shape != (B * NB,) \
            or plan.slot.shape != (B * NB // entries,):
        raise ValueError(
            f"a plan for {plan.offsets.shape[1]} slots of "
            f"{plan.table.shape[0] // plan.offsets.shape[1]} blocks, "
            f"{plan.table.shape[0] // plan.slot.shape[0]} a step, handed to "
            f"a call on {B} slots of {NB} blocks, {entries} a step"
        )
    return plan[:_PLAN_PREFETCH], plan.count


def _paged_decode_step(
    offs_ref,  # SMEM (2, B) scalar-prefetch: per-batch [q_offset|kv_offset]
    tbl_ref,   # SMEM (E * entries,) scalar-prefetch: the block table in list
               # order — read by the K/V index maps (PagedAttention,
               # arXiv:2309.06180); the body reads it only for a signed
               # table's ownership
    slot_ref,  # SMEM (E,) x 3 scalar-prefetch: the work list
    step_ref,  # (``paged_step_plan``): entry e is step ``step[e]`` of slot
    flag_ref,  # ``slot[e]``'s table
    refs,      # lead (q_ref, or q_ref and qs_ref), [tb_ref when tree],
               # k_ref x entries, v_ref x entries, [ks_ref, vs_ref when
               # block_scales], out_ref, lse_ref, m_scr, l_scr, acc_scr:
               #   q_ref   VMEM (1, heads, bq, D) — each head's packed
               #           (group x Tq) queries
               #   qs_ref  VMEM (1, heads, bq, LANES) f32 — per-row Q scales
               #   tb_ref  VMEM (1, heads, bq, LANES) int32 — tree bitmasks
               #   k/v_ref VMEM (1, heads, block, D) — every head of pool
               #           block tbl[e * entries + j], one operand an entry
               #           (the same pool through its own index map)
               #   ks/vs_ref VMEM (1, heads, 8, LANES) f32 — the 8-row scale
               #           tile that holds this step's entries
               #   out_ref VMEM (1, heads, bq, D)
               #   lse_ref VMEM (1, heads, bq, LANES)
               #   m/l_scr VMEM (heads, bq, LANES) f32
               #   acc_scr VMEM (heads, bq, D) f32
    scores,    # (*lead refs, k_tile (heads, entries * block, D)) ->
               # (heads, bq, entries * block) f32 scores, softmax scale
               # applied
    *,
    n_lead: int,
    causal: bool,
    tq: int,
    tk: int,
    block_q: int,
    block: int,
    entries: int,
    tree: bool,
    block_scales: bool,
    local_blocks: bool,
    window: Optional[WindowRule] = None,
):
    """One grid step of the paged decode kernels: every KV head of
    ``entries`` consecutive table entries of one slot.

    The split-KV grid dimension walks the work list (``paged_step_plan``):
    a slot's LOGICAL blocks ``entries`` at a time, as far as the slot's
    length reaches and no further, then the next slot's. The index maps
    read the scalar-prefetched table, so fragmented / non-monotone physical
    layouts stream like a contiguous buffer. The step's blocks are put end
    to end in logical order into one ``(heads, entries * block, D)`` K and
    V tile, so the mask and the online-softmax fold see a tile of
    ``entries * block`` columns exactly as the contiguous kernel sees one
    of its own, and every head's scores, softmax state and accumulator are
    worked as one batched array: a loop over the heads, each with its own
    slice of the scratch, ran the heads one after the other and took
    1.5-1.8 times as long on the chip (``ops/tuning.py``). A pool block is
    a few tens of KB a head: one head of one entry a step left the step's
    fixed cost (a quarter of a microsecond) in charge of the kernel's time
    (``ops/tuning.py`` ``paged_decode_step`` has the numbers and the rule
    for ``heads`` and ``entries``). The logical capacity ``tk = NB *
    block`` is step-divisible by construction, so the ragged-tail mask is
    statically off; the causal mask against each slot's own ``q_offset``
    hides every unwritten (or garbage-mapped) position of a slot's last
    step.

    ``block_scales`` (ISSUE 13, the shareable-int8 pool): two extra
    lane-broadcast operands carry each logical block's K and V
    dequantization SCALARS — K's multiplies the score tile after the
    matmul (a scalar commutes out of the dot product, so no per-element
    K dequant rides the KV stream), V's folds into ``p`` (see
    :func:`_decode_softmax_fold`). A step spans ``entries`` rows of the
    8-row scale tile, one scalar over each entry's ``block`` columns.

    ``local_blocks`` (ISSUE 18, the sequence-sharded pool): the table is
    SIGNED — a negative entry marks a logical block another shard owns.
    The index map clamps its DMA to pool row 0 (some valid row must
    stream); the body masks a remote entry's columns and skips a step
    whose entries are all remote, so the online-softmax state accumulates
    exactly this shard's partial; rows whose every block is remote
    finalize to the ``(0, -inf)`` merge identity that
    :func:`tree_attention_tpu.parallel.tree._weigh` absorbs.

    ``window`` (a sliding-window layer): the list the call walks starts at
    the step that holds the lowest position the slot's rows see
    (:func:`paged_plan`), and the mask hides the columns under each row's
    own lower edge; the table's entries behind the window name no block of
    the slot's (block 0) and are streamed at most inside that first step,
    masked."""
    refs = list(refs)
    lead, refs = refs[:n_lead], refs[n_lead:]
    tb_ref = refs.pop(0) if tree else None
    k_refs, v_refs, refs = refs[:entries], refs[entries:2 * entries], \
        refs[2 * entries:]
    ks_ref, vs_ref = (refs.pop(0), refs.pop(0)) if block_scales \
        else (None, None)
    out_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    e = pl.program_id(2)
    b, si, flags = slot_ref[e], step_ref[e], flag_ref[e]
    bq, bk = block_q, entries * block
    q_offset = offs_ref[0, b]
    kv_offset = offs_ref[1, b]

    @pl.when(flags & _PLAN_FIRST != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = flags & _PLAN_LIVE != 0
    if local_blocks:
        owner = [tbl_ref[e * entries + j] for j in range(entries)]
        live &= functools.reduce(jnp.maximum, owner) >= 0

    def by_entry(values):
        """``values[j]`` (a scalar, or an array whose last dim is ``bk``)
        over entry ``j``'s ``block`` columns: compares and selects only."""
        col = lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        row = jnp.broadcast_to(values[0], jnp.broadcast_shapes(
            jnp.shape(values[0]), (1, bk)))
        for j in range(1, entries):
            row = jnp.where(col >= j * block, values[j], row)
        return row

    def block_scale(ref):
        """The step's dequant scalars as a ``(heads, 1, bk)`` tile, ready
        to multiply the score / probability tile. The operand is already
        lane-broadcast, so a row is only cut (or repeated) along the lanes
        to ``bk``, and the multiply spreads it over the sublanes — Mosaic
        has no broadcast of a ``(1, 1)`` tile over sublanes and lanes at
        once."""
        first = (si * entries) % _SCALE_ROWS
        rows = []
        for j in range(entries):
            row = ref[0, :, pl.ds(first + j, 1), :]  # (heads, 1, LANES)
            if bk > _LANES:
                row = jnp.concatenate([row] * -(-bk // _LANES), axis=2)
            rows.append(row[:, :, :bk])
        return by_entry(rows)

    def tile(entry_refs):
        """The step's blocks end to end: ``(heads, bk, D)``."""
        if entries == 1:
            return entry_refs[0][0]
        return jnp.concatenate([r[0] for r in entry_refs], axis=1)

    @pl.when(live)
    def _compute():
        s = scores(*lead, tile(k_refs))  # (heads, bq, bk)
        if ks_ref is not None:
            s = s * block_scale(ks_ref)  # each block's K dequant
        # One mask for every head: packed row j is query j % Tq in all.
        s = _decode_visibility_mask(
            s, qi, si, bq=bq, bk=bk, tq=tq, tk=tk,
            q_offset=q_offset, kv_offset=kv_offset, causal=causal,
            tree_bits=None if tb_ref is None else tb_ref[0, 0][:, :1],
            window=window,
        )
        if local_blocks and entries > 1:
            # A remote entry inside a live step: its columns are masked
            # (its DMA brought pool row 0, any finite rows).
            s = jnp.where(by_entry(owner) >= 0, s, NEG_INF)
        _decode_softmax_fold(
            s, tile(v_refs), m_scr, l_scr, acc_scr, si=si, bk=bk, tk=tk,
            v_scale=None if vs_ref is None else block_scale(vs_ref),
        )

    @pl.when(flags & _PLAN_LAST != 0)
    def _finalize():
        _decode_finalize(out_ref, lse_ref, m_scr, l_scr, acc_scr)


def _flash_decode_paged_kernel(*refs, scale: float, **step):
    """Block-table variant of :func:`_flash_decode_kernel`; the step is
    :func:`_paged_decode_step`'s. bf16 (or any float) pool, or an int8
    pool cast tile by tile to bf16 (exact for [-127, 127])."""

    def scores(q_ref, k_tile):
        if k_tile.dtype == jnp.int8:
            k_tile = k_tile.astype(jnp.bfloat16)
        return lax.dot_general(
            q_ref[0],
            k_tile,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(q_ref.dtype, k_tile.dtype),
        ) * scale

    _paged_decode_step(*refs[:_PLAN_PREFETCH], refs[_PLAN_PREFETCH:], scores,
                       n_lead=1, **step)


def _flash_decode_paged_q8q_kernel(*refs, **step):
    """Block-table variant of :func:`_flash_decode_q8q_kernel` — same
    int8-MXU score path (``q_ref`` int8, per-row-quantized and scale-folded;
    ``qs_ref`` its per-row scales), KV streamed through the
    scalar-prefetched table by :func:`_paged_decode_step`. With
    ``block_scales`` (ISSUE 13) the per-BLOCK K scalars join the per-row Q
    scale in the post-matmul rescale (both are scalars w.r.t. the int8 dot,
    so the MXU path stays int8 x int8 -> int32), V's fold into ``p``."""

    def scores(q_ref, qs_ref, k_tile):
        s_i = lax.dot_general(
            q_ref[0],
            k_tile,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )
        return s_i.astype(jnp.float32) * qs_ref[0][..., :1]

    _paged_decode_step(*refs[:_PLAN_PREFETCH], refs[_PLAN_PREFETCH:], scores,
                       n_lead=2, **step)


def _paged_q_map(qi, e, offs_ref, tbl_ref, slot_ref, step_ref, flag_ref):
    """Q/out/lse index map of the latent paged decode grid: the entry's
    slot."""
    del offs_ref, tbl_ref, step_ref, flag_ref
    return (slot_ref[e], qi, 0)


def _paged_rows_map(hg, qi, e, offs_ref, tbl_ref, slot_ref, step_ref,
                    flag_ref):
    """Index map of the paged decode grid's per-row operands (q, q scales,
    tree bits, out, lse), all ``(B, Hkv, rows, lanes)``: the entry's slot,
    and the head group of grid dim 0 (one group, every head, unless
    ``paged_decode_step`` had to split them)."""
    del offs_ref, tbl_ref, step_ref, flag_ref
    return (slot_ref[e], hg, qi, 0)


def _paged_kv_map(j: int, entries: int, local: bool = False):
    """K/V index map of a step's ``j``-th entry: list entry ``e`` loads
    every head (of this head group) of pool block ``table[e * entries +
    j]``, the table in list order (``_plan_operands``) — the block-table
    indirection happens HERE, in the prefetch-driven DMA schedule, not in
    the body, and costs a map one scalar load.

    ``local`` (ISSUE 18): the table is signed; a negative entry marks a
    block this shard does not own. The DMA engine still needs SOME valid
    pool row, so the map clamps to 0 — the body masks the entry's columns
    before they touch the softmax state."""

    def index_map(hg, qi, e, offs_ref, tbl_ref, slot_ref, step_ref,
                  flag_ref):
        del qi, offs_ref, slot_ref, step_ref, flag_ref
        t = tbl_ref[e * entries + j]
        if local:
            t = jnp.maximum(t, 0)
        return (t, hg, 0, 0)

    return index_map


# The per-block scale operand streams in tiles of one f32 sublane group:
# the TPU lowering needs a block's second-minor dim divisible by 8 (a
# one-row block is refused), so each DMA brings 8 logical blocks' scalars
# and the body picks its step's rows: the entries a step takes divide 8
# (``tuning.PAGED_STEP_ENTRIES``), so a step never straddles two tiles.
_SCALE_ROWS = 8


def _paged_scale_map(entries: int):
    """Per-block scale operand map (ISSUE 13): the scales were pre-
    gathered per LOGICAL block (see :func:`_block_scale_rows`), so the
    entry for step ``si`` of a slot reads the 8-row tile holding rows ``si
    * entries ...`` — no second table dereference, and no re-fetch while
    the step stays inside the tile."""

    def index_map(hg, qi, e, offs_ref, tbl_ref, slot_ref, step_ref,
                  flag_ref):
        del qi, offs_ref, tbl_ref, flag_ref
        return (slot_ref[e], hg, (step_ref[e] * entries) // _SCALE_ROWS, 0)

    return index_map


def _block_scale_rows(scale: jax.Array, block_table: jax.Array) -> jax.Array:
    """Arrange ``(N, Hkv)`` per-block scale scalars into the
    ``(B, Hkv, NB8, LANES)`` lane-broadcast operand the paged kernels read
    (``NB8`` = the table width rounded up to :data:`_SCALE_ROWS`; the pad
    rows are never read) — one scalar per (slot, head, logical block),
    gathered through the table once per call (O(B·NB·Hkv) floats, noise
    next to the KV bytes the grid streams). The same VMEM idiom as the q8q
    per-row Q scales and the tree bitmasks."""
    N, Hkv = scale.shape
    g = scale[jnp.clip(block_table, 0, N - 1)]      # (B, NB, Hkv)
    g = _pad_dim(jnp.moveaxis(g, 2, 1), 2, _SCALE_ROWS)
    return jnp.broadcast_to(g[..., None], (*g.shape, _LANES))


def _paged_decode_call(
    kernel_body,
    kernel_kwargs,
    label: str,
    rows,
    k: jax.Array,
    v: jax.Array,
    *,
    scales=None,
    tree: bool,
    group: int,
    tq: int,
    bq: int,
    causal: bool,
    local_blocks: bool = False,
    q_offset,
    kv_offset,
    block_table: jax.Array,
    step_plan: Optional[PagedPlan] = None,
    window: Optional[WindowRule] = None,
    launch: Optional[str] = None,
    out_dtype,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Shared ``pallas_call`` plumbing of the paged decode kernels.

    ``rows`` holds the kernel's per-row operands (q; for q8q its row scales
    too; last the tree bitmasks when ``tree``), each
    ``(B, Hkv, n_q * bq, lanes)``; ``k`` / ``v`` are the
    ``(N, Hkv, block, D)`` pools and ``scales`` the per-block ``(N, Hkv)``
    pair of an int8 pool, if it has them. ``tuning.paged_decode_step`` reads
    the shapes and says how many heads and table entries a grid step takes;
    the offsets say which steps of which slots hold a token the rows may
    see (``paged_step_plan``), and the grid is ``(head groups, n_q, entries
    of that list)``: a slot at a third of its capacity costs a third of its
    steps, and with every slot full the list is the whole ``B x NB /
    entries`` rectangle. Offsets, list and the table in list order ride
    scalar prefetch (``PrefetchScalarGridSpec``); the pools are handed in
    once an entry, each with its own index map into the table, so the DMA
    pipeline prefetches physical blocks in logical order with no gather
    copy. Returns ``(out, lse)`` as ``(B, Hq, Tq, D)`` in ``out_dtype`` and
    ``(B, Hq, Tq)``. With ``window`` the call is a sliding-window layer's:
    one body, one more compare in its mask and a list that starts at each
    slot's window, under a kernel name of its own
    (:func:`window_kernel_name`), so that a device trace tells a window
    layer's calls from a full layer's, and an EVA layer's two calls from
    each other."""
    from tree_attention_tpu.ops.tuning import paged_decode_step

    B, Hkv, n_rows, D = rows[0].shape
    block = k.shape[2]
    NB = block_table.shape[1]
    n_q = n_rows // bq
    heads, entries = paged_decode_step(
        Hkv, block, D, k.dtype.itemsize, NB, bq)
    head_groups = Hkv // heads
    name = kernel_body.__name__.strip("_").removesuffix("_kernel")
    if window is not None:
        if tree or local_blocks or scales is not None:
            raise ValueError(
                "a sliding window is built for the exact replicated pool "
                "without a tree mask")
        name = label = window_kernel_name(window)
    if launch:
        # The same kernel body under a launch name that says whose rows it
        # reads (``"shared"``: another layer's, a cross layer's call): a
        # trace tells the two apart, and no two tick programs give one
        # operation name two parts of the model.
        name = f"{name}_{launch}"
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(
            kernel=label, heads=heads, entries=entries).inc()
    prefetch, n_entries = _plan_operands(
        step_plan, q_offset, kv_offset, block_table, tq=tq, entries=entries,
        block=block, causal=causal, window=window)
    tensors = list(rows)
    in_specs = [
        pl.BlockSpec((1, heads, bq, t.shape[3]), _paged_rows_map)
        for t in tensors
    ]
    for pool in (k, v):
        tensors += [pool] * entries
        in_specs += [
            pl.BlockSpec((1, heads, block, D),
                         _paged_kv_map(j, entries, local=local_blocks))
            for j in range(entries)
        ]
    if scales is not None:
        tensors += [_block_scale_rows(s, block_table) for s in scales]
        in_specs += [
            pl.BlockSpec((1, heads, _SCALE_ROWS, _LANES),
                         _paged_scale_map(entries))
        ] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=_PLAN_PREFETCH,
        grid=(head_groups, n_q, n_entries),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, heads, bq, D), _paged_rows_map),
            pl.BlockSpec((1, heads, bq, _LANES), _paged_rows_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, bq, _LANES), jnp.float32),
            pltpu.VMEM((heads, bq, _LANES), jnp.float32),
            pltpu.VMEM((heads, bq, D), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            kernel_body, **kernel_kwargs, causal=causal, tq=tq,
            tk=NB * block, block_q=bq, block=block, entries=entries,
            tree=tree, block_scales=scales is not None,
            local_blocks=local_blocks, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, n_rows, D), out_dtype),
            jax.ShapeDtypeStruct((B, Hkv, n_rows, _LANES), jnp.float32),
        ],
        # Only the list is sequential (a slot's entries carry its softmax
        # state), as the split-KV dim is in the contiguous kernels.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # A stable name per kernel body (a window layer's call: its own),
        # carried into the compiled module (the custom call's op_name) and
        # the profiler trace.
        name=name,
    )(*prefetch, *tensors)
    r = group * tq
    return (out[:, :, :r].reshape(B, Hkv * group, tq, D),
            lse[:, :, :r, 0].reshape(B, Hkv * group, tq))


def _mla_decode_paged_kernel(
    offs_ref,  # SMEM (2, B) scalar-prefetch: per-batch [q_offset|kv_offset]
    tbl_ref,   # SMEM (E * per,) scalar-prefetch: the block table in list
               # order (index maps only)
    slot_ref,  # SMEM (E,) x 3 scalar-prefetch: the work list
    step_ref,  # (``paged_step_plan``)
    flag_ref,
    *refs,     # q_ref, kv_ref x blocks_per_step, out_ref, lse_ref,
               # m_scr, l_scr, acc_scr:
               #   q_ref   VMEM (1, bq, W) — packed (head x Tq) queries,
               #           each row [q_lat rank | q_rope]
               #   kv_ref  VMEM (1, block, W) — latent pool block
               #           tbl[e * blocks_per_step + j]
               #   out_ref VMEM (1, bq, rank); lse_ref VMEM (1, bq, LANES)
               #   m/l_scr VMEM (bq, LANES) f32; acc_scr VMEM (bq, rank) f32
    scale: float,
    tq: int,
    tk: int,
    block_q: int,
    block: int,
    rank: int,
    blocks_per_step: int,
):
    """Latent (MLA) attention in absorbed form over a paged pool of
    ``[c_kv | k_rope]`` rows: ONE row a token serves every query head, so
    all heads (x Tq rows) pack into the Q-tile sublanes like a GQA group
    with one KV head. Scores contract the whole row (latent + rotary
    part), values are the row's first ``rank`` lanes: the pool block is
    streamed once and used for both. A grid step folds
    ``blocks_per_step`` logical blocks (one operand each, every one the
    same pool through its own table entry): a latent block is a few tens
    of KB, so one a step would leave the step's fixed cost in charge. The
    steps are the work list's (``paged_step_plan``), as the GQA kernels'
    are: none past a slot's length."""
    del tbl_ref  # consumed by the index maps
    q_ref = refs[0]
    kv_refs = refs[1:1 + blocks_per_step]
    out_ref, lse_ref, m_scr, l_scr, acc_scr = refs[1 + blocks_per_step:]
    qi = pl.program_id(0)
    e = pl.program_id(1)
    b, si, flags = slot_ref[e], step_ref[e], flag_ref[e]
    bq, bk = block_q, block * blocks_per_step
    q_offset = offs_ref[0, b]
    kv_offset = offs_ref[1, b]

    @pl.when(flags & _PLAN_FIRST != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(flags & _PLAN_LIVE != 0)
    def _compute():
        kv = kv_refs[0][0] if blocks_per_step == 1 else jnp.concatenate(
            [r[0] for r in kv_refs], axis=0)           # (bk, W)
        s = lax.dot_general(
            q_ref[0], kv,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(q_ref.dtype, kv.dtype),
        ) * scale
        s = _decode_visibility_mask(
            s, qi, si, bq=bq, bk=bk, tq=tq, tk=tk,
            q_offset=q_offset, kv_offset=kv_offset, causal=True,
        )
        _decode_softmax_fold(
            s, kv[:, :rank], m_scr, l_scr, acc_scr, si=si, bk=bk, tk=tk,
        )

    @pl.when(flags & _PLAN_LAST != 0)
    def _finalize():
        _decode_finalize(out_ref, lse_ref, m_scr, l_scr, acc_scr)


def mla_step_entries(table_width: int) -> int:
    """Table entries a grid step of ``mla_decode_paged`` folds."""
    return next(p for p in (4, 2, 1) if table_width % p == 0)


def mla_plan(tq: int, pool: jax.Array, block_table: jax.Array, q_offset
             ) -> PagedPlan:
    """The plan :func:`attention_pallas_mla_paged` builds for ``tq`` rows a
    slot at ``q_offset`` against this ``(..., block, W)`` pool through
    ``block_table``."""
    return paged_plan(
        q_offset, 0, block_table, tq=tq, block=pool.shape[-2],
        entries=mla_step_entries(block_table.shape[1]))


def _mla_kv_map(j: int, blocks_per_step: int):
    def index_map(qi, e, offs_ref, tbl_ref, slot_ref, step_ref, flag_ref):
        del qi, offs_ref, slot_ref, step_ref, flag_ref
        return (tbl_ref[e * blocks_per_step + j], 0, 0)

    return index_map


def attention_pallas_mla_paged(
    q: jax.Array,
    pool: jax.Array,
    block_table: jax.Array,
    *,
    q_offset,
    scale: float,
    rank: int,
    step_plan: Optional[PagedPlan] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Absorbed latent attention against a paged latent pool.

    ``q`` is ``(B, H, Tq, W)``, each row ``[q_lat (rank) | q_rope | 0
    pad]``; ``pool`` is ``(N, block, W)`` rows ``[c_kv (rank) | k_rope
    | pad]`` and batch row ``b``'s logical block ``j`` is pool row
    ``block_table[b, j]``. Row ``t`` of slot ``b`` sits at position
    ``q_offset[b] + t`` and sees every position up to its own. Returns
    ``(out, lse)`` like its siblings: ``out`` ``(B, H, Tq, rank)`` is
    ``softmax(q·row) · row[:rank]`` (still latent: the caller multiplies
    by the value up-projection), ``lse`` ``(B, H, Tq)`` float32, so a
    partial over some of the blocks merges with any other by the repo's
    ``(out, lse)`` monoid. The device event is ``mla_decode_paged``.
    ``step_plan``: the call's work list, if the caller built it already
    (:func:`mla_plan`: a layer loop builds it once for all its layers).
    """
    B, H, Tq, W = q.shape
    if pool.ndim != 3 or pool.shape[2] != W:
        raise ValueError(
            f"a latent pool is (N, block, {W}) for queries of width {W}, "
            f"got {pool.shape}"
        )
    N, block, _ = pool.shape
    NB = block_table.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Every head reads the same rows: pack heads x Tq into the sublanes.
    # Decode (Tq = 1) is one tile of up to 128 heads a slot (half a tile at
    # 64 heads); chunk rows take tiles of 1024 so that a slot's blocks are
    # walked by few tiles.
    r = H * Tq
    bq = _packed_rows(r, 128 if Tq == 1 else 1024)
    qp = _pad_dim(q.reshape(B, r, W), 1, bq)
    n_q = qp.shape[1] // bq
    per = mla_step_entries(NB)
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(kernel="mla_paged", heads=H, entries=per).inc()
    in_specs = [pl.BlockSpec((1, bq, W), _paged_q_map)] + [
        pl.BlockSpec((1, block, W), _mla_kv_map(j, per))
        for j in range(per)
    ]
    prefetch, n_entries = _plan_operands(
        step_plan, q_offset, 0, block_table, tq=Tq, entries=per, block=block,
        causal=True)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=_PLAN_PREFETCH,
        grid=(n_q, n_entries),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, rank), _paged_q_map),
            pl.BlockSpec((1, bq, _LANES), _paged_q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, rank), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _mla_decode_paged_kernel, scale=scale, tq=Tq, tk=NB * block,
            block_q=bq, block=block, rank=rank, blocks_per_step=per,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_q * bq, rank), q.dtype),
            jax.ShapeDtypeStruct((B, n_q * bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mla_decode_paged",
    )(*prefetch, qp, *([pool] * per))
    return (out[:, :r].reshape(B, H, Tq, rank),
            lse[:, :r, 0].reshape(B, H, Tq))


ROW_WRITE_KERNEL = "paged_row_write"


def row_write_tile(block: int) -> int:
    """Rows of a pool block that :func:`paged_row_write` moves for one new
    row: the 8 rows of the tile the compiler lays a pool out in on the chip
    (``T(8,128)`` whatever the dtype; a packed dtype's rows share words
    inside it), the least a copy may cut out of the pool; the whole block
    where 8 does not divide it. (The packed sublane tile, 16 rows of bf16,
    moves twice the bytes for 0.1-1.8 us more a call: ``ops/tuning.py``.)"""
    return block if block % 8 else 8


def _paged_row_write_kernel(
    ids_ref,   # SMEM (B,) scalar-prefetch: slot b's pool block in its
               # layer, or -1: the slot writes nothing
    off_ref,   # SMEM (B,) scalar-prefetch: the row of that block
    base_ref,  # SMEM (1,) scalar-prefetch: the layer's first block (l * N)
    *refs,     # rows x P     VMEM (B, Hkv, 1, D): the new rows, a pool each
               # pools_in x P HBM: the aliased inputs, reached as outputs
               # pools x P    HBM (M, Hkv, block, D)
               # tiles x P    VMEM (B, Hkv, tile, D) scratch
               # sem          DMA semaphores (P, B)
    n_pools: int,
    tile: int,
):
    """One new row a slot into every pool, through the 8-row tile that
    holds it (:func:`row_write_tile`): each live slot's tile is copied in,
    the row overlaid at its sublane, the tile copied back to where it came
    from. Every slot's copy
    in is started before any is awaited, and every copy back before any of
    those, so a call costs two copies' latency and not ``2 x B``. A slot
    with ``ids[b] < 0`` starts no copy at all: its table entry may be a
    stale name of the block a live slot writes in this very call, and a
    tile read and written back unchanged would race that row. Live slots
    never share a writable block, so no two copies meet."""
    P = n_pools
    rows, pools, tiles, sem = (
        refs[:P], refs[2 * P:3 * P], refs[3 * P:4 * P], refs[4 * P])
    B = rows[0].shape[0]

    def home(p, b):
        at = pl.multiple_of(off_ref[b] // tile * tile, tile)
        return pools[p].at[base_ref[0] + ids_ref[b], :, pl.ds(at, tile), :]

    def fetch(p, b):
        return pltpu.make_async_copy(home(p, b), tiles[p].at[b], sem.at[p, b])

    def store(p, b):
        return pltpu.make_async_copy(tiles[p].at[b], home(p, b), sem.at[p, b])

    def every_live(do):
        def slot(b, carry):
            @pl.when(ids_ref[b] >= 0)
            def _live():
                for p in range(P):
                    do(p, b)
            return carry

        lax.fori_loop(0, B, slot, 0)

    def overlay(p, b):
        fetch(p, b).wait()
        old = tiles[p][b]
        here = lax.broadcasted_iota(jnp.int32, old.shape, 1) \
            == off_ref[b] % tile
        tiles[p][b] = jnp.where(here, rows[p][b], old)
        store(p, b).start()

    every_live(lambda p, b: fetch(p, b).start())
    every_live(overlay)
    every_live(lambda p, b: store(p, b).wait())


def paged_row_write(
    pools: Tuple[jax.Array, ...],
    rows: Tuple[jax.Array, ...],
    block_ids: jax.Array,
    offsets: jax.Array,
    base,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, ...]:
    """Write one new row a slot into paged pools, in place.

    ``pools`` are ``(M, Hkv, block, D)`` arrays of one shape and dtype (K
    and V of every layer as the decode kernels see them, or the one latent
    pool with ``Hkv`` 1), ``rows`` a ``(B, Hkv, 1, D)`` array a pool in the
    pool's dtype. Slot ``b``'s row lands at ``pool[base + block_ids[b], :,
    offsets[b], :]``; a slot with ``block_ids[b] < 0`` writes nothing and
    touches nothing. The caller keeps live slots' blocks distinct and
    ``base + block_ids`` inside the pool. Each pool is aliased to its
    output, so under a donating ``jit`` the pool that enters is the one
    that leaves, and a row moves :func:`row_write_tile` rows of its block
    each way and no more. The device event is ``paged_row_write``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_row_write_call(
        tuple(pools), tuple(rows), jnp.asarray(block_ids, jnp.int32),
        jnp.asarray(offsets, jnp.int32),
        jnp.asarray(base, jnp.int32).reshape(1), interpret=interpret)


# Jitted, as the kernels above: a step program launches it once for every run
# of layers (five in a window configuration's) and every one of its calls
# traces and lowers the kernel body afresh otherwise, 0.03-0.08 s a call site
# in every tick program's set-up, compile cache or not.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_row_write_call(pools, rows, block_ids, offsets, base, *,
                          interpret: bool):
    P = len(pools)
    M, Hkv, block, D = pools[0].shape
    B = rows[0].shape[0]
    dtype = pools[0].dtype
    if any(p.shape != pools[0].shape or p.dtype != dtype for p in pools) \
            or any(r.shape != (B, Hkv, 1, D) or r.dtype != dtype
                   for r in rows) or len(rows) != P:
        raise ValueError(
            f"paged_row_write takes pools of one shape and dtype and a "
            f"(B, {Hkv}, 1, {D}) {dtype} row array a pool, got pools "
            f"{[(p.shape, p.dtype) for p in pools]} and rows "
            f"{[(r.shape, r.dtype) for r in rows]}"
        )
    tile = row_write_tile(block)
    if obs.REGISTRY.enabled:
        # One step a call: every KV head of one table entry a slot.
        _KERNEL_BUILDS.labels(
            kernel=ROW_WRITE_KERNEL, heads=Hkv, entries=B).inc()
    scalars = (block_ids, offsets, base)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * P
        + [pl.BlockSpec(memory_space=pl.ANY)] * P,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * P,
        scratch_shapes=[pltpu.VMEM((B, Hkv, tile, D), dtype)] * P
        + [pltpu.SemaphoreType.DMA((P, B))],
    )
    return tuple(pl.pallas_call(
        functools.partial(_paged_row_write_kernel, n_pools=P, tile=tile),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={len(scalars) + P + i: i for i in range(P)},
        interpret=interpret,
        name=ROW_WRITE_KERNEL,
    )(*scalars, *rows, *pools))


CHUNK_READ_KERNEL = "paged_chunk_read"


def _paged_chunk_read_kernel(
    blk_ref,   # SMEM (E,) scalar-prefetch: entry e's pool block
    row_ref,   # SMEM (E,): the first of its ``chunk`` rows in that block
    n_ref,     # SMEM (B,): member b's live entries, its first ``n[b]`` of
               # ``per``
    *refs,     # pools x P  HBM (M, Hkv, block, D)
               # outs x P   VMEM (E, Hkv, chunk, D)
               # sem        DMA semaphores (P, E)
    n_pools: int,
    chunk: int,
    per: int,
):
    """``chunk`` consecutive rows of a pool block an entry, out of every
    pool, by one copy each: every live entry's copy is started before any is
    awaited. An entry past its member's count starts none, and its rows of
    the output are whatever the buffer held."""
    P = n_pools
    pools, outs, sem = refs[:P], refs[P:2 * P], refs[2 * P]
    E = outs[0].shape[0]

    def copy(p, e):
        at = pl.multiple_of(row_ref[e], chunk)
        return pltpu.make_async_copy(
            pools[p].at[blk_ref[e], :, pl.ds(at, chunk), :], outs[p].at[e],
            sem.at[p, e])

    def every_live(do):
        def entry(e, carry):
            @pl.when(e % per < n_ref[e // per])
            def _live():
                for p in range(P):
                    do(p, e)
            return carry

        lax.fori_loop(0, E, entry, 0)

    every_live(lambda p, e: copy(p, e).start())
    every_live(lambda p, e: copy(p, e).wait())


def paged_chunk_read(
    pools: Tuple[jax.Array, ...],
    block_ids: jax.Array,
    rows: jax.Array,
    counts: jax.Array,
    chunk: int,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, ...]:
    """Read ``chunk`` consecutive rows of a pool block, for ``(B, J)`` of
    them, out of every pool: ``pools`` are ``(M, Hkv, block, D)`` arrays of
    one shape and dtype, entry ``(b, j)`` is rows ``rows[b, j] .. rows[b,
    j] + chunk - 1`` (``rows`` a multiple of ``chunk``) of block
    ``block_ids[b, j]``, read only where ``j < counts[b]``. Returns a
    ``(B, J, Hkv, chunk, D)`` array a pool; an entry not read is not
    defined. What an EVA layer forms a chunk's summary from
    (``models/hybrid.py`` ``eva_summaries``): a gather of the same rows in
    plain XLA made the compiler re-lay the whole pool for it
    (``tests/test_chip_compile.py``). The device event is
    ``paged_chunk_read``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, J = block_ids.shape
    out = _paged_chunk_read_call(
        tuple(pools), jnp.asarray(block_ids, jnp.int32).reshape(-1),
        jnp.asarray(rows, jnp.int32).reshape(-1),
        jnp.asarray(counts, jnp.int32), chunk=chunk, interpret=interpret)
    return tuple(o.reshape((B, J) + o.shape[1:]) for o in out)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _paged_chunk_read_call(pools, block_ids, rows, counts, *, chunk: int,
                           interpret: bool):
    P = len(pools)
    M, Hkv, block, D = pools[0].shape
    E, B = block_ids.shape[0], counts.shape[0]
    dtype = pools[0].dtype
    if any(p.shape != pools[0].shape or p.dtype != dtype for p in pools) \
            or block % chunk or E % B:
        raise ValueError(
            f"paged_chunk_read takes pools of one shape and dtype whose "
            f"block is a whole number of chunks of {chunk}, got "
            f"{[(p.shape, p.dtype) for p in pools]}")
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(
            kernel=CHUNK_READ_KERNEL, heads=Hkv, entries=E).inc()
    scalars = (block_ids, rows, counts)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * P,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * P,
        scratch_shapes=[pltpu.SemaphoreType.DMA((P, E))],
    )
    return tuple(pl.pallas_call(
        functools.partial(_paged_chunk_read_kernel, n_pools=P, chunk=chunk,
                          per=E // B),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((E, Hkv, chunk, D), dtype)] * P,
        interpret=interpret,
        name=CHUNK_READ_KERNEL,
    )(*scalars, *pools))


def _packed_rows(r: int, cap: int = 128) -> int:
    """Rows of a Q tile for ``r`` packed rows a KV head: a multiple of the
    8 sublanes, ``cap`` at most."""
    return min(-(-r // 8) * 8, cap)


def decode_step_entries(q_heads: int, tq: int, pool: jax.Array,
                        table_width: int) -> int:
    """Table entries a grid step of the GQA paged kernels takes for
    ``q_heads`` query heads x ``tq`` rows a slot against this ``(..., Hkv,
    block, D)`` pool (``tuning.paged_decode_step`` at the call's shapes)."""
    from tree_attention_tpu.ops.tuning import paged_decode_step

    Hkv, block, D = pool.shape[-3:]
    return paged_decode_step(
        Hkv, block, D, pool.dtype.itemsize, table_width,
        _packed_rows(q_heads // Hkv * tq))[1]


def decode_plan(q_heads: int, tq: int, pool: jax.Array,
                block_table: jax.Array, q_offset,
                window: Optional[WindowRule] = None) -> PagedPlan:
    """The plan the GQA paged kernels (:func:`attention_pallas_decode`,
    ``_q8``, ``_q8q`` with a ``block_table``) build for a causal call of
    ``q_heads`` query heads x ``tq`` rows a slot against this pool
    through ``block_table``."""
    return paged_plan(
        q_offset, 0, block_table, tq=tq, block=pool.shape[-2],
        entries=decode_step_entries(
            q_heads, tq, pool, block_table.shape[1]), window=window)


def _tree_bits_rows(
    tree_mask: jax.Array, G: int, Hkv: int, bq: int, n_q: int
) -> jax.Array:
    """Pack a ``(B, Tq, Tq)`` bool ancestor mask into the per-packed-row
    bitmask operand the decode kernels read: ``(B*Hkv, n_q*bq, LANES)``
    int32, bit ``j`` of packed row ``r`` = query row ``r % Tq`` sees window
    position ``j``. Rows ride VMEM lane-broadcast exactly like the q8q
    per-row Q scales (the kernel reads ``[:, :1]``). Padded rows get 0 —
    their window is fully masked (committed history stays visible) and the
    host slices them away."""
    B, Tq, _ = tree_mask.shape
    # One bit per window column; bit 31 wraps to INT32_MIN, which is the
    # correct bit PATTERN (the kernel shifts logically), and bits never
    # collide, so the sum is a bitwise OR.
    bits = jnp.sum(
        tree_mask.astype(jnp.int32)
        * jnp.left_shift(1, jnp.arange(Tq, dtype=jnp.int32))[None, None, :],
        axis=2,
    )  # (B, Tq)
    rows = jnp.broadcast_to(bits[:, None, None, :], (B, Hkv, G, Tq))
    rows = _pad_dim(rows.reshape(B, Hkv, G * Tq), 2, bq)
    rows = rows.reshape(B * Hkv, n_q * bq, 1)
    return jnp.broadcast_to(rows, (B * Hkv, n_q * bq, _LANES))


def _paged_rows(q_rows, tb):
    """The paged call's per-row operands: the kernel's own, then the packed
    tree bitmasks (if any) with the slot and head dims apart, as the paged
    grid indexes them."""
    if tb is None:
        return q_rows
    B, Hkv, n_rows, _ = q_rows[0].shape
    return [*q_rows, tb.reshape(B, Hkv, n_rows, _LANES)]


def resolve_q8_kernel(kernel: str):
    """The one home of the q8-kernel-name contract: ``"q8q"`` → the int8-MXU
    kernel (:func:`attention_pallas_decode_q8q`), ``"q8"`` → the bf16-cast
    kernel (:func:`attention_pallas_decode_q8`); anything else raises."""
    if kernel == "q8q":
        return attention_pallas_decode_q8q
    if kernel == "q8":
        return attention_pallas_decode_q8
    raise ValueError(f"q8 kernel must be 'q8q' or 'q8', got {kernel!r}")


def quantize_kv_channelwise(
    k: jax.Array, v: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-channel symmetric int8 quantization of a KV buffer.

    Returns ``(k_q, v_q, k_scale, v_scale)``: int8 tensors shaped like k/v
    and float32 scales of shape ``(B, Hkv, 1, D)`` with
    ``k ≈ k_q * k_scale``. Per-channel (one scale per head-dim lane per KV
    head) rather than per-token so the decode kernel never touches the
    scales on the hot KV stream: K's scale folds into Q before the kernel
    and V's applies to the accumulator in the epilogue — both O(D) per
    step, not O(T·D).
    """
    k_q, k_s = quantize_symmetric_int8(k, axis=2)
    v_q, v_s = quantize_symmetric_int8(v, axis=2)
    return k_q, v_q, k_s, v_s


@functools.partial(jax.jit, static_argnames=("axis",))
def quantize_symmetric_int8(x: jax.Array, axis: int):
    """The one definition of the q8 numeric contract the kernels dequant
    against: absmax/127 scale (zero-channel scale = 1.0), f32 intermediate,
    round, clip to ±127, int8. ``axis`` is the reduction (token) axis —
    2 for a (B, Hkv, T, D) buffer, 3 for a (L, B, Hkv, T, D) cache."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_size", "interpret"),
)
def attention_pallas_decode_q8(
    q: jax.Array,
    k_q: jax.Array,
    v_q: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    block_size: Optional[int] = None,
    interpret: Optional[bool] = None,
    block_table: Optional[jax.Array] = None,
    tree_mask: Optional[jax.Array] = None,
    step_plan: Optional[PagedPlan] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Split-KV flash decode over an int8-quantized KV buffer.

    Same ``(out, lse)`` contract as :func:`attention_pallas_decode`, computed
    over the *dequantized* values ``k_q·k_scale`` / ``v_q·v_scale`` — the lse
    is of the dequantized logits, so the output plugs into the tree merge
    unchanged. Decode is bandwidth-bound (the kernel's whole job is to
    stream every KV byte once), so int8 halves the bytes and doubles the
    tokens/sec ceiling at the same roofline; the scales never ride the KV
    stream (see :func:`quantize_kv_channelwise`).

    Opt-in: quantization is approximate (≈2–3 decimal digits per channel).
    The framework's default path stays exact.
    """
    B, Hq, Tq, D = q.shape
    Hkv = k_q.shape[1]
    if k_q.dtype != jnp.int8 or v_q.dtype != jnp.int8:
        raise ValueError(
            f"k_q/v_q must be int8, got {k_q.dtype}/{v_q.dtype}"
        )
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    G = Hq // Hkv
    if block_table is not None and getattr(k_scale, "ndim", 4) == 2:
        # Per-BLOCK scale scalars (ISSUE 13): the fold-into-Q trick below
        # cannot express a scale that varies along the KV stream, so this
        # shape takes its own paged call — Q rides bf16 un-folded (softmax
        # scale applied by the kernel), each block's K scalar rescales the
        # score tile post-matmul, V's folds into p.
        N = k_q.shape[0]
        if k_scale.shape != (N, Hkv) or v_scale.shape != (N, Hkv):
            raise ValueError(
                f"per-block scales must be (N, Hkv) = {(N, Hkv)}, got "
                f"{k_scale.shape}/{v_scale.shape}"
            )
        if tree_mask is not None:
            if not causal:
                raise ValueError("tree_mask requires causal=True")
            if Tq > 32:
                raise ValueError(
                    f"tree_mask packs into int32 bitmasks: Tq={Tq} "
                    f"exceeds 32"
                )
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        out_dtype = q.dtype
        sm = (D ** -0.5) if scale is None else scale
        bq = _packed_rows(G * Tq)
        qp = _pad_dim(
            q.astype(jnp.bfloat16).reshape(B, Hkv, G * Tq, D), 2, bq)
        tb = None if tree_mask is None else _tree_bits_rows(
            tree_mask, G, Hkv, bq, qp.shape[2] // bq)
        out, lse = _paged_decode_call(
            _flash_decode_paged_kernel, dict(scale=sm), "paged_q8_block",
            _paged_rows([qp], tb), k_q, v_q, scales=(k_scale, v_scale),
            tree=tb is not None, group=G, tq=Tq, bq=bq, causal=causal,
            q_offset=q_offset, kv_offset=kv_offset,
            block_table=block_table, step_plan=step_plan,
            out_dtype=jnp.bfloat16, interpret=interpret,
        )
        return out.astype(out_dtype), lse
    if k_scale.shape != (B, Hkv, 1, D) or v_scale.shape != (B, Hkv, 1, D):
        raise ValueError(
            f"scales must be (B, Hkv, 1, D) = {(B, Hkv, 1, D)}, got "
            f"{k_scale.shape}/{v_scale.shape}"
        )
    # block_size=None falls through to the base kernel, which resolves it
    # from the q8 tile table when K/V are int8 (the one home of that
    # default).
    # Fold K's per-channel scale into Q: (q ⊙ k_s)·k_qᵀ == q·(k_q ⊙ k_s)ᵀ.
    # The fold runs in f32; the folded Q is carried bf16 into the kernel
    # (the MXU fast path, and the same operand precision the unquantized
    # bf16 decode runs at).
    qf = (
        q.astype(jnp.float32).reshape(B, Hkv, G * Tq, D) * k_scale
    ).astype(jnp.bfloat16).reshape(B, Hq, Tq, D)
    # The base split-KV kernel runs the int8 K/V directly (in-kernel bf16
    # casts, exact for [-127, 127]; no dequant multiplies on the KV stream).
    # A block_table passes straight through: the base kernel's paged path
    # streams int8 pool blocks the same way.
    out, lse = attention_pallas_decode(
        qf, k_q, v_q, causal=causal, scale=scale,
        q_offset=q_offset, kv_offset=kv_offset, block_size=block_size,
        interpret=interpret, block_table=block_table, tree_mask=tree_mask,
        step_plan=step_plan,
    )
    # V's per-channel scale applies to the normalised accumulator.
    out = (
        out.astype(jnp.float32).reshape(B, Hkv, G * Tq, D) * v_scale
    ).reshape(B, Hq, Tq, D).astype(q.dtype)
    return out, lse


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_size", "interpret"),
)
def attention_pallas_decode_q8q(
    q: jax.Array,
    k_q: jax.Array,
    v_q: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    block_size: Optional[int] = None,
    interpret: Optional[bool] = None,
    block_table: Optional[jax.Array] = None,
    tree_mask: Optional[jax.Array] = None,
    step_plan: Optional[PagedPlan] = None,
) -> Tuple[jax.Array, jax.Array]:
    """int8-MXU flash decode over an int8 KV buffer: Q quantized too.

    Same contract and cache format as :func:`attention_pallas_decode_q8`,
    one step further down the precision/bandwidth trade: K's channel scale
    and the softmax scale fold into Q in f32, then each packed query ROW is
    absmax-quantized to int8, the score matmul runs natively
    int8 x int8 -> int32 on the MXU (no per-tile K dequant cast — the cast
    kernel's dominant VPU cost), and the int32 scores are rescaled by the
    per-row Q scale. Measured 92% of the int8 roofline at 64k ctx vs 86%
    for the cast kernel; adds ~1/254 relative Q-rounding error to the
    logits on top of q8's K error (measured max 0.7% relative output
    error; see measurements/r3/experiment_q8q.jsonl).
    """
    B, Hq, Tq, D = q.shape
    Hkv = k_q.shape[1]
    # Paged: k_q/v_q are (N, Hkv, block, D) pools; the logical context is
    # the table width in blocks (see attention_pallas_decode).
    Tk = (
        block_table.shape[1] * k_q.shape[2] if block_table is not None
        else k_q.shape[2]
    )
    if k_q.dtype != jnp.int8 or v_q.dtype != jnp.int8:
        raise ValueError(
            f"k_q/v_q must be int8, got {k_q.dtype}/{v_q.dtype}"
        )
    # Per-BLOCK scale scalars (ISSUE 13): (N, Hkv) — one dequant scalar
    # per pool block per head, riding block-indexed lane-broadcast
    # operands into the kernel. Only meaningful with a block table; the
    # contiguous shape keeps the per-slot (B, Hkv, 1, D) channel scales
    # (which fold into Q — a per-block scale cannot, it varies along
    # the KV stream).
    per_block = block_table is not None and getattr(k_scale, "ndim", 4) == 2
    if per_block:
        N = k_q.shape[0]
        if k_scale.shape != (N, Hkv) or v_scale.shape != (N, Hkv):
            raise ValueError(
                f"per-block scales must be (N, Hkv) = {(N, Hkv)}, got "
                f"{k_scale.shape}/{v_scale.shape}"
            )
    elif k_scale.shape != (B, Hkv, 1, D) or v_scale.shape != (B, Hkv, 1, D):
        raise ValueError(
            f"scales must be (B, Hkv, 1, D) = {(B, Hkv, 1, D)}, got "
            f"{k_scale.shape}/{v_scale.shape}"
        )
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    G = Hq // Hkv
    sm = (D ** -0.5) if scale is None else scale
    if tree_mask is not None:
        if not causal:
            raise ValueError("tree_mask requires causal=True")
        if Tq > 32:
            raise ValueError(
                f"tree_mask packs into int32 bitmasks: Tq={Tq} exceeds 32"
            )
        if tree_mask.shape != (B, Tq, Tq):
            raise ValueError(
                f"tree_mask must be (B, Tq, Tq) = {(B, Tq, Tq)}, got "
                f"{tree_mask.shape}"
            )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out_dtype = q.dtype

    if Tk == 0:
        return (
            jnp.zeros(q.shape, out_dtype),
            jnp.full((B, Hq, Tq), NEG_INF, jnp.float32),
        )

    # Fold the scales into Q in f32, then per-row absmax int8 quantize
    # (the one q8 numeric contract, quantize_symmetric_int8, reduced over
    # the head-dim axis) — the row scale rides a separate (bq, LANES)
    # input into the kernel. Per-block K scales cannot fold (they vary
    # along the KV stream): only the softmax scale folds, and the
    # kernel's post-matmul rescale picks up each block's scalar.
    r = G * Tq
    qf = q.astype(jnp.float32).reshape(B, Hkv, r, D) * (
        sm if per_block else (k_scale * sm)
    )
    q_i, qs = quantize_symmetric_int8(qf, axis=3)

    bq = _packed_rows(r)
    qp = _pad_dim(q_i, 2, bq)                       # (B, Hkv, n_q * bq, D)
    n_q = qp.shape[2] // bq
    # Padded rows get scale 0 — their int32 scores then rescale to exactly
    # 0 everywhere, a harmless finite value (the host slices those rows
    # away; under causality they alias a real row's mask anyway).
    qsp = jnp.broadcast_to(
        _pad_dim(qs, 2, bq), (B, Hkv, n_q * bq, _LANES))

    tb = None if tree_mask is None else _tree_bits_rows(
        tree_mask, G, Hkv, bq, n_q)
    if block_table is not None:
        out, lse = _paged_decode_call(
            _flash_decode_paged_q8q_kernel, {}, "paged_q8q",
            _paged_rows([qp, qsp], tb), k_q, v_q,
            scales=(k_scale, v_scale) if per_block else None,
            tree=tb is not None, group=G, tq=Tq, bq=bq, causal=causal,
            q_offset=q_offset, kv_offset=kv_offset,
            block_table=block_table, step_plan=step_plan,
            out_dtype=jnp.bfloat16, interpret=interpret,
        )
        if not per_block:
            # (per-block scalars dequantized V in-kernel, folded into p;
            # per-channel scales apply to the normalised accumulator here)
            out = (
                out.astype(jnp.float32).reshape(B, Hkv, r, D) * v_scale
            ).reshape(B, Hq, Tq, D)
        return out.astype(out_dtype), lse

    if block_size is None:
        from tree_attention_tpu.ops.tuning import decode_block_k_q8

        block_size = decode_block_k_q8(Tk)
    bk = min(block_size, max(Tk, _LANES))
    kp = k_q.reshape(B * Hkv, Tk, D)
    vp = v_q.reshape(B * Hkv, Tk, D)
    n_s = -(-Tk // bk)
    qp = qp.reshape(B * Hkv, n_q * bq, D)
    qsp = qsp.reshape(B * Hkv, n_q * bq, _LANES)

    offs = _offsets_smem(q_offset, kv_offset, B)

    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(kernel="q8q", heads=1, entries=0).inc()
    tensors = [offs, qp, qsp, kp, vp]
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda bh, qi, si: (bh, qi, 0)),
        pl.BlockSpec((1, bq, _LANES), lambda bh, qi, si: (bh, qi, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, si: (bh, si, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, si: (bh, si, 0)),
    ]
    if tb is not None:
        tensors.insert(3, tb)
        in_specs.insert(
            3,
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, si: (bh, qi, 0)),
        )
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_decode_q8q_kernel,
            causal=causal, tk=Tk, tq=Tq, block_q=bq, block_k=bk,
            n_kv_heads=Hkv, tree=tree_mask is not None,
        ),
        grid=(B * Hkv, n_q, n_s),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, si: (bh, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, si: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, n_q * bq, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((B * Hkv, n_q * bq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode_q8q",
    )(*tensors)

    out = out[:, :r]
    # V's per-channel scale on the normalised accumulator, like the q8 path.
    out = (
        out.astype(jnp.float32).reshape(B, Hkv, r, D) * v_scale
    ).reshape(B, Hq, Tq, D).astype(out_dtype)
    lse = lse[:, :r, 0].reshape(B, Hq, Tq)
    return out, lse


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_size", "interpret", "local_blocks",
        "window", "launch",
    ),
)
def attention_pallas_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    block_size: Optional[int] = None,
    interpret: Optional[bool] = None,
    block_table: Optional[jax.Array] = None,
    tree_mask: Optional[jax.Array] = None,
    local_blocks: bool = False,
    step_plan: Optional[PagedPlan] = None,
    window: Optional[WindowRule] = None,
    launch: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Split-KV flash decode. Same ``(out, lse)`` contract as the other impls.

    Intended for Tq < 128 (the decode/speculative regime); any Tq works but
    the Q-tiled training kernel
    (:func:`tree_attention_tpu.ops.pallas_attention.attention_pallas_fwd`)
    is the right shape for large Tq. ``interpret=None`` auto-selects:
    compiled on TPU, interpreter elsewhere (what CI exercises on CPU).

    ``q_offset`` (and ``kv_offset``) may be a scalar or a ``(B,)`` vector —
    the ragged-batch shape: each batch row is a cache slot with its own
    filled length, and the causal mask hides every row's unwritten future
    independently (offsets ride SMEM; the grid and tiles are unchanged).

    With ``block_table`` (a ``(B, NB)`` int32 array) the call is **paged**:
    ``k``/``v`` are ``(N, Hkv, block, D)`` pools and batch row ``b``'s
    logical KV block ``j`` lives in pool row ``block_table[b, j]``. The
    table rides scalar prefetch and the index maps dereference it. A grid
    step takes every KV head of several consecutive table entries
    (``ops/tuning.py`` ``paged_decode_step`` works out how many from the
    shapes: 1 MB of K + V a step, 4 entries at 8 KV heads and 8 at 4 for
    64-token bf16 blocks of 128; a width that 2 does not divide gets one
    entry a step), so the split-KV tile is that many pool blocks end to end.
    The grid is ``(head groups, Q tiles, live steps)``: a list of the steps
    of each slot's table that hold a position its rows may see
    (:func:`paged_step_plan`), none past a slot's length, so a call costs
    what its slots hold and not what their tables could. ``step_plan`` is
    that list, if the caller built it already (:func:`decode_plan`: a
    layer loop builds it once and shifts it as it shifts the table,
    :meth:`PagedPlan.shifted`; it then is what addresses the pools, and
    ``block_table`` must be the table it holds); ``block_size`` is ignored. On a real TPU keep the pool
    block >= the dtype's min sublane tile, 8/16/32 for f32/bf16/int8. The
    entries of a slot's live steps must be valid pool indices; the last
    step's entries past the slot's length are masked but still dereferenced
    (the engine keeps them at 0). Bit-exact with gathering ``pool[table]``
    into a contiguous buffer and calling the unpaged kernel at
    ``block_size = entries * block`` — the tiles stream identical rows in
    identical order; at another tile the fold order differs and the results
    agree to float tolerance.

    ``tree_mask`` (a ``(B, Tq, Tq)`` bool array; requires ``causal`` and
    ``Tq <= 32``) switches on the speculative tree-verification window
    rule (see :func:`_decode_visibility_mask`): it is packed into int32
    per-row bitmasks that ride a lane-broadcast VMEM operand, exactly
    like the q8q per-row Q scales.

    ``local_blocks`` (ISSUE 18, requires ``block_table``): the table is a
    SIGNED per-shard local view — negative entries mark logical blocks
    owned by other shards of a sequence-sharded pool. Those entries clamp
    their DMA to row 0 and the body masks their columns (a step that owns
    none of its entries is skipped), so the returned
    ``(out, lse)`` is this shard's flash PARTIAL over its own blocks
    (rows with no local blocks emit the ``(0, -inf)`` merge identity).

    ``window`` (requires ``block_table`` and ``causal``): a sliding-window
    layer's call. Row ``i`` sees positions ``(q_offset + i - window,
    q_offset + i]``; the work list starts at the step that holds the
    lowest of them (``step_plan``, if handed in, must have been built with
    the same ``window``: :func:`decode_plan`), so the call walks one or
    two steps a slot whatever its length, at any ``Tq`` (a chunk's rows
    take Q tiles of 128 packed rows over that short list). The kernel is
    named :data:`WINDOW_KERNEL`; ``launch`` (a paged call's) is a suffix to
    the launch's name (``flash_decode_paged_shared``: a cross layer's call
    over another layer's rows). ``window`` may also be an
    :class:`~.block_utils.AlignedWindow` (row ``i`` sees ``[w0, q_offset +
    i]``, ``w0`` the start of its own block of ``window`` positions) or
    :class:`~.block_utils.ChunkSummaries` (the pool holds one summary row a
    chunk, row ``i`` sees those of the windows closed before its own; a
    slot with none returns ``(0, -inf)``, the merge identity): the same
    body under :func:`window_kernel_name`'s names.
    """
    B, Hq, Tq, D = q.shape
    if local_blocks and block_table is None:
        raise ValueError("local_blocks requires block_table")
    if window is not None and (block_table is None or not causal):
        raise ValueError("window requires block_table and causal=True")
    if tree_mask is not None:
        if not causal:
            raise ValueError("tree_mask requires causal=True")
        if Tq > 32:
            raise ValueError(
                f"tree_mask packs into int32 bitmasks: Tq={Tq} exceeds 32"
            )
        if tree_mask.shape != (B, Tq, Tq):
            raise ValueError(
                f"tree_mask must be (B, Tq, Tq) = {(B, Tq, Tq)}, got "
                f"{tree_mask.shape}"
            )
    if block_table is not None:
        Hkv, Tk = k.shape[1], block_table.shape[1] * k.shape[2]
    else:
        Hkv, Tk = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    G = Hq // Hkv
    s = (D ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out_dtype = q.dtype
    if k.dtype == jnp.int8 and q.dtype != jnp.bfloat16:
        # The kernel casts int8 KV tiles to bf16 in-VMEM (exact for
        # [-127, 127]); a non-bf16 q would make the score dot mixed-dtype
        # and fail at trace time. Callers normally arrive via the q8
        # wrapper, which folds scales into q in f32 and emits bf16; direct
        # callers get the same operand precision applied here (ADVICE r2),
        # with the output returned in their original dtype.
        q = q.astype(jnp.bfloat16)

    if Tk == 0:
        return (
            jnp.zeros(q.shape, out_dtype),  # not zeros_like: q may be the
            jnp.full((B, Hq, Tq), NEG_INF, jnp.float32),  # bf16-cast copy
        )

    # Pack each KV head's queries (its whole GQA group × Tq rows) into the
    # Q-tile sublanes: (B, Hq, Tq, D) -> (B, Hkv, r8, D).
    r = G * Tq
    bq = _packed_rows(r)
    qp = _pad_dim(q.reshape(B, Hkv, r, D), 2, bq)
    n_q = qp.shape[2] // bq

    tb = None if tree_mask is None else _tree_bits_rows(
        tree_mask, G, Hkv, bq, n_q)
    if block_table is not None:
        out, lse = _paged_decode_call(
            _flash_decode_paged_kernel, dict(scale=s),
            "paged_q8" if k.dtype == jnp.int8 else "paged",
            _paged_rows([qp], tb), k, v, tree=tb is not None, group=G,
            tq=Tq, bq=bq, causal=causal, local_blocks=local_blocks,
            q_offset=q_offset, kv_offset=kv_offset,
            block_table=block_table, step_plan=step_plan, window=window,
            launch=launch, out_dtype=q.dtype, interpret=interpret,
        )
        return out.astype(out_dtype), lse
    qp = qp.reshape(B * Hkv, n_q * bq, D)

    if block_size is None:
        from tree_attention_tpu.ops.tuning import decode_block_k, decode_block_k_q8

        # Direct int8 callers (the q8 wrapper normally resolves first) get
        # the q8 table: half the bytes per tile leaves the exact path's tile
        # size overhead-bound (measured 76.3% vs 85.2% of the int8 roofline
        # at 64k).
        block_size = (
            decode_block_k_q8(Tk) if k.dtype == jnp.int8 else decode_block_k(Tk)
        )

    # No host-side KV padding: Pallas handles a ragged last block itself and
    # the kernel's ``col_idx < tk`` mask drops the garbage columns. An
    # explicit jnp.pad here would copy the ENTIRE KV buffer every decode step
    # whenever Tk % bk != 0 — measured as the difference between 27% and 92%
    # of the HBM roofline on the reference's 64000-token workload.
    bk = min(block_size, max(Tk, _LANES))
    kp = k.reshape(B * Hkv, Tk, D)
    vp = v.reshape(B * Hkv, Tk, D)
    n_s = -(-Tk // bk)

    offs = _offsets_smem(q_offset, kv_offset, B)

    if obs.REGISTRY.enabled:
        # int8 operands here are the q8 (bf16-cast) path riding the base
        # kernel; the q8q wrapper has its own pallas_call and label.
        _KERNEL_BUILDS.labels(
            kernel="q8" if k.dtype == jnp.int8 else "exact",
            heads=1, entries=0,
        ).inc()
    tensors = [offs, qp, kp, vp]
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda bh, qi, si: (bh, qi, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, si: (bh, si, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, si: (bh, si, 0)),
    ]
    if tb is not None:
        tensors.insert(2, tb)
        in_specs.insert(
            2,
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, si: (bh, qi, 0)),
        )
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_decode_kernel,
            scale=s, causal=causal, tk=Tk, tq=Tq, block_q=bq, block_k=bk,
            n_kv_heads=Hkv, tree=tree_mask is not None,
        ),
        grid=(B * Hkv, n_q, n_s),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, si: (bh, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, si: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, n_q * bq, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hkv, n_q * bq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        # Only the split-KV dim is sequential (carried online-softmax state);
        # batch-head and Q-tile dims can split across megacore parts.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(*tensors)

    out = out[:, :r].reshape(B, Hq, Tq, D).astype(out_dtype)
    lse = lse[:, :r, 0].reshape(B, Hq, Tq)
    return out, lse
