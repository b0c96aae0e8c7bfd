"""Measured per-shape kernel tile sizes (TPU v5e).

The Pallas kernels take ``(block_q, block_k)`` tile sizes; the best choice
depends on the shape class, not the exact shape, so a small measured table
suffices (VERDICT round-1 item 4). ``tools/tune_sweep.py`` regenerates the
measurements on hardware; entries here are its output on the one v5e chip
this repo is benched on. Lookup is by bucket:

- decode (Tq < 128): keyed by context-length bucket. Streaming tiles — the
  only trade-off is fewer grid steps (bigger bk) vs VMEM and ragged-tail
  waste.

- training (Tq >= 128): ``_TRAIN_TILES`` keyed by sequence length, from an
  on-chip sweep (fwd-first, fwd+bwd tiebreak).

Callers pass ``block_size=None`` / ``block_q=None`` end to end to land here;
any explicit value wins unchanged. ``block_q`` is threaded through the
dispatcher and the custom VJP.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

# context-length upper bound -> block_k. Measured on v5e
# (tools/tune_sweep.py, 2026-07-31): bigger contexts amortise the
# ~360 ns/tile fixed cost over more streaming — 64k MHA measures 92.5% of
# the HBM roofline at bk=4096 vs 89.9% at 2048, and 1M GQA 91.6% at 4096
# with high run variance at 2048. VMEM caps the top end.
_DECODE_BLOCK_K = (
    (16_384, 1024),
    (float("inf"), 4096),
)

# The int8 cache streams half the bytes per tile, so the per-tile fixed cost
# weighs twice as much relative to DMA — the q8 kernel wants tiles ~2x the
# exact path's. Measured 2026-07-31 (64k ctx): 62.2% of the int8 roofline at
# bk=2048, 76.3% at 4096, 85.2% at 8192 (375.9 us = 1.89x the exact path's
# tokens/sec).
_DECODE_BLOCK_K_Q8 = (
    (16_384, 2048),
    (float("inf"), 8192),
)


def decode_block_k(tk: int) -> int:
    """KV tile length for the flash-decode kernel."""
    for bound, bk in _DECODE_BLOCK_K:
        if tk <= bound:
            return bk
    raise AssertionError("unreachable")


def decode_block_k_q8(tk: int) -> int:
    """KV tile length for the int8-cache flash-decode kernel."""
    for bound, bk in _DECODE_BLOCK_K_Q8:
        if tk <= bound:
            return bk
    raise AssertionError("unreachable")


# The paged decode kernel's grid step (flash_decode_paged*): a pool block is
# the configuration's ``kv_block`` tokens (64 in both benchmark
# configurations, tied to the radix cache's ``prefix_block``), so the tile
# cannot grow by a setting; a step grows instead by taking every KV head of a
# block (contiguous in the ``(N, Hkv, block, D)`` pool: one DMA) and several
# table entries. With one head of one entry a step (16 KB of K and of V,
# 40 ns of HBM time) the kernel's time was its step count times 239-265 ns
# whatever the bytes (PERF_LEDGER.jsonl, PR 27: 19.59 ms over 81,920 steps a
# tick at Mistral-7B's shapes, 8.69 ms over 32,768 at Yi-6B's): 5-6% of the
# HBM roofline.
#
# Measured on v5e 2026-09-28 (a scratch sweep of the kernel alone, 128 calls
# in one program over a 16-layer pool, as a tick makes them; us a call, best
# of 7; in brackets the share of 819 GB/s the slots' live K + V bytes reach).
# "served": slots at the lengths the cells serve (Mistral 200-1,500 of 2,560
# tokens, Yi 300-3,700 of 4,096); "full": every slot at its capacity.
#
#   (heads, entries)      Mistral 16 x 8 x 40     Yi-6B 8 x 4 x 64
#   a step                served      full        served      full
#   (1, 1) the old grid   1306 ( 5%)  2271 ( 9%)  628 ( 7%)   912 ( 9%)
#   (1, 8) heads off       585 (12%)   712 (29%)  251 (17%)   286 (29%)
#   (all, 1)               208 (34%)   408 (50%)  181 (24%)   275 (30%)
#   (all, 2)               158 (44%)   279 (74%)  122 (35%)   169 (48%)
#   (all, 4)               144 (49%)   234 (88%)   94 (45%)   119 (69%)
#   (all, 8)               154 (45%)   234 (88%)   86 (50%)   101 (81%)
#
# Heads in the step matter more than entries (a block's heads are one DMA),
# and the step wants 1 MB: (8, 4) at Mistral's 8 KV heads, (4, 8) at Yi's 4;
# past it nothing is gained and the steps that straddle a slot's end waste
# more. A step costs 0.33 us (Mistral, one entry) to 2.9 us (2 MB): no
# longer a constant, the bytes show. The heads are folded as one batched
# array: a Python loop over the heads, each on its own slice of the scratch,
# took 223 / 416 us at (8, 4) and 142 / 203 at (4, 4) (the stores to one
# scratch ordered the heads one after the other).
#
# What was left in "served" on that grid, a rectangle of slots x table steps,
# was the steps past a slot's length: culled in the body, but each still a
# grid step whose every operand's index map the pipeline ran (~0.3 us at
# Mistral's 8 operands, about a live step's cost at Yi's 16). Two tries to
# make those steps cheap kept them in the grid and lost: holding a step past
# a slot's length at the slot's last live step, so that the pipeline streams
# nothing for it (else the first such step fetches pool block 0 `entries`
# times). With the live step worked out in the index maps (a division each)
# served / full read 258 / 458 (Mistral, head loop); handed in as a third row
# of the offsets and a `min` in each map, 153 / 235 against 144 / 234 without
# (Mistral) and 91 / 104 against 86 / 101 (Yi): the scalar work in every map
# of every step cost more than the fetch it saved.
#
# Since PR 37 those steps are not in the grid: its last dimension is the
# dynamic length of a list of the (slot, step) pairs that hold a live token
# (`pallas_decode.paged_step_plan`, from `paged_live_steps` below), as
# `pallas_moe.tile_plan`'s is. Not a third of those tries: it adds no work to
# a step that runs (a K/V map reads the table in list order with one scalar
# load, fewer operations than `table[b, si * entries + j]` was) and removes
# the others. Measured on v5e 2026-09-30, the same sweep (128 calls in one
# program over a 16-layer pool, best of 7; served lengths drawn uniformly,
# Mistral 200-1,500 of 2,560, Yi 300-3,700 of 4,096 and 100-900 as a paced
# cell's; the plan built once outside the loop and shifted a call, as a tick
# program does, and in brackets built inside every call):
#
#   us a call (share of 819 GB/s)   served                    full
#   Mistral 16 x 8 x 40   list      105 (73%)  [111]          231 (89%)  [237]
#                         rectangle 151 (51%)                 233 (88%)
#   Yi-6B 8 x 4 x 64      list       68 (69%)  [ 74]           99 (82%)  [105]
#                         rectangle  88 (53%)                 101 (81%)
#   Yi-6B, paced lengths  list       28 (42%)  [ 34]          100 (82%)
#                         rectangle  66 (18%)                 101 (82%)
#   LFM2 64 x 4 x 40      list      214 (66%)  [234]          467 (88%)  [486]
#                         rectangle 370 (38%)                 469 (87%)
#   mla, dsv2 16 x 128h   list       75 (32%)  [ 80]          163 (39%)  [167]
#                         rectangle 103 (23%)                 164 (39%)
#   mla, LongCat 32 x 64h list      121 (36%)  [129]          278 (46%)  [290]
#                         rectangle 176 (25%)                 280 (46%)
#
# (The rectangle's rows: the parent commit in the same sweep on the same
# draw of lengths, its tables zero past each slot's mapped blocks as the
# engine keeps them, so that a dead step's indices do not change and it
# streams nothing; with other blocks there the rectangle streamed them all
# and read its full-slot time at any length. PR 28's rows above, 144 / 234
# and 86 / 101, were another draw of the same ranges.)
#
# Full slots read what the rectangle read (the list IS the rectangle there);
# the plan costs 5-6 us a call where a call builds its own, which is why a
# step program builds it once (`models/decode.py` `_plan_groups`). What is
# left in "served": the whole step at each slot's tail (half a step a slot
# on average: 16 x 128 tokens of Mistral's 15,300 live ones) and, for the
# latent kernel, the operations (near the ridge at 128 heads).
#
# The pool WRITE of a decode tick (ISSUE 39): one new row a slot into the
# pools of a layer. Measured on v5e 2026-09-30, a scratch sweep of the write
# alone at each cell's decode shape (slots x KV heads x lanes; blocks of 64
# rows; K and V together, or the one latent pool): 128 calls in one program
# over a 16-layer pool donated to it, a call's layer i % 16, one slot idle,
# the (block, row) targets worked out once outside the loop as a tick program
# does; us a call, best of 7; every variant's pool bit-equal to the block
# path's.
#
#   us a call                 block path   row kernel   its tile   pipelined
#                             (_paged_     (8-row tile, the packed  grid, a
#                             pool_write)  chosen)      sublane's  row a step
#   Mistral 16 x 8 x 128 K+V     42.2          9.7        10.5       15.9
#   K-EXAONE 32 x 8 x 128 K+V    71.1         12.1        13.7       22.9
#   LFM2 64 x 4 x 128 K+V        92.9         17.4        19.4       31.2
#   LongCat 32 x 1 x 640         41.0          8.3         8.3       16.5
#   Yi-6B 8 x 4 x 128 K+V        22.2          7.2         7.4       10.3
#   DeepSeek-V2 16 x 1 x 640     23.9          6.2         6.1       11.8
#   Yi-6B int8 8 x 4 x 128 K+V   20.8          7.4         7.6       10.6
#
# The block path gathers, overlays and scatters a whole block a slot (64-131
# KB for one 2 KB row): 1.1-1.4 us a slot-block and pool. The row kernel
# (`pallas_decode.paged_row_write`) is ONE launch for every pool of the layer
# and no grid: it starts a copy of every live slot's 8-row tile into fast
# memory, then for each awaits it, overlays the row under an iota mask and
# starts the copy back, then awaits those: ~6 us a call (the launch and two
# copies' latency) + 0.2-0.25 us a slot (four copies' descriptors). Not
# chosen, and why:
# - the rows copied straight into the pool from fast memory (2 KB a slot
#   where a tile is 16): the compile for the chip refuses a one-row slice of
#   a packed array, on either side of the copy ("Slice shape along dimension
#   2 must be aligned to tiling (2), but is 1"; 4 for int8);
# - a pipelined grid, a live row a step on a list of dynamic length, the tile
#   as an aliased in/out block (the issue's first shape): 0.3-0.45 us a step
#   for its operands' index maps and a tile's copy in awaited before its copy
#   out starts, 1.4-2.0x the chosen one at every shape;
# - the packed dtype's sublane tile (16 rows of bf16, 32 of int8): twice to
#   four times the bytes for nothing; the pool's layout on the chip is tiled
#   by 8 rows whatever the dtype (`T(8,128)(2,1)`, `T(8,128)(4,1)`), and an
#   8-row cut compiles and reads back bit-equal for bf16 and int8 alike.
# A chunk group (Tq > 1) keeps the block path: PR 25 measured a row scatter
# at ~70 ns a row there and a block is what 5 blocks of 64 rows want.
#
# A conv layer's decode step over the TAIL pool (ISSUE 48; no tile of this
# module's: `ops/pallas_conv.py` `conv_tail_step`, chosen by `models/hybrid.py`
# `tail_write_path`). LFM2's shape: 64 slots, one row of 2 x 2048 bf16 lanes a
# block and layer, `(9 x 2560, 4096)` as it lies. Measured on v5e 2026-10-03 /
# 04. In the cell (`lfm2_agentturn_sat`, traced decode ticks, the events joined
# to the program tables, us a LAYER; nine layers a tick):
#
#   today's XLA chain between the layer's two products            44.0
#     the scatter of 64 rows into bf16[23040,4096]                  25.0
#     three gathers of 64 rows out of it (+ the half select's)      16.9
#     table lookups s32[64], redone in every layer                   2.7
#     the gates, taps, selects, broadcasts, copies (~30 operations)  ~6
#   one launch, overlay-all on 8-row cuts (first form, below)     30.6
#   one launch, a slot's pair of rows out of its cut (chosen)     24.8
#
# and alone (a scratch program of 36 calls over the pool donated to it, layer
# i % 9, one slot idle, the plan built once outside the loop; the profiler's
# device time of the program's leaf operations a call, less an empty loop's;
# the kernel's own launch in brackets; blocks scattered / 64 slots' blocks
# neighbours in the pool, eight to a cut):
#
#   us a call (the launch)              scattered      neighbours
#   today's XLA chain                    48.5           44.1
#   read-only kernel + XLA scatter       37.9 (15.9)    34.3 (15.8)
#   overlay-all on 8-row cuts (first)    30.3 (31.0)    30.3 (31.0)
#   a slot's pair out of its cut, six
#     loops over the slots               28.8 (29.4)    39.1 (39.7)
#   ... the loops merged to three
#     (chosen)                           23.5 (24.1)    33.6 (34.1)
#
# (the empty loop 1.2; every variant's pool bit-equal to the XLA chain's on
# the chip, its rows within 0.25% of the chain's own: below.) The chosen one
# is 64 slots x three loops of scalar work and copies' descriptors (~0.3 us
# a slot) round ~5 us of arithmetic; the first is flat in the layout and
# wins where all 64 slots share 8 cuts, which a run's first ticks may and
# its steady state does not (the cell's traced ticks read the scattered
# column).
#
# The cut. The compile for the chip refuses a copy of fewer than 8 rows of a
# 2-D bf16 array on either side ("Slice shape along dimension 0 must be
# aligned to tiling (8), but is 2"; the same through a `uint32` view of the
# pool, whose 4 rows are those 8; `(M/8, 8, 4096)` and `(M/2, 2, 4096)` views
# refuse the 2-row slice of their middle dimension too): the chip lays the
# array out `T(8,128)(2,1)`, rows 2j and 2j + 1 the halves of the same words.
# So a slot's copy is 64 KB in (both halves of its row: z at p-1 and p-2) and
# 32 KB back (the half its z went to: a lane slice compiles), 6 MB a layer at
# 64 slots = 7.5 us at the memory's pace; the 16-row packed tile would double
# it. A cut's 8 rows are 8 BLOCKS of perhaps 8 live slots (with 64 scattered
# blocks among 2,560 some eleven slots a tick share a cut; neighbours at the
# start of a run: all of them). Ways round the shared cut:
# - every copy of a cut gets the z of EVERY live slot in it, all slots at
#   once: the owners found by one (64, 64) compare, their z brought over by a
#   one-hot `(512, 64) x (64, 2048)` product on the MXU (exact), the cuts
#   blended word by word. Bit-equal on the chip, and 30.6-31.0 us a launch: not
#   the product (2.7 us) but the layout. A `(slots, 4 pairs, 4096)` array of
#   cuts puts a slot's pair in ONE sublane of its own tile, so every read of
#   "pair j of every slot" and every write back is a single-sublane access a
#   slot and lane tile: 8 such passes over 64 x 32 tiles each way, ~30k
#   bundles of a 1.5 MB straight-line program. Not chosen.
# - the chosen one: the cuts stay where the copies put them; a slot's OWN
#   pair is copied out by one dynamic-sublane read into a `(slots, 4096)`
#   array (64 x 32 loads), everything runs on that array (0.35 MB of code),
#   the pair goes back the same way; the plan lists, once a tick, for each
#   slot the other live slots of its cut that write the same half (at most
#   7), and their z goes into the slot's copy row by row under `pl.when`.
#   Cost follows the sharing: nothing where blocks are scattered.
# - the cuts made distinct in the plan (one entry a cut, its sharers listed):
#   not built; it saves copies only where slots share, which a run's steady
#   state does not, and the arithmetic would have to be gathered by entry.
# - the read-only fallback (the kernel reads, gates and convolves; the 64-row
#   scatter stays in XLA): the scatter IS the largest piece (25 of the 44 us),
#   so it was never a candidate for the cell; row "read + scatter" above.
# The rows (`c * s`) differ from XLA's own on the CHIP by at most one bf16
# rounding: XLA keeps z and s in float32 inside a fusion
# (`xla_allow_excess_precision`), the kernel rounds where the source rounds,
# as XLA does on the CPU; the pool is bit-equal on both (`chip_smoke.py`
# `conv_tail_step_tq1`, `tests/test_conv_tail_step.py`).
#
# A latent layer's query up-projection, `c_q x wqb_t` (ISSUE 41; no tile of
# this module's: the product is plain XLA in `models/latent.py` `latent_qkv`,
# and what was tried is the form it reaches the compiler in). Measured on v5e
# 2026-10-01, a scratch sweep of `latent_qkv`'s arithmetic alone (the q-norm,
# `wqa`, `wkva`, the rotary, the `wkb` einsum and the product) in a scan over
# the cell's stack of layers, 32 x L layers in one program, best of 7, us a
# layer; every form's result bit-equal to the folded one's on the chip:
#
#   us a layer                       dsv2 24576 x 1536     LongCat 12288 x 1536
#                                    16 rows   272 rows    32 rows   288 rows
#   folded (the reshape to heads in  274.5     451.9       112.7     246.9
#     the product: until PR 41)
#   heads einsum (`bti,hni->bthn`)   273.7     452.8       113.2     246.7
#   flat behind a barrier (chosen)   164.3     343.0       104.1     216.7
#   ... and `c_q` behind one too     164.5     343.3       104.2     216.4
#   `(out, rows)`, transposed back   164.4     340.9       104.7     215.6
#   float32 result, then cast        164.0     343.5       104.2     216.4
#   everything but the product        56.8     149.5        48.1     106.2
#
# The two upper forms compile to one program: the layer's whole `wqb_t` (75.5
# / 37.7 MB) sliced into fast memory by an operation that does nothing else,
# then the product from there, whose result the compiler lays out by heads
# (`bf16[16,128,192]{1,0,2}` and a copy). That product is slow at 128 heads
# (in `dsv2_codegen_sat`'s traced ~3 s it took 0.113 s where the slice took
# 0.112 s; the leading dense layer's, from its prefetched copy, 0.0282 s) and
# cheap at 64 (`lcflash_agentturn_sat`: 0.0117 s a sublayer where its slice
# took 0.0436 s), which is why the cure is worth 110 us a layer in one family
# and 9 (30 at 288 rows) in the other. The four lower forms compile to the
# other: no slice, the product's fusion takes the `(L, out, rank)` stack and
# reads the layer's rows in place with the q-norm fused in (0.1165 s of
# dsv2's traced ~3 s, 0.0436 s a sublayer of LongCat's: what the slice alone
# took; here 107 and 56 us a layer over "everything but": 86% and 82% of
# 819 GB/s for the weight's bytes), and the flat product from the dense
# layer's prefetched copy takes 0.0077 s where the folded one took 0.0282 s:
# it was the result's layout by heads that was slow, not the MXU at 16 rows.
# Nothing is left to win in this product but the last tenth of the memory's
# pace. The four tie to 1%, so the one that adds least to the code stays (one
# `lax.optimization_barrier` on the flat result). Not tried on the chip:
# `nope` and `rope` columns as two weights (a second layout of the leaf;
# ISSUE 41's compile for the chip sliced both) and a Pallas product
# streaming `wqb_t`'s row tiles (the fallback, had no plain-XLA form held in
# the packed programs: the barrier holds in all six).
# An EVA layer's two calls (ISSUE 44; `eva_local_decode`, `eva_summary_decode`:
# the paged decode body under the aligned and the summary rule) at EvaByte's
# MHA 32 x 128 over blocks of 64 rows: an entry is 1 MB of K + V, so the rule
# below hands a row a slot every head of ONE entry a step (a fourth tiling
# beside 8 heads x 4, 4 x 8 and 2 x 8 entries), and a chunk group's 128 packed
# rows a Q tile 8 heads of 4 entries (the heads' Q-side state at 128 rows does
# not fit beside 32 heads' K and V). Measured on v5e 2026-10-02 in the cell
# `evabyte_bytedoc_sat` (a traced run, decode ticks, 16 slots, contexts of
# 4k-15k): the exact rows' call 451 us a layer at 89.2% of 819 GB/s for the
# rows its cost file counts (a slot's list is the steps from its window's
# start to its row: half a step of dead tail a slot), the summary rows' call
# 178 us at 90.7% (2-14 whole blocks a slot). Nothing was swept: the rule's
# first choice reads nine tenths of the memory's pace.
PAGED_STEP_TARGET_BYTES = 1 << 20
PAGED_STEP_ENTRIES = (1, 2, 4, 8)  # divisors of the 8-row scale tile
# What a step may hold of the 16 MB of scoped VMEM a v5e kernel gets by
# default: its K and V tiles twice (the pipeline double-buffers them), and
# for every head in the step the Q-side tiles (q, out, lse and the per-row
# scale / tree operands, double-buffered too) and the m / l / acc scratch.
# The rest is left to the score and probability tiles the body makes.
PAGED_STEP_VMEM_BYTES = 12 << 20


def paged_decode_step(
    n_kv_heads: int,
    block: int,
    d: int,
    itemsize: int,
    table_width: int,
    block_q: int,
) -> Tuple[int, int]:
    """``(heads, entries)`` one grid step of the paged decode kernel takes,
    worked out from the shapes the call sees.

    ``heads``: every KV head of a pool block, unless the heads' Q-side state
    at ``block_q`` packed rows would not fit under
    :data:`PAGED_STEP_VMEM_BYTES` with one entry beside it (many heads at a
    chunk's 128 rows); then the largest divisor of the head count that does.
    ``entries``: the smallest of :data:`PAGED_STEP_ENTRIES` that divides the
    table width and brings the step's K + V bytes to
    :data:`PAGED_STEP_TARGET_BYTES`; where none reaches it (an int8 pool, a
    few heads), the largest that divides and fits. An int8 pool halves an
    entry's bytes, so the same rule hands it twice the entries.
    """
    entry = 2 * block * d * itemsize  # one head's K and V of one entry
    # Per head, worst case (float32 q and out): q, out (D lanes) and lse,
    # q-scale, tree bits (128 lanes), all twice; acc (D) and m, l (128).
    rows = block_q * 4 * (5 * d + 8 * 128)

    def fits(heads: int, entries: int) -> bool:
        return heads * (2 * entries * entry + rows) <= PAGED_STEP_VMEM_BYTES

    heads = next(
        (h for h in range(n_kv_heads, 0, -1)
         if n_kv_heads % h == 0 and fits(h, 1)),
        1,
    )
    entries = 1
    for per in PAGED_STEP_ENTRIES:
        if table_width % per or not fits(heads, per):
            continue
        entries = per
        if heads * per * entry >= PAGED_STEP_TARGET_BYTES:
            break
    return heads, entries


def paged_first_step(first_pos, kv_offset, step_tokens: int, n_steps: int):
    """The step of each slot's table that holds position ``first_pos``, the
    lowest its rows may see (a window layer's ``max(0, q_offset - window +
    1)``): where such a slot's work list starts."""
    return ((first_pos - kv_offset) // step_tokens).clip(0, n_steps - 1)


def paged_live_steps(q_offset, kv_offset, tq: int, step_tokens: int,
                     n_steps: int, first_pos=None):
    """Steps of each slot's table that hold a token its rows may see, 0 to
    ``n_steps``: step ``si`` covers positions ``kv_offset + si *
    step_tokens ...`` and the slot's last row sits at ``q_offset + tq -
    1``, so the steps ``0 .. (q_offset + tq - 1 - kv_offset) // step_tokens``
    do. With ``first_pos`` (the lowest position a slot's rows may see: a
    layer whose rows see a window of the context) the steps under
    :func:`paged_first_step` hold nothing live either and are not counted:
    the list starts there. Offsets are ``(B,)`` integer arrays of numpy's
    or of jax's: the kernels build their work list from this on the device
    (``pallas_decode.paged_step_plan``), and the serve loop counts with it
    on the host what a tick's list will hold (``kv_steps_run``)."""
    live = ((q_offset + (tq - 1) - kv_offset) // step_tokens + 1).clip(
        0, n_steps)
    if first_pos is None:
        return live
    return (live - paged_first_step(
        first_pos, kv_offset, step_tokens, n_steps)).clip(0, n_steps)


def paged_rule_steps(q_offset, kv_offset, tq: int, step_tokens: int,
                     n_steps: int, window=None, maximum=None):
    """``(first, live)`` of each slot's work list under a window rule
    (``block_utils.WindowRule``; None: the whole causal context): the step
    its list starts at (None: 0) and the steps it holds. A position rule
    (sliding, aligned) starts at the step that holds the lowest position the
    slot's FIRST row sees; the summary rule's columns are summary rows, and
    the list holds the steps under those its LAST row sees, none where that
    row has no closed window behind it. The one rule the kernels' plan
    (``pallas_decode.paged_plan``) and the serve loop's count share;
    ``maximum``: the array library's (numpy's unless the caller hands
    jax's)."""
    from tree_attention_tpu.ops.block_utils import (
        ChunkSummaries, summaries_seen, window_low,
    )

    if window is None:
        return None, paged_live_steps(
            q_offset, kv_offset, tq, step_tokens, n_steps)
    if isinstance(window, ChunkSummaries):
        seen = summaries_seen(window, q_offset + (tq - 1))
        return None, paged_live_steps(
            seen - 1, kv_offset, 1, step_tokens, n_steps)
    if maximum is None:
        import numpy as np

        maximum = np.maximum
    low = maximum(window_low(window, q_offset), 0)
    return (paged_first_step(low, kv_offset, step_tokens, n_steps),
            paged_live_steps(
                q_offset, kv_offset, tq, step_tokens, n_steps, low))


# The one home of the TPU kernel-dispatch policy shared by flash_attention's
# auto gate and flash_decode: which Pallas kernel fits a query count, and
# each impl's default KV tile.
DECODE_KERNEL_MAX_TQ = 128


def tpu_kernel_for(tq: int) -> str:
    """"pallas_decode" below the Q-tile width, "pallas" (Q-tiled) above."""
    return "pallas_decode" if tq < DECODE_KERNEL_MAX_TQ else "pallas"


# (seq-length upper bound, block_q, block_k) for the Q-tiled training
# kernel. Re-measured on v5e 2026-08-01 (tools/ab_fwd_tiles.py, min-stat
# repeated-slope protocol with deflation screens, after the round-5
# lane-replicated-state and prefetch-zero-culling kernel changes made the
# round-3 table stale): (1024, 1024) wins through 32k — 4k fwd+bwd
# 3.29 -> 2.79 ms (1.18x) vs the old (512, 2048) through the product
# default path, 16k fwd+bwd 36.45 -> 35.24 ms, 32k 133.8 -> 132.4 ms —
# and the smaller KV tile halves the backward kernels' VMEM so their Q
# tile can double (see BWD_MAX_TILE_ELEMS below). At 64k the bases tie
# and at 128k the deeper KV tile is ~1% faster (bench train records,
# same day), so the long bucket keeps (1024, 2048). Wall-clock per model
# step is the comparison basis — the launched-tile MFU shrinks with
# finer tiles because less diagonal waste is launched at all. Both
# kernels clamp tiles to the actual shape, so the table is safe for
# short sequences too.
_TRAIN_TILES = (
    (32768, 1024, 1024),
    (float("inf"), 1024, 2048),
)


def _train_tile(t: int):
    for bound, bq, bk in _TRAIN_TILES:
        if t <= bound:
            return bq, bk
    raise AssertionError("unreachable")


# KV block for the XLA blockwise fallback. The _TRAIN_TILES table above was
# measured on the Pallas kernels only; blockwise (a lax.scan over KV chunks,
# any backend) keeps the round-1 default so an unmeasured table change can't
# silently shift its memory/perf profile on CPU/GPU (ADVICE r3).
BLOCKWISE_BLOCK_K = 512


def default_block_size(impl: str, tk: int) -> int:
    if impl == "pallas_decode":
        return decode_block_k(tk)
    if impl == "pallas":
        return _train_tile(tk)[1]
    return BLOCKWISE_BLOCK_K


# VMEM ceiling for the backward kernels' tiles. The bwd kernels hold more
# per-tile live state than the forward (recomputed s/p/ds alongside the
# dq/dkv accumulators), and the dominant term scales with bq*bk:
# (1024, 2048) measures 24.6 MB of scoped VMEM against the v5e's 16 MB
# limit — a compile-time OOM (observed 2026-07-31, T=16384) — while
# (1024, 1024) and (512, 2048) both compile and run (the former measured
# fastest in the 2026-08-01 A/B). The cap is therefore a product bound,
# not a bare block_q bound. Applied only when the tile comes from this
# table's defaults; an explicitly passed block_q always wins unchanged
# (sweeps must measure what they label).
BWD_MAX_TILE_ELEMS = 1024 * 1024
# Largest bwd Q tile ever validated on-chip; the product bound alone
# would allow (2048, 512), which no sweep has measured.
BWD_MAX_BLOCK_Q = 1024


def default_block_q(tq: int, tk: int) -> int:
    """Q-tile length for the Q-tiled Pallas forward kernel."""
    return _train_tile(tq)[0]


def default_block_q_bwd(tq: int, tk: int, block_k: Optional[int] = None) -> int:
    """Q-tile length for the Pallas backward kernels (VMEM-capped).

    ``block_k`` is the RESOLVED KV tile the backward kernels will run
    with (it may be caller-supplied rather than this table's default);
    the cap keeps ``bq * bk`` within the measured VMEM-feasible product.
    The fallback mirrors ``default_block_size("pallas", tk)`` — keyed by
    the KV length, exactly what the dispatcher would resolve — so a
    direct caller that omits ``block_k`` gets a cap consistent with the
    tile the kernels will actually run.
    """
    if block_k is None:
        block_k = _train_tile(tk)[1]
    # No lower floor above the kernels' own minimum (they clamp bq to
    # >= 8): flooring at, say, 128 rows would silently emit a product
    # ABOVE the cap for a huge caller-supplied KV tile (bk=16384 ->
    # 128 * 16384 = 2M elems, the documented compile-OOM class).
    return min(
        default_block_q(tq, tk),
        BWD_MAX_BLOCK_Q,
        max(8, BWD_MAX_TILE_ELEMS // max(block_k, 1)),
    )


# ---------------------------------------------------------------------------
# The state step's phases (ops/pallas_ssm.py)
# ---------------------------------------------------------------------------

# ``ssm_decode_update`` reads a live slot's float32 state of a layer and
# writes it back: at nemotron-3-super-120b-a12b's shapes (128 heads x 64 x
# 128 as ``(Hp, N, L) = (64, 128, 128)``, ``G`` = 8) 4.19 MB each way and
# 74 KB of rows, 8.46 MB a slot by ``benchmark/kernel_costs/
# ssm_decode_update.py``. Until PR 45 a grid step took one slot through a
# BlockSpec pipeline (a 4 MB block in and one out, double-buffered) and read
# 80.9% of 819 GB/s in the cell (ledger, PRs 40 and 44: 816 us a launch).
#
# Measured on v5e 2026-10-02 (ISSUE 45 step 1 and what followed: scratch
# sweeps of the kernel alone, three chip calls; the pool ``(5 x 64, 64, 128,
# 128)`` float32 donated, 64 live slots, 128 calls in one program, a call's
# layer ``i % 5``, best of 7 with the median within 0.3%; us a call, and the
# share of 819 GB/s by the cost file's 541.6 MB a call; every form's state
# bit-equal to ``ssm_step``'s on the chip, ``y`` equal too but the MXU's).
# The program's own loop adds ~16 us a call over the cell's 816.
#
#   (a) the kernel as it stood (one slot a grid step)         832.3  79.5%
#   (b) the same specs, the body ``o_ref[...] = s_ref[...]``  832.1  79.5%
#   (c) (b), the block cut along Hp in 2 / 4 / 8       833.7 / 836.9 / 835.0
#       (a) cut likewise                               833.9 / 835.6 / 837.2
#   (d) (a), B / C lane-dense (S, G, N), transposed a step    834.8  79.2%
#       ... as a (L, N) broadcast transposed                  832.2  79.5%
#   the broadcasts of b, c from VMEM scratch, once a group    832.9  79.4%
#   ... and the rows a ``fori_loop`` (by 1 / by 2)     833.8 / 832.7
#   ``y``'s reduce on the MXU (HIGHEST; y off by 9.5e-7)      832.9  79.4%
#   the kernel's own ring (pool in pl.ANY, chunks of Hp/4 or Hp/8, 4 or 8
#     buffers, 2 or 4 reads ahead), copy only          835.2 - 836.7
#     ... with the body                                835.4 - 837.7
#   ``pipeline_mode=pl.Buffered(3)``: refused by this jaxlib's lowering
#     ("Only single (1) and double (2) buffering are supported")
#
# Nothing in the body binds (the scheduler's dump of (a): 3,392 bundles a
# step, ~2.3 us beside 10.3 us of bytes) and nothing in the blocks' shape:
# (b) IS the ceiling of a stream in and out with a read and a write in
# flight together, whatever issues them. What binds is the memory's own pace
# by direction (the same 64 x 4.19 MB, two phases of copies in flight;
# 1, 4 or 16 copies a slot read the same):
#
#   reads alone    365.2 us   735 GB/s   89.8% of 819
#   writes alone   418.6 us   641 GB/s   78.3%
#   one after the other: 783.8 us, 84.4% by the cost file's bytes
#
# and a read beside a write costs 48 us a call MORE than the two apart. So
# the kernel keeps them apart: PHASES of Q slots, phase p + 1 read whole with
# nothing else in flight, computed where it landed while phase p is written
# back whole (the body hides under the writes; under the reads of phase 1
# for phase 0).
#
#   Q slots a phase                      1      2      4      8
#   copies only (reads, then writes)   806.3  790.7  782.4  777.4
#   the kernel, computed under the
#     NEXT phase's reads (the last
#     phase's body shows: Q x 2.3 us)  814.9  801.2  797.3  800.9
#   the kernel as it is (under the
#     writes of the phase before)      812.7  799.1  788.4  784.1
#   ... of 819 GB/s                    81.4%  82.8%  83.9%  84.3%
#
# Q = 4 (33 MB of buffers): 44 us a call under (a), 6 us over its own
# copies; Q = 8 wins 4 us more for 66 MB. In the cell (one traced run a
# side, seed 2450004504): 815.6 -> 771.5 us a launch, 80.96 -> 85.55% of the
# roofline, `tbt_p50_ms` 16.98 -> 16.74 over six seeds a side. Not 88-92%, which ISSUE 45
# predicted before anyone had timed a write: 84.4% is what the two
# directions allow, and what is left over it is the rows' own copies and 32
# turns between reading and writing a launch (~0.25 us each, from the Q
# column). PR 40's two untried ideas: "two slots a step" is Q = 2 (wins 4%
# only because it is phased; as BlockSpecs a pair of slots is not one block
# of the pool, the list's slots lie anywhere); "y's reduce on the MXU" is the
# line above (no faster: the body never showed; and y moves in its last
# bits).
SSM_PHASE_SLOTS = (4, 2, 1)
# What two phases' buffers may take of the chip's 128 MB of fast memory
# (the one-slot pipeline asked for 32 MB).
SSM_PHASE_VMEM_BYTES = 48 << 20


def _tiles(rows: int, lanes: int) -> int:
    """Bytes of a float32 ``(rows, lanes)`` array in fast memory: whole
    ``(8, 128)`` tiles."""
    return -(-rows // 8) * 8 * -(-lanes // 128) * 128 * 4


def ssm_phase_vmem_bytes(slots: int, hp: int, n: int, l: int, g: int) -> int:
    """Fast memory ``ssm_decode_update``'s buffers take at ``slots`` slots a
    phase: two phases of a slot's state, ``x``, ``a``, ``y`` and the ``B`` /
    ``C`` rows."""
    return 2 * slots * (hp * _tiles(n, l) + 3 * _tiles(hp, l)
                        + 2 * _tiles(g, n))


def ssm_phase_slots(hp: int, n: int, l: int, g: int) -> int:
    """Slots a phase of ``ssm_decode_update`` moves, worked out from the
    shapes the call sees: the most of :data:`SSM_PHASE_SLOTS` whose two
    phases fit :data:`SSM_PHASE_VMEM_BYTES`; one where none does."""
    return next((q for q in SSM_PHASE_SLOTS
                 if ssm_phase_vmem_bytes(q, hp, n, l, g)
                 <= SSM_PHASE_VMEM_BYTES), 1)


def ssm_phase_vmem_limit(slots: int, hp: int, n: int, l: int, g: int) -> int:
    """``vmem_limit_bytes`` of the call: the buffers and 8 MB for a row's
    values in flight and what the compiler adds."""
    return ssm_phase_vmem_bytes(slots, hp, n, l, g) + (8 << 20)


# ---------------------------------------------------------------------------
# A chunk group's scan (ops/pallas_ssm.py ssm_chunk_scan)
# ---------------------------------------------------------------------------

# Measured on v5e 2026-10-05 (ISSUE 51; `tools/tune_sweep.py --scan`, two
# sweeps, `chiprun_out/scan_sweep*.jsonl`): a chunk group of ONE member through
# 16 layers in a loop at the two served shapes `(Hp, N, L, G)`; microseconds
# of ONE launch, the kernel's own device events in a traced run. (The sweep's
# host-clock column also times the loop's other operations, which are not the
# cell's; the cells' readings are in PERF.md, PR 51.)
#
#   rows of the chunk              256     128      64
#   (64, 128, 128, 8)  `ssm_scan` in XLA, whole branch, host clock
#                                 156.5   132.6   118.5
#     block 128, 4 rows a step     89.0    50.8      -
#     block 128, 2 / 1 rows        95.6 / 114.6    58.0 / 75.6
#     block 64,  4 rows           114.6    62.5    36.6
#     block 256, 4 rows           121.6      -       -
#   (32, 256, 128, 2)  `ssm_scan` in XLA
#                                 117.9   106.8   104.1
#     block 128, 4 rows a step     54.4    28.0      -
#     block 128, 2 / 1 rows        58.1 / 68.5     30.4 / 35.9
#     block 64,  4 rows            84.4    42.9    22.2
#     block 256, 4 rows            60.5      -       -
#
# The products alone, six bf16 passes at 197 TFLOP/s, are 67 us at the first
# shape (13.3 GFLOP of passes: with two heads a row the product inside the
# block runs once a head over all 128 lanes, the other head's zeroed) and 42
# at the second: the launch reads 75% and 77% of that. What lost, so that
# nobody retries it: blocks of 64 rows (half the MXU's depth a product, and
# lane slices of `B^T` off the tiles); blocks of 256 at two heads a row (the
# masked half of a `(256, 256)` product is computed: twice the work inside
# the block; at a head a row it ties, 60.5 against 54.4); one row of heads a
# grid step (a step's fixed cost, and `C . B^T` waits on fewer rows); AND THE
# FIRST FORM OF THE KERNEL, which took `dt x` as `(Hp, T, L)`, `B^T` and `C`
# a group turned by XLA, and gave `y` back as `(Hp, T, L)`: 98.2 / 61.6 us a
# launch at 256 rows, but the five 8 MB changes of layout round it cost
# ~50 us a layer in the cell, as much as it saved (`nemotron3s_agentturn_sat`
# `tbt_p99_ms` 22.17 -> 21.85 where the form below reads 21.67). The kernel
# now takes `x`, `B`, `C` and gives `y` as the convolution lays them, rows of
# heads a lane block, and XLA forms only `cumsum(dt A)` and `dt` a head as
# rows (seven operations on 128 KB, 7-12 us a launch together). Not tried: the
# state kept turned `(L, N)` in fast memory so that `C^T` / `B` stay in the
# MXU while every row of a group streams through (two more transposes a row
# and block; the products already run at three quarters of their bound).
SSM_SCAN_BLOCK = 128
# What a grid step's blocks may weigh (the rows' states in and out, their
# ``x`` in and ``y`` out): the pipeline holds them twice. Four rows of heads
# fit at both served shapes and a 256-row chunk (1.6 and 2.1 MB).
SSM_SCAN_STEP_BYTES = 5 << 19


def ssm_scan_block(t: int) -> int:
    """Rows a block of ``ssm_chunk_scan`` over a chunk of ``t`` rows: the
    measured :data:`SSM_SCAN_BLOCK`; a shorter chunk is one block."""
    return min(t, SSM_SCAN_BLOCK)


def ssm_scan_step_bytes(t: int, rows: int, n: int, l: int) -> int:
    """What one grid step of ``rows`` rows of heads moves: their states in
    and out ``(n, l)``, ``x`` in and ``y`` out ``(t, l)``, the heads'
    ``cum`` and ``dt`` ``(8, t)``."""
    return rows * (2 * _tiles(n, l) + 2 * _tiles(t, l) + _tiles(8, t))


def ssm_scan_rows(t: int, per: int, n: int, l: int) -> int:
    """Rows of heads a grid step of ``ssm_chunk_scan`` takes, worked out
    from the shapes the call sees: the most of 4, 2, 1 (the body is
    unrolled over them) that divide a group's ``per`` rows (a step never
    straddles two groups' ``B`` / ``C``) and whose blocks fit
    :data:`SSM_SCAN_STEP_BYTES`; one where none does."""
    return next((r for r in (4, 2, 1)
                 if per % r == 0
                 and ssm_scan_step_bytes(t, r, n, l) <= SSM_SCAN_STEP_BYTES),
                1)


def ssm_scan_vmem_bytes(t: int, block: int, rows: int, n: int, l: int) -> int:
    """Fast memory ``ssm_chunk_scan``'s blocks take: a step's blocks and the
    group's ``B`` and ``C`` ``(t, n)`` twice (the pipeline fetches a step
    ahead), and the scratch: ``B^T`` ``(n, t)`` and ``C . B^T`` a block
    ``(block, block)``."""
    return 2 * (ssm_scan_step_bytes(t, rows, n, l) + 2 * _tiles(t, n)) \
        + _tiles(n, t) + t // block * _tiles(block, block)


def ssm_scan_vmem_limit(t: int, block: int, rows: int, n: int, l: int) -> int:
    """``vmem_limit_bytes`` of the call: the blocks and 8 MB for a row's
    values in flight (a head's ``(block, block)`` decays, the products'
    results) and what the compiler adds."""
    return ssm_scan_vmem_bytes(t, block, rows, n, l) + (8 << 20)


# ---------------------------------------------------------------------------
# The Mamba-1 scan's blocks (ops/pallas_ssm.py ssm1_scan)
# ---------------------------------------------------------------------------

# Lanes of a state the row loop carries in registers: a ``(16, 512)`` float32
# state is 8 vector registers, its ``A`` 8 more, a row's few values beside
# them. Not measured against other widths: 1,024 spills.
SSM1_LANES = 512
# Rows a grid step takes of a chunk: B and C come in broadcast over a lane
# tile (``(rows, d_state, 128)`` float32 each), 0.5 MB a block at 64.
SSM1_TIME_BLOCK = 64
# What the blocks of one grid step may take, fetched a step ahead.
SSM1_STEP_VMEM_BYTES = 12 << 20


def ssm1_time_block(t: int) -> int:
    """Rows a grid step of ``ssm1_scan`` takes of a group of ``t`` rows a
    member: the whole group up to :data:`SSM1_TIME_BLOCK`."""
    return min(t, SSM1_TIME_BLOCK)


def ssm1_step_vmem_bytes(tb: int, n: int, ct: int) -> int:
    """Fast memory one grid step's blocks take, each twice (the pipeline
    fetches a step ahead): ``x``, ``dt`` in and ``y`` out ``(tb, ct)``, the
    state in and out and ``A`` ``(n, ct)``, ``B`` and ``C`` ``(tb, n, 128)``;
    float32, a block's rows rounded up to a sublane tile."""
    rows = -(-tb // 8) * 8
    return 2 * 4 * (3 * rows * ct + 3 * n * ct + 2 * tb * n * 128)


def ssm1_channel_tile(tb: int, n: int, channels: int) -> int:
    """Channels a grid step of ``ssm1_scan`` takes, from bytes: the most
    lane chunks (:data:`SSM1_LANES`) that divide ``channels`` and whose
    blocks fit :data:`SSM1_STEP_VMEM_BYTES`; all of them where they fit (the
    layer's ``A`` is then fetched once a launch, and a decode group is one
    grid step a slot), one chunk where none does."""
    chunks = channels // SSM1_LANES
    return SSM1_LANES * next(
        (c for c in range(chunks, 0, -1)
         if chunks % c == 0 and ssm1_step_vmem_bytes(
             tb, n, c * SSM1_LANES) <= SSM1_STEP_VMEM_BYTES), 1)


def ssm1_vmem_limit(tb: int, n: int, ct: int) -> int:
    """``vmem_limit_bytes`` of the call: the blocks and 8 MB for what the
    compiler adds."""
    return ssm1_step_vmem_bytes(tb, n, ct) + (8 << 20)


# ---------------------------------------------------------------------------
# The grouped expert product's blocks (ops/pallas_moe.py)
# ---------------------------------------------------------------------------

# What a kernel gets of fast memory without asking (v5e: 16 MB scoped).
DEFAULT_SCOPED_VMEM_BYTES = 16 << 20
# The most a plan may ask for: the chip holds 128 MB; the rest is XLA's.
GROUPED_VMEM_CEILING_BYTES = 64 << 20


class GroupedPlan(NamedTuple):
    """The blocks one launch of the grouped product moves: a row tile of
    ``tm`` pair rows, ``tk`` of the contraction a grid step (``tk == k``:
    one step an entry, no accumulator carried between steps, and an expert
    whose rows straddle a row tile finds its strip resident at its second
    entry), column strips of ``tn``; ``rows_whole``: the rows' tile holds
    the whole contraction (fetched once a row tile, sliced in the body)
    where ``tk < k``."""
    tm: int
    tk: int
    tn: int
    rows_whole: bool = False

    @property
    def label(self) -> str:
        return (f"tm{self.tm}_tk{self.tk}_tn{self.tn}"
                + ("_rows" if self.rows_whole else ""))

    def vmem_bytes(self, k: int, n_rhs: int, itemsize: int) -> int:
        """Fast memory the blocks take: every operand's block twice (the
        pipeline fetches a step ahead), the accumulators where ``tk < k``,
        and the float32 values the body makes of a ``(tm, tn)`` tile."""
        rows = self.tm * (k if self.rows_whole else self.tk)
        blocks = rows + n_rhs * self.tk * self.tn + self.tm * self.tn
        acc = n_rhs if self.tk < k else 0
        return (2 * blocks * itemsize
                + (acc + n_rhs + 2) * self.tm * self.tn * 4)

    def vmem_limit_bytes(self, k: int, n_rhs: int,
                         itemsize: int) -> Optional[int]:
        """``None`` (the default scope) where the blocks leave it a
        quarter; else the blocks and 4 MB for what the compiler adds."""
        need = self.vmem_bytes(k, n_rhs, itemsize)
        if need <= DEFAULT_SCOPED_VMEM_BYTES * 3 // 4:
            return None
        return need + (4 << 20)


def row_tile(m: int) -> int:
    """Rows the expert layer pads its pairs to, and the row tile of a shape
    with no measured plan: 128 (a decode tick's pairs fit one), 256 once a
    tick carries chunk rows."""
    return 128 if m < 2048 else 256


def _divisor_tile(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is <= ``cap``;
    ``n`` itself where none does (a small test size)."""
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def grouped_plan_unmeasured(m: int, k: int, n: int, n_rhs: int) -> GroupedPlan:
    """The plan of a shape the sweep has not seen: the two constants the
    kernel was written with (PR 27, for DeepSeek-V2's widths)."""
    return GroupedPlan(row_tile(m), _divisor_tile(k, 1024),
                       _divisor_tile(n, 1024 // n_rhs))


# Measured on v5e 2026-10-01 (`tools/tune_sweep.py --grouped`, ISSUE 42): a
# program of 32 (8 at a mixed tick's rows) expert layers in a loop as a tick
# makes them (the pair rows gathered from the token rows, the product in, the
# product out on its result; a call's layer `i % layers` of a stack of 2-4
# layers' experts), the product under test on the candidate plan and the
# other on the first row's; us = the mean device time of that product's
# events in one traced run (what `moe_ffn_ms_tick` sums; a plan measured
# 12-21 times as "the other" repeats to 0.1 us), in brackets the share of
# 819 GB/s the cost function's bytes reach (the touched experts' matrices
# once, the pairs' rows in and out). Rows: pairs a tick / experts held /
# touched, as the cells lay them (a pair lands on a held expert with the
# deployment's share, on any of them alike). `*` the plan chosen; the first
# row of a block is the plan until PR 42 (`grouped_plan_unmeasured`); `_rows`
# the rows' tile at whole k. Every plan's result equal to the first row's to
# a bf16 rounding of the float32 sum's order (0.0002-0.03 at |values| ~ 4).
#
#   LFM2 2048 x 1792         256 pairs / 32 / 32          1,280 / 32 / 32
#   gate/up  tk1024 tn256     678.5 (84.9)                 823.6 (71.1)
#            tk1024 tn256_rows 677.3 (85.0)                820.3 (71.4)
#            tk1024 tn896_rows 645.5 (89.2)                781.7 (74.9)
#            tk1024 tn1792_rows 646.8 (89.1)               782.5 (74.8)
#            tk2048 tn256     637.4 (90.4) *               739.1 (79.2)
#            tk2048 tn896     639.1 (90.1)                 716.5 (81.7) *
#            tk2048 tn1792    641.1 (89.8)                 712.5 (82.2)
#   down     tk896 tn1024     322.9 (89.6)                 392.3 (76.2)
#            tk896 tn2048_rows 323.5 (89.4)                392.4 (76.1)
#            tk1792 tn512     321.2 (90.0)                 375.7 (79.5)
#            tk1792 tn1024    320.5 (90.2) *               365.7 (81.7)
#            tk1792 tn2048    321.4 (90.0)                 360.2 (83.0) *
#
#   6144 x 2048              K-EXAONE 256 / 8 / 7   LongCat 384 / 16 / 8   2,304 / 8 / 8      3,584 / 16 / 16
#   gate/up  tm256 tk1024 tn512     (row tile 128 under 2,048 rows)        631.6 (78.2)       1252.4 (78.6)
#            tm256 tk1024 tn2048_rows                                      580.1 (85.2)       1148.3 (85.7)
#            tm256 tk6144 tn512                                            558.1 (88.6)       1097.3 (89.7)
#            tm128 tk1024 tn512     469.3 (91.7) *  535.5 (91.9) *         -                  -
#            tm128 tk1024 tn512_rows 469.2 (91.8)   535.5 (91.9)           535.8 (92.2) *     1068.0 (92.2) *
#            tm128 tk1024 tn256_rows 505.5 (85.2)   575.8 (85.4)           577.2 (85.6)       1152.8 (85.4)
#            tm128 tk1024 tn2048_rows 473.2 (91.0)  539.6 (91.2)           539.6 (91.6)       1071.5 (91.9)
#            tm128 tk2048 tn512_rows 470.7 (91.5)   537.2 (91.6)           537.3 (92.0)       1069.6 (92.1)
#            tm128 tk2048 tn2048_rows 478.6 (90.0)  545.1 (90.2)           545.1 (90.7)       1077.1 (91.4)
#            tm128 tk6144 tn256     501.4 (85.9)    573.0 (85.8)           572.6 (86.3)       1140.0 (86.4)
#            tm128 tk6144 tn512     475.9 (90.5)    542.3 (90.7)           542.4 (91.1)       1075.1 (91.6)
#   down     tm256 tk1024 tn1024    (row tile 128 under 2,048 rows)        314.8 (78.9)       626.4 (78.7)
#            tm256 tk2048 tn1024                                           286.9 (86.6)       567.7 (86.8)
#            tm128 tk1024 tn1024    235.3 (91.5) *  268.5 (91.6) *         -                  -
#            tm128 tk1024 tn1024_rows 235.3 (91.5)  268.5 (91.6)           268.5 (92.5)       534.4 (92.2)
#            tm128 tk1024 tn6144_rows 242.3 (88.9)  275.6 (89.3)           275.6 (90.1)       541.5 (91.0)
#            tm128 tk2048 tn256     249.1 (86.5)    280.7 (87.7)           283.6 (87.6)       560.5 (88.0)
#            tm128 tk2048 tn512     235.1 (91.6)    268.3 (91.7)           268.3 (92.6) *     534.3 (92.3) *
#            tm128 tk2048 tn3072    242.6 (88.8)    275.8 (89.2)           276.0 (90.0)       542.4 (90.9)
#
#   DeepSeek-V2 5120 x 1536  128 rows (96 pairs) / 40 / 17     1,664 (1,632) / 40 / 40
#   gate/up  tk1024 tn512     710.1 (92.0) *               1791.6 (86.2)
#            tk1024 tn256_rows 733.5 (89.1)                1852.0 (83.3)
#            tk1024 tn1536_rows 712.4 (91.7)               1792.8 (86.1)
#            tk1280 tn512_rows 710.5 (92.0)                1792.2 (86.1)
#            tk5120 tn256     711.1 (91.9)                 1741.2 (88.6) *
#            tk5120 tn512     715.5 (91.3)                 1741.4 (88.6)
#            tk5120 tn768     725.7 (90.0)                 1766.2 (87.4)
#   down     tk768 tn1024     355.9 (91.8) *               896.9 (86.4)
#            tk768 tn5120_rows 359.6 (90.9)                899.9 (86.2)
#            tk1536 tn512     357.1 (91.5)                 890.0 (87.1)
#            tk1536 tn1024    356.6 (91.7)                 876.9 (88.4) *
#            tk1536 tn2560    359.7 (90.9)                 873.4 (88.8)
#            tk1536 tn5120    364.4 (89.7)                 874.8 (88.6)
#
#   Nemotron 1024 x 2688 (ungated)  1,408 / 128 / 120      7,168 (7,040) / 128 / 128
#   in       tm256 tk1024 tn896     (row tile 128)         1101.3 (79.5)
#            tm256 tk1024 tn2688                           1018.4 (86.0)
#            tm128 tk1024 tn896     884.3 (91.6) *         1006.0 (87.1)
#            tm128 tk1024 tn2688    881.3 (91.9)           985.4 (88.9) *
#   out      tm256 tk896 tn1024     (row tile 128)         1151.6 (76.1)
#            tm256 tk2688 tn1024                           1017.3 (86.1)
#            tm128 tk896 tn1024     882.2 (91.8) *         -
#            tm128 tk896 tn1024_rows 882.1 (91.8)          1020.3 (85.8)
#            tm128 tk2688 tn256     972.7 (83.3)           1105.0 (79.3)
#            tm128 tk2688 tn512     880.4 (92.0)           992.8 (88.2)
#            tm128 tk2688 tn1024    881.3 (91.9)           986.1 (88.8) *
#
# What the rows say. (1) A step wants 2 MB or more of weights: LFM2's
# gate/up moved 1 MB a step (1792 divides by no multiple of 128 between 256
# and 896, and `1024 // 2` capped the strip at 512: two 512 KB blocks, 14
# steps an expert) and read 84.9; the same strip at whole k (2 MB, 7 steps)
# reads 90.4, as the 896- and 1792-wide strips do (89.1-90.1). 256-wide
# strips of a 2048-wide matrix (512 B segments at a 4 KB stride) read 85-86%
# at 1, 2 and 6 MB a step alike where at 1792 and 1536 wide they read 90-92:
# the stride, not the segment alone. Past 2 MB a step nothing more is won
# at a decode tick's rows: every shape's best rows tie to 0.5%, and the
# largest blocks lose 1-2 points (a launch's first block is fetched with
# nothing under it: 12-16 MB is 15-20 us of a 470-540 us launch).
# (2) At a mixed tick's rows
# an expert whose rows straddle a row tile has two entries, and with k
# tiled its weight blocks alternate, so its matrices are read twice: whole
# k (one step an entry; the second entry finds the strip resident) is worth
# 3-13% there (LFM2 823.6 -> 712.5, DeepSeek-V2 1791.6 -> 1741.2, Nemotron
# out 1151.6 -> 986.1) and nothing at a decode tick's. (3) The 256-row tile
# that 2,048 rows and more took loses 14-15% to the 128-row one at every
# plan: an entry computes its whole tile whatever rows of it are its
# expert's, and at 256 rows the MXU's time nears the DMA's (12.9 GFLOP
# against 75 MB at 6144 x 2048 x 2); what it was chosen for, fewer entries
# an expert and so fewer second reads, whole k or the rows' tile gives
# without it. (4) The rows' tile at whole k is worth nothing at a decode
# tick's rows inside a layer (the compiler leaves the gathered rows, and
# the product's result, in fast memory: `bf16[256,6144]{...S(1)}` in the
# tick programs) and 1-3% at a mixed tick's with k tiled. Known to push against each other: a step's fixed
# cost and short segments (small blocks lose) and a launch's first block
# (large blocks lose); the optimum is flat between 2 and 8 MB a step.
# A plan that ties the one until PR 42 at a decode tick's rows (within
# 0.5%) is not taken there: the program stays what the parent compiled.
# Not the blocks': K-EXAONE's cell reads 84% where its shape reads 91.7
# here. A launch over ALL 8 held experts (most of its decode ticks') takes
# 569.0 us alone (71.1 an expert; 611.5 in the cell) where 7 of the 8 take
# 469.3 (67.0), at stacks of 40-64 experts, with or without a spacer
# between the two stacks: the sweep's K-EXAONE row should touch every held
# expert before the next look (PERF.md section 7).
def _plans(k, n, n_rhs, few, many, bound=512):
    """``few``: (tk, tn, rows_whole) under ``bound`` rows (``None``: the
    plan until PR 42), ``many`` from there on; the row tile 128 at any
    measured row."""
    return (k, n, n_rhs), (
        (bound, None if few is None else GroupedPlan(128, *few)),
        (float("inf"), GroupedPlan(128, *many)))


# (k, n, n_rhs) -> ((rows under, plan or None), ...): the first whose bound
# holds; None: :func:`grouped_plan_unmeasured`.
_GROUPED_PLANS = dict((
    _plans(2048, 1792, 2, (2048, 256, False), (2048, 896, False)),   # LFM2
    _plans(1792, 2048, 1, (1792, 1024, False), (1792, 2048, False)),
    _plans(6144, 2048, 2, None, (1024, 512, True)),    # K-EXAONE, LongCat
    _plans(2048, 6144, 1, None, (2048, 512, False)),
    _plans(5120, 1536, 2, None, (5120, 256, False)),   # DeepSeek-V2
    _plans(1536, 5120, 1, None, (1536, 1024, False)),
    _plans(1024, 2688, 1, None, (1024, 2688, False), bound=2048),  # Nemotron
    _plans(2688, 1024, 1, None, (2688, 1024, False), bound=2048),
))


def grouped_plan(m: int, k: int, n: int, n_rhs: int) -> GroupedPlan:
    """The blocks for ``(m, k) x n_rhs (k, n)``: the measured row of the
    shape, else :func:`grouped_plan_unmeasured`. Reads the operands'
    shapes and nothing else."""
    for bound, plan in _GROUPED_PLANS.get((k, n, n_rhs), ()):
        if m < bound:
            if plan is not None and m % plan.tm == 0:
                return plan
            break
    return grouped_plan_unmeasured(m, k, n, n_rhs)
