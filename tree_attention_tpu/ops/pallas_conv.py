"""A gated short-convolution layer's decode step over its state, in place
(Pallas TPU).

A conv layer's state is the tail pool (``models/decode.py``
``PagedHybridCache``): one row of ``2 x D`` lanes a pool block and layer,
the gated inputs ``z`` of the block's two highest positions, position ``q``
in half ``q % 2``. A slot's ONE new token at position ``p`` reads
``z_{p-1}`` and ``z_{p-2}`` out of it and writes ``z_p`` over ``z_{p-2}``::

    z   = b * u                     (``[b | c | u] = h . W_in``; bf16)
    s   = w0 * z_{p-2} + w1 * z_{p-1} + w2 * z      (float32, rounded once)
    out = c * s                     (bf16: what ``W_out`` multiplies)
    row of p's block, half p % 2 <- z

In plain XLA that is three 64-row gathers of the pool, a scatter back and a
chain of some thirty small operations between a layer's two weight streams
(``models/hybrid.py`` ``conv_mixer``, which keeps it for a chunk group and
off the TPU). Here it is one launch, no grid, the pool aliased to its
output: every live slot's piece of the pool is copied into fast memory,
every copy started before any is awaited, the arithmetic runs on all slots
at once, and every piece goes back before any of those copies is awaited
(``ops/pallas_decode.py`` ``paged_row_write`` is the pattern). A slot with
no row starts no copy: its table entry may be a stale name of a block a
live slot writes in this very call.

**The cut.** The pool is ``(layers x N, 2 x D)`` bf16 as it lies, and the
chip lays a 2-D bf16 array out in tiles of 8 rows whose rows ``2j`` and ``2j
+ 1`` share 32-bit words (low and high halves): the least a copy may cut out
is the tile (:data:`CUT`; the compiler refuses fewer rows), and its 8 rows are
8 BLOCKS, which may belong to as many live slots. So every copy of a cut gets
the new ``z`` of EVERY live slot whose block lies in it before any is written
back, and goes back by the half of the lanes its own slot wrote: the plan
lists, for each slot, the other live slots of its cut that write the same
half (:func:`conv_tail_plan`, once a tick; rare where blocks are scattered,
seven a slot where they are neighbours), and the kernel writes their ``z``
into the slot's copy row by row. Two copies of one cut then hold the same
bytes in the lanes both bring back, and which lands last does not matter.

**The arithmetic never unpacks a pair.** The cuts ride in fast memory as
uint32 words; a slot's pair of rows is copied out of its cut by one dynamic
sublane read into a ``(slots, 2D)`` array, the gates and taps run on all
slots at once (a bf16 value is the high half of its float32), and the pair
goes back the same way. (Working on the cuts themselves, four pairs a slot,
costs a single-sublane access a slot, lane tile and pair: 31 us a call where
this takes 24, ``ops/tuning.py``.)

Off the TPU ``conv_mixer`` takes the XLA path; the tests hold this kernel to
it bit for bit in interpret mode, and ``chip_smoke.py`` on the chip.
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

CONV_TAIL_KERNEL = "conv_tail_step"
# The named scope of the plan's operations in a step program, as the paged
# kernels' ``PLAN_SCOPE``: a tick builds it once, before its layer loops.
TAIL_PLAN_SCOPE = "conv_tail_plan"

_KERNEL_BUILDS = obs.counter(
    "pallas_conv_kernel_builds_total",
    "conv tail-step kernel program builds (one per distinct shape)",
    labels=("kernel",),
)

# Rows of the pool a copy cuts out: the tile the chip lays a 2-D array out
# in (``T(8,128)(2,1)`` for bf16), the least the compiler lets a copy take.
CUT = 8
# ... and the most other live slots whose blocks lie in a slot's cut.
MATES = CUT - 1

# The bits of a slot's ``flags`` (:func:`conv_tail_plan`).
_ODD, _PREV1, _PREV2, _HAS1, _HAS2, _HIGH, _PHIGH, _LIVE = (
    1, 2, 4, 8, 16, 32, 64, 128)


class ConvTailPlan(NamedTuple):
    """Where a group of one row a slot meets the tail pool, the same for
    every conv layer (:func:`conv_tail_plan`)."""

    ids: jax.Array     # (B,) the block position p lies in, or -1: no row
    prev: jax.Array    # (B,) the block before it, where p-1 or p-2 lies
                       # there (the first two positions of a block); else -1
    meta: jax.Array    # (B,) ids % CUT | prev % CUT << 3 | p % 2 << 6
    mates: jax.Array   # (B x MATES,) the other live slots whose block lies
                       # in slot b's cut and whose p has b's parity; else -1
    flags: jax.Array   # (B, 1) the bits above, a slot a sublane
    count: jax.Array   # () how many slots write


def conv_tail_plan(table: jax.Array, start: jax.Array, n: jax.Array,
                   blocks: int, block: int) -> ConvTailPlan:
    """The plan of :func:`conv_tail_step` for slots whose ONE new token
    lies at ``start`` (``n`` 0: no token), through a table of
    ``blocks``-block layers (:data:`CUT` divides ``blocks``, so a block's
    place in its cut is the same in every layer). A slot writes where the
    block path's ``_tail_write`` writes (``models/decode.py``
    ``_row_targets``' rule: not past the table, not through an entry
    outside the pool); what lies before position 0, or behind an entry
    outside the pool, reads as zero and is never fetched."""
    with jax.named_scope(TAIL_PLAN_SCOPE):
        B, NB = table.shape
        lb = start // block
        at = lambda j: jnp.take_along_axis(  # noqa: E731
            table, jnp.clip(j, 0, NB - 1)[:, None], axis=1)[:, 0]
        pb, before = at(lb), at(lb - 1)
        live = (n > 0) & (start < NB * block) & (pb >= 0) & (pb < blocks)
        off, odd = start % block, start % 2
        prev = jnp.where(
            live & (off < 2) & (lb >= 1) & (before >= 0) & (before < blocks),
            before, -1)
        ids = jnp.where(live, pb, -1)
        there = prev >= 0
        flags = (_ODD * odd + _PREV1 * (off < 1) + _PREV2 * (off < 2)
                 + _HAS1 * ((start >= 1) & ((off >= 1) | there))
                 + _HAS2 * ((start >= 2) & ((off >= 2) | there))
                 + _HIGH * (ids % 2) + _PHIGH * (prev % 2) + _LIVE * live)
        meta = ids % CUT + (prev % CUT) * 8 + odd * 64
        # The live slots that share slot b's cut and write b's half of
        # their rows, lowest first: a stable sort of "is not one".
        mate = (live[:, None] & live[None, :] & ~jnp.eye(B, dtype=bool)
                & (ids[:, None] // CUT == ids[None, :] // CUT)
                & (odd[:, None] == odd[None, :]))
        order = jnp.argsort(~mate, axis=1, stable=True)[:, :MATES]
        mates = jnp.where(jnp.take_along_axis(mate, order, axis=1), order, -1)
        if mates.shape[1] < MATES:      # fewer slots than a cut has rows
            mates = jnp.pad(mates, ((0, 0), (0, MATES - mates.shape[1])),
                            constant_values=-1)
        return ConvTailPlan(
            ids, prev, meta.astype(jnp.int32),
            mates.reshape(-1).astype(jnp.int32),
            flags.astype(jnp.int32)[:, None], jnp.sum(live, dtype=jnp.int32))


def _conv_tail_kernel(
    ids_ref,    # SMEM (B,) scalar-prefetch: ConvTailPlan.ids
    prev_ref,   # SMEM (B,): ConvTailPlan.prev
    meta_ref,   # SMEM (B,): ConvTailPlan.meta
    mates_ref,  # SMEM (B x MATES,): ConvTailPlan.mates
    base_ref,   # SMEM (1,): the layer's first row (c * N)
    bcu_ref,    # VMEM (B, 3D) bf16: [b | c | u]
    w_ref,      # VMEM (3, D): the taps, tap k on z_{p-2+k}
    flags_ref,  # VMEM (B, 1) int32: ConvTailPlan.flags
    pool_in,    # HBM: the aliased input, reached as the output
    pool,       # HBM (M, 2D) bf16
    out_ref,    # VMEM (B, D) bf16: c * s
    cur,        # VMEM (B, CUT / 2, 2D) uint32: the cut slot b's row lies in
    prv,        # VMEM (B, CUT / 2, 2D) uint32: the cut of the block before
    own,        # VMEM (B, 2D) uint32: slot b's pair of rows out of its cut
    was,        # VMEM (B, 2D) uint32: ... out of the cut before
    zs,         # VMEM (B, D) uint32: the new z, a bf16 in a float32's bits
    sem,        # DMA (2, B)
):
    """Rows ``2j`` and ``2j + 1`` of a cut are the low and high halves of
    the words of pair ``j`` (a packed dtype's layout), and a bf16 value is
    the high half of its float32: the cuts ride as uint32 words, a slot's
    pair is copied out of its cut by one dynamic sublane read, the
    arithmetic runs on all slots' pairs at once, and a row is read and
    written in place inside its words."""
    del pool_in
    B, D = out_ref.shape
    base = base_ref[0]
    f32, u32, bf16 = jnp.float32, jnp.uint32, jnp.bfloat16
    HI, LO = u32(0xFFFF0000), u32(0x0000FFFF)
    bits = lambda x: lax.bitcast_convert_type(x, u32)  # noqa: E731
    words = pool.bitcast(u32)              # (M / 2, 2D): a pair of rows a row

    def fetch(b, before=False):
        """The copy in of slot ``b``'s cut, or of the cut before it."""
        buf, blk_ref = (prv, prev_ref) if before else (cur, ids_ref)
        at = pl.multiple_of(
            (base + blk_ref[b]) // CUT * (CUT // 2), CUT // 2)
        return pltpu.make_async_copy(
            words.at[pl.ds(at, CUT // 2), :], buf.at[b],
            sem.at[int(before), b])

    def store(b, act):
        # The half of the lanes the slot's z went to, and no more: what a
        # live slot of the cut wrote to the other half is its own copy's to
        # bring.
        at = pl.multiple_of((base + ids_ref[b]) // CUT * CUT, CUT)
        for k in (0, 1):
            @pl.when(meta_ref[b] // 64 == k)
            def _(k=k):
                lanes = pl.ds(k * D, D)
                act(pltpu.make_async_copy(
                    cur.bitcast(bf16).at[b, :, lanes],
                    pool.at[pl.ds(at, CUT), lanes], sem.at[0, b]))

    def each(do):
        lax.fori_loop(0, B, lambda b, carry: (do(b), carry)[1], 0)

    def live_do(blk_ref, b, do):
        pl.when(blk_ref[b] >= 0)(lambda: do(b))

    def pair(b, of_prev=False):
        return pl.ds(((meta_ref[b] // 8 if of_prev else meta_ref[b]) % CUT)
                     // 2, 1)

    def start(b):
        live_do(ids_ref, b, lambda b: fetch(b).start())
        live_do(prev_ref, b, lambda b: fetch(b, True).start())

    each(start)

    # While the cuts are on their way: what needs nothing of the pool.
    flags = jnp.broadcast_to(flags_ref[...], (B, D))
    flag = lambda bit: (flags & bit) != 0  # noqa: E731
    live, odd = flag(_LIVE), flag(_ODD)
    z = (bcu_ref[:, :D].astype(f32) * bcu_ref[:, 2 * D:].astype(f32)
         ).astype(bf16)
    zf = z.astype(f32)
    zs[...] = bits(zf)

    def gather(b):
        # A slot's pair of rows out of its cut as soon as the cut is there.
        def mine(b):
            fetch(b).wait()
            own[pl.ds(b, 1), :] = cur[b, pair(b), :]

        def before(b):
            fetch(b, True).wait()
            was[pl.ds(b, 1), :] = prv[b, pair(b, True), :]

        live_do(ids_ref, b, mine)
        live_do(prev_ref, b, before)

    each(gather)

    def value(w, high):
        """One row of pairs' words as float32."""
        return lax.bitcast_convert_type(
            jnp.where(high, w & HI, w << 16), f32)

    # Half k holds z_{p-2} where p's parity is k, z_{p-1} otherwise; either
    # may lie in the block before.
    halves = []
    for k in (0, 1):
        lanes = slice(k * D, (k + 1) * D)
        second = odd if k else ~odd      # this half is z_{p-2}'s
        there = (flags & jnp.where(second, _PREV2, _PREV1)) != 0
        halves.append(jnp.where(there, value(was[:, lanes], flag(_PHIGH)),
                                value(own[:, lanes], flag(_HIGH))))
    zero = jnp.zeros((B, D), f32)
    zm2 = jnp.where(live & flag(_HAS2),
                    jnp.where(odd, halves[1], halves[0]), zero)
    zm1 = jnp.where(live & flag(_HAS1),
                    jnp.where(odd, halves[0], halves[1]), zero)
    w = w_ref[...].astype(f32)
    s = (w[0:1] * zm2 + w[1:2] * zm1) + w[2:3] * zf
    out_ref[...] = (bcu_ref[:, D:2 * D].astype(f32)
                    * s.astype(bf16).astype(f32)).astype(bf16)

    # z into its half of the slot's row, inside the pair's words ...
    for k in (0, 1):
        lanes = slice(k * D, (k + 1) * D)
        old = own[:, lanes]
        new = jnp.where(flag(_HIGH), (old & LO) | bits(zf),
                        (old & HI) | (bits(zf) >> 16))
        own[:, lanes] = jnp.where(live & (odd if k else ~odd), new, old)

    # ... the pair back into the slot's copy of its cut, then the z of
    # every OTHER live slot whose block lies in the cut and whose z goes to
    # the same half (the plan's list), and the copy on its way home: every
    # copy of a cut holds the same bytes in the lanes it brings back, and
    # which lands last does not matter.
    def back(b):
        cur[b, pair(b), :] = own[pl.ds(b, 1), :]
        for t in range(MATES):
            m = mates_ref[b * MATES + t]

            @pl.when(m >= 0)
            def _(m=m):
                high = meta_ref[m] % 2 == 1
                at = pl.ds((meta_ref[m] % CUT) // 2, 1)
                for k in (0, 1):
                    @pl.when(meta_ref[b] // 64 == k)
                    def _(k=k):
                        lanes = pl.ds(k * D, D)
                        znew = zs[pl.ds(m, 1), :]
                        old = cur[b, at, lanes]
                        cur[b, at, lanes] = jnp.where(
                            high, (old & LO) | znew,
                            (old & HI) | (znew >> 16))

        store(b, lambda copy: copy.start())

    each(lambda b: live_do(ids_ref, b, back))
    each(lambda b: live_do(
        ids_ref, b, lambda b: store(b, lambda copy: copy.wait())))


def conv_tail_step(
    pool: jax.Array,
    bcu: jax.Array,
    w: jax.Array,
    plan: ConvTailPlan,
    base,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One token's step of one conv layer, for the slots that have one, in
    place.

    ``pool`` is the tail pool of every layer as ``(layers x N, 2D)`` bf16
    (:data:`CUT` divides ``N``), this layer's block ``j`` its row ``base +
    j``, ``base`` a multiple of ``N``; ``bcu`` ``(B, 3D)`` bf16 the slots'
    ``h . W_in``; ``w`` ``(3, D)`` the taps; ``plan``
    :func:`conv_tail_plan`. Returns the pool (the buffer that came in,
    under a donating ``jit``) and ``c * s`` ``(B, D)`` bf16, for a slot
    with no row whatever the arithmetic gives over zeros. The caller keeps
    live slots' blocks distinct. The device event is ``conv_tail_step``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _conv_tail_call(
        pool, bcu, w, ConvTailPlan(*plan),
        jnp.asarray(base, jnp.int32).reshape(1), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_tail_call(pool, bcu, w, plan, base, *, interpret: bool):
    M, W = pool.shape
    B, D = bcu.shape[0], W // 2
    if M % CUT or W % 256 or bcu.shape != (B, 3 * D) or w.shape != (3, D) \
            or pool.dtype != jnp.bfloat16 or bcu.dtype != jnp.bfloat16:
        raise ValueError(
            f"conv_tail_step takes a bf16 pool ({CUT} dividing its rows, "
            f"2D), bcu (B, 3D) bf16 and taps (3, D); got "
            f"{[(t.shape, t.dtype) for t in (pool, bcu, w)]}")
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(kernel=CONV_TAIL_KERNEL).inc()
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    scalars = (plan.ids, plan.prev, plan.meta, plan.mates, base)
    u32 = jnp.uint32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(),
        in_specs=[vmem] * 3 + [anywhere],
        out_specs=[anywhere, vmem],
        scratch_shapes=[pltpu.VMEM((B, CUT // 2, W), u32)] * 2
        + [pltpu.VMEM((B, W), u32)] * 2 + [pltpu.VMEM((B, D), u32)]
        + [pltpu.SemaphoreType.DMA((2, B))],
    )
    return pl.pallas_call(
        _conv_tail_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, D), bcu.dtype)],
        input_output_aliases={len(scalars) + 3: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * B * CUT * W * 2 + (32 << 20)),
        interpret=interpret,
        name=CONV_TAIL_KERNEL,
    )(*scalars, bcu, w, plan.flags, pool)
