"""A state-space layer's steps on the state pool, in place (Pallas TPU): a
Mamba-2 layer's decode step over the slots that have a row
(``ssm_decode_update``) and its chunk group's scan (``ssm_chunk_scan``, below
the decode step); and a Mamba-1 layer's rows at any count a member
(``ssm1_scan``, last: its decay is a number for every (channel, state) pair,
so the chunked matrix form below does not apply to it and the rows pass one
by one over a state tile held in fast memory).

A state-space (Mamba-2) layer holds, for every slot, a state ``S`` of ``heads
x d_head x d_state`` float32 that EVERY token rewrites whole::

    S <- a * S + (dt * x) (x) B        y = S . C

(``a = exp(dt * A)`` a head, ``B`` and ``C`` rows of ``d_state`` shared by a
group of heads). In a decode tick that is the whole pool read and written
once, and nothing else of the layer comes near it in bytes, so two things
are what this kernel is for. The pool is never copied: it stays where it is
(``pl.ANY``, aliased to its output), a live slot's state of the layer is
copied into VMEM, updated where it landed and copied back where it came
from, and a slot with no row is not visited at all (the list of live slots
rides scalar prefetch). And the memory is asked for one thing at a time: the
slots go through in PHASES of a few (``ops/tuning.py`` ``ssm_phase_slots``),
a phase's reads in flight with nothing else, then the phase's arithmetic
under the writes of the phase before it. On a v5e reads alone run at 90% of
the memory's pace and writes alone at 78%, and a read and a write in flight
together (what a BlockSpec pipeline keeps) at less than the two apart:
``ops/tuning.py`` has the table.

The pool lays ``pack`` heads side by side on a row's 128 lanes
(``StateSpace.state_shape``: ``(heads / pack, d_state, pack x d_head)``), so
that ``dt * x``, ``a`` and ``y`` are rows of lanes as they come out of the
projections, ``B`` and ``C`` rows of ``d_state`` a group as they come too
(turned to columns once a slot), the update three multiplies and an add a
vector register, and ``y`` a sum over registers with one cross-sublane
reduce a row of heads: no transposed operand and no cross-lane reduce a
head.

Two shapes are served, ``(Hp, N, L, G)``: ``(64, 128, 128, 8)`` (128 heads of
64, two a row, ``pack`` 2; eight rows a group) and ``(32, 256, 128, 2)`` (32
heads of 128, a head a row, ``pack`` 1; ``B`` and ``C`` a ``(2, 256)`` tile
turned to columns, sixteen rows a group). A slot's state of a layer is the
same 4,194,304 B at both, so the phase rule gives both four slots a phase, and
both run at 85% of the memory's pace (PERF.md, PRs 45 and 47).

A **chunk group** (a prompt's next ``tq`` rows a member) takes the chunked
form of the same recurrence, blocks of ``T`` rows with the state carried block
to block (``models/hybrid.py`` ``ssm_scan`` is the form in ``jax.numpy``)::

    cum_l = sum_{r <= l} dt_r A                 (inside the block)
    y_l   = sum_{s <= l} exp(cum_l - cum_s) (C_l . B_s) dt_s x_s
            + exp(cum_l) C_l . S
    S    <- exp(cum_T) S + sum_s exp(cum_T - cum_s) B_s (x) dt_s x_s

As XLA operations that is a ``(T, T, heads)`` float32 array made three times
over in HBM, ``B`` and ``C`` repeated to every head, and the members' states
gathered, turned out of the pool's layout, turned back and scattered: ~150 MB
a layer at the first shape beside 0.05 ms of products (ISSUE 51). The pool's
layout IS the operand both state products want (``C (T, N) . S (N, L)`` and
``B^T (N, T) . (.) (T, L)``), so ``ssm_chunk_scan`` is a grid over (member, a
few rows of heads) whose blocks the pipeline moves: the rows' states from the
pool and back into it (the pool aliased to the output, a member's block found
through scalar prefetch), their ``x`` in and ``y`` out and the group's ``B``
and ``C`` (fetched once a group) as the convolution lays them, ``(T, heads x
d_head)`` and ``(T, groups x d_state)``: no operand changes its layout on its
way in or out (the first form of this kernel took ``dt x`` and ``B^T`` turned
by XLA and gave ``y`` back turned: those copies cost as much as the kernel
saved, ``ops/tuning.py``). ``B^T`` and ``C . B^T`` are formed once a group
into scratch; a head's ``(T, T)`` decays are made in registers from its
``cum`` and ``dt`` as a row and as a column, the only operands XLA forms
before the launch (kilobytes). A member with no row is not visited (its
steps point at the last visited block and do nothing); a member whose first
position is 0 starts from zeros whatever the pool holds. Every product is
float32 at ``Precision.HIGHEST``, as the XLA form's. ``ops/tuning.py`` has
the block length, the rows a step and the sweep they come from.

Off the TPU the layer body takes the same steps in ``jax.numpy``
(``models/hybrid.py`` ``ssm_step`` / ``ssm_scan``); the tests hold both
kernels to them in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tree_attention_tpu import obs
from tree_attention_tpu.ops import tuning

SSM_KERNEL = "ssm_decode_update"
SCAN_KERNEL = "ssm_chunk_scan"
SSM1_KERNEL = "ssm1_scan"
SSM1_LANES = tuning.SSM1_LANES
_HI = lax.Precision.HIGHEST

_KERNEL_BUILDS = obs.counter(
    "pallas_ssm_kernel_builds_total",
    "state-space kernel program builds (one per distinct shape), by kernel: "
    "ssm_decode_update, ssm_chunk_scan, ssm1_scan",
    labels=("kernel",),
)


def live_list(n_valid: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The slots that have a row, in order, and how many: ``(ids (S,),
    count (1,))`` from the rows a slot ``n_valid`` ``(S,)``. What lies past
    the count is slot 0 and never visited. No sort: a running count places
    each live slot."""
    S = n_valid.shape[0]
    live = n_valid > 0
    at = jnp.cumsum(live, dtype=jnp.int32) - 1
    ids = jnp.zeros((S,), jnp.int32).at[jnp.where(live, at, S)].set(
        jnp.arange(S, dtype=jnp.int32), mode="drop")
    return ids, jnp.sum(live, dtype=jnp.int32).reshape(1)


def _ssm_update_kernel(
    ids_ref,   # SMEM (S,) scalar-prefetch: the live slots, in order
    cnt_ref,   # SMEM (1,) scalar-prefetch: how many
    base_ref,  # SMEM (1,) scalar-prefetch: the layer's first row (l * S)
    x_hbm,     # ANY (S, Hp, L): dt * x, a row of `pack` heads a sublane
    a_hbm,     # ANY (S, Hp, L): exp(dt * A), each head's over its lanes
    b_hbm,     # ANY (S, G, N): B, a row a group
    c_hbm,     # ANY (S, G, N): C, likewise
    s_hbm,     # ANY (layers x S, Hp, N, L): the pool
    o_hbm,     # ... and where it goes back (aliased to the pool)
    y_hbm,     # ANY (S, Hp, L): S . C
    sbuf,      # VMEM (2, Q, Hp, N, L): two phases' states, updated in place
    xbuf,      # VMEM (2, Q, Hp, L)
    abuf,      # VMEM (2, Q, Hp, L)
    bbuf,      # VMEM (2, Q, G, N)
    cbuf,      # VMEM (2, Q, G, N)
    ybuf,      # VMEM (2, Q, Hp, L)
    rsem,      # DMA (2, Q, 5): a phase's reads, a slot's five operands
    wsem,      # DMA (2, Q, 2): its writes, the state and y
    *,
    slots: int,
    groups: int,
    rows: int,
):
    """The memory sees reads OR writes, never both (``ops/tuning.py``
    ``ssm_phase_slots``): a phase is ``slots`` live slots; phase ``p + 1``
    is read whole with nothing else in flight, and computed where it
    landed while phase ``p`` is written back whole."""
    Q, cnt = slots, cnt_ref[0]
    phases = (cnt + Q - 1) // Q

    def copies(p, q, half, out):
        slot = ids_ref[p * Q + q]
        home = base_ref[0] + slot
        at = (half, q)
        if out:
            sem, pairs = wsem, ((sbuf.at[at], o_hbm.at[home]),
                                (ybuf.at[at], y_hbm.at[slot]))
        else:
            sem, pairs = rsem, (
                (s_hbm.at[home], sbuf.at[at]),
                (x_hbm.at[slot], xbuf.at[at]), (a_hbm.at[slot], abuf.at[at]),
                (b_hbm.at[slot], bbuf.at[at]), (c_hbm.at[slot], cbuf.at[at]))
        return [pltpu.make_async_copy(src, dst, sem.at[half, q, i])
                for i, (src, dst) in enumerate(pairs)]

    def live(p):
        """How many of phase ``p``'s slots the list holds."""
        return jnp.clip(cnt - p * Q, 0, Q)

    def every(p, half, out, act):
        """``act`` on every copy of phase ``p``'s live slots: all of a
        phase's copies are started before any is awaited."""
        def slot(q, carry):
            for copy in copies(p, q, half, out):
                act(copy)
            return carry

        lax.fori_loop(0, live(p), slot, 0)

    def update(p, half):
        def slot(q, carry):
            bt = bbuf[half, q].T                            # (N, G)
            ct = cbuf[half, q].T
            for g in range(groups):
                b, c = bt[:, g:g + 1], ct[:, g:g + 1]       # (N, 1)
                for r in range(g * rows, (g + 1) * rows):
                    new = abuf[half, q, r:r + 1, :] * sbuf[half, q, r] \
                        + b * xbuf[half, q, r:r + 1, :]     # (N, L)
                    sbuf[half, q, r] = new
                    ybuf[half, q, r:r + 1, :] = jnp.sum(
                        new * c, axis=0, keepdims=True)
            return carry

        lax.fori_loop(0, live(p), slot, 0)

    start, wait = (lambda copy: copy.start()), (lambda copy: copy.wait())
    every(0, 0, False, start)
    every(0, 0, False, wait)

    def phase(p, carry):
        # Phase p is computed under ONE stream: the reads of phase 1 (p = 0)
        # or the writes of phase p - 1; the reads of p + 1 then go alone.
        half = p % 2
        first = p == 0
        pl.when(first)(lambda: every(1, 1, False, start))
        pl.when(~first)(lambda: every(p - 1, 1 - half, True, start))
        update(p, half)
        pl.when(first)(lambda: every(1, 1, False, wait))

        @pl.when(~first)
        def _():
            every(p - 1, 1 - half, True, wait)
            every(p + 1, 1 - half, False, start)
            every(p + 1, 1 - half, False, wait)

        return carry

    lax.fori_loop(0, phases, phase, 0)
    last = jnp.maximum(phases - 1, 0)       # an empty list: no copy is live
    every(last, last % 2, True, start)
    every(last, last % 2, True, wait)


def ssm_decode_update(
    state: jax.Array,
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    ids: jax.Array,
    count: jax.Array,
    base,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One token's update of one state-space layer, for the slots in the
    list, in place.

    ``state`` is the pool of every layer as ``(layers x S, Hp, N, L)``
    float32 (``Hp`` rows of ``pack`` heads, ``L = pack x d_head`` lanes);
    slot ``s``'s state of this layer is row ``base + s``. ``x`` (``dt * x``)
    and ``a`` (``exp(dt * A)`` over each head's lanes) are ``(S, Hp, L)``
    float32, ``b`` / ``c`` ``(S, G, N)`` float32 with ``Hp / G`` rows a
    group. ``ids`` / ``count``: :func:`live_list`. Returns the pool (the
    buffer that came in, under a donating ``jit``) and ``y = S . C`` ``(S,
    Hp, L)``, unwritten for a slot not in the list: the caller masks it.
    The device event is ``ssm_decode_update``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ssm_update_call(
        state, x, a, b, c, ids, jnp.reshape(count, (1,)),
        jnp.reshape(base, (1,)), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "slots"))
def _ssm_update_call(state, x, a, b, c, ids, count, base, *,
                     interpret: bool, slots: Optional[int] = None):
    M, Hp, N, L = state.shape
    S, G, _ = b.shape
    if x.shape != (S, Hp, L) or a.shape != x.shape or c.shape != b.shape \
            or b.shape[2] != N or Hp % G or M % S \
            or any(t.dtype != jnp.float32 for t in (state, x, a, b, c)):
        raise ValueError(
            f"ssm_decode_update takes a float32 pool (layers x S, Hp, N, L), "
            f"x and a (S, Hp, L), b and c (S, G, N) with G dividing Hp; "
            f"got {[(t.shape, t.dtype) for t in (state, x, a, b, c)]}")
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(kernel=SSM_KERNEL).inc()
    Q = slots or tuning.ssm_phase_slots(Hp, N, L, G)
    ids = jnp.asarray(ids, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[anywhere] * 5,
        out_specs=[anywhere] * 2,
        scratch_shapes=[
            pltpu.VMEM((2, Q, Hp, N, L), f32),
            pltpu.VMEM((2, Q, Hp, L), f32), pltpu.VMEM((2, Q, Hp, L), f32),
            pltpu.VMEM((2, Q, G, N), f32), pltpu.VMEM((2, Q, G, N), f32),
            pltpu.VMEM((2, Q, Hp, L), f32),
            pltpu.SemaphoreType.DMA((2, Q, 5)),
            pltpu.SemaphoreType.DMA((2, Q, 2)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ssm_update_kernel, slots=Q, groups=G,
                          rows=Hp // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(x.shape, x.dtype)],
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=tuning.ssm_phase_vmem_limit(Q, Hp, N, L, G),
        ),
        interpret=interpret,
        name=SSM_KERNEL,
    )(ids, count, base, x, a, b, c, state)


# ---------------------------------------------------------------------------
# A chunk group's scan
# ---------------------------------------------------------------------------


def _dot(a, b):
    """Float32 at the highest precision: what is added to a state is never
    rounded below it."""
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _ssm_scan_kernel(
    ids_ref,    # SMEM (b,) scalar-prefetch: the members that have a row
    cnt_ref,    # SMEM (1,) scalar-prefetch: how many
    home_ref,   # SMEM (b,) scalar-prefetch: a member's row of the pool
    fresh_ref,  # SMEM (b,) scalar-prefetch: 1 where its first position is 0
    x_ref,      # VMEM (1, T, R x L): x as the projection lays it, R rows of
                # `pack` heads
    hd_ref,     # VMEM (1, R, 8, T): a head a sublane, the first `pack`
                # cumsum(dt * A) inside a block, the next `pack` dt
    b_ref,      # VMEM (1, T, N): the group's B
    c_ref,      # VMEM (1, T, N): the group's C
    s_ref,      # VMEM (1, R, N, L): the member's state, these rows of heads
    o_ref,      # ... and where it goes back (aliased to the pool)
    y_ref,      # VMEM (1, T, R x L)
    bt_ref,     # VMEM scratch (blocks, N, Tb): the group's B^T a block
    cb_ref,     # VMEM scratch (blocks, Tb, Tb): the group's C . B^T a block
    *,
    block: int,
    pack: int,
    steps: int,
):
    """One grid step: ``R`` rows of heads of one member through every block
    of the chunk, the state carried in registers and fast memory. ``steps``
    grid steps make a group; its first turns ``B`` and forms ``C . B^T``
    for the rest."""
    i, q = pl.program_id(0), pl.program_id(1)
    cnt = cnt_ref[0]
    _, R, N, L = s_ref.shape
    T = x_ref.shape[1]
    Tb = block
    head = L // pack
    blocks = [slice(j * Tb, (j + 1) * Tb) for j in range(T // Tb)]

    # No member has a row: the one block every step was pointed at goes
    # back as it came.
    @pl.when(cnt == 0)
    def _():
        o_ref[...] = s_ref[...]

    @pl.when(i < cnt)
    def _():
        fresh = fresh_ref[ids_ref[i]] != 0

        @pl.when(q % steps == 0)
        def _():
            for j, at in enumerate(blocks):
                bt_ref[j] = b_ref[0, at, :].T
                cb_ref[j] = _dot(c_ref[0, at, :], bt_ref[j])

        tri = lax.broadcasted_iota(jnp.int32, (Tb, Tb), 0) \
            >= lax.broadcasted_iota(jnp.int32, (Tb, Tb), 1)
        lane = lax.broadcasted_iota(jnp.int32, (1, L), 1) // head
        bottom = lax.broadcasted_iota(jnp.int32, (8, L), 0) == 7

        def over_lanes(cols):
            """Each head's value over the head's own lanes: ``pack``
            columns ``(rows, 1)`` as ``(rows, L)``."""
            out = jnp.broadcast_to(cols[0], (cols[0].shape[0], L))
            for k in range(1, pack):
                out = jnp.where(lane >= k, cols[k], out)
            return out

        for r in range(R):
            lanes = slice(r * L, (r + 1) * L)
            # A fresh member starts from zeros, whatever the pool holds.
            s = jnp.where(fresh, 0.0, s_ref[0, r])            # (N, L)
            across = hd_ref[0, r]                             # (8, T)
            down = across.T                                   # (T, 8)
            for j, at in enumerate(blocks):
                x = x_ref[0, at, lanes]                       # (Tb, L)
                y = None
                into, left = [], []
                for k in range(pack):
                    cum = down[at, k:k + 1]                   # (Tb, 1)
                    dt = down[at, pack + k:pack + k + 1]
                    # Row l sees row s <= l through exp(cum_l - cum_s), and
                    # takes dt_s x_s of it.
                    w = jnp.exp(jnp.where(
                        tri, cum - across[k:k + 1, at], -jnp.inf)) \
                        * (cb_ref[j] * across[pack + k:pack + k + 1, at])
                    mine = x if pack == 1 else jnp.where(lane == k, x, 0.0)
                    part = _dot(w, mine)
                    y = part if y is None else y + part
                    into.append(jnp.exp(cum))
                    left.append(jnp.exp(cum[Tb - 1:Tb, :] - cum) * dt)
                # From the state the block started with; the state it
                # leaves.
                seen = over_lanes(into)                       # exp(cum_l)
                y_ref[0, at, lanes] = y + seen * _dot(c_ref[0, at, :], s)
                # exp(cum) of the block's last row, a head over its lanes
                # (a sum over a tile's sublanes: Mosaic has no broadcast
                # of one element over both).
                whole = jnp.sum(jnp.where(bottom, seen[Tb - 8:Tb, :], 0.0),
                                axis=0, keepdims=True)        # (1, L)
                s = whole * s + _dot(bt_ref[j], over_lanes(left) * x)
            o_ref[0, r] = s


def ssm_chunk_scan(
    state: jax.Array,
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    home: jax.Array,
    n_valid: jax.Array,
    fresh: jax.Array,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """A chunk group's rows through one state-space layer, in place: what
    ``models/hybrid.py`` ``ssm_scan`` computes, from and into the pool as it
    lies and from the rows as the projection lays them.

    ``state`` is the pool ``(layers x S, Hp, N, L)`` float32; member ``i``'s
    state is row ``home[i]``. ``x`` ``(b, T, H x P)`` (head ``h`` on lanes
    ``[h P, (h + 1) P)``), ``dt`` ``(b, T, H)`` (0: the row leaves the state
    as it is and adds nothing), ``A`` ``(H,)``, ``B`` / ``C`` ``(b, T, G x
    N)`` (a group's ``N`` side by side), all float32: :func:`ssm_scan`'s
    operands with their last two axes as one, which is how they lie in
    memory (a view of ``(T, H, P)`` with ``P`` under a lane tile is another
    tiling: a copy). A member with ``fresh`` starts from zeros whatever the
    pool holds; one with ``n_valid`` 0 is not visited. Returns the pool
    (the buffer that came in, under a donating ``jit``) and ``y`` ``(b, T,
    H x P)``, zero for a member not visited. The device event is
    ``ssm_chunk_scan``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ssm_scan_call(state, x, dt, A, B, C, home, n_valid, fresh,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "block", "rows"))
def _ssm_scan_call(state, x, dt, A, B, C, home, n_valid, fresh, *,
                   interpret: bool, block: Optional[int] = None,
                   rows: Optional[int] = None):
    M, Hp, N, L = state.shape
    b, T, H = dt.shape
    pack, G = H // max(Hp, 1), B.shape[2] // N
    if H != Hp * pack or x.shape != (b, T, Hp * L) or not 1 <= pack <= 4 \
            or L % pack or B.shape != (b, T, G * N) or C.shape != B.shape \
            or G < 1 or Hp % G \
            or any(t.dtype != jnp.float32 for t in (state, x, dt, B, C)):
        raise ValueError(
            f"ssm_chunk_scan takes a float32 pool (layers x S, Hp, N, L), x "
            f"(b, T, Hp x L), dt (b, T, H) with H = pack x Hp and pack <= "
            f"4, B and C (b, T, G x N) with G dividing Hp; got "
            f"{[(t.shape, t.dtype) for t in (state, x, dt, B, C)]}")
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(kernel=SCAN_KERNEL).inc()
    per = Hp // G
    blk = block or tuning.ssm_scan_block(T)
    R = rows or tuning.ssm_scan_rows(T, per, N, L)
    if per % R:
        raise ValueError(f"{R} rows a step do not divide a group's {per}")
    pad = -T % blk
    if pad:
        # Rows with dt 0: they move no state and their y is dropped.
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                       for t in (x, dt, B, C))
    Tp = T + pad
    # The per-row vectors, formed here: kilobytes. Everything else goes in
    # as the projections lay it.
    cum = jnp.cumsum((dt * A).reshape(b, Tp // blk, blk, H), axis=2)
    hd = jnp.stack([cum.reshape(b, Tp, Hp, pack),
                    dt.reshape(b, Tp, Hp, pack)], axis=3)    # cum, then dt
    hd = jnp.moveaxis(hd.reshape(b, Tp, Hp, 2 * pack), 1, 3)
    hd = jnp.pad(hd, ((0, 0), (0, 0), (0, 8 - 2 * pack), (0, 0)))
    ids, count = live_list(n_valid)
    ids = jnp.asarray(ids, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    nq = Hp // R

    def where(i, q, ids, cnt):
        """The member and the rows of heads of step ``(i, q)``: past the
        list, the last step's again (no block moves, nothing is written
        twice)."""
        dead = i >= cnt[0]
        return (ids[jnp.minimum(i, jnp.maximum(cnt[0] - 1, 0))],
                jnp.where(dead, nq - 1, q))

    def lanes_of(i, q, ids, cnt, home, fresh):
        j, q = where(i, q, ids, cnt)
        return j, 0, q

    def rows_of(i, q, ids, cnt, home, fresh):
        j, q = where(i, q, ids, cnt)
        return j, q, 0, 0

    def group_of(i, q, ids, cnt, home, fresh):
        j, q = where(i, q, ids, cnt)
        return j, 0, q * R // per

    def state_of(i, q, ids, cnt, home, fresh):
        j, q = where(i, q, ids, cnt)
        return home[j], q, 0, 0

    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nq),
        in_specs=[
            pl.BlockSpec((1, Tp, R * L), lanes_of),
            pl.BlockSpec((1, R, 8, Tp), rows_of),
            pl.BlockSpec((1, Tp, N), group_of),
            pl.BlockSpec((1, Tp, N), group_of),
            pl.BlockSpec((1, R, N, L), state_of),
        ],
        out_specs=[
            pl.BlockSpec((1, R, N, L), state_of),
            pl.BlockSpec((1, Tp, R * L), lanes_of),
        ],
        scratch_shapes=[pltpu.VMEM((Tp // blk, N, blk), f32),
                        pltpu.VMEM((Tp // blk, blk, blk), f32)],
    )
    new, y = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, block=blk, pack=pack,
                          steps=per // R),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, Tp, Hp * L), f32)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=tuning.ssm_scan_vmem_limit(Tp, blk, R, N, L),
        ),
        interpret=interpret,
        name=SCAN_KERNEL,
    )(ids, count, jnp.asarray(home, jnp.int32),
      jnp.asarray(fresh, jnp.int32), x, hd, B, C, state)
    return new, jnp.where((n_valid > 0)[:, None, None], y[:, :T], 0.0)


# ---------------------------------------------------------------------------
# A Mamba-1 layer's rows, a decode group's or a chunk group's
# ---------------------------------------------------------------------------


def _ssm1_kernel(
    ids_ref,    # SMEM (b,) scalar-prefetch: the members that have a row
    cnt_ref,    # SMEM (1,) scalar-prefetch: how many
    home_ref,   # SMEM (b,) scalar-prefetch: a member's row of the pool
    fresh_ref,  # SMEM (b,) scalar-prefetch: 1 where its first position is 0
    n_ref,      # SMEM (b,) scalar-prefetch: its valid rows
    x_ref,      # VMEM (1, tb, ct): x after the convolution, these channels
    dt_ref,     # VMEM (1, tb, ct): the step sizes
    a_ref,      # VMEM (N, ct): A, the layer's decays as the pool lies
    b_ref,      # VMEM (1, tb, N, 128): B, a row's N over a lane tile
    c_ref,      # VMEM (1, tb, N, 128): C, likewise
    s_ref,      # VMEM (1, N, ct): the member's state, these channels
    o_ref,      # ... and where it goes back (aliased to the pool)
    y_ref,      # VMEM (1, tb, ct)
):
    """One grid step: ``tb`` rows of one member through ``ct`` channels of
    its state, a row at a time. The state's block is the same for every
    time block of a (channel tile, member), so it stays in fast memory from
    the member's first row to its last; a lane chunk of it rides the row
    loop in registers, ``exp(dt (x) A)`` formed there."""
    i, k = pl.program_id(1), pl.program_id(2)
    cnt = cnt_ref[0]
    tb = x_ref.shape[1]
    N, ct = a_ref.shape
    lanes = 128 if ct % SSM1_LANES else SSM1_LANES

    # No member has a row: the one block every step was pointed at goes
    # back as it came.
    @pl.when(cnt == 0)
    def _():
        o_ref[...] = s_ref[...]

    @pl.when(i < cnt)
    def _():
        j = ids_ref[i]

        @pl.when(k == 0)
        def _():
            # A fresh member starts from zeros, whatever the pool holds.
            o_ref[0] = jnp.where(fresh_ref[j] != 0, 0.0, s_ref[0])

        # A row past the member's valid count is never computed: it leaves
        # the state bit for bit.
        rows = jnp.clip(n_ref[j] - k * tb, 0, tb)
        for c0 in range(0, ct, lanes):
            at = slice(c0, c0 + lanes)
            A = a_ref[:, at]

            def row(r, s, at=at, A=A):
                dt = dt_ref[0, pl.ds(r, 1), at]               # (1, lanes)
                dx = dt * x_ref[0, pl.ds(r, 1), at]
                b = jnp.tile(b_ref[0, r], (1, lanes // 128))  # (N, lanes)
                c = jnp.tile(c_ref[0, r], (1, lanes // 128))
                s = jnp.exp(dt * A) * s + b * dx
                y_ref[0, pl.ds(r, 1), at] = jnp.sum(
                    s * c, axis=0, keepdims=True)
                return s

            o_ref[0, :, at] = lax.fori_loop(0, rows, row, o_ref[0, :, at])


def ssm1_scan(
    state: jax.Array,
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    home: jax.Array,
    n_valid: jax.Array,
    fresh: jax.Array,
    *,
    live: Optional[Tuple[jax.Array, jax.Array]] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """A group's rows through one Mamba-1 layer, in place: what
    ``models/hybrid.py`` ``ssm1_rows`` computes, from and into the pool as
    it lies, at any rows a member (1: a decode group; a chunk).

    A Mamba-1 state decays by a number of its own for every (channel,
    state) pair, ``S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + B_t[n]
    dt_t[c] x_t[c]``, so the chunked matrix form of ``ssm_chunk_scan`` does
    not exist for it (a block's decays would be a ``(T, T)`` matrix for
    every pair), an associative scan materialises ``(T, N, channels)``
    float32 pairs several times a layer, and a ``lax.scan`` is ``T``
    launches' worth of loop overhead. Here the rows pass one by one over a
    state tile that stays in fast memory.

    ``state`` is the pool ``(layers x S, N, channels)`` float32
    (``Mamba1.state_shape``); member ``i``'s state is row ``home[i]``.
    ``x`` / ``dt`` ``(b, T, channels)``, ``A`` ``(N, channels)``, ``B`` /
    ``C`` ``(b, T, N)``, all float32. A member with ``fresh`` starts from
    zeros whatever the pool holds; its rows past ``n_valid`` are not
    computed; one with ``n_valid`` 0 is not visited. ``live``:
    :func:`live_list` of ``n_valid``, where the caller built it once for
    every layer. Returns the pool (the buffer that came in, under a
    donating ``jit``) and ``y = C . S`` ``(b, T, channels)``, zero for a row
    not computed. The device event is ``ssm1_scan``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ids, count = live if live is not None else live_list(n_valid)
    return _ssm1_call(state, x, dt, A, B, C, home, n_valid, fresh, ids,
                      jnp.reshape(count, (1,)), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm1_call(state, x, dt, A, B, C, home, n_valid, fresh, ids, count, *,
               interpret: bool):
    M, N, Ch = state.shape
    b, T, _ = x.shape
    if x.shape != (b, T, Ch) or dt.shape != x.shape or A.shape != (N, Ch) \
            or B.shape != (b, T, N) or C.shape != B.shape or N % 8 \
            or Ch % 128 \
            or any(t.dtype != jnp.float32 for t in (state, x, dt, A, B, C)):
        raise ValueError(
            f"ssm1_scan takes a float32 pool (layers x S, N, channels), x "
            f"and dt (b, T, channels), A (N, channels), B and C (b, T, N), "
            f"N whole sublane tiles and the channels whole lane tiles; "
            f"got {[(t.shape, t.dtype) for t in (state, x, dt, A, B, C)]}")
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(kernel=SSM1_KERNEL).inc()
    tb = tuning.ssm1_time_block(T)
    ct = tuning.ssm1_channel_tile(tb, N, Ch) if Ch % SSM1_LANES == 0 else Ch
    pad = -T % tb
    if pad:
        # Rows past every member's count: never computed.
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                       for t in (x, dt, B, C))
    nk, nq = (T + pad) // tb, Ch // ct
    # B and C as columns: a row's N numbers over a lane tile each, so that
    # the body multiplies a (N, lanes) state by them with no transposed
    # operand (kilobytes a row).
    wide = tuple(jnp.broadcast_to(t[..., None], t.shape + (128,))
                 for t in (B, C))

    def where(i, k, ids, cnt):
        """The member and time block of step ``(q, i, k)``: past the list,
        the last step's again (no block moves, nothing is written
        twice)."""
        dead = i >= cnt[0]
        return (ids[jnp.minimum(i, jnp.maximum(cnt[0] - 1, 0))],
                jnp.where(dead, nk - 1, k))

    def rows_of(q, i, k, ids, cnt, home, fresh, n):
        j, k = where(i, k, ids, cnt)
        return j, k, q

    def cols_of(q, i, k, ids, cnt, home, fresh, n):
        j, k = where(i, k, ids, cnt)
        return j, k, 0, 0

    def state_of(q, i, k, ids, cnt, home, fresh, n):
        j, _ = where(i, k, ids, cnt)
        return home[j], 0, q

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nq, b, nk),
        in_specs=[
            pl.BlockSpec((1, tb, ct), rows_of),
            pl.BlockSpec((1, tb, ct), rows_of),
            pl.BlockSpec((N, ct), lambda q, i, k, *_: (0, q)),
            pl.BlockSpec((1, tb, N, 128), cols_of),
            pl.BlockSpec((1, tb, N, 128), cols_of),
            pl.BlockSpec((1, N, ct), state_of),
        ],
        out_specs=[
            pl.BlockSpec((1, N, ct), state_of),
            pl.BlockSpec((1, tb, ct), rows_of),
        ],
    )
    new, y = pl.pallas_call(
        _ssm1_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, T + pad, Ch), jnp.float32)],
        input_output_aliases={10: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=tuning.ssm1_vmem_limit(tb, N, ct),
        ),
        interpret=interpret,
        name=SSM1_KERNEL,
    )(jnp.asarray(ids, jnp.int32), jnp.asarray(count, jnp.int32),
      jnp.asarray(home, jnp.int32), jnp.asarray(fresh, jnp.int32),
      jnp.asarray(n_valid, jnp.int32), x, dt, A, *wide, state)
    seen = jnp.arange(T, dtype=jnp.int32)[None, :, None] \
        < n_valid[:, None, None]
    return new, jnp.where(seen, y[:, :T], 0.0)
