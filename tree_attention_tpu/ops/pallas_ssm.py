"""A state-space layer's decode step over the slots that have a row, in
place (Pallas TPU).

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

Off the TPU the layer body takes the same step in ``jax.numpy``
(``models/hybrid.py``); the tests hold this kernel to it in interpret mode.
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

_KERNEL_BUILDS = obs.counter(
    "pallas_ssm_kernel_builds_total",
    "state-space decode-update kernel program builds (one per distinct "
    "shape)",
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
