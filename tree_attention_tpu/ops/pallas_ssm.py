"""A state-space layer's decode step over the slots that have a row, in
place (Pallas TPU).

A state-space (Mamba-2) layer holds, for every slot, a state ``S`` of ``heads
x d_head x d_state`` float32 that EVERY token rewrites whole::

    S <- a * S + (dt * x) (x) B        y = S . C

(``a = exp(dt * A)`` a head, ``B`` and ``C`` rows of ``d_state`` shared by a
group of heads). In a decode tick that is the whole pool read and written
once, and nothing else of the layer comes near it in bytes, so the one thing
this kernel is for is that the pool is never copied: it is aliased to its
output (``input_output_aliases``), a grid step takes ONE live slot's state of
the layer through VMEM and puts it back where it came from, and a slot with
no row is not visited at all (the list of live slots rides scalar prefetch
and its count is the grid's dynamic bound, as ``pallas_decode.PagedPlan``'s
is).

The pool lays ``pack`` heads side by side on a row's 128 lanes
(``StateSpace.state_shape``: ``(heads / pack, d_state, pack x d_head)``), so
that ``dt * x``, ``a`` and ``y`` are rows of lanes as they come out of the
projections, ``B`` and ``C`` columns, the update three multiplies and an add
a vector register, and ``y`` a sum over registers with one cross-sublane
reduce a row of heads: no transposed operand and no cross-lane reduce a
head.

Off the TPU the layer body takes the same step in ``jax.numpy``
(``models/hybrid.py``); the tests hold this kernel to it in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tree_attention_tpu import obs

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
    the count is slot 0 and never visited (but by an empty list's one
    padding step, which puts slot 0's state back as it was). No sort: a
    running count places each live slot."""
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
    x_ref,     # VMEM (1, Hp, L): dt * x, a row of `pack` heads a sublane
    a_ref,     # VMEM (1, Hp, L): exp(dt * A), each head's over its lanes
    bt_ref,    # VMEM (1, N, G): B, a column a group
    ct_ref,    # VMEM (1, N, G): C, likewise
    s_ref,     # VMEM (1, Hp, N, L): the slot's state in this layer
    o_ref,     # ... and where it goes back (aliased to the pool)
    y_ref,     # VMEM (1, Hp, L): S . C
    *,
    groups: int,
    rows: int,
):
    live = pl.program_id(0) < cnt_ref[0]

    @pl.when(live)
    def _step():
        for g in range(groups):
            b = bt_ref[0, :, g:g + 1]                   # (N, 1)
            c = ct_ref[0, :, g:g + 1]
            for r in range(g * rows, (g + 1) * rows):
                new = a_ref[0, r:r + 1, :] * s_ref[0, r] \
                    + b * x_ref[0, r:r + 1, :]          # (N, L)
                o_ref[0, r] = new
                y_ref[0, r:r + 1, :] = jnp.sum(new * c, axis=0,
                                               keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _empty_list():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_decode_update(
    state: jax.Array,
    x: jax.Array,
    a: jax.Array,
    b_t: jax.Array,
    c_t: jax.Array,
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
    float32, ``b_t`` / ``c_t`` ``(S, N, G)`` float32 with ``Hp / G`` rows a
    group. ``ids`` / ``count``: :func:`live_list`. Returns the pool (the
    buffer that came in, under a donating ``jit``) and ``y = S . C`` ``(S,
    Hp, L)``, unwritten for a slot not in the list: the caller masks it.
    The device event is ``ssm_decode_update``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ssm_update_call(
        state, x, a, b_t, c_t, ids, jnp.reshape(count, (1,)),
        jnp.reshape(base, (1,)), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_update_call(state, x, a, b_t, c_t, ids, count, base, *,
                     interpret: bool):
    M, Hp, N, L = state.shape
    S, _, G = b_t.shape
    if x.shape != (S, Hp, L) or a.shape != x.shape or c_t.shape != b_t.shape \
            or b_t.shape[1] != N or Hp % G or M % S \
            or any(t.dtype != jnp.float32 for t in (state, x, a, b_t, c_t)):
        raise ValueError(
            f"ssm_decode_update takes a float32 pool (layers x S, Hp, N, L), "
            f"x and a (S, Hp, L), b_t and c_t (S, N, G) with G dividing Hp; "
            f"got {[(t.shape, t.dtype) for t in (state, x, a, b_t, c_t)]}")
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(kernel=SSM_KERNEL).inc()
    ids = jnp.asarray(ids, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    row = lambda e, ids, cnt, base: (ids[e], 0, 0)            # noqa: E731
    home = lambda e, ids, cnt, base: (base[0] + ids[e], 0, 0, 0)  # noqa: E731
    block = Hp * N * L * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(jnp.maximum(count[0], 1),),
        in_specs=[
            pl.BlockSpec((1, Hp, L), row), pl.BlockSpec((1, Hp, L), row),
            pl.BlockSpec((1, N, G), row), pl.BlockSpec((1, N, G), row),
            pl.BlockSpec((1, Hp, N, L), home),
        ],
        out_specs=[pl.BlockSpec((1, Hp, N, L), home),
                   pl.BlockSpec((1, Hp, L), row)],
    )
    return pl.pallas_call(
        functools.partial(_ssm_update_kernel, groups=G, rows=Hp // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(x.shape, x.dtype)],
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # A slot's state of a layer in and out, each double-buffered.
            vmem_limit_bytes=int(4 * block + (16 << 20)),
        ),
        interpret=interpret,
        name=SSM_KERNEL,
    )(ids, count, base, x, a, b_t, c_t, state)
