"""Grouped matrix product over rows sorted by expert (Pallas TPU).

An expert layer multiplies each routed row by the weights of ITS expert. With
the rows sorted by expert the product is a run of ordinary matmuls, one per
expert, over consecutive row ranges: ``out[start_g:end_g] = lhs[start_g:end_g]
@ rhs[g]``. The kernel walks a work list of (expert, row tile) entries built
from the per-expert row counts: an expert with no rows has no entry, so its
weights are never read, and rows past the last expert's range (pairs whose
expert another chip holds) are never computed. The grid's middle dimension is
the DYNAMIC length of that list.

The blocks a launch moves (the row tile, how much of the contraction a grid
step takes, the column strips' width, whether the rows' tile holds the whole
contraction) are a :class:`~tree_attention_tpu.ops.tuning.GroupedPlan`,
chosen from the operands' shapes by ``ops/tuning.py`` ``grouped_plan`` (a
measured table; a shape it has no row for takes the two constants the kernel
was written with). With the whole contraction a step (``tk == k``) the body
carries nothing between steps, and an expert whose rows straddle a row tile
(two entries) is read once: its second entry asks for the block the first
left resident.

Two products share the kernel body: the gate/up pair with the SwiGLU fused
(``silu(x @ w1[g]) * (x @ w3[g])``: the rows are read once for both), and the
down projection. Both are named ``moe_grouped_matmul`` in the compiled module
and the profiler trace. An UNGATED expert layer (one matrix in, relu squared,
one matrix out: no gate) takes the same body with the activation on its one
accumulator, and both its products carry a name of their own,
``moe_ungated_matmul`` (:data:`UNGATED_KERNEL`): the benchmark's readers
match kernels by substring and count a gated layer's three matrices an
expert under the other name.

Off the TPU :func:`grouped_matmul` is ``lax.ragged_dot`` (the CPU reference
path the tests hold the kernel against in interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tree_attention_tpu import obs
from tree_attention_tpu.ops.tuning import GroupedPlan, grouped_plan

_KERNEL_BUILDS = obs.counter(
    "pallas_moe_kernel_builds_total",
    "grouped-matmul kernel program builds (one per distinct shape/config), "
    "by the block plan each took (ops/tuning.py grouped_plan)",
    labels=("kernel", "plan"),
)
_KERNEL_DISPATCH = obs.counter(
    "pallas_moe_dispatch_total",
    "grouped-product dispatches by path, counted per program build",
    labels=("path",),
)


GATED_KERNEL, UNGATED_KERNEL = "moe_grouped_matmul", "moe_ungated_matmul"


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jnp.maximum(x, 0.0))


def tile_plan(group_sizes: jax.Array, m: int, tm: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The work list for ``m`` rows in tiles of ``tm``: ``(offsets (G+1,),
    group of entry e, row tile of entry e, number of entries)``. Group ``g``
    owns rows ``[offsets[g], offsets[g+1])``; it gets one entry per row tile
    its range touches, none if it is empty. At most ``m // tm + G - 1``
    entries exist (each group boundary inside a tile adds one)."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first, 0)
    cum = jnp.cumsum(tiles)
    e = jnp.arange(m // tm + G - 1, dtype=jnp.int32)
    gid = jnp.minimum(
        jnp.searchsorted(cum, e, side="right").astype(jnp.int32), G - 1
    )
    m_tile = jnp.clip(first[gid] + e - (cum[gid] - tiles[gid]),
                      0, m // tm - 1)
    return offsets, gid, m_tile, cum[-1]


def _grouped_kernel(offs_ref, gid_ref, mt_ref, first_ref, lhs_ref, *refs,
                    n_rhs: int, plan: GroupedPlan, tiles_k: int,
                    relu2: bool = False):
    del first_ref  # the index maps' (where the groups start in the stack)
    tm, tk, tn = plan.tm, plan.tk, plan.tn
    rhs_refs = refs[:n_rhs]
    out_ref = refs[n_rhs]
    accs = refs[n_rhs + 1:]
    e = pl.program_id(1)
    k_i = pl.program_id(2)

    def products():
        if plan.rows_whole:  # the tile holds every k: this step's part
            x = lhs_ref[:, pl.ds(pl.multiple_of(k_i * tk, tk), tk)]
        else:
            x = lhs_ref[...]
        return [lax.dot_general(x, w[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
                for w in rhs_refs]

    def store(vals):
        g = gid_ref[e]
        rows = mt_ref[e] * tm + lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])
        val = vals[0]
        if n_rhs == 2:  # the gate/up pair: SwiGLU on the accumulators
            val = jax.nn.silu(val) * vals[1]
        elif relu2:     # an ungated expert's one matrix in
            val = _relu2(val)
        # Another expert's rows of this tile were stored by its own entry
        # (the output block stays resident while the row tile repeats).
        out_ref[...] = jnp.where(
            mine, val, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    if tiles_k == 1:  # one step an entry: nothing to carry between steps
        store(products())
        return

    @pl.when(k_i == 0)
    def _zero():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    for acc, part in zip(accs, products()):
        acc[...] += part

    @pl.when(k_i == tiles_k - 1)
    def _store():
        store([acc[...] for acc in accs])


def _grouped_pallas(lhs: jax.Array, rhs: Sequence[jax.Array],
                    group_sizes: jax.Array, first_group: jax.Array, *,
                    plan: GroupedPlan, interpret: bool, relu2: bool = False,
                    name: str = GATED_KERNEL) -> jax.Array:
    m, k = lhs.shape
    n = rhs[0].shape[2]
    tm, tk, tn = plan.tm, plan.tk, plan.tn
    if m % tm or k % tk or n % tn:
        raise ValueError(
            f"grouped_matmul: {plan} does not divide ({m}, {k}) x ({k}, {n})")
    tiles_k = k // tk
    offsets, gid, m_tile, n_entries = tile_plan(group_sizes, m, tm)
    offsets = jnp.asarray(offsets, jnp.int32)
    gid = jnp.asarray(gid, jnp.int32)
    m_tile = jnp.asarray(m_tile, jnp.int32)
    first_group = jnp.asarray(first_group, jnp.int32)
    if obs.REGISTRY.enabled:
        _KERNEL_BUILDS.labels(
            kernel="moe_up" if len(rhs) == 2 else "moe_ungated_up" if relu2
            else "moe_down", plan=plan.label).inc()
    if plan.rows_whole:
        rows = pl.BlockSpec((tm, k), lambda ni, e, ki, o, g, t, f: (t[e], 0))
    else:
        rows = pl.BlockSpec((tm, tk),
                            lambda ni, e, ki, o, g, t, f: (t[e], ki))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, jnp.maximum(n_entries, 1), tiles_k),
        in_specs=[rows] + [
            pl.BlockSpec((None, tk, tn),
                         lambda ni, e, ki, o, g, t, f: (f[0] + g[e], ki, ni))
            for _ in rhs
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda ni, e, ki, o, g, t, f: (t[e], ni)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)
                        for _ in rhs if tiles_k > 1],
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, n_rhs=len(rhs), plan=plan,
                          tiles_k=tiles_k, relu2=relu2),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes(
                k, len(rhs), lhs.dtype.itemsize),
        ),
        interpret=interpret,
        name=name,
    )(offsets, gid, m_tile, first_group, lhs, *rhs)


def grouped_matmul(lhs: jax.Array, rhs: Sequence[jax.Array],
                   group_sizes: jax.Array, *, first_group=0,
                   interpret: Optional[bool] = None, relu2: bool = False,
                   name: str = GATED_KERNEL,
                   plan: Optional[GroupedPlan] = None) -> jax.Array:
    """``lhs`` ``(m, k)`` rows sorted by group; ``rhs`` one ``(S, k, n)``
    stack (the product; with ``relu2`` an ungated expert's matrix in:
    ``relu(x@a)^2``) or two (gate and up: ``silu(x@a) * (x@b)``); ``name``
    the kernel's, a gated layer's or :data:`UNGATED_KERNEL`;
    ``group_sizes`` ``(G,)`` int32 rows a group, in order from row 0; group
    ``g``'s matrix is ``rhs[first_group + g]`` (``first_group`` may be
    traced: every layer's experts in one stack, a layer's reached by
    offset, so that a layer loop never slices the stack). Rows past
    ``sum(group_sizes)`` belong to no group: the kernel leaves them
    unwritten (whatever the buffer held) and the reference path zero, so
    the caller masks them. ``plan`` (default: ``ops/tuning.py``
    ``grouped_plan`` of the operands' shapes) is the kernel's blocks, and
    ``m`` must divide by its row tile (``tuning.row_tile(m)`` rows always
    do); an explicit one wins unchanged (the sweep and the tests measure
    what they label)."""
    from tree_attention_tpu.ops import _on_tpu, _pallas_available

    m = lhs.shape[0]
    first = jnp.asarray(first_group, jnp.int32)
    on_tpu = _on_tpu(lhs) and _pallas_available()
    if interpret is None and not on_tpu:
        if obs.REGISTRY.enabled:
            _KERNEL_DISPATCH.labels(path="ragged_dot").inc()
        G = group_sizes.shape[0]
        outs = [lax.ragged_dot(lhs, lax.dynamic_slice_in_dim(w, first, G),
                               group_sizes.astype(jnp.int32),
                               preferred_element_type=jnp.float32)
                for w in rhs]
        if len(rhs) == 2:
            val = jax.nn.silu(outs[0]) * outs[1]
        else:
            val = _relu2(outs[0]) if relu2 else outs[0]
        return val.astype(lhs.dtype)
    if obs.REGISTRY.enabled:
        _KERNEL_DISPATCH.labels(path=name).inc()
    if plan is None:
        plan = grouped_plan(m, lhs.shape[1], rhs[0].shape[2], len(rhs))
    return _grouped_pallas(lhs, list(rhs), group_sizes.astype(jnp.int32),
                           first.reshape(1), plan=plan,
                           interpret=bool(interpret), relu2=relu2, name=name)
