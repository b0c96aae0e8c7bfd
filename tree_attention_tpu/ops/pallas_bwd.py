"""Pallas TPU flash-attention backward kernels.

Two kernels, the standard split (SURVEY.md §7 hard part 1):

- **dQ kernel** — grid ``(B·Hq, Tq/bq, Tk/bk)``: for one Q tile, stream KV
  tiles, accumulate ``dq += ds·K·scale`` in VMEM scratch.
- **dKV kernel** — grid ``(B·Hkv, Tk/bk, G·Tq/bq)``: for one KV tile, stream
  every query head of the group and every Q tile, accumulate
  ``dk += dsᵀ·Q·scale`` and ``dv += pᵀ·dO`` in scratch. GQA reduction over
  the group happens in-register — KV gradients never materialise per
  query head.

Both recompute ``p = exp(q·kᵀ·scale − lse)`` from the saved lse (no stored
probabilities), and consume a host-precomputed
``delta = rowsum(dO ⊙ O) − dlse`` — the lse-cotangent folding described in
:mod:`tree_attention_tpu.ops.vjp`. The two per-row f32 residuals ride ONE
128-lane tensor (lse in lane 0, delta in lane ``DELTA_LANE``): the dKV
kernel's Q-side blocks change every grid step, making residual reads its
dominant un-elidable HBM stream, and packing halves them. Padded query rows
are neutralised by padding lse with ``+inf`` (making ``p`` exactly 0 there);
padded key columns by the in-kernel range mask. Causally dead tiles skip
all compute via ``pl.when``, and with static offsets their DMAs are culled
at the grid level (see ``block_utils.culled_ki``/``culled_qi``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tree_attention_tpu.ops.block_utils import (
    culled_ki,
    culled_qi,
    mask_scores,
    pad_to_block,
    static_offsets,
    tile_live,
)

from tree_attention_tpu.ops.block_utils import (
    LANES as _LANES,
    matmul_precision,
)


DELTA_LANE = 64  # lane carrying delta in the packed residual (lse rides 0)


def _recompute_p_ds(q, k, v, dout, lse, delta, *, scale, causal,
                    qi, ki, block_q, block_k, q_offset, kv_offset, tk):
    """p and ds for one (Q-tile, KV-tile) pair, f32 results.

    Matmul operands stay in their storage dtype (bf16 rides the MXU fast
    path; a prior f32 upcast quarters throughput) and accumulate in f32.
    """
    s = lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=matmul_precision(q.dtype, k.dtype),
    ) * scale
    # Ragged-tail + causal masking (broadcast-form; the backward pays the
    # mask in BOTH kernels per tile pair, so its cost matters double here).
    s = mask_scores(s, qi, ki, block_q, block_k, q_offset, kv_offset, tk,
                    causal)
    # lse is padded with +inf on padded rows -> p == 0 there; masked cols give
    # exp(-inf - lse) == 0.
    p = jnp.exp(s - lse)
    dp = lax.dot_general(
        dout, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=matmul_precision(dout.dtype, v.dtype),
    )
    ds = p * (dp - delta)
    return p, ds


def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, res_ref,
               dq_ref, dq_scr, *, scale, causal, tk, block_q, block_k,
               n_heads):
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)
    b = pl.program_id(0) // n_heads  # grid dim 0 runs over B*Hq
    q_offset, kv_offset = offs_ref[0, b], offs_ref[1, b]

    @pl.when(ki == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(tile_live(qi, ki, block_q, block_k, q_offset, kv_offset, causal))
    def _():
        _, ds = _recompute_p_ds(
            q_ref[0], k_ref[0], v_ref[0],
            do_ref[0], res_ref[0][:, :1],
            res_ref[0][:, DELTA_LANE:DELTA_LANE + 1],
            scale=scale, causal=causal, qi=qi, ki=ki,
            block_q=block_q, block_k=block_k,
            q_offset=q_offset, kv_offset=kv_offset, tk=tk,
        )
        dq_scr[...] += lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(k_ref.dtype, k_ref.dtype),
        ) * scale

    @pl.when(ki == n_k - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, res_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, tk, block_q, block_k, n_q, n_heads):
    ki, gq = pl.program_id(1), pl.program_id(2)
    n_gq = pl.num_programs(2)
    b = pl.program_id(0) // n_heads  # grid dim 0 runs over B*Hkv
    q_offset, kv_offset = offs_ref[0, b], offs_ref[1, b]

    @pl.when(gq == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # gq enumerates (g, qi) pairs — same decoding as the BlockSpec index maps.
    qi = gq % n_q

    @pl.when(tile_live(qi, ki, block_q, block_k, q_offset, kv_offset, causal))
    def _():
        p, ds = _recompute_p_ds(
            q_ref[0], k_ref[0], v_ref[0],
            do_ref[0], res_ref[0][:, :1],
            res_ref[0][:, DELTA_LANE:DELTA_LANE + 1],
            scale=scale, causal=causal, qi=qi, ki=ki,
            block_q=block_q, block_k=block_k,
            q_offset=q_offset, kv_offset=kv_offset, tk=tk,
        )
        dk_scr[...] += lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(q_ref.dtype, q_ref.dtype),
        ) * scale
        dv_scr[...] += lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision(do_ref.dtype, do_ref.dtype),
        )

    @pl.when(gq == n_gq - 1)
    def _():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def attention_bwd_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    dout: jax.Array,
    dlse: jax.Array,
    *,
    causal: bool,
    scale: Optional[float],
    q_offset=0,
    kv_offset=0,
    block_size: int = 512,
    block_q: int = 256,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas backward: same contract as ``attention_bwd_blockwise``.

    Static integer offsets under ``causal`` enable grid-level culling (see
    ``attention_pallas_fwd``): the dQ kernel repeats the last live KV block
    past the diagonal, the dKV kernel repeats the first live Q block before
    it, and the elided DMAs remove the dead half of the causal HBM traffic.
    """
    cull = (
        (int(q_offset), int(kv_offset))
        if causal and static_offsets(q_offset, kv_offset)
        else None
    )
    return _attention_bwd_pallas(
        q, k, v, out, lse, dout, dlse, causal=causal, scale=scale,
        q_offset=q_offset, kv_offset=kv_offset, block_size=block_size,
        block_q=block_q, interpret=interpret, cull=cull,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_size", "block_q", "interpret", "cull"
    ),
)
def _attention_bwd_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    dout: jax.Array,
    dlse: jax.Array,
    *,
    causal: bool,
    scale: Optional[float],
    q_offset,
    kv_offset,
    block_size: int,
    block_q: int,
    interpret: Optional[bool],
    cull: Optional[Tuple[int, int]],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    s = (D ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if Tk == 0:
        return jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)

    bq = min(block_q, max(Tq, 8))
    bk = min(block_size, max(Tk, _LANES))

    qp = pad_to_block(q.reshape(B * Hq, Tq, D), 1, bq)
    dop = pad_to_block(dout.reshape(B * Hq, Tq, D), 1, bq)
    kp = pad_to_block(k.reshape(B * Hkv, Tk, D), 1, bk)
    vp = pad_to_block(v.reshape(B * Hkv, Tk, D), 1, bk)
    tq_pad, tk_pad = qp.shape[1], kp.shape[1]
    n_q, n_k = tq_pad // bq, tk_pad // bk

    # delta with the lse cotangent folded in; +inf-pad lse so padded rows
    # recompute p == 0 (see module docstring).
    delta = (
        jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
        - dlse.astype(jnp.float32)
    ).reshape(B * Hq, Tq)
    pad_rows = tq_pad - Tq
    # Rows with no visible keys carry lse == -inf; the in-kernel recompute
    # would hit exp(-inf - (-inf)) == nan wherever the causal boundary
    # straddles a tile. Mapping them to +inf makes p exactly 0 for the whole
    # row — the correct vanishing gradient — same neutralisation as the
    # padded rows below.
    lse_f = jnp.where(jnp.isneginf(lse), jnp.inf, lse).reshape(B * Hq, Tq)
    if pad_rows:
        lse_f = jnp.pad(lse_f, ((0, 0), (0, pad_rows)), constant_values=jnp.inf)
        delta = jnp.pad(delta, ((0, 0), (0, pad_rows)))
    # Per-row scalars must ride a 128-lane axis (TPU tiling rejects (1, bq)
    # blocks of a 2-D (B*Hq, tq_pad) array: sublane dim 1 is neither
    # 8-aligned nor full). Rather than broadcasting lse and delta into two
    # full 128-lane tensors, both pack into ONE: lse in lane 0, delta in
    # lane DELTA_LANE. Residual HBM traffic is the dominant stream of the
    # dKV kernel (its Q-side blocks change every grid step, so nothing is
    # elided), and the f32 residuals outweigh the bf16 Q/dO tiles — packing
    # halves that cost.
    res_b = jnp.zeros((B * Hq, tq_pad, _LANES), jnp.float32)
    res_b = res_b.at[..., 0].set(lse_f).at[..., DELTA_LANE].set(delta)

    from tree_attention_tpu.ops.block_utils import offsets_smem

    # (2, B) per-batch offset columns — same ragged contract as the fwd
    # kernels (scalars broadcast; the kernels index their own batch row).
    offs = offsets_smem(q_offset, kv_offset, B)

    def kv_from_qrow(bh, *_rest):
        return bh // Hq * Hkv + (bh % Hq) // G

    def ki_live(qi, ki):
        return culled_ki(qi, ki, cull, bq, bk, n_k)

    # ---- dQ ----
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=s, causal=causal, tk=Tk, block_q=bq, block_k=bk,
            n_heads=Hq,
        ),
        grid=(B * Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (kv_from_qrow(bh), ki_live(qi, ki), 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (kv_from_qrow(bh), ki_live(qi, ki), 0)),
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, tq_pad, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        # dq accumulates across the (sequential) KV dim; the rest are
        # independent — see the fwd kernel's note on megacore splitting.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(offs, qp, kp, vp, dop, res_b)

    # ---- dK, dV ----
    def q_from_kvrow(bkh, ki, gq):
        b, hkv = bkh // Hkv, bkh % Hkv
        g = gq // n_q
        return b * Hq + hkv * G + g

    def qi_live(ki, gq):
        return culled_qi(ki, gq % n_q, cull, bq, bk, n_q)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=s, causal=causal, tk=Tk, block_q=bq,
            block_k=bk, n_q=n_q, n_heads=Hkv,
        ),
        grid=(B * Hkv, n_k, G * n_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, D), lambda bkh, ki, gq: (q_from_kvrow(bkh, ki, gq), qi_live(ki, gq), 0)),
            pl.BlockSpec((1, bk, D), lambda bkh, ki, gq: (bkh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bkh, ki, gq: (bkh, ki, 0)),
            pl.BlockSpec((1, bq, D), lambda bkh, ki, gq: (q_from_kvrow(bkh, ki, gq), qi_live(ki, gq), 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bkh, ki, gq: (q_from_kvrow(bkh, ki, gq), qi_live(ki, gq), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bkh, ki, gq: (bkh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bkh, ki, gq: (bkh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, tk_pad, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, tk_pad, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        # dk/dv accumulate across the (sequential) grouped-Q dim.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(offs, qp, kp, vp, dop, res_b)

    return (
        dq[:, :Tq].reshape(B, Hq, Tq, D),
        dk[:, :Tk].reshape(B, Hkv, Tk, D),
        dv[:, :Tk].reshape(B, Hkv, Tk, D),
    )
