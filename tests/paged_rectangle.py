"""The paged decode kernels' RECTANGULAR grid, kept for the tests alone.

Until ISSUE 37 ``flash_decode_paged`` / ``flash_decode_paged_q8q`` /
``mla_decode_paged`` launched ``(slots x head groups, Q tiles, NB / entries)``
grid steps, every slot walking every step of its table, a step past the
slot's length culled in the body. ``ops/pallas_decode.py`` now walks a list
of the live steps; this is the old call, copied from the parent commit with
its comments cut, so that a test can hold both to the same bits. It shares
the mask, the fold and the finalize with the kernels under test (they did
not change) and nothing else.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tree_attention_tpu.ops.block_utils import (
    LANES as _LANES,
    NEG_INF,
    matmul_precision,
    offsets_smem as _offsets_smem,
    pad_to_block as _pad_dim,
)
from tree_attention_tpu.ops.pallas_decode import (
    _SCALE_ROWS,
    _block_scale_rows,
    _decode_finalize,
    _decode_softmax_fold,
    _decode_visibility_mask,
)


def _paged_decode_step(
    offs_ref,  # SMEM (2, B) scalar-prefetch: per-batch [q_offset|kv_offset]
    tbl_ref,   # SMEM (B, NB) scalar-prefetch block table — read by the
               # K/V index maps (PagedAttention, arXiv:2309.06180); the
               # body reads it only for a signed table's ownership
    refs,      # lead (q_ref, or q_ref and qs_ref), [tb_ref when tree],
               # k_ref x entries, v_ref x entries, [ks_ref, vs_ref when
               # block_scales], out_ref, lse_ref, m_scr, l_scr, acc_scr:
               #   q_ref   VMEM (1, heads, bq, D) — each head's packed
               #           (group x Tq) queries
               #   qs_ref  VMEM (1, heads, bq, LANES) f32 — per-row Q scales
               #   tb_ref  VMEM (1, heads, bq, LANES) int32 — tree bitmasks
               #   k/v_ref VMEM (1, heads, block, D) — every head of pool
               #           block tbl[b, si * entries + j], one operand an
               #           entry (the same pool through its own index map)
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
    block_q: int,
    block: int,
    entries: int,
    head_groups: int,
    tree: bool,
    block_scales: bool,
    local_blocks: bool,
):
    refs = list(refs)
    lead, refs = refs[:n_lead], refs[n_lead:]
    tb_ref = refs.pop(0) if tree else None
    k_refs, v_refs, refs = refs[:entries], refs[entries:2 * entries], \
        refs[2 * entries:]
    ks_ref, vs_ref = (refs.pop(0), refs.pop(0)) if block_scales \
        else (None, None)
    out_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    si = pl.program_id(2)
    n_s = pl.num_programs(2)
    bq, bk = block_q, entries * block
    tk = n_s * bk  # logical capacity; step-divisible by construction

    b = pl.program_id(0) // head_groups
    q_offset = offs_ref[0, b]
    kv_offset = offs_ref[1, b]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = si * bk < tk
    if causal:
        live &= (kv_offset + si * bk) <= (q_offset + tq - 1)
    if local_blocks:
        owner = [tbl_ref[b, si * entries + j] for j in range(entries)]
        live &= functools.reduce(jnp.maximum, owner) >= 0

    def by_entry(values):
        col = lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        row = jnp.broadcast_to(values[0], jnp.broadcast_shapes(
            jnp.shape(values[0]), (1, bk)))
        for j in range(1, entries):
            row = jnp.where(col >= j * block, values[j], row)
        return row

    def block_scale(ref):
        first = (si * entries) % _SCALE_ROWS
        rows = []
        for j in range(entries):
            row = ref[0, :, pl.ds(first + j, 1), :]  # (heads, 1, LANES)
            if bk > _LANES:
                row = jnp.concatenate([row] * -(-bk // _LANES), axis=2)
            rows.append(row[:, :, :bk])
        return by_entry(rows)

    def tile(entry_refs):
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
        )
        if local_blocks and entries > 1:
            # A remote entry inside a live step: its columns are masked
            # (its DMA brought pool row 0, any finite rows).
            s = jnp.where(by_entry(owner) >= 0, s, NEG_INF)
        _decode_softmax_fold(
            s, tile(v_refs), m_scr, l_scr, acc_scr, si=si, bk=bk, tk=tk,
            v_scale=None if vs_ref is None else block_scale(vs_ref),
        )

    @pl.when(si == n_s - 1)
    def _finalize():
        _decode_finalize(out_ref, lse_ref, m_scr, l_scr, acc_scr)


def _flash_decode_paged_kernel(offs_ref, tbl_ref, *refs, scale: float,
                               **step):

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

    _paged_decode_step(offs_ref, tbl_ref, refs, scores, n_lead=1, **step)


def _flash_decode_paged_q8q_kernel(offs_ref, tbl_ref, *refs, **step):

    def scores(q_ref, qs_ref, k_tile):
        s_i = lax.dot_general(
            q_ref[0],
            k_tile,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )
        return s_i.astype(jnp.float32) * qs_ref[0][..., :1]

    _paged_decode_step(offs_ref, tbl_ref, refs, scores, n_lead=2, **step)


def _paged_q_map(bh, qi, si, offs_ref, tbl_ref):
    del si, offs_ref, tbl_ref
    return (bh, qi, 0)


def _paged_rows_map(head_groups: int):

    def index_map(bh, qi, si, offs_ref, tbl_ref):
        del si, offs_ref, tbl_ref
        return (bh // head_groups, bh % head_groups, qi, 0)

    return index_map


def _paged_kv_map(j: int, entries: int, head_groups: int,
                  local: bool = False):

    def index_map(bh, qi, si, offs_ref, tbl_ref):
        del qi, offs_ref
        t = tbl_ref[bh // head_groups, si * entries + j]
        if local:
            t = jnp.maximum(t, 0)
        return (t, bh % head_groups, 0, 0)

    return index_map


def _paged_scale_map(entries: int, head_groups: int):

    def index_map(bh, qi, si, offs_ref, tbl_ref):
        del qi, offs_ref, tbl_ref
        return (bh // head_groups, bh % head_groups,
                (si * entries) // _SCALE_ROWS, 0)

    return index_map


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
    step_plan=None,   # the list's; a rectangle has none
    out_dtype,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    # The wrappers hand the call the new module's kernel body: take the
    # rectangle's of the same name.
    kernel_body = globals()[kernel_body.__name__]
    from tree_attention_tpu.ops.tuning import paged_decode_step

    B, Hkv, n_rows, D = rows[0].shape
    block = k.shape[2]
    NB = block_table.shape[1]
    n_q = n_rows // bq
    heads, entries = paged_decode_step(
        Hkv, block, D, k.dtype.itemsize, NB, bq)
    head_groups = Hkv // heads
    rows_map = _paged_rows_map(head_groups)
    tensors = list(rows)
    in_specs = [
        pl.BlockSpec((1, heads, bq, t.shape[3]), rows_map) for t in tensors
    ]
    for pool in (k, v):
        tensors += [pool] * entries
        in_specs += [
            pl.BlockSpec(
                (1, heads, block, D),
                _paged_kv_map(j, entries, head_groups, local=local_blocks))
            for j in range(entries)
        ]
    if scales is not None:
        scale_map = _paged_scale_map(entries, head_groups)
        tensors += [_block_scale_rows(s, block_table) for s in scales]
        in_specs += [
            pl.BlockSpec((1, heads, _SCALE_ROWS, _LANES), scale_map)
        ] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * head_groups, n_q, NB // entries),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, heads, bq, D), rows_map),
            pl.BlockSpec((1, heads, bq, _LANES), rows_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, bq, _LANES), jnp.float32),
            pltpu.VMEM((heads, bq, _LANES), jnp.float32),
            pltpu.VMEM((heads, bq, D), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            kernel_body, **kernel_kwargs, causal=causal, tq=tq, block_q=bq,
            block=block, entries=entries, head_groups=head_groups, tree=tree,
            block_scales=scales is not None, local_blocks=local_blocks,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, n_rows, D), out_dtype),
            jax.ShapeDtypeStruct((B, Hkv, n_rows, _LANES), jnp.float32),
        ],
        # Only the split-KV (table) dim is sequential, as in the
        # contiguous kernels.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # A stable name per kernel body, carried into the compiled module
        # (the custom call's op_name) and the profiler trace.
        name=kernel_body.__name__.strip("_").removesuffix("_kernel"),
    )(_offsets_smem(q_offset, kv_offset, B),
      jnp.asarray(block_table, jnp.int32), *tensors)
    r = group * tq
    return (out[:, :, :r].reshape(B, Hkv * group, tq, D),
            lse[:, :, :r, 0].reshape(B, Hkv * group, tq))


def _mla_decode_paged_kernel(
    offs_ref,  # SMEM (2, B) scalar-prefetch: per-batch [q_offset|kv_offset]
    tbl_ref,   # SMEM (B, NB) scalar-prefetch block table (index maps only)
    *refs,     # q_ref, kv_ref x blocks_per_step, out_ref, lse_ref,
               # m_scr, l_scr, acc_scr:
               #   q_ref   VMEM (1, bq, W) — packed (head x Tq) queries,
               #           each row [q_lat rank | q_rope]
               #   kv_ref  VMEM (1, block, W) — latent pool block
               #           tbl[b, si * blocks_per_step + j]
               #   out_ref VMEM (1, bq, rank); lse_ref VMEM (1, bq, LANES)
               #   m/l_scr VMEM (bq, LANES) f32; acc_scr VMEM (bq, rank) f32
    scale: float,
    tq: int,
    block_q: int,
    block: int,
    rank: int,
    blocks_per_step: int,
):
    del tbl_ref  # consumed by the index maps
    q_ref = refs[0]
    kv_refs = refs[1:1 + blocks_per_step]
    out_ref, lse_ref, m_scr, l_scr, acc_scr = refs[1 + blocks_per_step:]
    qi = pl.program_id(1)
    si = pl.program_id(2)
    n_s = pl.num_programs(2)
    bq, bk = block_q, block * blocks_per_step
    tk = n_s * bk

    b = pl.program_id(0)
    q_offset = offs_ref[0, b]
    kv_offset = offs_ref[1, b]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((kv_offset + si * bk) <= (q_offset + tq - 1))
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

    @pl.when(si == n_s - 1)
    def _finalize():
        _decode_finalize(out_ref, lse_ref, m_scr, l_scr, acc_scr)


def _mla_kv_map(j: int, blocks_per_step: int):
    def index_map(b, qi, si, offs_ref, tbl_ref):
        del qi, offs_ref
        return (tbl_ref[b, si * blocks_per_step + j], 0, 0)

    return index_map


def attention_pallas_mla_paged(
    q: jax.Array,
    pool: jax.Array,
    block_table: jax.Array,
    *,
    q_offset,
    scale: float,
    rank: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
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
    bq = min(-(-r // 8) * 8, 128 if Tq == 1 else 1024)
    qp = _pad_dim(q.reshape(B, r, W), 1, bq)
    n_q = qp.shape[1] // bq
    per = next(p for p in (4, 2, 1) if NB % p == 0)
    in_specs = [pl.BlockSpec((1, bq, W), _paged_q_map)] + [
        pl.BlockSpec((1, block, W), _mla_kv_map(j, per))
        for j in range(per)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_q, NB // per),
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
            _mla_decode_paged_kernel, scale=scale, tq=Tq, block_q=bq,
            block=block, rank=rank, blocks_per_step=per,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_q * bq, rank), q.dtype),
            jax.ShapeDtypeStruct((B, n_q * bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mla_decode_paged",
    )(_offsets_smem(q_offset, 0, B), jnp.asarray(block_table, jnp.int32),
      qp, *([pool] * per))
    return (out[:, :r].reshape(B, H, Tq, rank),
            lse[:, :r, 0].reshape(B, H, Tq))


