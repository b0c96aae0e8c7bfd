"""The kernels of the served and the train path compile for the chip.

Interpret mode cannot show what the TPU's compiler refuses (a block shape the
tiling rejects, a broadcast Mosaic lacks, too much VMEM), and every other test
here runs the kernels in interpret mode. The compiler is installed, though,
and compiles for a chip that is described and not attached: each case lowers
one kernel with ``interpret=False`` at ``chip_smoke.py``'s real widths (32
query / 4 KV heads x 128, 8 slots over a pool of 64-token blocks, bf16) for a
``v5e:2x2`` topology and looks for the Mosaic custom call in the optimized
HLO. Nothing runs, so this says nothing about results or times — those come
from ``python chip_smoke.py`` on the chip.
"""

import dataclasses
import functools
import json
import math
import os
import re
from typing import NamedTuple

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tree_attention_tpu.bench.comm import pallas_kernels
from tree_attention_tpu.ops.pallas_attention import attention_pallas_fwd
from tree_attention_tpu.ops.pallas_bwd import attention_bwd_pallas
from tree_attention_tpu.ops.block_utils import AlignedWindow, ChunkSummaries
from tree_attention_tpu.ops.pallas_decode import (
    attention_pallas_decode,
    attention_pallas_decode_q8,
    ROW_WRITE_KERNEL as ROW_WRITE,
    attention_pallas_decode_q8q,
    paged_row_write,
)
from tree_attention_tpu.ops.tuning import (
    default_block_q,
    default_block_q_bwd,
    default_block_size,
)

B, HQ, HKV, D = 8, 32, 4, 128
BLK, NB = 64, 33          # 2112-token slots: 1280 + 768 prompt + 64 new
N = B * NB
T_TRAIN = 2048


@functools.lru_cache(maxsize=None)
def _topology():
    """The described v5e 2x2 host's devices, or None where the topology
    cannot be described (no TPU compiler in the installation)."""
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception:  # whatever the plugin raises: no compiler, no test
        return None


@functools.lru_cache(maxsize=None)
def _chip():
    """One described v5e chip, or None (see :func:`_topology`)."""
    devices = _topology()
    return None if devices is None else SingleDeviceSharding(devices[0])


@pytest.fixture(autouse=True, scope="module")
def _no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_chip())


# The benchmark cells' decode shapes (slots, KV heads, table entries): the
# table widths the step rule can fold (``tuning.paged_decode_step``: 4 and 8
# entries a step); the smoke's 33 entries divide by none, so its cases above
# compile the one-entry step.
# Their pools hold 16 layers, as the tick programs' do.
MISTRAL = dict(slots=16, hkv=8, nb=40, layers=16)
YI = dict(slots=8, hkv=4, nb=64, layers=16)
# The hybrid's 3 attention layers: 8 KV heads of 64 lanes, two to a row.
LFM2 = dict(slots=64, hkv=4, nb=40, layers=3)
# The state configuration's one attention layer: 2 KV heads x 128 under 32
# query heads, 16 a KV head.
NEMOTRON3S = dict(slots=64, hkv=2, nb=40, layers=1)
# The two-branch configuration: every one of its 9 layers holds K/V rows, 4
# KV heads x 128 (Yi-6B's pool rows) under 20 query heads, a group of FIVE
# (5 packed query rows in a tile of 8 sublanes).
FALCONH1 = dict(slots=48, hkv=4, nb=40, layers=9, hq=20)
# The decoder-hybrid configuration: 20 KV heads of 64 laid in PAIRS, 10 rows
# of 128 lanes, under 40 query heads (a group of 4), the one shared layer's
# table of 144 entries; its eight window layers' pool under the same table.
PHI4FLASH = dict(slots=48, hkv=10, nb=144, layers=1, hq=40)


def _paged(kernel, tq, *, int8=False, tree=False, slots=B, hkv=HKV, nb=NB,
           layers=1, hq=HQ, **kw):
    """(fn, abstract args) of one paged decode kernel call at Tq = tq."""
    n = layers * slots * nb
    pool = _s((n, hkv, BLK, D), jnp.int8 if int8 else jnp.bfloat16)
    args = [_s((slots, hq, tq, D)), pool, pool]
    if int8:
        args += [_s((n, hkv), jnp.float32)] * 2
    args += [_s((slots, nb), jnp.int32), _s((slots,), jnp.int32)]
    if tree:
        args.append(_s((slots, tq, tq), jnp.bool_))

    def fn(*a):
        *tensors, table, pos = a[:len(a) - tree]
        return kernel(*tensors, causal=True, q_offset=pos, block_table=table,
                      tree_mask=a[-1] if tree else None, interpret=False,
                      **kw)

    return fn, args


def _eva_paged(window, tq, blocks, nb, slots=16, layers=8):
    """(fn, abstract args) of one of an EVA layer's two paged calls at the
    EvaByte cell's shapes (32 KV heads x 128 under 32 query heads, 16 slots,
    8 layers a pool): the exact rows' pool (608 blocks a layer, a table of
    256 entries) under the aligned lower edge, or the summary rows' (256
    blocks a layer, 16 entries) under the summary rule."""
    pool = _s((layers * blocks, 32, BLK, D))
    args = [_s((slots, 32, tq, D)), pool, pool,
            _s((slots, nb), jnp.int32), _s((slots,), jnp.int32)]

    def fn(q, k, v, table, pos):
        return attention_pallas_decode(
            q, k, v, causal=True, q_offset=pos, block_table=table,
            window=window, interpret=False)

    return fn, args


def _row_write(*, int8=False, pools=2, slots=B, hkv=HKV, nb=NB, layers=1,
               d=D):
    """(fn, abstract args) of one ``paged_row_write`` call: a row a slot
    into ``pools`` pools (K and V; the one latent pool: 1 head of 640
    lanes)."""
    dtype = jnp.int8 if int8 else jnp.bfloat16
    args = [_s((layers * slots * nb, hkv, BLK, d), dtype)] * pools \
        + [_s((slots, hkv, 1, d), dtype)] * pools \
        + [_s((slots,), jnp.int32)] * 2 + [_s((), jnp.int32)]

    def fn(*a):
        return paged_row_write(a[:pools], a[pools:2 * pools], *a[2 * pools:],
                               interpret=False)

    return fn, args, tuple(range(pools))    # the pools donated, as a tick's


def _ssm_update(slots=64, layers=5, hp=64, n=128, lanes=128, groups=8):
    """(fn, abstract args, donated) of one ``ssm_decode_update`` call at the
    state configuration's widths: 128 heads x 64 x 128, two heads a row of
    lanes, the five layers' pool of 64 slots (1.34 GB) donated. (The
    two-branch configuration's: 32 heads x 128 x 256 in 2 groups, a head a
    row, nine layers' pool of 48 slots, 1.81 GB.)"""
    from tree_attention_tpu.ops.pallas_ssm import ssm_decode_update

    f32 = jnp.float32
    args = [_s((layers * slots, hp, n, lanes), f32),
            _s((slots, hp, lanes), f32), _s((slots, hp, lanes), f32),
            _s((slots, groups, n), f32), _s((slots, groups, n), f32),
            _s((slots,), jnp.int32), _s((1,), jnp.int32), _s((), jnp.int32)]

    def fn(*a):
        return ssm_decode_update(*a, interpret=False)

    return fn, args, (0,)


def _ssm_scan(tq, slots=64, layers=5, heads=128, d_head=64, n=128, groups=8):
    """(fn, abstract args, donated) of one ``ssm_chunk_scan`` call over a
    chunk group of one member of ``tq`` rows at the state configuration's
    widths (the pool of :func:`_ssm_update`, donated; the two-branch
    configuration's: 32 heads x 128 in 2 groups over a state of 256)."""
    from tree_attention_tpu.ops.pallas_ssm import ssm_chunk_scan

    f32, i32 = jnp.float32, jnp.int32
    hp = heads * d_head // 128
    args = [_s((layers * slots, hp, n, 128), f32),
            _s((1, tq, heads * d_head), f32), _s((1, tq, heads), f32),
            _s((heads,), f32), _s((1, tq, groups * n), f32),
            _s((1, tq, groups * n), f32), _s((1,), i32), _s((1,), i32),
            _s((1,), jnp.bool_)]

    def fn(*a):
        return ssm_chunk_scan(*a, interpret=False)

    return fn, args, (0,)


def _ssm1_scan(tq, members, slots=48, layers=9, n=16, channels=5120):
    """(fn, abstract args, donated) of one ``ssm1_scan`` call at the
    decoder-hybrid configuration's widths: nine Mamba-1 layers' pool of 48
    slots x (16, 5120) float32 (142 MB, donated), a decode group's row a
    slot or one chunk member's ``tq`` rows."""
    from tree_attention_tpu.ops.pallas_ssm import ssm1_scan

    f32, i32 = jnp.float32, jnp.int32
    args = [_s((layers * slots, n, channels), f32),
            _s((members, tq, channels), f32), _s((members, tq, channels), f32),
            _s((n, channels), f32), _s((members, tq, n), f32),
            _s((members, tq, n), f32), _s((members,), i32),
            _s((members,), i32), _s((members,), jnp.bool_)]

    def fn(*a):
        return ssm1_scan(*a, interpret=False)

    return fn, args, (0,)


def _conv_tail(slots=64, layers=9, blocks=2560, d=2048):
    """(fn, abstract args, donated) of one ``conv_tail_step`` call at the
    hybrid's widths: nine conv layers' tails of 2,560 blocks x (2 x 2048)
    lanes as they lie (189 MB, donated), 64 slots' ``h . W_in``."""
    from tree_attention_tpu.ops.pallas_conv import (
        MATES, ConvTailPlan, conv_tail_step)

    i32 = jnp.int32
    plan = ConvTailPlan(
        _s((slots,), i32), _s((slots,), i32), _s((slots,), i32),
        _s((slots * MATES,), i32), _s((slots, 1), i32), _s((), i32))
    args = [_s((layers * blocks, 2 * d)), _s((slots, 3 * d)), _s((3, d)),
            plan, _s((), i32)]

    def fn(*a):
        return conv_tail_step(*a, interpret=False)

    return fn, args, (0,)


def _moe_ungated(m, latent=1024, width=2688, held=128, layers=5):
    """(fn, abstract args) of an ungated expert layer's two products over
    ``m`` pairs: one matrix in with relu squared, one out, in the latent."""
    from tree_attention_tpu.ops.pallas_moe import (
        UNGATED_KERNEL, grouped_matmul)

    def fn(x, w1, w2, sizes, first):
        h = grouped_matmul(x, (w1,), sizes, first_group=first, relu2=True,
                           interpret=False, name=UNGATED_KERNEL)
        return grouped_matmul(h, (w2,), sizes, first_group=first,
                              interpret=False, name=UNGATED_KERNEL)

    return fn, [_s((m, latent)), _s((layers * held, latent, width)),
                _s((layers * held, width, latent)),
                _s((held,), jnp.int32), _s((), jnp.int32)]


def _prefill():
    kv = _s((B, HKV, NB * BLK, D))

    def fn(q, k, v, pos):
        return attention_pallas_fwd(q, k, v, causal=True, q_offset=pos,
                                    kv_offset=0, block_size=512,
                                    interpret=False)

    return fn, [_s((B, HQ, 256, D)), kv, kv, _s((B,), jnp.int32)]


def _train_fwd_bwd():
    bk = default_block_size("pallas", T_TRAIN)
    q, kv = _s((1, HQ, T_TRAIN, D)), _s((1, HKV, T_TRAIN, D))

    def fn(q, k, v, dout):
        out, lse = attention_pallas_fwd(
            q, k, v, causal=True, block_size=bk,
            block_q=default_block_q(T_TRAIN, T_TRAIN), interpret=False)
        return attention_bwd_pallas(
            q, k, v, out, lse, dout, jnp.zeros_like(lse), causal=True,
            scale=None, block_size=bk,
            block_q=default_block_q_bwd(T_TRAIN, T_TRAIN, bk),
            interpret=False)

    return fn, [q, kv, kv, q]


# case id -> (builder, the kernel name the compiled module must contain)
CASES = {
    "paged_decode_tq1": (
        lambda: _paged(attention_pallas_decode, 1), "flash_decode_paged"),
    "paged_ragged_pack_tq17": (
        lambda: _paged(attention_pallas_decode, 17), "flash_decode_paged"),
    "paged_chunk_tq64": (
        lambda: _paged(attention_pallas_decode, 64), "flash_decode_paged"),
    "paged_tree_verify_tq8": (
        lambda: _paged(attention_pallas_decode, 8, tree=True),
        "flash_decode_paged"),
    "paged_local_blocks_partial": (
        lambda: _paged(attention_pallas_decode, 1, local_blocks=True),
        "flash_decode_paged"),
    # The two that the parent commit's (1, 1, LANES) scale block failed.
    "paged_int8_q8q_block_scales": (
        lambda: _paged(attention_pallas_decode_q8q, 1, int8=True),
        "flash_decode_paged_q8q"),
    "paged_int8_q8_block_scales": (
        lambda: _paged(attention_pallas_decode_q8, 1, int8=True),
        "flash_decode_paged"),
    "paged_int8_q8q_chunk_tq64": (
        lambda: _paged(attention_pallas_decode_q8q, 64, int8=True),
        "flash_decode_paged_q8q"),
    "paged_int8_q8q_tree_verify_tq8": (
        lambda: _paged(attention_pallas_decode_q8q, 8, int8=True, tree=True),
        "flash_decode_paged_q8q"),
    "paged_int8_q8_tree_verify_tq8": (
        lambda: _paged(attention_pallas_decode_q8, 8, int8=True, tree=True),
        "flash_decode_paged"),
    # The cells' shapes: every head of 4 (Mistral) / 8 (Yi) entries a step.
    "paged_decode_mistral7b_tq1": (
        lambda: _paged(attention_pallas_decode, 1, **MISTRAL),
        "flash_decode_paged"),
    "paged_decode_yi6b_tq1": (
        lambda: _paged(attention_pallas_decode, 1, **YI),
        "flash_decode_paged"),
    "paged_chunk_mistral7b_tq64": (
        lambda: _paged(attention_pallas_decode, 64, **MISTRAL),
        "flash_decode_paged"),
    "paged_chunk_yi6b_tq112": (
        lambda: _paged(attention_pallas_decode, 112, **YI),
        "flash_decode_paged"),
    "paged_tree_verify_yi6b_tq8": (
        lambda: _paged(attention_pallas_decode, 8, tree=True, **YI),
        "flash_decode_paged"),
    "paged_local_blocks_mistral7b": (
        lambda: _paged(attention_pallas_decode, 1, local_blocks=True,
                       **MISTRAL),
        "flash_decode_paged"),
    "paged_int8_q8_block_scales_mistral7b": (
        lambda: _paged(attention_pallas_decode_q8, 1, int8=True, **MISTRAL),
        "flash_decode_paged"),
    "paged_int8_q8q_block_scales_yi6b": (
        lambda: _paged(attention_pallas_decode_q8q, 1, int8=True, **YI),
        "flash_decode_paged_q8q"),
    "paged_int8_q8q_tree_verify_yi6b_tq8": (
        lambda: _paged(attention_pallas_decode_q8q, 8, int8=True, tree=True,
                       **YI),
        "flash_decode_paged_q8q"),
    # The other cells' slots (32 and 64; the latent cells' kernel is below)
    # and the packed programs' smallest chunk bucket.
    "paged_decode_lfm2_tq1": (
        lambda: _paged(attention_pallas_decode, 1, **LFM2),
        "flash_decode_paged"),
    "paged_chunk_lfm2_tq16": (
        lambda: _paged(attention_pallas_decode, 16, **LFM2),
        "flash_decode_paged"),
    "paged_chunk_mistral7b_tq8": (
        lambda: _paged(attention_pallas_decode, 8, **MISTRAL),
        "flash_decode_paged"),
    "paged_decode_32_slots_tq1": (
        lambda: _paged(attention_pallas_decode, 1, slots=32, hkv=8, nb=40,
                       layers=8),
        "flash_decode_paged"),
    # 32 KV heads at a chunk's 128 packed rows a head: every head's Q-side
    # state in one step is refused for VMEM; the step rule cuts the heads.
    "paged_chunk_mha32_tq127_heads_cut": (
        lambda: _paged(attention_pallas_decode, 127, hkv=32, nb=64,
                       layers=16),
        "flash_decode_paged"),
    # The decode rows' write (ISSUE 39): K and V in one call at the cells'
    # shapes, an int8 pool's 8-row tile (a quarter of its packed sublane
    # tile), the latent pools' one head of 640 lanes, the hybrid's 64 slots.
    "row_write_mistral7b": (lambda: _row_write(**MISTRAL), ROW_WRITE),
    "row_write_yi6b": (lambda: _row_write(**YI), ROW_WRITE),
    "row_write_int8_yi6b": (lambda: _row_write(int8=True, **YI), ROW_WRITE),
    "row_write_lfm2": (lambda: _row_write(**LFM2), ROW_WRITE),
    # The conv layers' one-launch step over the tail pool (ISSUE 48).
    "conv_tail_lfm2": (_conv_tail, "conv_tail_step"),
    "row_write_latent_32_slots": (
        lambda: _row_write(pools=1, slots=32, hkv=1, nb=50, layers=8, d=640),
        ROW_WRITE),
    # The state configuration (ISSUE 40): the paged kernel at 2 KV heads x
    # 128 with 16 query heads each, its row write, the state-space layers'
    # in-place step and the ungated expert products at 1024 x 2688.
    "paged_decode_nemotron3s_tq1": (
        lambda: _paged(attention_pallas_decode, 1, **NEMOTRON3S),
        "flash_decode_paged"),
    "row_write_nemotron3s": (lambda: _row_write(**NEMOTRON3S), ROW_WRITE),
    # An EVA layer's two calls (ISSUE 44), a row a slot and a chunk group's
    # 256 rows (one query head a KV head: two Q tiles of 128 packed rows).
    "eva_local_evabyte_tq1": (
        lambda: _eva_paged(AlignedWindow(2048), 1, 608, 256),
        "eva_local_decode"),
    "eva_local_evabyte_tq256": (
        lambda: _eva_paged(AlignedWindow(2048), 256, 608, 256, slots=1),
        "eva_local_decode"),
    "eva_summary_evabyte_tq1": (
        lambda: _eva_paged(ChunkSummaries(2048, 16), 1, 256, 16),
        "eva_summary_decode"),
    "eva_summary_evabyte_tq256": (
        lambda: _eva_paged(ChunkSummaries(2048, 16), 256, 256, 16, slots=1),
        "eva_summary_decode"),
    "row_write_evabyte": (
        lambda: _row_write(slots=16, hkv=32, nb=38, layers=8), ROW_WRITE),
    "ssm_update_nemotron3s": (_ssm_update, "ssm_decode_update"),
    # The kernel's second shape: `pack` 1, a (2, 256) tile of B and C turned
    # to columns, 16 unrolled rows a group.
    "ssm_update_falconh1": (
        lambda: _ssm_update(slots=48, layers=9, hp=32, n=256, groups=2),
        "ssm_decode_update"),
    # A chunk group's scan (ISSUE 51) at the cells' three chunk lengths.
    **{f"ssm_scan_nemotron3s_tq{tq}": (
        functools.partial(_ssm_scan, tq), "ssm_chunk_scan")
       for tq in (64, 128, 256)},
    **{f"ssm_scan_falconh1_tq{tq}": (
        functools.partial(_ssm_scan, tq, slots=48, layers=9, heads=32,
                          d_head=128, n=256, groups=2), "ssm_chunk_scan")
       for tq in (64, 128, 256)},
    "paged_decode_falconh1_tq1": (
        lambda: _paged(attention_pallas_decode, 1, **FALCONH1),
        "flash_decode_paged"),
    # A 256-row chunk group's call (one member), on the Q-tiled path.
    "paged_chunk_falconh1_tq256": (
        lambda: _paged(attention_pallas_decode, 256,
                       **dict(FALCONH1, slots=1)), "flash_decode_paged"),
    # The decoder-hybrid configuration (ISSUE 52): the Mamba-1 scan at a row
    # a slot and at a chunk member's 256 rows; the shared layer's call at a
    # row a slot (the cross layers' call too: S rows under one table) and
    # at a chunk's 256 rows; a window layer's at both.
    "ssm1_scan_phi4flash_tq1": (
        functools.partial(_ssm1_scan, 1, 48), "ssm1_scan"),
    "ssm1_scan_phi4flash_tq256": (
        functools.partial(_ssm1_scan, 256, 1), "ssm1_scan"),
    "paged_decode_phi4flash_tq1": (
        lambda: _paged(attention_pallas_decode, 1, **PHI4FLASH),
        "flash_decode_paged"),
    "paged_chunk_phi4flash_tq256": (
        lambda: _paged(attention_pallas_decode, 256,
                       **dict(PHI4FLASH, slots=1)), "flash_decode_paged"),
    "window_decode_phi4flash_tq1": (
        lambda: _paged(attention_pallas_decode, 1, window=512, **PHI4FLASH),
        "window_decode_paged"),
    "window_chunk_phi4flash_tq256": (
        lambda: _paged(attention_pallas_decode, 256, window=512,
                       **dict(PHI4FLASH, slots=1)),
        "window_decode_paged"),
    "moe_ungated_decode_pairs": (lambda: _moe_ungated(1408),
                                 "moe_ungated_matmul"),
    "moe_ungated_chunk_pairs": (lambda: _moe_ungated(7168),
                                "moe_ungated_matmul"),
    # The gated product's plan (`ops/tuning.py` `grouped_plan`) at the
    # widths DeepSeek-V2's constants were not chosen for (ISSUE 42): LFM2
    # 2048 x 1792 and K-EXAONE / LongCat 6144 x 2048, a decode tick's pairs
    # and a mixed tick's: a plan over the chip's fast memory fails here.
    "moe_lfm2_decode_pairs": (lambda: _moe_kernel("lfm2-8b-a1b", 256),
                              "moe_grouped_matmul"),
    "moe_lfm2_chunk_pairs": (lambda: _moe_kernel("lfm2-8b-a1b", 1280),
                             "moe_grouped_matmul"),
    "moe_kexaone_decode_pairs": (
        lambda: _moe_kernel("k-exaone-236b-a23b", 256),
        "moe_grouped_matmul"),
    "moe_longcat_chunk_pairs": (
        lambda: _moe_kernel("longcat-flash-omni", 3584),
        "moe_grouped_matmul"),
    "prefill_fwd": (_prefill, "flash_fwd"),
    "train_fwd": (_train_fwd_bwd, "flash_fwd"),
    "bwd_dq": (_train_fwd_bwd, "flash_bwd_dq"),
    "bwd_dkv": (_train_fwd_bwd, "flash_bwd_dkv"),
}


def _dynamic_grids(text, kernel):
    """For each launch of ``kernel`` in the module, whether its grid has a
    dynamic bound: the scalar that leads its operands, before the five
    scalar-prefetch lists (offsets, table, slot, step, flags). The
    rectangular grid's launches led with the offsets."""
    return [
        "operand_layout_constraints={s32[], s32[2," in line
        for line in text.splitlines()
        if re.match(rf"\s+(?:ROOT )?%{kernel}(\.\d+)? = .* custom-call\(",
                    line)
    ]


@functools.lru_cache(maxsize=None)
def _compiled_text(builder) -> str:
    fn, args, *donated = builder()
    return jax.jit(fn, donate_argnums=tuple(*donated)).lower(
        *args).compile().as_text()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    builder, kernel = CASES[case]
    text = _compiled_text(builder)
    assert "tpu_custom_call" in text
    assert kernel in pallas_kernels(text), pallas_kernels(text)
    if kernel.startswith(("flash_decode_paged", "eva_")):
        assert _dynamic_grids(text, kernel) == [True]
    if kernel.startswith("eva_"):
        # Under a name of its own: the benchmark's readers match a kernel's
        # events by substring, and another kernel's cost file counts other
        # rows.
        assert not any(other in kernel for other in (
            "flash_decode_paged", "window_decode_paged", "mla_decode_paged"))
    if kernel == ROW_WRITE:
        assert _row_writes(text) == 1
    if kernel == "conv_tail_step":
        # The pool that goes in is the pool that comes out (aliased through
        # the call), under a name the two kernels' readers do not match.
        assert _tail_steps(text) == 1
        assert not any(other in kernel for other in (
            "moe_grouped_matmul", "flash_decode_paged"))
    if kernel == "ssm_decode_update":
        # The pool that goes in is the pool that comes out, and nothing of
        # its size is made beside it.
        mem = jax.jit(builder()[0], donate_argnums=(0,)).lower(
            *builder()[1]).compile().memory_analysis()
        pool = math.prod(builder()[1][0].shape) * 4
        assert mem.alias_size_in_bytes >= pool and mem.temp_size_in_bytes \
            < pool // 64, (mem.alias_size_in_bytes, mem.temp_size_in_bytes)
        # Two phases of the rule's four slots, in the fast memory the call
        # asks for, under the ceiling the expert product's plans keep to
        # (half the chip's 128 MB: the rest is XLA's).
        from tree_attention_tpu.ops import tuning
        shape = builder()[1][0].shape[1:] + (builder()[1][3].shape[1],)
        q = tuning.ssm_phase_slots(*shape)
        limit = tuning.ssm_phase_vmem_limit(q, *shape)
        assert q == 4 and tuning.ssm_phase_vmem_bytes(q, *shape) < limit \
            <= tuning.GROUPED_VMEM_CEILING_BYTES, (q, limit)
        assert f'"size":"{limit}"' in text, limit
        assert "moe_grouped_matmul" not in "moe_ungated_matmul"
    if kernel == "ssm_chunk_scan":
        # The pool is aliased through the call, nothing of its size beside
        # it; the blocks of the rule's choice twice fit the limit the call
        # asks for; under a name no other kernel's reader matches.
        fn, args, _ = builder()
        mem = jax.jit(fn, donate_argnums=(0,)).lower(
            *args).compile().memory_analysis()
        pool = math.prod(args[0].shape) * 4
        assert mem.alias_size_in_bytes >= pool and mem.temp_size_in_bytes \
            < pool // 64, (mem.alias_size_in_bytes, mem.temp_size_in_bytes)
        from tree_attention_tpu.ops import tuning
        (_, hp, n, lanes), tq = args[0].shape, args[1].shape[1]
        blk = tuning.ssm_scan_block(tq)
        rows = tuning.ssm_scan_rows(tq, hp * n // args[4].shape[2], n, lanes)
        limit = tuning.ssm_scan_vmem_limit(tq, blk, rows, n, lanes)
        assert tuning.ssm_scan_vmem_bytes(tq, blk, rows, n, lanes) < limit \
            <= tuning.GROUPED_VMEM_CEILING_BYTES, limit
        assert f'"size":"{limit}"' in text, limit
        assert not any(other in kernel for other in (
            "ssm_decode_update", "flash_decode_paged", "moe_grouped_matmul",
            "moe_ungated_matmul"))
    if kernel == "ssm1_scan":
        # The pool is aliased through the call, nothing of its size beside
        # it; the blocks of the rule's choice fit the limit the call asks
        # for; under a name no other kernel's reader matches.
        fn, args, _ = builder()
        mem = jax.jit(fn, donate_argnums=(0,)).lower(
            *args).compile().memory_analysis()
        pool = math.prod(args[0].shape) * 4
        assert mem.alias_size_in_bytes >= pool and mem.temp_size_in_bytes \
            < pool // 8, (mem.alias_size_in_bytes, mem.temp_size_in_bytes)
        from tree_attention_tpu.ops import tuning
        (_, n, channels), tq = args[0].shape, args[1].shape[1]
        tb = tuning.ssm1_time_block(tq)
        ct = tuning.ssm1_channel_tile(tb, n, channels)
        limit = tuning.ssm1_vmem_limit(tb, n, ct)
        assert ct == channels and tuning.ssm1_step_vmem_bytes(tb, n, ct) \
            < limit <= tuning.GROUPED_VMEM_CEILING_BYTES, (ct, limit)
        assert f'"size":"{limit}"' in text, limit
        assert not any(other in kernel or kernel in other for other in (
            "ssm_decode_update", "ssm_chunk_scan", "flash_decode_paged"))
    if "_mistral7b" in case or "_yi6b" in case or "_lfm2" in case \
            or ("_falconh1" in case and not kernel.startswith("ssm_")) \
            or ("_evabyte" in case and kernel != ROW_WRITE):
        # The pool goes into the call as it is: no copy, slice or change of
        # layout of a pool-sized array before the launch (what a 576-lane
        # latent row cost before PR 27 padded it). Not asked of the smoke's
        # one-layer pools: the compiler stages those whole in fast memory.
        pool = max(math.prod(a.shape) for a in jax.tree.leaves(builder()[1]))
        moved = [
            (name, opcode, result)
            for name, result, opcode, _ in _materialised(text)
            if opcode not in _MOVES_NOTHING
            and not name.startswith((f"%{ROW_WRITE}", "%conv_tail_step"))
            and any(
                math.prod(int(d) for d in dims.split(",")) >= pool
                for dims in re.findall(r"\[([\d,]+)\]", result))
        ]
        assert not moved, moved


# -- the step the tick programs run: the KV pool stays in place (ISSUE 25) ---
#
# ``forward_step`` carries the paged pool through the layer loop and writes
# each layer's rows into it in place; the compiled tick must hold no slice,
# layout copy, restack or whole-pool copy of it. The parent of that change
# held about five passes over the pool a tick (PERF.md section 6, PR 25), all
# of them XLA's own choices that no CPU test can see. Each case compiles the
# step as the engine's tick programs call it (``n_tokens``, cache donated) at
# one configuration's widths and reads the optimized HLO.

# Compiled at the cells' own depth (the loop compiles once whatever it is): a
# pool that fits the chip's fast memory is staged there whole by the compiler
# (3 layers of Yi's int8 pool, 51 MB, were: slice-start/done into S(1)), which
# no cell's pool does.
STEP_CONFIGS = ("yi-6b", "mistral-7b-v0.3")  # benchmark/configs/<name>.json
# Results that only name a buffer (or hand the loop's state round) and move
# no byte of it.
_MOVES_NOTHING = {"parameter", "bitcast", "get-tuple-element", "tuple",
                  "while"}
_INSTR = re.compile(r"^\s+(?:ROOT )?(%[\w.\-]+) = (.*?)\s([a-z][a-z0-9\-]*)\(")


def _materialised(text):
    """(name, result type, opcode, body of the computation it calls) of every
    instruction of the module that is not inside a fused computation: what
    gets a buffer of its own."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    comps, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if m:
                called = re.search(r"calls=(%[\w.\-]+)", line)
                inner = "\n".join(comps.get(called.group(1), ())) \
                    if called else ""
                yield m.group(1), m.group(2), m.group(3), inner


def _row_writes(text):
    """The launches of ``paged_row_write`` in the module (ISSUE 39): one row
    a slot into every pool of a layer, each pool aliased through the call
    (output ``i`` is the operand the pool came in as), so that the loop's
    carry, the kernel's operand and its result are one buffer."""
    lines = [
        line for line in text.splitlines()
        if re.match(rf"\s+(?:ROOT )?%{ROW_WRITE}(\.\d+)? = .* custom-call\(",
                    line)
    ]
    for line in lines:
        pools = line.split(" custom-call(")[0].count("[")
        aliases = re.search(
            r"output_to_operand_aliasing=\{(.*?)\}, \w+=", line).group(1)
        assert len(re.findall(r"\{\d*\}: \(\d+, \{\}\)", aliases)) == pools, \
            aliases
    return len(lines)


def _tail_steps(text):
    """The launches of ``conv_tail_step`` in the module (ISSUE 48): a conv
    layer's step for one row a slot, the tail pool aliased through the
    call (output 0 is the operand the pool came in as)."""
    lines = [
        line for line in text.splitlines()
        if re.match(r"\s+(?:ROOT )?%conv_tail_step(\.\d+)? = .* custom-call\(",
                    line)
    ]
    for line in lines:
        aliases = re.search(
            r"output_to_operand_aliasing=\{(.*?)\}, \w+=", line).group(1)
        assert re.findall(r"\{0\}: \(\d+, \{\}\)", aliases), aliases
    return len(lines)


def _block_moves(text, tail, dtype="bf16"):
    """The gathers and scatters anywhere in the module (fused computations
    too) whose result is made of pool blocks ``(..., tail)``: what the block
    path of the pool write holds (the old blocks gathered, the overlaid ones
    scattered back) and a group of one row a slot must not."""
    found = [
        re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = (\S+) (gather|scatter)\(", line)
        for line in text.splitlines()]
    return [(m.group(1), m.group(3), m.group(2)) for m in found
            if m and re.search(rf"\b{dtype}\[[\d,]+,{tail}\]", m.group(2))]


@functools.lru_cache(maxsize=None)
def _model(name):
    """(the configuration file, the model it says) of any of the five."""
    from tree_attention_tpu.models.transformer import (
        TransformerConfig, model_from_config)

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", f"{name}.json")) as f:
        c = json.load(f)
    if name not in STEP_CONFIGS:
        return c, model_from_config(c, max_seq_len=c["serving"]["cache_len"])
    return c, TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_head=c["assumed"]["head_dim"], d_ff=c["intermediate_size"],
        n_layers=c["num_hidden_layers"], dtype=jnp.bfloat16)


def _pool_blocks(name):
    """The blocks of a configuration's pool as the cases below build it.
    The dense ones get a spare block a slot: the logical view (slots x nb
    blocks, which the Q-tiled path gathers) is then smaller than one layer
    of the pool, and an array of either size says which of the two it is."""
    serving = _model(name)[0]["serving"]
    nb = serving["cache_len"] // serving["kv_block"]
    # (An EVA model's first pool holds one summary row a chunk.)
    nb = -(-nb // (_model(name)[1].chunk or 1))
    return serving["slots"] * (nb + (name in STEP_CONFIGS))


class _Tick(NamedTuple):
    text: str            # the optimized HLO
    alias_bytes: int     # donated buffers that are the output's
    temp_bytes: int


@functools.lru_cache(maxsize=None)
def _tick_program(config, tq, packed=False, int8=False, served=True):
    """One tick program of a benchmark cell compiled for the described
    chip, as the engine calls it: the cell's slots and pool, the cache
    donated, the parameters in the layout the engine serves from (its own
    ``served_layout``; ``served=False``: the outer format, which the
    engine never hands a program). ``packed``: ``forward_packed_step`` at C
    = 1, the engine's default, one chunk of ``tq`` beside a row a slot;
    else ``forward_step`` with ``n_tokens``. Memoised: the cases below read
    one compile each."""
    from tree_attention_tpu.models import decode
    from tree_attention_tpu.models.transformer import (
        init_params, served_layout)

    c, cfg = _model(config)
    serving = c["serving"]
    slots = serving["slots"]
    chip = lambda tree: jax.tree.map(
        lambda a: _s(a.shape, a.dtype), tree)
    layout = served_layout if served else (lambda p: p)
    params = chip(jax.eval_shape(
        lambda: layout(init_params(jax.random.PRNGKey(0), cfg))))
    extra = {}
    if cfg.cache_kind in ("window", "eva", "state_window"):
        # The engine's own size: (ceil((window + chunk) / block) + 2) a slot.
        extra["window_blocks"] = slots * (-(-(
            cfg.window + serving["prefill_chunk"]) // serving["kv_block"]) + 2)
    cache = chip(jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, slots, serving["cache_len"], _pool_blocks(config),
        block=serving["kv_block"], quantize=int8, **extra)))

    def step(params, tokens, cache, n_tokens):
        stats = {}
        logits, cache = decode.forward_step(params, tokens, cache, cfg,
                                            n_tokens=n_tokens, stats=stats)
        return logits, cache, stats

    def packed_step(params, chunk, cache, members, slots_i32):
        stats = {}
        logits, cache = decode.forward_packed_step(
            params, chunk, members, members, slots_i32, slots_i32, cache,
            cfg, stats=stats)
        return logits, cache, stats

    # The step asks the default backend whether the kernels apply; the
    # backend here is the CPU, the target the described chip.
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        if packed:
            compiled = jax.jit(packed_step, donate_argnums=(2,)).lower(
                params, _s((1, tq), jnp.int32), cache, _s((1,), jnp.int32),
                _s((slots,), jnp.int32)).compile()
        else:
            compiled = jax.jit(step, donate_argnums=(2,)).lower(
                params, _s((slots, tq), jnp.int32), cache,
                _s((slots,), jnp.int32)).compile()
    finally:
        jax.default_backend = backend
    mem = compiled.memory_analysis()
    return _Tick(compiled.as_text(), mem.alias_size_in_bytes,
                 mem.temp_size_in_bytes)


def _dense_sizes(config):
    """(elements of a layer of the K pool, of the logical view of every
    slot's blocks, the layers) of a dense configuration's case."""
    c, cfg = _model(config)
    serving = c["serving"]
    per_block = cfg.n_kv_heads * serving["kv_block"] * cfg.d_head
    return (_pool_blocks(config) * per_block,
            serving["slots"] * serving["cache_len"] // serving["kv_block"]
            * per_block, cfg.n_layers)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("tq", [1, 256])
@pytest.mark.parametrize("config", STEP_CONFIGS)
def test_step_keeps_the_pool_in_place(config, tq, int8):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    tick = _tick_program(config, tq, int8=int8)
    layer, view, layers = _dense_sizes(config)
    text = tick.text
    # The kernels (flash_decode_paged*, or flash_fwd for a bf16 chunk), not
    # the hoisted view of the CPU's runs.
    assert pallas_kernels(text), "no Pallas kernel in the compiled step"

    dtype = "s8" if int8 else "bf16"
    writes, views, moved = [], [], []
    for name, result, opcode, inner in _materialised(text):
        sizes = [math.prod(int(d) for d in dims.split(","))
                 for dims in re.findall(rf"\b{dtype}\[([\d,]+),{D}\]", result)]
        if not sizes or max(sizes) * D < view or opcode in _MOVES_NOTHING:
            continue
        if opcode == "scatter" or " scatter(" in inner:
            writes.append(name)       # _paged_pool_write, in place (below)
        elif name.startswith(f"%{ROW_WRITE}"):
            continue                  # aliased through (_row_writes)
        elif max(sizes) * D == view:
            views.append((name, opcode, result))
        else:
            moved.append((name, opcode, result))
    assert not moved, moved
    hkv = _model(config)[1].n_kv_heads
    if tq == 1:
        # One row a slot: K's and V's through one launch of the row kernel
        # in the loop's body, and no block of the pool gathered or scattered.
        assert _row_writes(text) == 1 and not writes, writes
        assert not _block_moves(text, f"{hkv},{BLK},{D}", dtype)
    else:
        # A chunk keeps the block path: K's and V's, once in the loop's body.
        assert len(writes) == 2 and not _row_writes(text), writes
    # The logical view of a slot's blocks, gathered per layer for the
    # Q-tiled prefill kernel of a chunk tick: ROADMAP queue 1 item 3 (the
    # mixed tick), not the pool. A decode tick holds none.
    assert all(op in ("fusion", "copy") for _, op, _ in views), views
    if tq == 1:
        assert not views, views
    # In place: the donated K and V pools are the output's buffers, and a
    # decode tick needs no scratch as large as a layer of the pool.
    pool_bytes = layers * layer * (1 if int8 else 2)
    assert tick.alias_bytes >= 2 * pool_bytes, tick.alias_bytes
    if tq == 1:
        assert tick.temp_bytes < layer * (1 if int8 else 2), tick.temp_bytes


def test_seq_sharded_decode_tick_takes_the_row_kernel_on_four_chips():
    """The sequence-sharded pool's write is the local call under
    ``shard_map`` (ISSUE 39): compiled for the four described chips, a
    decode tick over a pool sharded on its block axis launches
    ``paged_row_write`` once in the layer loop's body (K and V together, the
    shard's slice of both aliased through it) and scatters no block."""
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tree_attention_tpu.models import decode
    from tree_attention_tpu.models.transformer import (
        init_params, served_layout)

    c, cfg = _model("yi-6b")
    cfg = dataclasses.replace(cfg, n_layers=2)
    mesh = Mesh(np.asarray(_topology()).reshape(4), ("seq",))
    on = lambda spec: NamedSharding(mesh, spec)
    shaped = lambda a, spec=P(): jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=on(spec))
    params = jax.tree.map(shaped, jax.eval_shape(
        lambda: served_layout(init_params(jax.random.PRNGKey(0), cfg))))
    slots, blk = 8, c["serving"]["kv_block"]
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, slots, 2048, slots * 32, block=blk))
    cache = decode.PagedKVCache(
        k=shaped(cache.k, P(None, "seq")), v=shaped(cache.v, P(None, "seq")),
        table=shaped(cache.table), length=shaped(cache.length))

    def step(params, tokens, cache, n_tokens):
        return decode.forward_step(params, tokens, cache, cfg, mesh=mesh,
                                   n_tokens=n_tokens, kv_shard="seq")

    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=on(P()))
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        text = jax.jit(step, donate_argnums=(2,)).lower(
            params, i32((slots, 1)), cache, i32((slots,))).compile().as_text()
    finally:
        jax.default_backend = backend
    assert _row_writes(text) == 1
    assert any(k.startswith("flash_decode_paged")
               for k in pallas_kernels(text))
    assert not _block_moves(text, f"{cfg.n_kv_heads},{blk},{D}")


# -- the packed tick: only the rows that carry a token (ISSUE 30) -----------
#
# A tick with a prompt chunk runs ``forward_packed_step``: a chunk group of C
# members beside one decode row a slot. Compiled for the chip at a cell's
# widths it must hold what the padded step held (no pool-sized result but the
# in-place writes, the kernels on the path) and none of the padding: no array
# with a vocabulary axis beyond one row a slot, and no activation laid out as
# a Tq-row matrix a slot.

_ARRAY = re.compile(r"\b(bf16|f32|s8)\[([\d,]+)\]")


def _padding_arrays(text, slots, tq, vocab, d_model, packed_rows=None):
    """Arrays of the compiled module that only a padded tick would hold.
    ``packed_rows``: the rows the latent kernel packs a chunk's heads x Tq
    into, ``(1, heads x Tq, lanes)``; at 64 heads x 256 they number as many
    as a vocabulary of 16,384 and are no vocabulary axis."""
    out = set()
    for dtype, dims in _ARRAY.findall(text):
        dims = [int(d) for d in dims.split(",")]
        if dims[:2] == [1, packed_rows] and len(dims) == 3:
            continue
        if vocab in dims:
            rest = math.prod(dims) // vocab
            if rest > slots and rest != d_model:   # not logits, embed, wout
                out.add((dtype, tuple(dims)))
        elif len(dims) >= 3 and slots in dims \
                and tq in dims[dims.index(slots) + 1:]:
            out.add((dtype, tuple(dims)))
    return sorted(out)


# The decoder-hybrid configuration (ISSUE 52): nine Mamba-1 layers' states,
# eight window layers' rows and ONE shared layer's rows in one cache, carried
# whole through four runs of layers (two of them a period of two kinds). The
# compile for the chip holds the three kernels and the row write, copies none
# of the six pools, and a packed tick's layers above the seam hold no array
# of the chunk's rows.


@pytest.mark.parametrize("tq,packed", [(1, False), (256, True)],
                         ids=["tq1", "packed256"])
def test_decoder_hybrid_step_compiles_and_copies_no_pool(tq, packed):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    name = "phi-4-mini-flash-reasoning"
    c, cfg = _model(name)
    slots = c["serving"]["slots"]
    tick = _tick_program(name, tq, packed=packed)
    text = tick.text
    kernels = pallas_kernels(text)
    assert {"ssm1_scan", "window_decode_paged", ROW_WRITE} <= set(kernels), \
        kernels
    assert any(k.startswith("flash_decode_paged") for k in kernels), kernels
    assert not {"ssm_decode_update", "ssm_chunk_scan"} & set(kernels)
    # Four loop bodies (two periods of two layers, two single layers): the
    # Mamba-1 scan is launched in the first period's body and for layer 16,
    # a decode group's and, packed, a chunk group's.
    launches = len(re.findall(r"%ssm1_scan(\.\d+)? = ", text))
    assert launches == (4 if packed else 2), launches
    # Every pool goes through the program in place: what is aliased is at
    # least the six arrays, and nothing pool-sized is made beside them.
    pools = 2 * (6912 + 8 * slots * 14) * 10 * 64 * 128 * 2 \
        + 9 * slots * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert tick.alias_bytes >= pools, (tick.alias_bytes, pools)
    assert tick.temp_bytes < 1.5e9, tick.temp_bytes
    if packed:
        padding = _padding_arrays(text, slots, tq, cfg.vocab_size,
                                  cfg.d_model)
        assert not padding, padding
        # Below the seam the chunk's rows and one a slot, above it one a
        # slot alone: the memory is gathered to the slots' rows, and the
        # gated units' products are of that many.
        shapes = {dims for _, dims in _ARRAY.findall(text)}
        inner = cfg.ssm1.inner
        assert f"1,{tq + slots},{inner}" in shapes, sorted(shapes)[:40]
        assert f"1,{slots},{inner}" in shapes or f"{slots},{inner}" in shapes


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("tq", [256, 16])
@pytest.mark.parametrize("config", STEP_CONFIGS)
def test_packed_tick_computes_only_its_rows(config, tq, int8):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    tick = _tick_program(config, tq, packed=True, int8=int8)
    layer, _, layers = _dense_sizes(config)
    text = tick.text
    kernels = pallas_kernels(text)
    # The chunk group's kernel and the decode group's: Q-tiled flash_fwd
    # for a bf16 chunk of 128 rows or more, the paged decode kernels below
    # that, for int8 and for the row a slot.
    assert any(k.startswith("flash_decode_paged") for k in kernels), kernels
    if tq >= 128 and not int8:
        assert "flash_fwd" in kernels, kernels

    dtype = "s8" if int8 else "bf16"
    writes, moved = [], []
    for name, result, opcode, inner in _materialised(text):
        sizes = [math.prod(int(d) for d in dims.split(","))
                 for dims in re.findall(rf"\b{dtype}\[([\d,]+),{D}\]", result)]
        if not sizes or max(sizes) * D < layer or opcode in _MOVES_NOTHING:
            continue
        if opcode == "scatter" or " scatter(" in inner:
            writes.append(name)
        elif not name.startswith(f"%{ROW_WRITE}"):
            moved.append((name, opcode, result))
    assert not moved, moved
    # K's and V's scatters for the chunk group; the decode group's row a
    # slot goes through the row kernel and scatters nothing.
    assert len(writes) == 2 and _row_writes(text) == 1, writes
    hkv = _model(config)[1].n_kv_heads
    scatters = [m for m in _block_moves(text, f"{hkv},{BLK},{D}", dtype)
                if m[1] == "scatter"]
    assert len(scatters) == 2, scatters
    assert tick.alias_bytes >= 2 * layers * layer * (1 if int8 else 2)

    c = _model(config)[0]
    padding = _padding_arrays(text, c["serving"]["slots"], tq,
                              c["vocab_size"], c["hidden_size"])
    assert not padding, padding


# -- the latent (MLA) pool and the expert layer (ISSUE 27) ------------------
#
# The latent kernel at the benchmark cell's own shapes (128 heads against one
# row a token, 512 + 64 values and the configuration's declared pad, blocks of
# 64, 16 slots), the grouped expert product, and the whole step at the cell's
# widths: one dense + 4 expert layers, the one latent pool carried through
# both layer loops in place. At a row of 576 lanes the compiler copied the
# whole pool before every launch of the kernel; the declared pad (640) is what
# keeps this test green. The second latent family's cell (ISSUE 31) brings
# the same kernels at its own shapes: 64 heads (half a tile a slot at Tq 1),
# 32 slots, a pool of two layers a model layer, experts of 6144 x 2048 in a
# stack of 4 x 16, and a step whose layer body is the double layer.

LATENT_CONFIGS = ("deepseek-v2", "longcat-flash-omni")


def _mla_kernel(name, tq):
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_mla_paged)

    c, cfg = _model(name)
    row = cfg.mla.row                      # 576 values on 640 lanes
    slots, blk = c["serving"]["slots"], c["serving"]["kv_block"]
    nb = c["serving"]["cache_len"] // blk

    def fn(q, pool, table, pos):
        return attention_pallas_mla_paged(
            q, pool, table, q_offset=pos, scale=0.1,
            rank=c["kv_lora_rank"], interpret=False)

    return fn, [_s((slots, cfg.n_heads, tq, row)),
                _s((cfg.cache_layers * slots * nb, blk, row)),
                _s((slots, nb), jnp.int32), _s((slots,), jnp.int32)]


def _moe_kernel(name, m):
    from tree_attention_tpu.ops.pallas_moe import grouped_matmul

    cfg = _model(name)[1]
    d, f, e = cfg.d_model, cfg.moe.width, 4 * cfg.moe.held

    def fn(x, w1, w3, w2, sizes, first):
        h = grouped_matmul(x, (w1, w3), sizes, first_group=first,
                           interpret=False)
        return grouped_matmul(h, (w2,), sizes, first_group=first,
                              interpret=False)

    return fn, [_s((m, d)), _s((e, d, f)), _s((e, d, f)), _s((e, f, d)),
                _s((cfg.moe.held,), jnp.int32), _s((), jnp.int32)]


EXPERT_CONFIGS = ("deepseek-v2", "k-exaone-236b-a23b", "lfm2-8b-a1b",
                  "longcat-flash-omni", "nemotron-3-super-120b-a12b")


@pytest.mark.parametrize("pairs", [128, 256, 384, 1280, 1408, 1664, 2304,
                                   3584, 7168, 24576])
@pytest.mark.parametrize("config", EXPERT_CONFIGS)
def test_the_expert_products_plan_divides_the_shape_and_fits_its_limit(
        config, pairs):
    """``grouped_plan`` at every ``(hidden or latent, width)`` under
    ``benchmark/configs`` and every row count the cells' ticks make: the
    blocks divide the operands, a block's last two dimensions are whole
    tiles, and the blocks fit the fast memory the plan itself asks for,
    which stays under what one kernel may take of the chip's."""
    from tree_attention_tpu.ops import tuning

    cfg = _model(config)[1]
    ex, hidden = cfg.moe, cfg.moe.latent or cfg.d_model
    for k, n, n_rhs in ((hidden, ex.width, 2 if ex.gated else 1),
                        (ex.width, hidden, 1)):
        plan = tuning.grouped_plan(pairs, k, n, n_rhs)
        assert pairs % plan.tm == 0 and k % plan.tk == 0 \
            and n % plan.tn == 0, plan
        assert plan.tm % 16 == 0 and plan.tk % 128 == 0 \
            and plan.tn % 128 == 0, plan
        assert plan.rows_whole is False or plan.tk < k, plan
        need = plan.vmem_bytes(k, n_rhs, 2)
        limit = plan.vmem_limit_bytes(k, n_rhs, 2)
        assert need <= (limit or tuning.DEFAULT_SCOPED_VMEM_BYTES), plan
        assert (limit or 0) <= tuning.GROUPED_VMEM_CEILING_BYTES, plan


LATENT_CASES = {
    "mla_decode_tq1": (lambda n: _mla_kernel(n, 1), "mla_decode_paged"),
    "mla_chunk_tq16": (lambda n: _mla_kernel(n, 16), "mla_decode_paged"),
    "mla_chunk_tq256": (lambda n: _mla_kernel(n, 256), "mla_decode_paged"),
    "moe_decode_pairs": (lambda n: _moe_kernel(n, 128),
                         "moe_grouped_matmul"),
    "moe_chunk_pairs": (lambda n: _moe_kernel(n, 24576),
                        "moe_grouped_matmul"),
}


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
@pytest.mark.parametrize("config", LATENT_CONFIGS)
def test_latent_and_expert_kernels_compile_for_v5e(config, case):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    builder, kernel = LATENT_CASES[case]
    text = _compiled_text(lambda: builder(config))
    assert "tpu_custom_call" in text
    assert kernel in pallas_kernels(text), pallas_kernels(text)
    if kernel == "mla_decode_paged":
        assert _dynamic_grids(text, kernel) == [True]
        # No operand of the kernel is copied on its way in.
        assert not re.search(r"= bf16\[[\d,]+\]\S* copy\(", text), text[:0] \
            + "an operand of mla_decode_paged is copied before the launch"


@pytest.mark.parametrize("tq,packed", [(1, False), (256, False),
                                       (256, True), (16, True)],
                         ids=["tq1", "tq256", "packed256", "packed16"])
@pytest.mark.parametrize("config, held_params", [
    ("deepseek-v2", 5.16e9), ("longcat-flash-omni", 5.17e9)])
def test_latent_step_compiles_and_keeps_the_pool_in_place(
        config, held_params, tq, packed):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from tree_attention_tpu.models.transformer import init_params

    c, cfg = _model(config)
    slots, blk = c["serving"]["slots"], c["serving"]["kv_block"]
    blocks = _pool_blocks(config)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == pytest.approx(held_params, rel=0.01)
    tick = _tick_program(config, tq, packed=packed)
    text = tick.text
    kernels = pallas_kernels(text)
    assert "mla_decode_paged" in kernels and "moe_grouped_matmul" in kernels
    if packed:
        padding = _padding_arrays(text, slots, tq, cfg.vocab_size,
                                  cfg.d_model, packed_rows=cfg.n_heads * tq)
        assert not padding, padding
    row = cfg.mla.row
    pool = cfg.cache_layers * blocks * blk * row
    experts = cfg.moe.held * cfg.d_model * cfg.moe.width
    moved = []
    for name, result, opcode, inner in _materialised(text):
        if opcode in _MOVES_NOTHING or opcode == "scatter" \
                or " scatter(" in inner \
                or name.startswith(f"%{ROW_WRITE}"):   # the writes, in place
            continue
        # Blocks of rows, a layer of the pool or more; or a layer's experts.
        of_pool = [math.prod(int(d) for d in dims.split(",")) * blk * row
                   for dims in re.findall(
                       rf"\bbf16\[([\d,]+),{blk},{row}\]", result)]
        of_experts = [math.prod(int(d) for d in dims.split(","))
                      for dims in re.findall(r"\bbf16\[([\d,]+)\]", result)
                      if dims.endswith((f"{cfg.d_model},{cfg.moe.width}",
                                        f"{cfg.moe.width},{cfg.d_model}"))]
        if max(of_pool, default=0) >= pool // cfg.cache_layers \
                or max(of_experts, default=0) >= experts:
            moved.append((name, opcode, result))
    # No copy of the pool, no slice of a layer's experts out of their stack.
    assert not moved, moved
    assert tick.alias_bytes >= pool * 2, tick.alias_bytes
    if tq == 1:
        assert tick.temp_bytes < pool * 2, tick.temp_bytes
    # The one latent pool's write, once an attention of a layer loop's body
    # (the leading dense stack's; the expert stack's, two in a double
    # layer): a group of one row a slot through the row kernel, with no
    # block gathered or scattered; a chunk group by the block path.
    loops = bool(cfg.n_dense_layers) + cfg.sublayers
    scatters = [m for m in _block_moves(text, f"{blk},{row}")
                if m[1] == "scatter"]
    if tq == 1:
        assert _row_writes(text) == loops
        assert not _block_moves(text, f"{blk},{row}")
    else:
        assert _row_writes(text) == (loops if packed else 0)
        assert scatters       # (a fused computation may be held twice)


# -- the hybrid pool: conv tails beside K/V rows of 64-lane heads (ISSUE 33) -
#
# The conv / attention hybrid's tick programs at ``benchmark/configs/
# lfm2-8b-a1b.json``'s widths: 9 conv + 3 attention layers in 7 runs, 2 dense
# FFNs, 10 expert layers of 32 experts of 2048 x 1792, 64 slots. What the
# compiler did at the shapes first tried, and must not do again: K/V pools
# whose rows are one head of 64 lanes it holds in a layout of its own (the
# block axis minor) and copies to the kernel's row-major layout and back, 2 x
# 2 x 1 GB a tick, so two KV heads lie side by side on 128 lanes
# (``TransformerConfig.kv_pack``); a tail pool ``(layers, N, 2, hidden)`` it
# tiles by the pair of rows, so that every flat view the gather and the
# scatter take is a copy of the pool (8 x 189 MB in the decode tick), so a
# block's two rows lie side by side too, ``(layers, N, 2 x hidden)``.


@pytest.mark.parametrize("tq,packed", [(1, False), (256, True), (16, True)],
                         ids=["tq1", "packed256", "packed16"])
def test_hybrid_step_compiles_and_keeps_the_pools_in_place(tq, packed):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from tree_attention_tpu.models import decode
    from tree_attention_tpu.models.transformer import init_params

    c, cfg = _model("lfm2-8b-a1b")
    slots, blk = c["serving"]["slots"], c["serving"]["kv_block"]
    blocks = _pool_blocks("lfm2-8b-a1b")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == pytest.approx(3.929e9, rel=0.001)
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, slots, c["serving"]["cache_len"], blocks, block=blk))
    assert cache.k.shape == (3, blocks, 4, blk, 128)      # two heads a row
    assert cache.tail.shape == (9, blocks, 2 * cfg.d_model)
    tick = _tick_program("lfm2-8b-a1b", tq, packed=packed)
    text = tick.text
    kernels = pallas_kernels(text)
    assert any(k.startswith("flash_decode_paged") for k in kernels), kernels
    assert "moe_grouped_matmul" in kernels, kernels
    if packed:
        padding = _padding_arrays(text, slots, tq, cfg.vocab_size,
                                  cfg.d_model)
        assert not padding, padding
    kv_layer = blocks * 4 * blk * 128
    tail_layer = blocks * 2 * cfg.d_model
    experts = cfg.moe.held * cfg.d_model * cfg.moe.width
    moved, writes = [], []
    for name, result, opcode, inner in _materialised(text):
        if opcode in _MOVES_NOTHING:
            continue
        sizes = [math.prod(int(d) for d in dims.split(","))
                 for dims in re.findall(r"\bbf16\[([\d,]+)\]", result)
                 if dims.endswith((f",4,{blk},128", f",{2 * cfg.d_model}"))]
        of_experts = [math.prod(int(d) for d in dims.split(","))
                      for dims in re.findall(r"\bbf16\[([\d,]+)\]", result)
                      if dims.endswith((f"{cfg.d_model},{cfg.moe.width}",
                                        f"{cfg.moe.width},{cfg.d_model}"))]
        if max(sizes, default=0) < min(kv_layer, tail_layer) \
                and max(of_experts, default=0) < experts:
            continue
        if opcode == "scatter" or " scatter(" in inner:
            writes.append(name)      # the pools' writes, in place (below)
        elif not name.startswith((f"%{ROW_WRITE}", "%conv_tail_step")):
            moved.append((name, opcode, result))
    # No copy of a K/V pool or of the tail pool (whole or a layer of it),
    # no slice of a layer's experts or tails out of their stack.
    assert not moved, moved
    # The tails: a row a slot goes through ONE launch of ``conv_tail_step``
    # in each of the 4 runs of conv layers, the pool aliased through it; a
    # chunk group's through the scatter of the block path, one a run, and
    # no other group's. K's and V's in each of the 3 attention layers (each
    # a run of one): a chunk group's by two scatters, a row a slot by one
    # launch of the row kernel and no block of the K/V pools gathered or
    # scattered.
    assert len(writes) == (4 + 2 * 3 if packed else 0), writes
    assert _tail_steps(text) == 4
    assert _row_writes(text) == 3
    tail_moves = _block_moves(text, str(2 * cfg.d_model))
    if not packed:
        # Tq 1: no gather and no scatter of the tail pool anywhere.
        assert not tail_moves, tail_moves
    else:
        # The chunk group's alone: its member's rows (a handful), never the
        # 64 rows of the decode group.
        assert not [m for m in tail_moves if f"[{slots}," in m[2]], \
            tail_moves
    kv_moves = _block_moves(text, f"4,{blk},128")
    assert len([m for m in kv_moves if m[1] == "scatter"]) \
        == (2 * 3 if packed else 0), kv_moves
    if not packed:
        assert not kv_moves, kv_moves
    assert tick.alias_bytes >= 2 * (2 * 3 * kv_layer + 9 * tail_layer), \
        tick.alias_bytes
    assert tick.temp_bytes < 2 * kv_layer, tick.temp_bytes


# -- a recurrent state a slot (ISSUE 40) -------------------------------------
#
# ``nemotron-3-super-120b-a12b``: five state-space layers' state ``(5, 64, 64,
# 128, 128)`` float32 (1.34 GB: 128 heads x 64 x 128 a slot a layer, two heads
# a row of lanes) and conv tails ``(5, 64, 30720)`` beside ONE attention
# layer's K/V pool ``(1, N, 2, 64, 128)``, carried whole through four runs of
# layers. The program's own parameter count at the published widths is the
# configuration file's arithmetic; the compile for the chip copies neither
# the state, the tails nor the K/V pool: a decode tick's states go through
# ``ssm_decode_update`` in place, a chunk member's through ``ssm_chunk_scan``
# in place.

STATE_CONFIG = "nemotron-3-super-120b-a12b"


@pytest.mark.parametrize("tq,packed", [(1, False), (256, True)],
                         ids=["tq1", "packed256"])
def test_state_step_compiles_and_keeps_the_pools_in_place(tq, packed):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from tree_attention_tpu.models import decode
    from tree_attention_tpu.models.transformer import init_params

    c, cfg = _model(STATE_CONFIG)
    slots, blk = c["serving"]["slots"], c["serving"]["kv_block"]
    blocks = _pool_blocks(STATE_CONFIG)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == pytest.approx(4648.2e6, rel=0.001)
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, slots, c["serving"]["cache_len"], blocks, block=blk))
    assert cache.k.shape == (1, blocks, 2, blk, 128)
    assert cache.ssm_state.shape == (5, slots, 64, 128, 128)
    assert cache.ssm_state.dtype == jnp.float32
    assert cache.ssm_tail.shape == (5, slots, 3 * 10240)
    tick = _tick_program(STATE_CONFIG, tq, packed=packed)
    text = tick.text
    kernels = pallas_kernels(text)
    assert {"flash_decode_paged", "moe_ungated_matmul",
            "ssm_decode_update"} <= set(kernels), kernels
    assert "moe_grouped_matmul" not in kernels, kernels
    if packed:
        # (The ONE chunk member's rows of heads number 64, as the slots do,
        # and its chunk 256 rows: the scan kernel's operands are no padded
        # rows.)
        padding = [(dt, dims) for dt, dims in _padding_arrays(
            text, slots, tq, cfg.vocab_size, cfg.d_model)
            if dims[:2] != (1, 64)]
        assert not padding, padding
    state_layer = slots * 64 * 128 * 128
    tails, kv_layer = 5 * slots * 3 * 10240, blocks * 2 * blk * 128
    moved, in_place = [], []
    for name, result, opcode, inner in _materialised(text):
        if opcode in _MOVES_NOTHING:
            continue
        sizes = {dt: max((math.prod(int(d) for d in dims.split(","))
                          for dims in re.findall(rf"\b{dt}\[([\d,]+)\]",
                                                 result)), default=0)
                 for dt in ("f32", "bf16")}
        if sizes["f32"] >= state_layer:
            # The state: the kernel's aliased output, or the update of a
            # chunk member's one slice in place.
            if name.startswith(("%ssm_decode_update", "%ssm_chunk_scan")):
                in_place.append(name)
            else:
                moved.append((name, opcode, result))
        elif sizes["bf16"] >= min(tails, kv_layer) and opcode == "copy":
            # A change of layout of the tails or of the K/V pool. (The
            # compiler stages the 20 MB of tails in fast memory and back,
            # ``copy-start`` / ``slice-start`` into ``S(1)``: its own
            # prefetch, no other layout.)
            moved.append((name, opcode, result))
    assert not moved, moved
    # Five decode launches a tick (a run of three layers is one loop: one
    # launch in its body); a chunk group's scan likewise one launch a run
    # (ISSUE 51: the members' states go from and into the pool inside it; no
    # gather, no scatter, no update of a slice is left of them).
    launches = [n for n in in_place if n.startswith("%ssm_decode_update")]
    assert len(launches) == 3, in_place
    assert len(in_place) - len(launches) == (3 if packed else 0), in_place
    assert ("ssm_chunk_scan" in kernels) == packed, kernels
    assert _row_writes(text) == 1
    assert tick.alias_bytes >= 4 * 5 * state_layer + 2 * tails \
        + 2 * 2 * kv_layer, tick.alias_bytes
    assert tick.temp_bytes < state_layer, tick.temp_bytes


# ``falcon-h1-34b-instruct``: EVERY layer holds both, nine layers' state ``(9,
# 48, 32, 256, 128)`` float32 (1.81 GB: 32 heads x 128 x 256 a slot a layer, a
# head a row of lanes) and tails ``(9, 48, 15360)`` beside nine layers' K/V
# pool ``(9, N, 4, 64, 128)``, all four carried whole through ONE run of
# layers under one body that calls both branches.

PARALLEL_CONFIG = "falcon-h1-34b-instruct"


@pytest.mark.parametrize("tq,packed", [(1, False), (256, True)],
                         ids=["tq1", "packed256"])
def test_parallel_step_compiles_and_keeps_the_four_pools_in_place(tq, packed):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    c, cfg = _model(PARALLEL_CONFIG)
    slots, blk = c["serving"]["slots"], c["serving"]["kv_block"]
    blocks = _pool_blocks(PARALLEL_CONFIG)
    tick = _tick_program(PARALLEL_CONFIG, tq, packed=packed)
    text = tick.text
    kernels = pallas_kernels(text)
    assert {"flash_decode_paged", "ssm_decode_update", ROW_WRITE} \
        <= set(kernels), kernels
    if packed:
        # (A state is 256 wide, as the chunk is long: the state pool and the
        # decode group's B and C rows are no padded rows.)
        padding = [(dt, dims) for dt, dims in _padding_arrays(
            text, slots, tq, cfg.vocab_size, cfg.d_model)
            if dims[-2:] != (256, 128) and dims != (slots, 2, 256)]
        assert not padding, padding
    state_layer = slots * 32 * 256 * 128
    tails, kv_layer = 9 * slots * 3 * 5120, blocks * 4 * blk * 128
    moved, in_place = [], []
    for name, result, opcode, inner in _materialised(text):
        if opcode in _MOVES_NOTHING:
            continue
        sizes = {dt: max((math.prod(int(d) for d in dims.split(","))
                          for dims in re.findall(rf"\b{dt}\[([\d,]+)\]",
                                                 result)), default=0)
                 for dt in ("f32", "bf16")}
        if sizes["f32"] >= state_layer:
            if name.startswith(("%ssm_decode_update", "%ssm_chunk_scan")):
                in_place.append(name)
            else:
                moved.append((name, opcode, result))
        elif sizes["bf16"] >= min(tails, kv_layer) and opcode == "copy":
            moved.append((name, opcode, result))
    assert not moved, moved
    # One run of nine layers is one loop: one decode launch in its body, and
    # one of the chunk group's scan.
    launches = [n for n in in_place if n.startswith("%ssm_decode_update")]
    assert len(launches) == 1, in_place
    assert len(in_place) - len(launches) == (1 if packed else 0), in_place
    assert ("ssm_chunk_scan" in kernels) == packed, kernels
    assert _row_writes(text) == 1
    assert tick.alias_bytes >= 4 * 9 * state_layer + 2 * tails \
        + 2 * 2 * 9 * kv_layer, tick.alias_bytes
    assert tick.temp_bytes < state_layer, tick.temp_bytes


# -- layers whose block counts differ: two pools under two tables (ISSUE 38) -
#
# ``k-exaone-236b-a23b``: two full-attention layers' K/V pools ``(2, N, 8, 64,
# 128)`` under the engine's table and six sliding-window layers' ``(6, Nw, 8,
# 64, 128)`` under a second one, both carried whole through five runs of
# layers. The program's own parameter count at the published widths is the
# configuration file's arithmetic; the compile for the chip copies neither
# pool, and every window layer's attention is the kernel named
# ``window_decode_paged``, at Tq 1 and for a chunk's 256 rows alike.


@pytest.mark.parametrize("tq,packed", [(1, False), (256, True), (16, True)],
                         ids=["tq1", "packed256", "packed16"])
def test_window_step_compiles_and_copies_neither_pool(tq, packed):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from tree_attention_tpu.models import decode
    from tree_attention_tpu.models.transformer import init_params

    name = "k-exaone-236b-a23b"
    c, cfg = _model(name)
    slots, blk = c["serving"]["slots"], c["serving"]["kv_block"]
    blocks = _pool_blocks(name)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == pytest.approx(3.8655e9, rel=0.0002)
    wblocks = slots * 8
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, slots, c["serving"]["cache_len"], blocks, block=blk,
        window_blocks=wblocks))
    assert cache.k.shape == (2, blocks, 8, blk, 128)
    assert cache.wk.shape == (6, wblocks, 8, blk, 128)
    assert cache.wtable.shape == cache.table.shape == (slots, 144)
    tick = _tick_program(name, tq, packed=packed)
    text = tick.text
    kernels = pallas_kernels(text)
    assert "window_decode_paged" in kernels, kernels
    assert any(k.startswith("flash_decode_paged") for k in kernels), kernels
    assert "moe_grouped_matmul" in kernels, kernels
    if packed:
        padding = _padding_arrays(text, slots, tq, cfg.vocab_size,
                                  cfg.d_model)
        assert not padding, padding
    block_elems = 8 * blk * 128
    full_layer, win_layer = blocks * block_elems, wblocks * block_elems
    # What the Q-tiled kernel gathers for a chunk of 128 rows or more on a
    # FULL layer: one member's logical view, far under a layer of the pool.
    view = (c["serving"]["cache_len"] // blk) * block_elems
    assert view < win_layer < full_layer
    experts = cfg.moe.held * cfg.d_model * cfg.moe.width
    moved, writes = [], []
    for iname, result, opcode, inner in _materialised(text):
        if opcode in _MOVES_NOTHING:
            continue
        sizes = [math.prod(int(d) for d in dims.split(","))
                 for dims in re.findall(r"\bbf16\[([\d,]+)\]", result)
                 if dims.endswith(f",8,{blk},128")]
        of_experts = [math.prod(int(d) for d in dims.split(","))
                      for dims in re.findall(r"\bbf16\[([\d,]+)\]", result)
                      if dims.endswith((f"{cfg.d_model},{cfg.moe.width}",
                                        f"{cfg.moe.width},{cfg.d_model}"))]
        if max(sizes, default=0) < win_layer \
                and max(of_experts, default=0) < experts:
            continue
        if opcode == "scatter" or " scatter(" in inner:
            writes.append(iname)     # the pools' writes, in place (below)
        elif not iname.startswith(f"%{ROW_WRITE}"):
            moved.append((iname, opcode, result))
    # No copy of either kind's K/V pools (whole or a layer of one), no
    # slice of a layer's experts out of their stack.
    assert not moved, moved
    # Once in each of the 2 full layers (runs of one) and once in each of
    # the 3 runs of window layers: K's and V's scatters for a chunk group,
    # one launch of the row kernel for a row a slot (both tables' pools take
    # it) and no block of either kind's pools gathered or scattered.
    assert len(writes) == (2 * (2 + 3) if packed else 0), writes
    assert _row_writes(text) == 2 + 3
    kv_moves = _block_moves(text, f"8,{blk},128")
    assert len([m for m in kv_moves if m[1] == "scatter"]) == len(writes)
    if not packed:
        assert not kv_moves, kv_moves
    assert tick.alias_bytes >= 2 * 2 * (2 * full_layer + 6 * win_layer), \
        tick.alias_bytes
    assert tick.temp_bytes < full_layer * 2, tick.temp_bytes


# -- two kinds of row for the same tokens: an EVA model's pools (ISSUE 44) ---
#
# ``evabyte``: every one of the 8 layers' exact rows ``(8, 608, 32, 64, 128)``
# under the second table and its summary rows ``(8, 256, 32, 64, 128)`` under
# the first, both carried whole through one loop. The program's own parameter
# count at the published widths is the configuration file's arithmetic; the
# compile for the chip copies neither pool; a layer's attention is the two
# kernels ``eva_local_decode`` and ``eva_summary_decode`` at Tq 1 and for a
# chunk's 256 rows alike; a row a slot reaches BOTH pools through the row
# kernel (the summary row under a count that is 1 for one slot in sixteen).


@pytest.mark.parametrize("tq,packed", [(1, False), (256, True), (16, True)],
                         ids=["tq1", "packed256", "packed16"])
def test_eva_step_compiles_and_copies_neither_pool(tq, packed):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from tree_attention_tpu.models import decode
    from tree_attention_tpu.models.transformer import init_params

    name = "evabyte"
    c, cfg = _model(name)
    slots, blk = c["serving"]["slots"], c["serving"]["kv_block"]
    blocks = _pool_blocks(name)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == pytest.approx(1.6309e9, rel=0.0002)
    wblocks = slots * 38
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, slots, c["serving"]["cache_len"], blocks, block=blk,
        window_blocks=wblocks))
    assert cache.k.shape == (8, 256, 32, blk, 128)
    assert cache.wk.shape == (8, wblocks, 32, blk, 128)
    assert (cache.table.shape, cache.wtable.shape) == (
        (slots, 16), (slots, 256))
    tick = _tick_program(name, tq, packed=packed)
    text = tick.text
    kernels = pallas_kernels(text)
    assert {"eva_local_decode", "eva_summary_decode"} <= set(kernels), kernels
    assert not any(k.startswith(("flash_decode_paged", "window_decode_paged"))
                   for k in kernels), kernels
    if packed:
        # (A decode group's chunk reads are (slots, heads, chunk, lanes): 16
        # slots x 16 rows of a chunk, no (slots, Tq) activation at Tq 16.)
        padding = [a for a in _padding_arrays(
            text, slots, tq, cfg.vocab_size, cfg.d_model)
            if a[1][:3] != (slots, 32, cfg.chunk)]
        assert not padding, padding
    block_elems = 32 * blk * 128
    sum_layer, local_layer = blocks * block_elems, wblocks * block_elems
    moved, writes = [], []
    for iname, result, opcode, inner in _materialised(text):
        if opcode in _MOVES_NOTHING:
            continue
        sizes = [math.prod(int(d) for d in dims.split(","))
                 for dims in re.findall(r"\bbf16\[([\d,]+)\]", result)
                 if dims.endswith(f",32,{blk},128")]
        if max(sizes, default=0) < sum_layer:
            continue
        if opcode == "scatter" or " scatter(" in inner:
            writes.append(iname)     # the pools' writes, in place (below)
        elif not iname.startswith(f"%{ROW_WRITE}"):
            moved.append((iname, opcode, result))
    # No copy of either pool, whole or a layer of one.
    assert not moved, moved
    # One loop over the 8 layers: a chunk group's rows into both pools by
    # K's and V's scatters, a row a slot into both by the row kernel; a
    # chunk group of 16 rows closes ONE chunk a member, a row a member too.
    # (The compiler's scheduler may run a group's small scatter twice, its
    # ".remat" copy in place on the same buffer: no second pool, below.)
    one_chunk = packed and tq <= cfg.chunk
    writes = [w for w in writes if not w.endswith(".remat")]
    assert len(writes) == (0 if not packed else 2 if one_chunk else 4), writes
    assert _row_writes(text) == 2 + one_chunk
    assert tick.alias_bytes >= 2 * 2 * 8 * (sum_layer + local_layer), \
        tick.alias_bytes
    assert tick.temp_bytes < sum_layer * 2, tick.temp_bytes


# -- the attention input projections: read where they lie (ISSUE 34) --------
#
# The compiler multiplies by ``wq`` / ``wk`` / ``wv`` and a latent layer's
# ``wqb`` with the contracted axis minor. Handed the outer format ``(L, in,
# out)`` every layer of every tick sliced the weight out of its stack into
# fast memory and transposed it there in a ``copy`` before the product (32 MB
# + 2 x 4 MB a layer at Yi-6B's widths, 0.65-0.9 ms a tick in four cells;
# ``deepseek-v2``'s 75 MB slice did not fit and made a round trip through HBM
# first). The engine serves from ``served_layout``'s form, and the programs
# compiled from it must hold neither. A latent layer's ``wqb_t`` went on
# through fast memory all the same (a ``kLoop`` slice of the layer's 38-75
# MB, then the product from there, one after the other) until
# ``latent_qkv`` held its reshape to heads off the product (ISSUE 41): the
# latent programs must hold no one-layer ``wqb_t`` at all.

ALL_CONFIGS = STEP_CONFIGS + LATENT_CONFIGS + (
    "lfm2-8b-a1b", "k-exaone-236b-a23b", "nemotron-3-super-120b-a12b",
    "evabyte", "falcon-h1-34b-instruct")
# Tq 1 and the packed programs at both ends of the chunk buckets.
TICK_PROGRAMS = {"tq1": (1, False), "packed16": (16, True),
                 "packed256": (256, True)}


def _projection_dims(cfg):
    """The dimensions of a layer's attention input projections, in either
    form and either order."""
    if cfg.mla is not None:
        dims = {(cfg.mla.q_rank or cfg.d_model,
                 cfg.n_heads * (cfg.mla.nope + cfg.mla.rope))}
    else:
        dims = {(cfg.d_model, out) for out in (
            cfg.q_dim, cfg.kv_dim, cfg.q_dim + 2 * cfg.kv_dim)}
    return dims | {d[::-1] for d in dims}


def _moved_projections(text, cfg):
    """What the module holds of a projection weight beside the stack itself:
    a ``copy`` of one layer's (the transposition), or any other result of
    its dimensions that lies in HBM (the slice that did not fit fast
    memory). For a latent configuration (ISSUE 41) a one-layer ``wqb_t`` in
    ANY memory space: the ``kLoop`` slice of a layer into fast memory,
    ``S(1)``, was the weight's one read, but nothing ran under it and the
    product from there took as long again, where a product whose fusion
    takes the stack reads the layer's rows in place. What stays allowed
    there is the asynchronous ``copy-start`` / ``copy-done`` of a one-layer
    stack (``deepseek-v2``'s leading dense layer, prefetched across
    programs). The other configurations' slices into ``S(1)`` stay where the
    compiler makes one. The packed programs hold larger copies of gathered
    views, which are not weights: the dimensions are matched whole, with or
    without a leading 1."""
    dims = _projection_dims(cfg)
    in_place = cfg.mla is not None
    moved = []
    for name, result, opcode, _ in _materialised(text):
        if opcode in _MOVES_NOTHING:
            continue
        arrays = re.findall(r"\bbf16\[([\d,]+)\](\{[^}]*\})?", result)
        prefetch = opcode in ("copy-start", "copy-done")
        if opcode == "copy-start":
            # (destination, source, context): a one-layer stack prefetched
            # from the parameter itself, the same read by another name.
            arrays = arrays[:1]
        for shape, layout in arrays:
            shape = tuple(int(d) for d in shape.split(","))
            if shape[:1] == (1,):
                shape = shape[1:]
            staged = "S(1)" in layout and (prefetch or not in_place)
            if shape in dims and (opcode == "copy" or not staged):
                moved.append((name, opcode, result))
    return moved


@pytest.mark.parametrize("program", sorted(TICK_PROGRAMS))
@pytest.mark.parametrize("config", ALL_CONFIGS)
def test_no_tick_program_moves_a_projection_weight(config, program):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    tq, packed = TICK_PROGRAMS[program]
    moved = _moved_projections(_tick_program(config, tq, packed=packed).text,
                               _model(config)[1])
    assert not moved, moved


def test_the_guard_catches_the_outer_format():
    """The control: handed ``init_params``' own layout, which the engine
    never hands a program, a dense layer's three projections are each copied
    transposed, as they were in every tick before the engine re-laid them."""
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    moved = _moved_projections(
        _tick_program("yi-6b", 1, served=False).text, _model("yi-6b")[1])
    assert sorted(op for _, op, _ in moved) == ["copy"] * 3, moved


@pytest.mark.parametrize("config", LATENT_CONFIGS)
def test_the_guard_catches_the_reshape_folded_into_the_product(
        config, monkeypatch):
    """The control for the latent rule (ISSUE 41): with ``latent_qkv``'s
    barrier taken out, the compiler folds the reshape to heads into the
    product, as it did until then, and every layer body that holds a latent
    sublayer slices that layer's whole ``wqb_t`` into fast memory first: one
    ``kLoop`` fusion a sublayer of the loop's body (``deepseek-v2``'s one
    expert body; ``longcat-flash-omni``'s two sublayers a double layer)."""
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from jax import lax

    cfg, barrier = _model(config)[1], lax.optimization_barrier
    with monkeypatch.context() as m:
        # The work lists' barrier (a tuple: ``_plan_groups``) stays.
        m.setattr(lax, "optimization_barrier",
                  lambda x: barrier(x) if isinstance(x, tuple) else x)
        text = _tick_program.__wrapped__(config, 1).text     # not memoised
    moved = _moved_projections(text, cfg)
    assert len(moved) == cfg.sublayers, moved
    assert all(op == "fusion" and "S(1)" in result
               for _, op, result in moved), moved


# -- the parts of the model, named in the compiled programs (ISSUE 35) -------
#
# The layer bodies wrap their seams in ``jax.named_scope`` with one
# vocabulary (``obs/scopes.py``), and the engine reads the optimized module's
# ``op_name`` metadata back into a table from each operation to its part. A
# scope is only worth its name if the compiler for the chip keeps it: on the
# fusions it makes, and near enough to the copies and slices it adds (which
# carry no scope of their own and take their users').


def _scopes_expected(cfg, packed):
    from tree_attention_tpu.obs import scopes

    want = {scopes.EMBED, scopes.ATTN_IN, scopes.ATTN_CACHE,
            scopes.ATTN_DECODE, scopes.ATTN_OUT, scopes.FFN, scopes.HEAD}
    if packed:
        want.add(scopes.ATTN_CHUNK)
    if cfg.moe is not None:
        want |= {scopes.ROUTE, scopes.EXPERTS}
    if cfg.conv_layers or cfg.ssm_layers:
        want.add(scopes.CONV)
    return want


@pytest.mark.parametrize("program", sorted(TICK_PROGRAMS))
@pytest.mark.parametrize("config", ALL_CONFIGS)
def test_tick_programs_keep_the_scopes(config, program):
    """Of the instructions that run as device operations of their own, those
    that resolve to a name of the vocabulary (by their own ``op_name`` or
    their neighbours') hold at least 95% of the result bytes, and every
    scope the configuration has is found at least once."""
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from tree_attention_tpu.obs import scopes

    tq, packed = TICK_PROGRAMS[program]
    text = _tick_program(config, tq, packed=packed).text
    leaf = [i for i in scopes.resolve(scopes.instructions(text))
            if i.opcode not in scopes.MOVES_NOTHING | scopes.ENCLOSES]
    assert len(leaf) > 50
    named = [i for i in leaf if i.scope]
    assert {i.scope.split("/")[0] for i in named} \
        == _scopes_expected(_model(config)[1], packed)
    # A result no instruction reads belongs to no part: the compiler leaves
    # one dead prefetch in ``deepseek-v2``'s programs (the one-layer dense
    # stack's ``wqb_t``, which the product then reads in place), 75 MB.
    read = {(i.computation, o) for i in scopes.instructions(text)
            for o in i.operands}
    dead = [i for i in leaf if not i.scope and i.opcode == "copy-done"
            and (i.computation, i.op) not in read]
    # (And one in ``evabyte``'s packed program: the 320-row embedding, 2.6
    # MB, staged in fast memory whole where the gather then reads it.)
    assert len(dead) <= (config in ("deepseek-v2", "evabyte")), dead
    total = sum(i.nbytes for i in leaf) - sum(i.nbytes for i in dead)
    share = sum(i.nbytes for i in named) / total
    assert share >= 0.95, (share, sorted(
        ((i.nbytes, i.op, i.result) for i in leaf if not i.scope),
        reverse=True)[:10])
    # By count as well: what is left is the step's own address arithmetic
    # (the groups' tables and lengths, which every layer reads), small
    # prefetches shared by several parts and the loops' counters: a quarter
    # of the operations at most.
    assert len(named) >= 0.75 * len(leaf), (len(named), len(leaf))
    # The kernels are rows under their own names, in their scopes.
    kernels = {i.op.rsplit(".", 1)[0]: i.scope.split("/")[0]
               for i in leaf if i.opcode == "custom-call" and i.scope}
    cfg = _model(config)[1]
    if cfg.mla is not None:
        assert kernels["mla_decode_paged"] in (
            scopes.ATTN_DECODE, scopes.ATTN_CHUNK)
    elif cfg.eva_layers:
        # An EVA layer's two calls, the summaries' read-back inside the
        # write's part, and no other paged kernel's name.
        for name in ("eva_local_decode", "eva_summary_decode"):
            assert kernels[name] in (scopes.ATTN_DECODE, scopes.ATTN_CHUNK)
        assert kernels["paged_chunk_read"] == scopes.ATTN_CACHE
        assert "flash_decode_paged" not in kernels
    else:
        assert kernels["flash_decode_paged"] == scopes.ATTN_DECODE
    if cfg.window_layers:
        # A window layer's calls: the decode group's, and the chunk
        # group's at every Tq (the last row wins in this dict).
        assert kernels["window_decode_paged"] in (
            scopes.ATTN_DECODE, scopes.ATTN_CHUNK)
    if cfg.moe is not None:
        assert kernels["moe_grouped_matmul" if cfg.moe.gated
                       else "moe_ungated_matmul"] == scopes.EXPERTS
    if cfg.ssm_layers:
        assert kernels["ssm_decode_update"] == scopes.CONV
        # A chunk group's scan is one kernel in the same part (ISSUE 51).
        assert kernels.get("ssm_chunk_scan") == (
            scopes.CONV if packed else None)
    # The decode group's row a slot reaches the pool inside the write's part.
    assert kernels[ROW_WRITE] == scopes.ATTN_CACHE


# -- the paged kernels' work lists: built once a tick (ISSUE 37) -------------
#
# The paged decode kernels walk a list of the (slot, step) pairs that hold a
# live token, and the list's length is their grid's dynamic bound. The list
# follows from a group's lengths, not from the layer, so a step program
# builds it before its layer loop (``models/decode.py`` ``_plan_groups``) and
# a layer's call only shifts the table in it. Built inside the call, the
# compiler left about eight small operations of it in the loop's body: a
# tenth of what the list saves, in every layer.


@pytest.mark.parametrize("program", sorted(TICK_PROGRAMS))
@pytest.mark.parametrize("config", ALL_CONFIGS)
def test_tick_programs_build_the_work_lists_outside_the_layer_loops(
        config, program):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    from tree_attention_tpu.obs import scopes
    from tree_attention_tpu.ops.pallas_decode import PLAN_SCOPE

    tq, packed = TICK_PROGRAMS[program]
    text = _tick_program(config, tq, packed=packed).text
    kernel = "mla_decode_paged" if _model(config)[1].mla is not None \
        else "eva_local_decode" if _model(config)[1].eva_layers \
        else "flash_decode_paged"
    # Every launch on the list's dynamic bound: the decode group's, and the
    # chunk group's where the paged kernel serves it.
    grids = _dynamic_grids(text, kernel)
    assert grids and all(grids), grids
    if _model(config)[1].eva_layers:
        # Two plans a group, one a pool: the summary rows' launches run on
        # their own list's bound, the chunk group's at every Tq.
        sgrids = _dynamic_grids(text, "eva_summary_decode")
        assert len(sgrids) == len(grids) == (2 if packed else 1) \
            and all(sgrids), (grids, sgrids)
    if _model(config)[1].window_layers:
        # Two plans a tick, one a kind: the window layers' launches run on
        # their own list's bound, the chunk group's at every Tq.
        wgrids = _dynamic_grids(text, "window_decode_paged")
        assert len(wgrids) >= (2 if packed else 1) and all(wgrids), wgrids
    entry = re.search(r"^ENTRY (%[\w.\-]+)", text, re.M).group(1)[1:]
    instrs = list(scopes.instructions(text))
    plans = [i for i in instrs if f"/{PLAN_SCOPE}/" in f"/{i.scope}/"]
    assert plans, "no operation of a plan is named in the module"
    assert {i.scope.split("/")[0] for i in plans} <= {
        scopes.ATTN_DECODE, scopes.ATTN_CHUNK}
    # The computations that launch the kernel from inside a loop: the layer
    # loops' bodies (the hybrid's attention layers are runs of one, no loop).
    bodies = {i.computation for i in instrs
              if i.opcode == "custom-call"
              and i.op.startswith((kernel, "window_decode_paged",
                                   "eva_summary_decode"))
              and i.computation != entry}
    if config not in ("lfm2-8b-a1b", STATE_CONFIG):
        assert bodies
    inside = [(i.computation, i.op, i.scope) for i in plans
              if i.computation in bodies]
    assert not inside, inside
    # The conv layers' tail targets likewise (ISSUE 48): built where the
    # model has conv layers and nowhere else, and not in the body of a loop
    # that launches the step.
    from tree_attention_tpu.ops.pallas_conv import TAIL_PLAN_SCOPE

    targets = [i for i in instrs if f"/{TAIL_PLAN_SCOPE}/" in f"/{i.scope}/"]
    assert bool(targets) == bool(_model(config)[1].conv_layers), targets[:3]
    steps = {i.computation for i in instrs if i.opcode == "custom-call"
             and i.op.startswith("conv_tail_step")}
    assert bool(steps) == bool(targets)
    if targets:
        # (A run of ONE conv layer is no loop: its launch sits in the entry
        # computation, where the targets are built.)
        assert steps - {entry}
        assert {i.scope.split("/")[0] for i in targets} == {scopes.CONV}
        inside = [(i.computation, i.op, i.scope) for i in targets
                  if i.computation in steps - {entry}]
        assert not inside, inside
