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

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tree_attention_tpu.bench.comm import pallas_kernels
from tree_attention_tpu.ops.pallas_attention import attention_pallas_fwd
from tree_attention_tpu.ops.pallas_bwd import attention_bwd_pallas
from tree_attention_tpu.ops.pallas_decode import (
    attention_pallas_decode,
    attention_pallas_decode_q8,
    attention_pallas_decode_q8q,
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
def _chip():
    """One described v5e chip, or None where the topology cannot be
    described (no TPU compiler in the installation)."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception:  # whatever the plugin raises: no compiler, no test
        return None
    return SingleDeviceSharding(topo.devices[0])


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


def _paged(kernel, tq, *, int8=False, tree=False, **kw):
    """(fn, abstract args) of one paged decode kernel call at Tq = tq."""
    pool = _s((N, HKV, BLK, D), jnp.int8 if int8 else jnp.bfloat16)
    args = [_s((B, HQ, tq, D)), pool, pool]
    if int8:
        args += [_s((N, HKV), jnp.float32)] * 2
    args += [_s((B, NB), jnp.int32), _s((B,), jnp.int32)]
    if tree:
        args.append(_s((B, tq, tq), jnp.bool_))

    def fn(*a):
        *tensors, table, pos = a[:len(a) - tree]
        return kernel(*tensors, causal=True, q_offset=pos, block_table=table,
                      tree_mask=a[-1] if tree else None, interpret=False,
                      **kw)

    return fn, args


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
    "prefill_fwd": (_prefill, "flash_fwd"),
    "train_fwd": (_train_fwd_bwd, "flash_fwd"),
    "bwd_dq": (_train_fwd_bwd, "flash_bwd_dq"),
    "bwd_dkv": (_train_fwd_bwd, "flash_bwd_dkv"),
}


@functools.lru_cache(maxsize=None)
def _compiled_text(builder) -> str:
    fn, args = builder()
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case):
    if _chip() is None:
        pytest.skip("the v5e:2x2 topology cannot be described here")
    builder, kernel = CASES[case]
    text = _compiled_text(builder)
    assert "tpu_custom_call" in text
    assert kernel in pallas_kernels(text), pallas_kernels(text)
