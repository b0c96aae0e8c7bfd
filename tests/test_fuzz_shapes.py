"""Seeded random-shape fuzz over the flash_attention contract.

Every impl must agree with the exact oracle on arbitrary (B, Hq, Hkv, Tq,
Tk, D, causal, offsets) combinations — ragged tile tails, GQA group sizes,
cross-shard offsets, tiny and lopsided extents. Deterministic seeds so a
failure reproduces exactly.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from tree_attention_tpu.ops import attention_naive, flash_attention

IMPLS = ("blockwise", "pallas", "pallas_decode")


def _rand_case(rng):
    B = int(rng.integers(1, 3))
    Hkv = int(rng.choice([1, 2, 3]))
    G = int(rng.choice([1, 2, 4]))
    Hq = Hkv * G
    Tq = int(rng.integers(1, 70))
    Tk = int(rng.integers(1, 700))
    D = int(rng.choice([8, 16, 32]))
    causal = bool(rng.integers(0, 2))
    # Offsets: unsharded decode-style or shard-style (kv block not at 0).
    if causal:
        q_offset = int(rng.integers(0, Tk + Tq))
        kv_offset = int(rng.integers(0, 2)) * int(rng.integers(0, Tk))
    else:
        q_offset = kv_offset = 0
    block = int(rng.choice([16, 64, 256]))
    return B, Hq, Hkv, Tq, Tk, D, causal, q_offset, kv_offset, block


@pytest.mark.parametrize("seed", range(10))
@pytest.mark.parametrize("impl", IMPLS)
def test_fuzz_matches_oracle(seed, impl):
    rng = np.random.default_rng(1000 + seed)
    B, Hq, Hkv, Tq, Tk, D, causal, qo, ko, block = _rand_case(rng)
    q = jnp.asarray(rng.standard_normal((B, Hq, Tq, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, Tk, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, Tk, D), np.float32))
    case = f"B={B} Hq={Hq} Hkv={Hkv} Tq={Tq} Tk={Tk} D={D} causal={causal} qo={qo} ko={ko} block={block}"

    out, lse = flash_attention(
        q, k, v, causal=causal, q_offset=qo, kv_offset=ko,
        impl=impl, block_size=block, custom_vjp=False,
    )
    ref_out, ref_lse = attention_naive(
        q, k, v, causal=causal, q_offset=qo, kv_offset=ko
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=5e-5, rtol=5e-5,
        err_msg=case,
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=5e-5, rtol=5e-5,
        err_msg=case,
    )


def _rand_tree_case(rng):
    """Random sharded training-shape case for tree_attention's run/dispatch
    arithmetic: layout, chunking (incl. non-dividing tails), GQA, and
    chunked-prefill Tq < Tk alignments."""
    n = int(rng.choice([2, 4]))
    Hkv = int(rng.choice([1, 2]))
    Hq = Hkv * int(rng.choice([1, 2]))
    D = int(rng.choice([8, 16]))
    layout = str(rng.choice(["contiguous", "zigzag"]))
    # Per-shard lengths; zigzag needs them even.
    tk_l = int(rng.integers(4, 40)) * 2
    tq_l = tk_l if rng.integers(0, 2) else int(rng.integers(2, tk_l // 2 + 1)) * 2
    causal = bool(rng.integers(0, 2))
    q_chunk = int(rng.integers(1, tq_l + 8))  # may exceed tq_l or leave a tail
    return n, Hq, Hkv, D, layout, tq_l * n, tk_l * n, causal, q_chunk


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_tree_attention_matches_oracle(seed):
    """The sharded chunked/culled tree path against the unsharded oracle on
    randomized geometry. Deterministic seeds; the case string reproduces."""
    from tree_attention_tpu.parallel import (
        cpu_mesh, shard_zigzag, tree_attention, unshard_zigzag,
    )

    rng = np.random.default_rng(2000 + seed)
    n, Hq, Hkv, D, layout, Tq, Tk, causal, q_chunk = _rand_tree_case(rng)
    case = (f"n={n} Hq={Hq} Hkv={Hkv} D={D} layout={layout} Tq={Tq} Tk={Tk} "
            f"causal={causal} q_chunk={q_chunk}")
    q = jnp.asarray(rng.standard_normal((1, Hq, Tq, D), np.float32))
    k = jnp.asarray(rng.standard_normal((1, Hkv, Tk, D), np.float32))
    v = jnp.asarray(rng.standard_normal((1, Hkv, Tk, D), np.float32))
    # tree_attention's default q_position is bottom-right aligned (the last
    # query is the last key); mirror it in the oracle.
    ref_out, ref_lse = attention_naive(
        q, k, v, causal=causal, q_offset=Tk - Tq
    )

    if layout == "zigzag":
        qs = shard_zigzag(q, 2, n)
        ks, vs = shard_zigzag(k, 2, n), shard_zigzag(v, 2, n)
    else:
        qs, ks, vs = q, k, v
    from tests.jitted import jitted

    out, lse = jitted(tree_attention)(
        qs, ks, vs, mesh=cpu_mesh(n), causal=causal, layout=layout,
        impl="naive", q_chunk=q_chunk,
    )
    if layout == "zigzag":
        out = unshard_zigzag(out, 2, n)
        lse = unshard_zigzag(lse, 2, n)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=5e-5, rtol=5e-5,
        err_msg=case,
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=5e-5, rtol=5e-5,
        err_msg=case,
    )
