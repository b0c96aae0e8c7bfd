"""Memory-boundedness of the chunked-gather tree_attention (VERDICT r2 item 3).

The previous form materialised the all-gathered Q (and its f32 numerator) at
*global* length on every device — O(T·D) per device, ~12 GB at the 1M-ctx
north star. The chunked form gathers ``q_chunk`` local rows at a time, so the
gathered transient is O(``n_shards·q_chunk·D``) and per-device peak memory
stays bounded as the global context grows.

These tests pin that property two ways: exact numerics equivalence of the
chunked path against the one-chunk path (including a non-dividing tail
chunk), and XLA ``memory_analysis`` bounds — chunking must strictly shrink
the compiled temp arena, and at fixed global T a *larger* mesh must not need
more per-device temp.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.ops import attention_naive
from tree_attention_tpu.parallel import (
    cpu_mesh,
    shard_zigzag,
    tree_attention,
    unshard_zigzag,
)
from tests.jitted import jitted

tree_attention = jitted(tree_attention)  # one program a call (tests/jitted.py)


def _qkv(rng, B=1, H=2, T=512, D=32, dtype=np.float32):
    mk = lambda: jnp.asarray(
        rng.standard_normal((B, H, T, D), np.float32).astype(dtype)
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("q_chunk", [64, 48])  # 48 does not divide 128: tail chunk
def test_chunked_matches_unchunked(layout, causal, q_chunk):
    rng = np.random.default_rng(0)
    n = 4
    q, k, v = _qkv(rng)
    if layout == "zigzag":
        q, k, v = (shard_zigzag(x, 2, n) for x in (q, k, v))
    mesh = cpu_mesh(n)
    # impl="naive": the inner kernel is mostly irrelevant to chunk
    # equivalence and the scan-free oracle keeps the many per-run
    # compilations cheap; test_chunked_blockwise_integration below keeps
    # one multi-chunk case on the blockwise kernel.
    run = functools.partial(
        tree_attention, mesh=mesh, causal=causal, layout=layout,
        impl="naive",
    )
    out_1, lse_1 = run(q, k, v, q_chunk=None)  # auto: one chunk at this size
    out_c, lse_c = run(q, k, v, q_chunk=q_chunk)
    np.testing.assert_allclose(
        np.asarray(out_c), np.asarray(out_1), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lse_c), np.asarray(lse_1), atol=2e-5, rtol=2e-5
    )


def test_chunked_blockwise_integration():
    """One multi-chunk (with tail) causal case on the *blockwise* kernel:
    the chunked q_off plumbing must agree with the scan kernel's own
    per-block masking/culling, not just the naive oracle's."""
    rng = np.random.default_rng(5)
    n = 4
    q, k, v = _qkv(rng, T=256)
    ref_out, ref_lse = attention_naive(q, k, v, causal=True)
    out, lse = tree_attention(
        q, k, v, mesh=cpu_mesh(n), causal=True, impl="blockwise",
        block_size=32, q_chunk=48,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5
    )


def test_chunked_matches_oracle_causal():
    """Chunked + zigzag + tail chunk against the unsharded oracle."""
    rng = np.random.default_rng(1)
    n = 4
    q, k, v = _qkv(rng, T=256)
    ref_out, ref_lse = attention_naive(q, k, v, causal=True)
    qz, kz, vz = (shard_zigzag(x, 2, n) for x in (q, k, v))
    out, lse = tree_attention(
        qz, kz, vz, mesh=cpu_mesh(n), causal=True, layout="zigzag",
        impl="naive", q_chunk=24,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(out, 2, n)), np.asarray(ref_out),
        atol=2e-5, rtol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(lse, 2, n)), np.asarray(ref_lse),
        atol=2e-5, rtol=2e-5,
    )


def _temp_bytes(mesh, q, k, v, q_chunk):
    f = jax.jit(
        functools.partial(
            tree_attention, mesh=mesh, causal=True, impl="blockwise",
            block_size=64, q_chunk=q_chunk,
        )
    )
    ma = f.lower(q, k, v).compile().memory_analysis()
    if ma is None:
        pytest.skip("backend exposes no memory_analysis")
    return ma.temp_size_in_bytes


def test_chunking_shrinks_temp_arena():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, T=8192, D=64)
    mesh = cpu_mesh(8)
    unchunked = _temp_bytes(mesh, q, k, v, q_chunk=None)
    chunked = _temp_bytes(mesh, q, k, v, q_chunk=256)
    assert chunked < unchunked, (chunked, unchunked)


def test_temp_flat_or_shrinking_as_mesh_grows():
    """Fixed global T, fixed chunk: more shards must not need more temp.

    This is the scaling property the all-gather form violated: its gathered
    transient was O(T_global) per device regardless of mesh size.
    """
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, T=8192, D=64)
    t2 = _temp_bytes(cpu_mesh(2), q, k, v, q_chunk=256)
    t8 = _temp_bytes(cpu_mesh(8), q, k, v, q_chunk=256)
    assert t8 <= t2, (t8, t2)


@pytest.mark.slow
def test_256k_ctx_train_shape_step_on_8cpu_mesh():
    """A 256k-token causal training-shape forward on the 8-device CPU mesh.

    The point is feasibility (VERDICT r2 item 3): the previous all-gather
    form materialised the global Q and its f32 numerator on every device —
    at this length that transient alone dwarfs the per-device shard — and
    did the full unculled T² work. With chunked gathering and live-FLOP
    culling the step runs in slow-tier time. Correctness is pinned on the
    first rows, whose causal receptive field is small enough for an exact
    oracle: row r attends keys [0, r], so rows [0, 128) of the sharded
    output must equal unsharded attention over the first 128 keys.
    """
    T, n, D = 1 << 18, 8, 16
    rng = np.random.default_rng(4)
    mk = lambda: jnp.asarray(
        rng.standard_normal((1, 1, T, D), np.float32), jnp.float32
    )
    q, k, v = mk(), mk(), mk()
    out, lse = tree_attention(
        q, k, v, mesh=cpu_mesh(n), causal=True, impl="blockwise",
        block_size=2048, q_chunk=4096,
    )
    out = np.asarray(out)
    lse = np.asarray(lse)
    # Full-array sanity first: a NaN from any later chunk's merge fails here.
    assert np.isfinite(out).all() and np.isfinite(lse).all()
    out = out[:, :, :128]
    lse = lse[:, :, :128]
    ref_out, ref_lse = attention_naive(
        q[:, :, :128], k[:, :, :128], v[:, :, :128], causal=True
    )
    np.testing.assert_allclose(out, np.asarray(ref_out), atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(lse, np.asarray(ref_lse), atol=3e-5, rtol=3e-5)


def test_chunked_zigzag_gqa_matches_oracle():
    """GQA (Hq != Hkv) through the chunked zigzag training path: the run
    decomposition slices only the sequence dim, so grouped KV must flow
    through segments, dispatch and merge unchanged."""
    rng = np.random.default_rng(6)
    n, T, D = 4, 256, 16
    q = jnp.asarray(rng.standard_normal((2, 8, T, D), np.float32))
    k = jnp.asarray(rng.standard_normal((2, 2, T, D), np.float32))
    v = jnp.asarray(rng.standard_normal((2, 2, T, D), np.float32))
    ref_out, ref_lse = attention_naive(q, k, v, causal=True)
    qz, kz, vz = (shard_zigzag(x, 2, n) for x in (q, k, v))
    out, lse = tree_attention(
        qz, kz, vz, mesh=cpu_mesh(n), causal=True, layout="zigzag",
        impl="naive", q_chunk=24,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(out, 2, n)), np.asarray(ref_out),
        atol=2e-5, rtol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(lse, 2, n)), np.asarray(ref_lse),
        atol=2e-5, rtol=2e-5,
    )
