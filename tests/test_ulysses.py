"""Ulysses (all-to-all head-swap) sequence parallelism tests: the third SP
family must compute the identical exact attention as the unsharded oracle
and the tree/ring implementations, and refuse head counts it cannot
re-shard."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.ops import attention_naive
from tree_attention_tpu import parallel
from tree_attention_tpu.parallel import cpu_mesh

from tests.jitted import jitted

# One program a call, as a user's jitted step runs them: eagerly a sharded
# call is a launch a primitive a shard (``tests/jitted.py``).
ring_attention = jitted(parallel.ring_attention)
tree_attention = jitted(parallel.tree_attention)
ulysses_attention = jitted(parallel.ulysses_attention)


def make_qkv(rng, B=2, Hq=8, Hkv=8, Tq=128, Tk=128, D=32, dtype=np.float32):
    q = rng.standard_normal((B, Hq, Tq, D), np.float32).astype(dtype)
    k = rng.standard_normal((B, Hkv, Tk, D), np.float32).astype(dtype)
    v = rng.standard_normal((B, Hkv, Tk, D), np.float32).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("n_shards", [1, 2, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_unsharded(n_shards, causal):
    rng = np.random.default_rng(0)
    q, k, v = make_qkv(rng)
    mesh = cpu_mesh(n_shards)
    out, lse = ulysses_attention(
        q, k, v, mesh=mesh, causal=causal, impl="blockwise"
    )
    ref_out, ref_lse = attention_naive(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5
    )


def test_ulysses_gqa_matches_tree_and_ring():
    """All three SP families produce the identical exact softmax on GQA."""
    rng = np.random.default_rng(1)
    q, k, v = make_qkv(rng, Hq=8, Hkv=4, Tq=64, Tk=64)
    mesh = cpu_mesh(4)
    u_out, u_lse = ulysses_attention(
        q, k, v, mesh=mesh, causal=True, impl="blockwise"
    )
    t_out, t_lse = tree_attention(q, k, v, mesh=mesh, causal=True, impl="blockwise")
    r_out, r_lse = ring_attention(q, k, v, mesh=mesh, causal=True, impl="blockwise")
    for a, b in ((u_out, t_out), (u_out, r_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)
    for a, b in ((u_lse, t_lse), (u_lse, r_lse)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_ulysses_composes_with_dp_and_tp():
    rng = np.random.default_rng(2)
    # head_axis="model" shards heads 2-way BEFORE the all-to-all, which then
    # re-shards the per-device slice: Hq=8 -> 4 per model shard -> 2 per seq
    # shard during local attention.
    q, k, v = make_qkv(rng, B=4, Tq=64, Tk=64)
    mesh = cpu_mesh(8, {"data": 2, "model": 2, "seq": 2})
    out, _ = ulysses_attention(
        q, k, v, mesh=mesh, causal=True,
        data_axis="data", head_axis="model", impl="blockwise",
    )
    ref_out, _ = attention_naive(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5
    )


def test_ulysses_gradients_match_unsharded():
    """Autodiff through the two all-to-alls (each transposes to its
    inverse) and the custom-VJP local kernel."""
    rng = np.random.default_rng(3)
    q, k, v = make_qkv(rng, B=1, Hq=4, Hkv=4, Tq=64, Tk=64, D=16)
    mesh = cpu_mesh(4)

    def loss_ref(q_, k_, v_):
        o, lse = attention_naive(q_, k_, v_, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)

    def loss_uly(q_, k_, v_):
        o, lse = ulysses_attention(
            q_, k_, v_, mesh=mesh, causal=True, impl="blockwise"
        )
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5
        )


def test_ulysses_rejects_indivisible_heads():
    rng = np.random.default_rng(4)
    mesh = cpu_mesh(4)
    q, k, v = make_qkv(rng, Hq=8, Hkv=2, Tq=64, Tk=64)
    with pytest.raises(ValueError, match="head"):
        ulysses_attention(q, k, v, mesh=mesh)
    q, k, v = make_qkv(rng, Hq=6, Hkv=6, Tq=64, Tk=64)
    with pytest.raises(ValueError, match="head"):
        ulysses_attention(q, k, v, mesh=mesh)


def test_ulysses_rejects_indivisible_per_shard_heads():
    # With a head-parallel axis, the all-to-all splits the PER-SHARD head
    # slice: 4 global heads over model=2 leaves 2 per shard, which cannot
    # split over seq=4 — the curated error must fire, not a trace-time
    # shape failure.
    rng = np.random.default_rng(6)
    mesh = cpu_mesh(8, {"model": 2, "seq": 4})
    q, k, v = make_qkv(rng, Hq=4, Hkv=4, Tq=64, Tk=64)
    with pytest.raises(ValueError, match="per-shard heads"):
        ulysses_attention(q, k, v, mesh=mesh, head_axis="model")


def test_ulysses_rejects_indivisible_seq():
    rng = np.random.default_rng(5)
    mesh = cpu_mesh(4)
    q, k, v = make_qkv(rng, Tq=66, Tk=66)
    with pytest.raises(ValueError, match="divide"):
        ulysses_attention(q, k, v, mesh=mesh)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_decode_matches_unsharded(causal):
    """Replicated-Q decode via the KV head-swap: parity with the oracle,
    including GQA (per-device q head group aligns with its kv heads)."""
    from tree_attention_tpu.parallel import ulysses_decode

    rng = np.random.default_rng(10)
    q, k, v = make_qkv(rng, B=1, Hq=8, Hkv=4, Tq=1, Tk=256)
    mesh = cpu_mesh(4)
    out, lse = ulysses_decode(q, k, v, mesh=mesh, causal=causal)
    ref_out, ref_lse = attention_naive(
        q, k, v, causal=causal, q_offset=256 - 1
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


def test_ulysses_decode_rejects_indivisible_heads():
    from tree_attention_tpu.parallel import ulysses_decode

    rng = np.random.default_rng(11)
    mesh = cpu_mesh(4)
    q, k, v = make_qkv(rng, Hq=8, Hkv=2, Tq=1, Tk=64)
    with pytest.raises(ValueError, match="head"):
        ulysses_decode(q, k, v, mesh=mesh)


def test_ulysses_decode_composes_with_head_axis():
    # The q head-group slice must come from the LOCAL (head-sharded) slice,
    # not the global head count (r4 review finding).
    from tree_attention_tpu.parallel import ulysses_decode

    rng = np.random.default_rng(12)
    q, k, v = make_qkv(rng, B=1, Hq=8, Hkv=8, Tq=1, Tk=64)
    mesh = cpu_mesh(4, {"model": 2, "seq": 2})
    out, lse = ulysses_decode(
        q, k, v, mesh=mesh, causal=True, head_axis="model"
    )
    ref_out, ref_lse = attention_naive(q, k, v, causal=True, q_offset=64 - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


def test_ulysses_decode_rejects_indivisible_per_shard_heads():
    from tree_attention_tpu.parallel import ulysses_decode

    rng = np.random.default_rng(13)
    q, k, v = make_qkv(rng, Hq=4, Hkv=4, Tq=1, Tk=64)
    mesh = cpu_mesh(8, {"model": 2, "seq": 4})
    with pytest.raises(ValueError, match="per-shard heads"):
        ulysses_decode(q, k, v, mesh=mesh, head_axis="model")
