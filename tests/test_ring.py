"""Ring-attention comparator tests: the ppermute ring must compute the same
exact attention as the tree merge and the unsharded oracle (it exists so the
benchmark's "vs ring" number is honest — SURVEY.md §7 hard part 4)."""

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
ring_decode = jitted(parallel.ring_decode)
tree_decode = jitted(parallel.tree_decode)


def make_qkv(rng, B=2, Hq=4, Hkv=4, Tq=128, Tk=128, D=32, dtype=np.float32):
    q = rng.standard_normal((B, Hq, Tq, D), np.float32).astype(dtype)
    k = rng.standard_normal((B, Hkv, Tk, D), np.float32).astype(dtype)
    v = rng.standard_normal((B, Hkv, Tk, D), np.float32).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("n_shards", [1, 2, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_unsharded(n_shards, causal):
    rng = np.random.default_rng(0)
    q, k, v = make_qkv(rng)
    mesh = cpu_mesh(n_shards)
    out, lse = ring_attention(q, k, v, mesh=mesh, causal=causal, impl="blockwise")
    ref_out, ref_lse = attention_naive(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


def test_ring_matches_tree():
    """Both sequence-parallel algorithms produce the identical exact softmax."""
    rng = np.random.default_rng(1)
    q, k, v = make_qkv(rng, Hq=8, Hkv=2)  # GQA
    mesh = cpu_mesh(8)
    r_out, r_lse = ring_attention(q, k, v, mesh=mesh, causal=True, impl="blockwise")
    t_out, t_lse = tree_attention(q, k, v, mesh=mesh, causal=True, impl="blockwise")
    np.testing.assert_allclose(np.asarray(r_out), np.asarray(t_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(r_lse), np.asarray(t_lse), atol=2e-5, rtol=2e-5)


def test_ring_composes_with_dp_and_tp():
    rng = np.random.default_rng(2)
    q, k, v = make_qkv(rng, B=4, Tq=64, Tk=64)
    mesh = cpu_mesh(8, {"data": 2, "model": 2, "seq": 2})
    out, _ = ring_attention(
        q, k, v, mesh=mesh, causal=True,
        data_axis="data", head_axis="model", impl="blockwise",
    )
    ref_out, _ = attention_naive(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)


def test_ring_gradients_match_unsharded():
    """Autodiff through scan + ppermute: backward is itself a ring rotation."""
    rng = np.random.default_rng(3)
    q, k, v = make_qkv(rng, B=1, Hq=2, Hkv=2, Tq=64, Tk=64, D=16)
    mesh = cpu_mesh(4)

    def loss_ring(q, k, v):
        o, _ = ring_attention(q, k, v, mesh=mesh, causal=True, impl="blockwise")
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        o, _ = attention_naive(q, k, v, causal=True)
        return jnp.sum(o ** 2)

    g = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)


def test_ring_chunked_prefill_alignment():
    """Tq < Tk causal: bottom-right aligned, same convention as tree."""
    rng = np.random.default_rng(4)
    q, k, v = make_qkv(rng, Tq=64, Tk=128)
    mesh = cpu_mesh(8)
    out, _ = ring_attention(q, k, v, mesh=mesh, causal=True, impl="blockwise")
    ref_out, _ = attention_naive(q, k, v, causal=True, q_offset=128 - 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n_shards", [1, 2, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_decode_matches_unsharded(n_shards, causal):
    """Replicated-Q decode via the unrolled partial-rotation ring: exact
    parity with the unsharded oracle (same monoid as the tree merge)."""
    rng = np.random.default_rng(7)
    q, k, v = make_qkv(rng, B=1, Hq=4, Hkv=2, Tq=1, Tk=256)
    mesh = cpu_mesh(n_shards)
    out, lse = ring_decode(q, k, v, mesh=mesh, causal=causal)
    ref_out, ref_lse = attention_naive(
        q, k, v, causal=causal, q_offset=256 - 1
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


def test_ring_decode_matches_tree_decode():
    """The decode comparator races identical math: ring_decode == tree_decode
    bit-for-allclose on the same data/mesh."""
    rng = np.random.default_rng(8)
    q, k, v = make_qkv(rng, B=2, Hq=4, Hkv=4, Tq=4, Tk=128)
    mesh = cpu_mesh(4)
    r_out, r_lse = ring_decode(q, k, v, mesh=mesh, causal=True)
    t_out, t_lse = tree_decode(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(r_out), np.asarray(t_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(r_lse), np.asarray(t_lse), atol=2e-5, rtol=2e-5)


def test_ring_decode_composes_with_dp_and_tp():
    rng = np.random.default_rng(9)
    q, k, v = make_qkv(rng, B=4, Hq=4, Hkv=4, Tq=1, Tk=64)
    mesh = cpu_mesh(8, {"data": 2, "model": 2, "seq": 2})
    out, _ = ring_decode(
        q, k, v, mesh=mesh, causal=True,
        data_axis="data", head_axis="model",
    )
    ref_out, _ = attention_naive(q, k, v, causal=True, q_offset=64 - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
