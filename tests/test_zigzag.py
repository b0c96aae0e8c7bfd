"""Zigzag (causally load-balanced) sequence layout for tree_attention.

SURVEY.md §7 hard part 2: with contiguous sharding under causal masking the
shard holding the first KV block has ~all query tiles live while the last has
~1/N — ~2× the balanced wall clock. The zigzag layout gives shard j the
half-blocks j and 2N-1-j so live work is equal. These tests assert (a) exact
numerics vs the unsharded oracle and vs the contiguous layout, (b) gradients
flow identically, and (c) the analytic live-tile balance that motivates it.
"""

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
    zigzag_perm,
)
from tests.jitted import jitted

tree_attention = jitted(tree_attention)  # one program a call (tests/jitted.py)


def _qkv(rng, B=1, H=4, T=256, D=32, dtype=np.float32):
    q = jnp.asarray(rng.standard_normal((B, H, T, D), np.float32).astype(dtype))
    k = jnp.asarray(rng.standard_normal((B, H, T, D), np.float32).astype(dtype))
    v = jnp.asarray(rng.standard_normal((B, H, T, D), np.float32).astype(dtype))
    return q, k, v


def _seq_mesh(n):
    return cpu_mesh(n)


def test_zigzag_perm_roundtrip():
    perm, inv = zigzag_perm(32, 4)
    assert sorted(perm.tolist()) == list(range(32))
    np.testing.assert_array_equal(perm[inv], np.arange(32))
    # shard 0 holds half-blocks 0 and 7 (half = 4)
    assert perm[:8].tolist() == [0, 1, 2, 3, 28, 29, 30, 31]


def test_zigzag_perm_rejects_odd():
    with pytest.raises(ValueError, match="half-blocks"):
        zigzag_perm(30, 4)


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_zigzag_matches_unsharded_causal(n_shards):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng)
    mesh = _seq_mesh(n_shards)
    ref_out, ref_lse = attention_naive(q, k, v, causal=True)

    qz = shard_zigzag(q, 2, n_shards)
    kz = shard_zigzag(k, 2, n_shards)
    vz = shard_zigzag(v, 2, n_shards)
    out_z, lse_z = tree_attention(
        qz, kz, vz, mesh=mesh, causal=True, layout="zigzag", impl="blockwise",
        block_size=32,
    )
    out = unshard_zigzag(out_z, 2, n_shards)
    lse = unshard_zigzag(lse_z, 2, n_shards)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


def test_zigzag_matches_contiguous_noncausal():
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, T=128)
    mesh = _seq_mesh(4)
    out_c, lse_c = tree_attention(
        q, k, v, mesh=mesh, causal=False, impl="blockwise", block_size=32
    )
    qz, kz, vz = (shard_zigzag(x, 2, 4) for x in (q, k, v))
    out_z, lse_z = tree_attention(
        qz, kz, vz, mesh=mesh, causal=False, layout="zigzag",
        impl="blockwise", block_size=32,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(out_z, 2, 4)), np.asarray(out_c),
        atol=2e-5, rtol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(lse_z, 2, 4)), np.asarray(lse_c),
        atol=2e-5, rtol=2e-5,
    )


def test_zigzag_gradients_match_unsharded():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, T=64, D=16)
    mesh = _seq_mesh(4)

    def loss_ref(q_, k_, v_):
        o, lse = attention_naive(q_, k_, v_, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)

    def loss_zig(q_, k_, v_):
        qz, kz, vz = (shard_zigzag(x, 2, 4) for x in (q_, k_, v_))
        # naive inner kernel: raw-autodiff oracle, scan-free — the zigzag
        # VJP structure under test is the tree machinery's, not the
        # blockwise kernel's (whose VJP test_gradients covers).
        o, lse = tree_attention(
            qz, kz, vz, mesh=mesh, causal=True, layout="zigzag",
            impl="naive",
        )
        # Loss is permutation-invariant; no unshard needed.
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_zig = jax.grad(loss_zig, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_zig, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)


def test_zigzag_rejects_bad_layout():
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, T=64)
    mesh = _seq_mesh(2)
    with pytest.raises(ValueError, match="layout"):
        tree_attention(q, k, v, mesh=mesh, layout="diagonal")


def _live_rows(kv_lo: int, kv_hi: int, t: int) -> int:
    """Causal live (query row, kv col) pairs contributed by KV cols [lo, hi)."""
    return sum(t - c for c in range(kv_lo, kv_hi))


@pytest.mark.parametrize("n_shards", [4, 8])
def test_zigzag_live_work_balance(n_shards):
    """Per-device live causal work is near-equal under zigzag and ~2×
    imbalanced under contiguous sharding (the motivation)."""
    T = 64 * n_shards
    half = T // (2 * n_shards)

    contiguous = [
        _live_rows(j * 2 * half, (j + 1) * 2 * half, T) for j in range(n_shards)
    ]
    zigzag = [
        _live_rows(j * half, (j + 1) * half, T)
        + _live_rows((2 * n_shards - 1 - j) * half, (2 * n_shards - j) * half, T)
        for j in range(n_shards)
    ]
    # Contiguous: first shard does ~2x the mean.
    assert max(contiguous) / min(contiguous) > 2.0
    # Zigzag: within 15% (VERDICT round-1 acceptance bar); actually exact.
    assert max(zigzag) / min(zigzag) <= 1.15


def test_transformer_zigzag_loss_equals_contiguous():
    """End-to-end LM train loss is layout-invariant: same tokens, same
    positions (via RoPE), permutation-invariant mean."""
    import dataclasses

    import jax.numpy as jnp

    from tree_attention_tpu.models import TransformerConfig, init_params
    from tree_attention_tpu.models.transformer import loss_fn

    mesh = cpu_mesh(4)
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=8, d_ff=64, max_seq_len=64, dtype=jnp.float32,
        attn_impl="blockwise", attn_block_size=8,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    batch = {
        "inputs": jax.random.randint(key, (2, 64), 0, 64),
        "targets": jax.random.randint(jax.random.fold_in(key, 1), (2, 64), 0, 64),
    }
    loss_c = loss_fn(params, batch, cfg, mesh=mesh)
    cfg_z = dataclasses.replace(cfg, seq_layout="zigzag")
    loss_z = loss_fn(params, batch, cfg_z, mesh=mesh)
    np.testing.assert_allclose(float(loss_z), float(loss_c), atol=1e-5, rtol=1e-5)


def test_transformer_zigzag_train_step_runs():
    """Full train step (fwd+bwd+optimizer) over data x seq mesh in zigzag."""
    import jax.numpy as jnp

    from tree_attention_tpu.models import (
        TransformerConfig, default_optimizer, init_train_state,
        make_train_step, shard_batch,
    )
    from tree_attention_tpu.parallel.mesh import AXIS_DATA, AXIS_SEQ

    mesh = cpu_mesh(8, {AXIS_DATA: 2, AXIS_SEQ: 4})
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=8, d_ff=64, max_seq_len=64, dtype=jnp.float32,
        attn_impl="blockwise", attn_block_size=8, seq_layout="zigzag",
    )
    opt = default_optimizer()
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh)
    key = jax.random.PRNGKey(1)
    batch = shard_batch(mesh, {
        "inputs": jax.random.randint(key, (2, 64), 0, 64),
        "targets": jax.random.randint(jax.random.fold_in(key, 1), (2, 64), 0, 64),
    })
    state, loss = step(state, batch)
    assert float(loss) > 0 and float(loss) == float(loss)


@pytest.mark.parametrize("q_chunk", [None, 32])
def test_zigzag_pallas_static_cull_matches_oracle(q_chunk):
    """Zigzag through the Pallas kernels (interpret): the static-offset
    dispatch (static_cull) with two KV half-segments per device — the
    branch geometry the real-TPU path compiles — against the oracle, with
    and without gather chunking (q_chunk=32 puts each chunk on one half)."""
    rng = np.random.default_rng(9)
    q, k, v = _qkv(rng, T=128, D=32)
    n = 2
    mesh = _seq_mesh(n)
    ref_out, ref_lse = attention_naive(q, k, v, causal=True)
    qz, kz, vz = (shard_zigzag(x, 2, n) for x in (q, k, v))
    out_z, lse_z = tree_attention(
        qz, kz, vz, mesh=mesh, causal=True, layout="zigzag", impl="pallas",
        block_size=32, q_chunk=q_chunk,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(out_z, 2, n)), np.asarray(ref_out),
        atol=2e-5, rtol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(unshard_zigzag(lse_z, 2, n)), np.asarray(ref_lse),
        atol=2e-5, rtol=2e-5,
    )
