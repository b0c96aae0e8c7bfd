"""Tile-size table lookups (``ops/tuning.py``).

The tables are measured artifacts (on-chip sweeps on v5e); these tests pin the lookup *semantics* —
bucket edges, the q8/exact split, and None-default resolution through the
kernels — not the measured values themselves, which later campaigns may
move.
"""

import jax.numpy as jnp

from tree_attention_tpu.ops.tuning import (
    decode_block_k,
    decode_block_k_q8,
    default_block_q,
    default_block_size,
)


def test_decode_tables_cover_all_contexts():
    for tk in (1, 128, 16_384, 16_385, 64_000, 1 << 20, 1 << 24):
        assert decode_block_k(tk) >= 128
        assert decode_block_k_q8(tk) >= 128


def test_q8_tiles_at_least_exact_tiles():
    # Half the bytes per tile -> the q8 kernel amortises its per-tile fixed
    # cost over less DMA time, so its tiles should never be smaller than
    # the exact path's (measured: 2x at 64k).
    for tk in (1024, 16_384, 64_000, 1 << 20):
        assert decode_block_k_q8(tk) >= decode_block_k(tk)


def test_train_tiles_bucketed_by_seq_len():
    bq4k, bk4k = default_block_q(4096, 4096), default_block_size("pallas", 4096)
    bq16k = default_block_q(16_384, 16_384)
    assert (bq4k, bk4k) == (1024, 1024)  # 2026-08-01 A/B (ab_fwd_tiles.py)
    assert bq16k >= bq4k  # deeper Q tile never measured slower at long seq
    # blockwise keeps its own (unmeasured-by-the-campaign) default; the
    # Pallas-measured table must not leak into the XLA fallback (ADVICE r3).
    from tree_attention_tpu.ops.tuning import BLOCKWISE_BLOCK_K

    assert default_block_size("blockwise", 4096) == BLOCKWISE_BLOCK_K == 512


def test_bwd_default_block_q_vmem_capped():
    # The bwd kernels' per-tile live state VMEM-OOMs when bq * bk exceeds
    # the measured-feasible product ((1024, 2048) = 24.6 MB scoped VMEM vs
    # the 16 MB chip limit); the bwd default must respect the product cap
    # for WHATEVER KV tile was resolved — including caller-supplied ones —
    # while never exceeding the largest validated Q tile.
    from tree_attention_tpu.ops.tuning import (
        BWD_MAX_BLOCK_Q,
        BWD_MAX_TILE_ELEMS,
        default_block_q_bwd,
    )

    for t in (128, 4096, 8192, 16_384, 1 << 20):
        for bk in (None, 512, 1024, 2048, 4096, 16_384):
            bq = default_block_q_bwd(t, t, bk)
            assert bq <= BWD_MAX_BLOCK_Q
            assert bq <= default_block_q(t, t)
            if bk is not None:
                # The product cap holds for EVERY caller-supplied KV
                # tile — no floor may push bq * bk back above it.
                assert bq * bk <= BWD_MAX_TILE_ELEMS
    # The table default (bk=1024) now admits the full 1024-row bwd tile
    # (the retune measured 1.18x at 4k fwd+bwd through the product default
    # path); an explicit bk=2048 halves it back.
    assert default_block_q_bwd(16_384, 16_384) == 1024
    assert default_block_q_bwd(16_384, 16_384, 2048) == 512


def test_decode_kernel_resolves_none_block_size():
    # block_size=None must resolve through the tuning table inside the
    # kernels (interpret mode on CPU; tiles clamp to the tiny shape).
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode,
        attention_pallas_decode_q8,
        quantize_kv_channelwise,
    )
    from tree_attention_tpu.ops.reference import attention_naive

    import jax

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (1, 4, 1, 8), jnp.float32)
    k = jax.random.normal(kk, (1, 4, 192, 8), jnp.float32)
    v = jax.random.normal(kv, (1, 4, 192, 8), jnp.float32)

    out, lse = attention_pallas_decode(q, k, v, interpret=True)
    ref, ref_lse = attention_naive(q, k, v)
    assert jnp.allclose(out, ref, atol=1e-5)
    assert jnp.allclose(lse, ref_lse, atol=1e-5)

    k_q, v_q, k_s, v_s = quantize_kv_channelwise(k, v)
    out8, _ = attention_pallas_decode_q8(
        q.astype(jnp.bfloat16), k_q, v_q, k_s, v_s, interpret=True
    )
    assert jnp.allclose(out8.astype(jnp.float32), ref, atol=0.05)
