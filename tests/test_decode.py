"""Decode-path tests (BASELINE config 4): split-KV flash decode, the sharded
KV cache, and incremental generation vs the full forward pass."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.models import (
    TransformerConfig,
    forward,
    forward_step,
    generate,
    init_cache,
    init_params,
)
from tree_attention_tpu.ops import attention_naive, flash_decode
from tree_attention_tpu.parallel import cpu_mesh


CFG = TransformerConfig(
    vocab_size=128,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_head=16,
    d_ff=128,
    max_seq_len=256,
    dtype=jnp.float32,
    attn_impl="blockwise",
    attn_block_size=16,
)


# ---------------------------------------------------------------------------
# ops-level: flash_decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("num_splits", [1, 4, 7])
def test_flash_decode_matches_oracle(num_splits):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 8, 1, 32), np.float32))
    k = jnp.asarray(rng.standard_normal((2, 2, 512, 32), np.float32))
    v = jnp.asarray(rng.standard_normal((2, 2, 512, 32), np.float32))
    out, lse = flash_decode(q, k, v, num_splits=num_splits)
    ref_out, ref_lse = attention_naive(q, k, v, causal=True, q_offset=511)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


def test_flash_decode_partial_buffer():
    """A cache of capacity 512 holding 200 valid tokens: q_position masks the
    tail without any explicit length mask."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 4, 1, 16), np.float32))
    kv_full = rng.standard_normal((2, 1, 4, 512, 16), np.float32)
    k, v = jnp.asarray(kv_full[0]), jnp.asarray(kv_full[1])
    length = 200
    out, lse = flash_decode(q, k, v, q_position=length - 1, num_splits=4)
    ref_out, ref_lse = attention_naive(q, k[:, :, :length], v[:, :, :length])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)


def test_flash_decode_traced_position():
    """q_position may be a traced scalar: one compile serves all lengths."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 2, 1, 16), np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 16), np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 16), np.float32))
    fn = jax.jit(lambda pos: flash_decode(q, k, v, q_position=pos, num_splits=4))
    for length in (1, 64, 128):
        out, _ = fn(jnp.int32(length - 1))
        ref_out, _ = attention_naive(q, k[:, :, :length], v[:, :, :length])
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5
        )


# ---------------------------------------------------------------------------
# model-level: cache prefill + incremental decode == full forward
# ---------------------------------------------------------------------------


def _jit_step(cfg, **kw):
    """``forward_step`` as one program a (cache type, token width): dispatched
    eagerly a step is a launch a primitive, sixteen steps sixteen times
    that."""
    return jax.jit(lambda p, t, c: forward_step(p, t, c, cfg, **kw))


def _stepwise_logits(params, tokens, cfg, mesh=None, cache_len=64):
    """Prefill then 1-token steps; returns logits at every position."""
    kw = {"mesh": mesh} if mesh is not None else {}
    B, T = tokens.shape
    split = T // 2
    cache = init_cache(cfg, B, cache_len, **kw)
    step = _jit_step(cfg, **kw)
    logits_pre, cache = step(params, tokens[:, :split], cache)
    chunks = [logits_pre]
    for t in range(split, T):
        logits_t, cache = step(params, tokens[:, t : t + 1], cache)
        chunks.append(logits_t)
    assert np.all(np.asarray(cache.length) == T)  # per-slot (B,) lengths
    return jnp.concatenate(chunks, axis=1)


def test_incremental_decode_matches_full_forward():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, CFG.vocab_size)
    full = forward(params, tokens, CFG)
    step = _stepwise_logits(params, tokens, CFG)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full), atol=2e-4, rtol=2e-4)


def test_incremental_decode_matches_full_forward_sharded():
    """Sequence-sharded KV cache over a 4-device mesh == unsharded decode."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, CFG.vocab_size)
    mesh = cpu_mesh(4)
    full = forward(params, tokens, CFG)
    step = _stepwise_logits(params, tokens, CFG, mesh=mesh, cache_len=64)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full), atol=2e-4, rtol=2e-4)


def test_forward_step_rejects_cache_overflow():
    params = init_params(jax.random.PRNGKey(0), CFG)
    cache = init_cache(CFG, 1, 8)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, CFG.vocab_size)
    _, cache = forward_step(params, tokens, cache, CFG)
    with pytest.raises(ValueError, match="overflow"):
        forward_step(params, tokens[:, :1], cache, CFG)


def test_generate_rejects_nonpositive_steps():
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(params, prompt, 0, CFG)


def test_cache_capacity_must_divide_shards():
    mesh = cpu_mesh(4)
    with pytest.raises(ValueError, match="divide"):
        init_cache(CFG, 1, 30, mesh=mesh)


def test_generate_greedy_matches_full_forward_argmax():
    """Greedy generation must agree with argmax over full-forward logits."""
    params = init_params(jax.random.PRNGKey(3), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 8), 0, CFG.vocab_size)
    n_new = 6
    toks = generate(params, prompt, n_new, CFG)
    assert toks.shape == (1, n_new)

    # replay: at each step the next token is argmax of the full forward
    seq = prompt
    for i in range(n_new):
        logits = forward(params, seq, CFG)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        assert int(nxt[0]) == int(toks[0, i]), f"step {i}"
        seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)], axis=1)


def test_generate_jits_and_runs_sharded():
    params = init_params(jax.random.PRNGKey(5), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, CFG.vocab_size)
    mesh = cpu_mesh(4)
    toks = generate(params, prompt, 4, CFG, mesh=mesh, cache_len=16)
    ref = generate(params, prompt, 4, CFG, cache_len=16)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))


def test_generate_temperature_sampling_shape():
    params = init_params(jax.random.PRNGKey(7), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 4), 0, CFG.vocab_size)
    toks = generate(
        params, prompt, 5, CFG, temperature=1.0, key=jax.random.PRNGKey(9)
    )
    assert toks.shape == (2, 5)
    assert int(jnp.min(toks)) >= 0 and int(jnp.max(toks)) < CFG.vocab_size


@pytest.mark.parametrize("tq", [1, 4, 256])
def test_flash_decode_tpu_branch_interpret(monkeypatch, tq):
    """Exercise the TPU dispatch branch of flash_decode (kernels in
    interpret mode): small Tq takes the flash-decode kernel, prefill-sized
    Tq the Q-tiled kernel — both must match the oracle with cache-style
    q_position masking."""
    import tree_attention_tpu.ops as ops_pkg
    from tree_attention_tpu.ops.decode import flash_decode
    from tree_attention_tpu.ops import attention_naive

    monkeypatch.setattr(ops_pkg, "_on_tpu", lambda q=None: True)

    rng = np.random.default_rng(21)
    B, Hq, Hkv, D, cap = 1, 4, 2, 32, 512
    length = 400  # valid prefix of the cache; the tail is masked future
    q = jnp.asarray(rng.standard_normal((B, Hq, tq, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    out, lse = flash_decode(q, k, v, q_position=length - tq)
    ref_out, ref_lse = attention_naive(
        q, k, v, causal=True, q_offset=length - tq
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# Quantized cache (quantize-after-prefill)
# ---------------------------------------------------------------------------


def test_quantize_cache_roundtrip():
    from tree_attention_tpu.models import init_cache, quantize_cache

    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, CFG.vocab_size)
    cache = init_cache(CFG, 1, 32)
    _, cache = forward_step(params, tokens, cache, CFG)
    qc = quantize_cache(cache)
    assert qc.k.dtype == jnp.int8 and qc.v.dtype == jnp.int8
    assert np.all(np.asarray(qc.length) == 24)
    k_dq = qc.k.astype(np.float32) * np.asarray(qc.k_scale)
    err = np.abs(k_dq[:, :, :, :24] - np.asarray(cache.k, np.float32)[:, :, :, :24])
    # int8 per-channel: error bounded by scale/2 = amax/254 per channel.
    bound = np.abs(np.asarray(cache.k, np.float32)).max() / 200.0
    assert float(err.max()) <= bound, (float(err.max()), bound)


def test_quantized_incremental_decode_tracks_exact():
    """Prefill exactly, quantize, decode the rest step-by-step: logits stay
    close to the exact incremental path (int8 error, not divergence)."""
    from tree_attention_tpu.models import quantize_cache

    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, CFG.vocab_size)
    Tp = 16

    step = _jit_step(CFG)

    def run(quant):
        cache = init_cache(CFG, 1, 32)
        logits, cache = step(params, tokens[:, :Tp], cache)
        if quant:
            cache = quantize_cache(cache)
        outs = [logits]
        for t in range(Tp, 32):
            logits, cache = step(params, tokens[:, t:t + 1], cache)
            outs.append(logits)
        return np.concatenate([np.asarray(o) for o in outs], axis=1)

    exact = run(False)
    quant = run(True)
    err = np.abs(exact - quant).max()
    assert err < 0.5, err  # small vs logit scale (~10); zero would mean no quant
    assert err > 0.0


def test_generate_quantize_after_prefill_runs_and_matches_greedy_mostly():
    from tree_attention_tpu.models import generate

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, CFG.vocab_size)
    toks_q = generate(
        params, prompt, 8, CFG, quantize_after_prefill=True
    )
    assert toks_q.shape == (1, 8)
    assert np.all((np.asarray(toks_q) >= 0) & (np.asarray(toks_q) < CFG.vocab_size))


@pytest.mark.parametrize("quant_kernel", ["q8q", "q8"])
def test_quantized_decode_sharded_matches_unsharded(quant_kernel):
    """QuantKVCache over a 4-way seq mesh: the tree merge == one device,
    for both the int8-MXU (q8q, the default) and bf16-cast (q8) kernels."""
    from tree_attention_tpu.models import quantize_cache

    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, CFG.vocab_size)
    mesh = cpu_mesh(4)

    def run(mesh_arg, cache_len=32):
        kw = {} if mesh_arg is None else {"mesh": mesh_arg}
        cache = init_cache(CFG, 1, cache_len, **kw)
        step = _jit_step(CFG, quant_kernel=quant_kernel, **kw)
        logits, cache = step(params, tokens[:, :16], cache)
        cache = quantize_cache(cache)
        outs = []
        for t in range(16, 24):
            logits, cache = step(params, tokens[:, t:t + 1], cache)
            outs.append(np.asarray(logits))
        return np.concatenate(outs, axis=1)

    np.testing.assert_allclose(
        run(None), run(mesh), atol=5e-3, rtol=5e-3
    )


@pytest.mark.parametrize("quant_kernel", ["q8q", "q8"])
def test_q8_long_horizon_drift_bounded(quant_kernel):
    """VERDICT r2 item 7 / r3 item 2: quantize-after-prefill drift over a
    long decode, for both int8 kernels — q8q's extra per-row Q-rounding
    error is exactly the kind that could compound over a horizon.

    Teacher-forced comparison isolates cache-quantization drift from
    trajectory divergence: both caches see the *same* token stream (the
    exact path's greedy choices), and we track per-step logit divergence
    and argmax agreement over 48 appended tokens — 4× the prefill length,
    so appended (frozen-scale-quantized) rows dominate the cache by the
    end. Tolerances: logits differ by well under the logit scale (~10 for
    this model), and the greedy token matches on ≥90% of steps.
    """
    from tree_attention_tpu.models import quantize_cache

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 12), 0, CFG.vocab_size)
    n_steps = 48
    cache_len = 12 + n_steps + 4

    exact = init_cache(CFG, 1, cache_len)
    logits_e, exact = forward_step(params, prompt, exact, CFG)
    quant = init_cache(CFG, 1, cache_len)
    logits_q, quant = forward_step(params, prompt, quant, CFG)
    quant = quantize_cache(quant)

    # One program a cache type for the 48 steps: dispatched eagerly a step
    # is a launch a primitive (the interpreted int8 kernel's included).
    step_e = jax.jit(lambda p, t, c: forward_step(p, t, c, CFG))
    step_q = jax.jit(lambda p, t, c: forward_step(
        p, t, c, CFG, quant_kernel=quant_kernel))
    tok = jnp.argmax(logits_e[:, -1], axis=-1)[:, None]
    max_err, agree = 0.0, 0
    for _ in range(n_steps):
        logits_e, exact = step_e(params, tok, exact)
        logits_q, quant = step_q(params, tok, quant)
        le = np.asarray(logits_e[:, -1], np.float32)
        lq = np.asarray(logits_q[:, -1], np.float32)
        max_err = max(max_err, float(np.abs(le - lq).max()))
        agree += int(le.argmax() == lq.argmax())
        tok = jnp.argmax(logits_e[:, -1], axis=-1)[:, None]
    assert max_err < 1.0, max_err     # bounded drift, not bit-equality
    assert max_err > 0.0              # zero would mean quantization is a no-op
    assert agree >= int(0.9 * n_steps), (agree, n_steps)


def test_q8_frozen_scale_clamps_out_of_range_appends():
    """Appended rows beyond the prefill's per-channel range clamp to ±127
    (dequantized: the prefix's absmax), and a zero-prefix channel follows
    the documented round(x) fallback (scale 1.0)."""
    from tree_attention_tpu.models.decode import _quantize_rows
    from tree_attention_tpu.ops.pallas_decode import quantize_symmetric_int8

    # Prefix: channel 0 spans ±1, channel 1 spans ±0.1, channel 2 all-zero.
    prefix = jnp.asarray(
        np.array([[1.0, 0.1, 0.0], [-0.5, -0.1, 0.0]], np.float32)
    )[None, None]  # (B=1, H=1, T=2, D=3)
    _, scale = quantize_symmetric_int8(prefix, axis=2)
    np.testing.assert_allclose(
        np.asarray(scale[0, 0, 0]), [1 / 127, 0.1 / 127, 1.0], rtol=1e-6
    )

    rows = jnp.asarray(np.array([[2.0, -0.35, 0.3]], np.float32))[None, None]
    q = _quantize_rows(rows, scale)
    deq = np.asarray(q, np.float32) * np.asarray(scale)
    # 2.0 is out of the prefix's ±1 range: clamps to the range edge.
    np.testing.assert_allclose(deq[0, 0, 0, 0], 1.0, rtol=1e-6)
    # -0.35 is out of channel 1's ±0.1 range: clamps to -0.1.
    np.testing.assert_allclose(deq[0, 0, 0, 1], -0.1, rtol=1e-6)
    # Zero-prefix channel: scale 1.0, round(0.3) == 0 (documented collapse).
    assert deq[0, 0, 0, 2] == 0.0
