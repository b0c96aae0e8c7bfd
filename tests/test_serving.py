"""Ragged-batch decode + continuous-batching scheduler tests (ISSUE 2/3).

The four parity contracts of the ragged decode stack:

(a) **equal-length slots reproduce lockstep generate() token-for-token**
    (exact pool; the int8 pool reproduces a plain ``forward_step`` loop
    over a hand-built int8 pool, ``paged_int8_stream``) — raggedness is
    a strict generalisation;
(b) **mixed lengths match per-request single-stream decode** — no slot
    reads another slot's cache rows, ever;
(c) **scheduler property**: a random admit/retire trace delivers every
    request exactly its tokens, identical to its own single-stream run;
(d) **chunked admission == a whole-prompt prefill** (ISSUE 3): prefill
    chunks fused into the per-tick mixed-Tq step — for chunk sizes that
    do and do not divide the prompt, exact AND int8 (staged
    quantize-at-final-chunk) — produce the tokens of a reference that
    prefills the prompt in one piece (lockstep ``generate``; the plain
    int8 loop).

The engine under test is the one that serves: the paged pool, at pages
of ``attn_block_size`` tokens so that the engine and its references fold
identical KV tiles in identical order (two pages a slot at ``cache_len``
32). Cases that share a configuration share one engine (``engine``
fixture): a drained engine serves the next trace from a clean state, and
every instance pays its own compiles.

Everything here is CPU-safe and fast-tier: plain jnp paths plus the Pallas
kernels in interpret mode, meshes from ``cpu_mesh``.
"""

import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.models import (
    TransformerConfig,
    forward_step,
    generate,
    init_cache,
    init_params,
)
from tree_attention_tpu.ops import attention_naive
from tree_attention_tpu.ops.decode import default_num_splits, flash_decode
from tree_attention_tpu.parallel import cpu_mesh
from tree_attention_tpu.serving import Request, SlotServer, synthetic_trace

from tests.test_serving_paged import paged_int8_stream

CFG = TransformerConfig(
    vocab_size=128,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_head=16,
    d_ff=128,
    max_seq_len=256,
    dtype=jnp.float32,   # tight cross-path comparisons
    attn_impl="blockwise",
    attn_block_size=16,
)


KV_BLOCK = CFG.attn_block_size


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def engine(params):
    """``engine(**kw)``: the ``SlotServer`` of that configuration, built
    once for the module."""
    built = {}

    def get(**kw):
        key = tuple(sorted(kw.items()))
        if key not in built:
            built[key] = SlotServer(params, CFG, kv_block=KV_BLOCK, **kw)
        return built[key]

    return get


def _single_stream(params, prompt, n_new, cache_len=64):
    """Per-request reference: one prompt, one stream, greedy."""
    return np.asarray(
        generate(params, jnp.asarray(prompt)[None], n_new, CFG,
                 cache_len=cache_len)
    )[0].tolist()


# ---------------------------------------------------------------------------
# satellite: default_num_splits scales its cap with context
# ---------------------------------------------------------------------------


def test_default_num_splits_scales_with_context():
    # Short contexts keep the measured 16-way cap...
    assert default_num_splits(1024, 512) == 2
    assert default_num_splits(100, 512) == 1
    assert default_num_splits(65536, 512) == 16
    assert default_num_splits(16 * 16384, 512) == 16
    # ...and past 256k tokens the cap grows one chunk per 16k tokens, so
    # the chunked-vmap path keeps exposing parallelism.
    assert default_num_splits(1 << 19, 512) == 32
    assert default_num_splits(1 << 22, 512) == 256
    # Never more chunks than blocks.
    assert default_num_splits(1 << 22, 1 << 21) == 2


# ---------------------------------------------------------------------------
# ops-level ragged parity (test_decode.py is not collected on legacy JAX,
# so the ragged kernel contracts are anchored here)
# ---------------------------------------------------------------------------


def test_flash_decode_ragged_matches_per_row_scalar():
    """A (B,) q_position must equal B scalar-position calls bit-for-bit on
    the chunked path (same chunking, same merge, per-row masking)."""
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, cap = 3, 4, 2, 16, 192
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    pos = jnp.asarray([4, 77, 191], jnp.int32)
    out, lse = flash_decode(q, k, v, q_position=pos, num_splits=4)
    for i in range(B):
        o_i, l_i = flash_decode(
            q[i:i + 1], k[i:i + 1], v[i:i + 1],
            q_position=int(pos[i]), num_splits=4,
        )
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(o_i[0]))
        np.testing.assert_array_equal(np.asarray(lse[i]), np.asarray(l_i[0]))
        L = int(pos[i]) + 1
        ref, _ = attention_naive(q[i:i + 1], k[i:i + 1, :, :L],
                                 v[i:i + 1, :, :L])
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(ref[0]), atol=2e-5, rtol=2e-5
        )


def test_pallas_decode_ragged_interpret():
    """The Pallas flash-decode kernel's per-batch SMEM offsets (interpret
    mode): each row masks its own tail."""
    from tree_attention_tpu.ops.pallas_decode import attention_pallas_decode

    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, cap = 3, 4, 2, 32, 256
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    pos = jnp.asarray([9, 100, 255], jnp.int32)
    out, lse = attention_pallas_decode(q, k, v, causal=True, q_offset=pos)
    for i in range(B):
        L = int(pos[i]) + 1
        ref_o, ref_l = attention_naive(q[i:i + 1], k[i:i + 1, :, :L],
                                       v[i:i + 1, :, :L])
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(ref_o[0]), atol=3e-5, rtol=3e-5
        )
        np.testing.assert_allclose(
            np.asarray(lse[i]), np.asarray(ref_l[0]), atol=3e-5, rtol=3e-5
        )


def test_pallas_decode_q8q_ragged_interpret():
    """The int8-MXU kernel takes the same (B,) offsets."""
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode_q8q,
        quantize_kv_channelwise,
    )

    rng = np.random.default_rng(2)
    B, Hq, Hkv, D, cap = 2, 4, 2, 32, 128
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, Hkv, cap, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, Hkv, cap, D)), jnp.bfloat16)
    k_q, v_q, k_s, v_s = quantize_kv_channelwise(k, v)
    pos = jnp.asarray([17, 127], jnp.int32)
    out, _ = attention_pallas_decode_q8q(
        q, k_q, v_q, k_s, v_s, causal=True, q_offset=pos
    )
    for i in range(B):
        L = int(pos[i]) + 1
        ref, _ = attention_naive(q[i:i + 1], k[i:i + 1, :, :L],
                                 v[i:i + 1, :, :L])
        err = np.abs(
            np.asarray(out[i], np.float32) - np.asarray(ref[0], np.float32)
        ).max()
        assert err < 0.15, (i, err)  # int8 error, not a masking bug


def test_forward_step_ragged_matches_single_stream(params):
    """Slots prefilled to different lengths step together and match each
    slot's own B=1 step exactly — the model-level no-cross-talk contract."""
    import dataclasses

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                CFG.vocab_size)
    ca = init_cache(CFG, 1, 64)
    _, ca = forward_step(params, tokens[:1, :16], ca, CFG)
    cb = init_cache(CFG, 1, 64)
    _, cb = forward_step(params, tokens[1:, :10], cb, CFG)
    ragged = dataclasses.replace(
        ca,
        k=jnp.concatenate([ca.k, cb.k], axis=1),
        v=jnp.concatenate([ca.v, cb.v], axis=1),
        length=jnp.concatenate([ca.length, cb.length]),
    )
    nt = jnp.stack([tokens[0, 16], tokens[1, 10]])[:, None]
    lr, ragged = forward_step(params, nt, ragged, CFG)
    la, _ = forward_step(params, tokens[:1, 16:17], ca, CFG)
    lb, _ = forward_step(params, tokens[1:, 10:11], cb, CFG)
    np.testing.assert_allclose(np.asarray(lr[0]), np.asarray(la[0]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(lr[1]), np.asarray(lb[0]),
                               atol=1e-5, rtol=1e-5)
    assert np.asarray(ragged.length).tolist() == [17, 11]


def test_forward_step_overflow_checks_max_slot(params):
    """The eager overflow guard fires off the FULLEST slot, not the mean."""
    import dataclasses

    cache = init_cache(CFG, 2, 8)
    cache = dataclasses.replace(
        cache, length=jnp.asarray([2, 8], jnp.int32)
    )
    with pytest.raises(ValueError, match="overflow"):
        forward_step(params, jnp.zeros((2, 1), jnp.int32), cache, CFG)


# ---------------------------------------------------------------------------
# (a) equal-length slots == lockstep generate()
# ---------------------------------------------------------------------------


def _as_requests(prompt, n_new, **kw):
    return [
        Request(uid=i, prompt=np.asarray(prompt[i]), max_new_tokens=n_new,
                **kw)
        for i in range(prompt.shape[0])
    ]


def test_equal_slots_reproduce_lockstep_generate(params, engine):
    B, Tp, n_new = 3, 12, 6
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, Tp), 0,
                                CFG.vocab_size)
    ref = np.asarray(generate(params, prompt, n_new, CFG, cache_len=32))
    server = engine(slots=B, cache_len=32)
    report = server.serve(_as_requests(prompt, n_new))
    got = np.stack([np.asarray(r.tokens) for r in report.results])
    np.testing.assert_array_equal(got, ref)
    assert report.tokens_generated == B * n_new


def _int8_stream(params, prompt, n_new, cache_len=32):
    return paged_int8_stream(params, CFG, prompt, n_new,
                             cache_len=cache_len, kv_block=KV_BLOCK)


def test_equal_slots_reproduce_lockstep_generate_quantized(params, engine):
    """Same contract through the int8 pool: each slot's per-block
    quantize-after-prefill must equal, token-for-token, a plain
    ``forward_step`` loop over its own hand-built int8 pool — whatever
    its neighbours hold."""
    B, Tp, n_new = 2, 12, 5
    prompt = jax.random.randint(jax.random.PRNGKey(3), (B, Tp), 0,
                                CFG.vocab_size)
    server = engine(slots=B, cache_len=32, quantize=True)
    report = server.serve(_as_requests(prompt, n_new))
    for r in report.results:
        assert r.tokens == _int8_stream(params, prompt[r.uid], n_new)


# ---------------------------------------------------------------------------
# (b) mixed lengths == per-request single-stream decode
# ---------------------------------------------------------------------------


def test_mixed_lengths_match_single_stream(params, engine):
    base = jax.random.randint(jax.random.PRNGKey(4), (4, 16), 0,
                              CFG.vocab_size)
    reqs = [
        Request(uid=0, prompt=np.asarray(base[0][:14]), max_new_tokens=5,
                arrival_tick=0),
        Request(uid=1, prompt=np.asarray(base[1][:7]), max_new_tokens=8,
                arrival_tick=2),
        Request(uid=2, prompt=np.asarray(base[2][:3]), max_new_tokens=4,
                arrival_tick=3),
        Request(uid=3, prompt=np.asarray(base[3][:9]), max_new_tokens=6,
                arrival_tick=5),
    ]
    server = engine(slots=2, cache_len=32)
    report = server.serve(reqs)
    assert len(report.results) == len(reqs)
    for res in report.results:
        req = next(r for r in reqs if r.uid == res.uid)
        assert res.tokens == _single_stream(
            params, req.prompt, req.max_new_tokens, cache_len=32
        ), f"request {res.uid} diverged from its single-stream decode"
        assert res.admit_tick >= req.arrival_tick


def test_ragged_position_composes_with_data_axis(params):
    """A (B,) q_position shards like the batch dim: generate() on a
    data x seq mesh must still match the single-device run (regression —
    the per-slot vector must not be rejected or replicated wrongly when
    the batch is data-sharded)."""
    mesh = cpu_mesh(4, {"data": 2, "seq": 2})
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 8), 0,
                                CFG.vocab_size)
    toks = generate(params, prompt, 4, CFG, mesh=mesh, cache_len=16)
    ref = generate(params, prompt, 4, CFG, cache_len=16)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))


def test_serving_mesh_matches_single_device(params, engine):
    """The same trace over a sequence-sharded pool (tree merge per tick)
    reproduces the single-device tokens."""
    mesh = cpu_mesh(2)
    B, Tp, n_new = 2, 12, 4
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, Tp), 0,
                                CFG.vocab_size)
    ref = engine(slots=B, cache_len=32).serve(_as_requests(prompt, n_new))
    mesh_server = engine(slots=B, cache_len=32, mesh=mesh, kv_shard="seq")
    got = mesh_server.serve(_as_requests(prompt, n_new))
    for a, b in zip(ref.results, got.results):
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)


# ---------------------------------------------------------------------------
# (c) scheduler properties: random admit/retire traces
# ---------------------------------------------------------------------------


def test_scheduler_property_random_trace(params, engine):
    """Random prompts/lengths/budgets/arrivals through few slots: every
    request finishes with exactly its budget, token-identical to its own
    single-stream decode (no slot cross-talk), and scheduling invariants
    hold (FIFO admission within arrival order, bounded occupancy)."""
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(7):
        plen = int(rng.integers(2, 20))
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(0, CFG.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=int(rng.integers(1, 8)),
            arrival_tick=int(rng.integers(0, 10)),
        ))
    server = engine(slots=3, cache_len=32)
    report = server.serve(reqs, max_ticks=500)
    assert sorted(r.uid for r in report.results) == list(range(7))
    for res in report.results:
        req = next(r for r in reqs if r.uid == res.uid)
        assert len(res.tokens) == req.max_new_tokens
        assert res.tokens == _single_stream(
            params, req.prompt, req.max_new_tokens, cache_len=32
        ), f"request {res.uid} cross-talked"
        assert res.admit_tick >= req.arrival_tick
        assert res.finish_tick >= res.admit_tick
    assert report.mean_occupancy <= server.slots + 1e-9
    # Total work is conserved: prefill token + decode appends per request.
    assert report.tokens_generated == sum(r.max_new_tokens for r in reqs)


def test_eos_retires_slot_early(params, engine):
    """A sampled EOS frees the slot immediately (outcome 'eos', truncated
    output) — pinned against the request's own single-stream decode."""
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(6), (10,), 0, CFG.vocab_size)
    )
    ref = _single_stream(params, prompt, 6, cache_len=32)
    eos = ref[2]  # force an early stop at the third sampled token
    server = engine(slots=2, cache_len=32)
    report = server.serve([
        Request(uid=0, prompt=prompt, max_new_tokens=6, eos_id=eos)
    ])
    res = report.results[0]
    assert res.outcome == "eos"
    assert res.tokens == ref[:3]  # EOS included, nothing after


def test_single_token_budget_retires_at_admit(params, engine):
    """max_new_tokens=1 finishes on the prefill sample alone — the trace
    drains entirely in the admit phase with zero decode ticks and must
    terminate cleanly (regression: the empty-queue fast-forward crashed)."""
    server = engine(slots=2, cache_len=32)
    prompt = jax.random.randint(jax.random.PRNGKey(9), (3, 6), 0,
                                CFG.vocab_size)
    report = server.serve(_as_requests(prompt, 1))
    assert sorted(r.uid for r in report.results) == [0, 1, 2]
    for res in report.results:
        assert len(res.tokens) == 1
        assert res.tokens == _single_stream(
            params, prompt[res.uid], 1, cache_len=32
        )
    assert report.tokens_generated == 3


def test_admit_rejects_overcapacity(engine):
    server = engine(slots=1, cache_len=16)
    with pytest.raises(ValueError, match="capacity"):
        server.serve([
            Request(uid=0, prompt=np.zeros(12, np.int32), max_new_tokens=8)
        ])


def test_serve_rejects_zero_token_budget(engine):
    """The prefill itself samples one token, so a zero budget is
    unservable — same contract as generate()."""
    server = engine(slots=1, cache_len=16)
    with pytest.raises(ValueError, match="max_new_tokens"):
        server.serve([
            Request(uid=0, prompt=np.zeros(4, np.int32), max_new_tokens=0)
        ])


def test_serving_data_axis_mesh(params, engine):
    """A mesh with a data axis serves too, over a sequence-sharded pool:
    the B=1 prefill drops the data axis (1 cannot shard over it) while
    the batched step keeps the full spec (regression — the first admit
    crashed in shard_map)."""
    mesh = cpu_mesh(4, {"data": 2, "seq": 2})
    B, Tp, n_new = 2, 10, 4
    prompt = jax.random.randint(jax.random.PRNGKey(10), (B, Tp), 0,
                                CFG.vocab_size)
    got = engine(slots=B, cache_len=16, mesh=mesh, kv_shard="seq").serve(
        _as_requests(prompt, n_new)
    )
    ref = engine(slots=B, cache_len=16).serve(_as_requests(prompt, n_new))
    for a, b in zip(ref.results, got.results):
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)


def test_synthetic_trace_shape():
    trace = synthetic_trace(5, prompt_len=8, prompt_jitter=3,
                            max_new_tokens=4, arrival_every=2, seed=1)
    assert [r.arrival_tick for r in trace] == [0, 2, 4, 6, 8]
    assert all(5 <= len(r.prompt) <= 11 for r in trace)
    assert all(r.max_new_tokens == 4 for r in trace)


# ---------------------------------------------------------------------------
# ISSUE 3: stall-free chunked prefill fused into the tick
# ---------------------------------------------------------------------------


def test_flash_decode_ragged_multitoken_chunk(params):
    """The mixed-Tq contract's kernel floor: a (B,) q_position with Tq > 1
    (a prefill chunk riding the tick) equals per-row scalar calls
    bit-for-bit on the chunked path AND the Q-tiled Pallas kernel
    (interpret) — each row's chunk attends at its own offset."""
    from tree_attention_tpu.ops.pallas_attention import attention_pallas_fwd

    rng = np.random.default_rng(3)
    B, Hq, Hkv, Tq, D, cap = 3, 4, 2, 8, 16, 128
    q = jnp.asarray(rng.standard_normal((B, Hq, Tq, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    pos = jnp.asarray([0, 41, cap - Tq], jnp.int32)
    out, lse = flash_decode(q, k, v, q_position=pos, num_splits=4)
    out_p, lse_p = attention_pallas_fwd(
        q, k, v, causal=True, q_offset=pos, kv_offset=0,
        block_size=32, interpret=True,
    )
    for i in range(B):
        o_i, l_i = flash_decode(
            q[i:i + 1], k[i:i + 1], v[i:i + 1],
            q_position=int(pos[i]), num_splits=4,
        )
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(o_i[0]))
        np.testing.assert_array_equal(np.asarray(lse[i]), np.asarray(l_i[0]))
        o_pi, l_pi = attention_pallas_fwd(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], causal=True,
            q_offset=int(pos[i]), kv_offset=0, block_size=32, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(out_p[i]),
                                      np.asarray(o_pi[0]))
        np.testing.assert_array_equal(np.asarray(lse_p[i]),
                                      np.asarray(l_pi[0]))


def test_mixed_tq_forward_step_masked_window(params):
    """forward_step(n_tokens=...): a padded mixed step must leave the cache
    bit-identical to exact per-slot steps — including the clamp case where
    a near-capacity slot's Tq-row window straddles the buffer end, and the
    inert case n == 0 (nothing written, length frozen)."""
    import dataclasses

    cap = 16
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 16), 0,
                                CFG.vocab_size)
    # Slot 0 nearly full (14/16), slot 1 short (3/16).
    ca = init_cache(CFG, 1, cap)
    _, ca = forward_step(params, tokens[:1, :14], ca, CFG)
    cb = init_cache(CFG, 1, cap)
    _, cb = forward_step(params, tokens[1:, :3], cb, CFG)
    mixed = dataclasses.replace(
        ca,
        k=jnp.concatenate([ca.k, cb.k], axis=1),
        v=jnp.concatenate([ca.v, cb.v], axis=1),
        length=jnp.concatenate([ca.length, cb.length]),
    )
    # A Tq=8 padded step: slot 0 consumes 2 rows (window 14..22 clamps to
    # 8..16 — the shifted-write case), slot 1 consumes 0 (inert).
    pad = jnp.zeros((2, 8), jnp.int32)
    pad = pad.at[0, :2].set(tokens[0, 14:16])
    logits, mixed = forward_step(
        params, pad, mixed, CFG, n_tokens=jnp.asarray([2, 0], jnp.int32)
    )
    ref_l, ca2 = forward_step(params, tokens[:1, 14:16], ca, CFG)
    np.testing.assert_array_equal(np.asarray(mixed.length), [16, 3])
    np.testing.assert_array_equal(np.asarray(mixed.k[:, 0]),
                                  np.asarray(ca2.k[:, 0]))
    np.testing.assert_array_equal(np.asarray(mixed.v[:, 0]),
                                  np.asarray(ca2.v[:, 0]))
    # Inert slot: cache bytes untouched.
    np.testing.assert_array_equal(np.asarray(mixed.k[:, 1]),
                                  np.asarray(cb.k[:, 0]))
    np.testing.assert_allclose(np.asarray(logits[0, 1]),
                               np.asarray(ref_l[0, 1]), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("chunk", [4, 5])  # 4 divides the 12-token prompt,
                                           # 5 leaves a 2-token final chunk
def test_chunked_admission_matches_lockstep_generate(params, engine, chunk):
    """The tentpole parity: admission in chunks (prefill fused into the
    tick at `chunk` tokens per slot per tick) is token-for-token identical
    to lockstep generate(), which prefills each prompt in one piece, for
    chunk sizes that do and do not divide the prompt."""
    B, Tp, n_new = 3, 12, 6
    prompt = jax.random.randint(jax.random.PRNGKey(13), (B, Tp), 0,
                                CFG.vocab_size)
    chunked = engine(slots=B, cache_len=32, prefill_chunk=chunk,
                     prefill_budget=chunk)
    got = chunked.serve(_as_requests(prompt, n_new))
    lock = np.asarray(generate(params, prompt, n_new, CFG, cache_len=32))
    np.testing.assert_array_equal(
        np.stack([np.asarray(r.tokens) for r in got.results]), lock
    )


@pytest.mark.parametrize("chunk", [4, 5])
def test_chunked_int8_admission_matches_the_plain_int8_loop(
        params, engine, chunk):
    """Same parity through the int8 pool: the staged exact prefill +
    quantize-at-final-chunk must reproduce a whole-prompt
    quantize-after-prefill bit-for-bit (same rows, the same per-block
    frozen scales): the plain int8 loop over each prompt."""
    B, Tp, n_new = 2, 12, 5
    prompt = jax.random.randint(jax.random.PRNGKey(14), (B, Tp), 0,
                                CFG.vocab_size)
    chunked = engine(slots=B, cache_len=32, quantize=True,
                     prefill_chunk=chunk, prefill_budget=chunk)
    got = chunked.serve(_as_requests(prompt, n_new))
    for b in got.results:
        assert b.tokens == _int8_stream(params, prompt[b.uid], n_new)


def test_mid_prefill_arrival(params, engine):
    """Requests arriving while another slot is mid-prefill are admitted
    into free slots and everyone still matches single-stream decode — the
    scheduler interleaves chunks and decode without cross-talk."""
    rng = np.random.default_rng(15)
    long_prompt = rng.integers(0, CFG.vocab_size, size=20).astype(np.int32)
    reqs = [
        Request(uid=0, prompt=long_prompt, max_new_tokens=4,
                arrival_tick=0),
        # Arrives while uid 0 is still chunking (20 tokens / chunk 4 = 5
        # ticks of prefill).
        Request(uid=1,
                prompt=rng.integers(0, CFG.vocab_size, size=6).astype(
                    np.int32),
                max_new_tokens=5, arrival_tick=1),
        Request(uid=2,
                prompt=rng.integers(0, CFG.vocab_size, size=9).astype(
                    np.int32),
                max_new_tokens=3, arrival_tick=2),
    ]
    server = engine(slots=2, cache_len=32, prefill_chunk=4,
                    prefill_budget=4)
    report = server.serve(reqs, max_ticks=300)
    assert sorted(r.uid for r in report.results) == [0, 1, 2]
    for res in report.results:
        req = next(r for r in reqs if r.uid == res.uid)
        assert res.tokens == _single_stream(
            params, req.prompt, req.max_new_tokens, cache_len=32
        ), f"request {res.uid} diverged under mid-prefill arrival"


def test_eos_on_final_chunk(params, engine):
    """EOS sampled ON the final prefill chunk retires the slot before it
    ever decodes: outcome 'eos', exactly one token out."""
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(16), (11,), 0, CFG.vocab_size)
    )
    first = _single_stream(params, prompt, 1, cache_len=32)[0]
    server = engine(slots=2, cache_len=32, prefill_chunk=4,
                    prefill_budget=4)
    report = server.serve([
        Request(uid=0, prompt=prompt, max_new_tokens=6, eos_id=first)
    ])
    res = report.results[0]
    assert res.outcome == "eos"
    assert res.tokens == [first]


def test_chunked_admission_mesh_parity(params, engine):
    """Chunked admission on a sequence-sharded pool (mixed-Tq step through
    the tree merge, each shard writing the rows its own blocks hold)
    reproduces the single-device chunked tokens."""
    mesh = cpu_mesh(2)
    B, Tp, n_new = 2, 12, 4
    prompt = jax.random.randint(jax.random.PRNGKey(17), (B, Tp), 0,
                                CFG.vocab_size)
    kw = dict(slots=B, cache_len=32, prefill_chunk=5, prefill_budget=5)
    ref = engine(**kw).serve(_as_requests(prompt, n_new))
    got = engine(mesh=mesh, kv_shard="seq", **kw).serve(
        _as_requests(prompt, n_new)
    )
    for a, b in zip(ref.results, got.results):
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)


def test_chunked_quantized_mesh_parity(params, engine):
    """The staged (quantized) chunked admission on a sequence-sharded
    int8 pool: staging, quantize-at-final-chunk, and insert all reshard
    correctly and reproduce the single-device tokens, which are the
    plain int8 loop's — a request's tokens do not depend on the mesh."""
    mesh = cpu_mesh(2)
    prompt = jax.random.randint(jax.random.PRNGKey(19), (2, 12), 0,
                                CFG.vocab_size)
    kw = dict(slots=2, cache_len=32, quantize=True, prefill_chunk=5)
    ref = engine(**kw).serve(_as_requests(prompt, 5))
    got = engine(mesh=mesh, kv_shard="seq", **kw).serve(
        _as_requests(prompt, 5)
    )
    for a, b in zip(ref.results, got.results):
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)
        assert a.tokens == _int8_stream(params, prompt[a.uid], 5)


def test_prefill_chunk_metrics(engine):
    """serving_prefill_chunks_total counts scheduled chunks; TTFT/TBT
    histograms record once the registry is armed."""
    from tree_attention_tpu import obs

    obs.enable()
    try:
        reg = obs.REGISTRY
        chunks0 = reg.counter("serving_prefill_chunks_total").value()
        server = engine(slots=2, cache_len=32, prefill_chunk=4,
                        prefill_budget=4)
        prompt = jax.random.randint(jax.random.PRNGKey(18), (2, 10), 0,
                                    CFG.vocab_size)
        server.serve(_as_requests(prompt, 3))
        # 10-token prompts at chunk 4 -> 3 chunks each.
        assert reg.counter("serving_prefill_chunks_total").value() \
            - chunks0 == 6
        assert reg.histogram("serving_ttft_seconds")._value_payload()[
            "count"] >= 2
        assert reg.histogram("serving_tbt_seconds")._value_payload()[
            "count"] >= 2
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# ISSUE 4: serving observability plane
# ---------------------------------------------------------------------------


def _traced_serve(engine, tmp_path, reqs, **server_kw):
    """Serve a trace with the span tracer armed; returns (report, events)."""
    from tree_attention_tpu import obs

    path = tmp_path / "serve_trace.jsonl"
    obs.TRACER.start(str(path))
    try:
        server = engine(**server_kw)
        report = server.serve(reqs)
    finally:
        obs.TRACER.close()
    events = [json.loads(l) for l in path.read_text().splitlines()]
    return report, events


def test_request_spans_rid_propagation(engine, tmp_path):
    """The tentpole trace contract: every request's life is one span plus
    queued/admitted/first_token/retired instants, all carrying its rid —
    loading the file shows each request from enqueue to retire."""
    prompt = jax.random.randint(jax.random.PRNGKey(20), (3, 10), 0,
                                CFG.vocab_size)
    report, events = _traced_serve(
        engine, tmp_path, _as_requests(prompt, 4),
        slots=2, cache_len=32, prefill_chunk=4, prefill_budget=4,
    )
    uids = {r.uid for r in report.results}

    spans = [e for e in events if e["ph"] == "X"
             and e["name"].startswith("request:")]
    assert {e["args"]["rid"] for e in spans} == uids
    for e in spans:
        # Open at admit, closed at retire, outcome + token count tagged.
        assert e["args"]["outcome"] == "budget"
        assert e["args"]["tokens"] == 4
        assert e["args"]["ttft_s"] >= 0
        assert e["dur"] > 0

    def rids(name):
        return [e["args"]["rid"] for e in events
                if e["ph"] == "i" and e["name"] == name]

    for name in ("request_queued", "request_admitted", "first_token",
                 "request_retired"):
        assert sorted(rids(name)) == sorted(uids), name
    # Chunked admission: 10-token prompts at chunk 4 -> 3 chunks each,
    # each instant tagged "k/N" with the owning rid.
    chunks = [e for e in events if e["ph"] == "i"
              and e["name"] == "prefill_chunk"]
    assert len(chunks) == 3 * len(uids)
    assert {c["args"]["rid"] for c in chunks} == uids
    assert [c["args"]["chunk"] for c in chunks
            if c["args"]["rid"] == min(uids)] == ["1/3", "2/3", "3/3"]


def test_tick_spans_tag_occupancy_and_queue(engine, tmp_path):
    """Per-tick mixed-step spans carry occupancy, chunk-budget spent, and
    queue depth — the three numbers a stall post-mortem starts from."""
    prompt = jax.random.randint(jax.random.PRNGKey(21), (4, 8), 0,
                                CFG.vocab_size)
    report, events = _traced_serve(
        engine, tmp_path, _as_requests(prompt, 3),
        slots=2, cache_len=32, prefill_chunk=4,
    )
    ticks = [e for e in events if e["ph"] == "X"
             and e["name"] == "serving:tick"]
    # One span an executed tick, and one more each time the loop lands
    # a pending tail before it idles (ISSUE 32): the drained exit here.
    assert len([e for e in ticks if not e["args"].get("drain")]) \
        == report.ticks
    assert len([e for e in ticks if e["args"].get("drain")]) == 1
    for e in ticks:
        args = e["args"]
        assert {"tick", "occupancy", "prefilling", "chunk_tokens",
                "queue_depth", "host_sync", "tokens"} <= set(args)
        assert 0 <= args["occupancy"] <= 2
    # 4 requests through 2 slots: early ticks see a nonzero queue.
    assert any(e["args"]["queue_depth"] > 0 for e in ticks)
    assert any(e["args"]["chunk_tokens"] > 0 for e in ticks)
    assert sum(e["args"]["tokens"] for e in ticks) \
        == report.tokens_generated


def test_flight_recorder_records_serving_ticks(engine):
    """The engine feeds the ring one record per tick: occupancy vector,
    slot states, chunk plan, host-sync flag, queue depth, and the pool's
    block occupancy."""
    from tree_attention_tpu.obs.flight import FLIGHT

    prompt = jax.random.randint(jax.random.PRNGKey(22), (2, 9), 0,
                                CFG.vocab_size)
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        server = engine(slots=2, cache_len=32, prefill_chunk=4,
                        prefill_budget=8)  # both prompts chunk side by side
        report = server.serve(_as_requests(prompt, 3))
    finally:
        FLIGHT.disarm()
    snap = FLIGHT.snapshot()
    assert snap["ticks_recorded"] == report.ticks
    recs = snap["records"]
    assert [r["tick"] for r in recs] == sorted(r["tick"] for r in recs)
    assert {"states", "chunk_plan", "tokens_emitted", "host_sync",
            "queue_depth", "occupancy", "t_s", "kv_blocks_used",
            "kv_frag"} <= set(recs[0])
    # Two 9-token prompts at pages of 16: one block each while they live.
    assert max(r["kv_blocks_used"] for r in recs) == 2
    # Chunk ticks then live decode then drained.
    assert any(r["chunk_tokens"] > 0 for r in recs)
    assert any(r["occupancy"] == 2 for r in recs)
    assert sum(r["tokens_emitted"] for r in recs) == report.tokens_generated
    FLIGHT.clear()


def test_flight_dump_on_engine_error(params, tmp_path):
    """An engine error (here: the max_ticks runaway guard) dumps the ring
    to the armed sink before the exception propagates — the black box."""
    from tree_attention_tpu.obs.flight import FLIGHT

    path = tmp_path / "flight_err.json"
    prompt = jax.random.randint(jax.random.PRNGKey(23), (2, 8), 0,
                                CFG.vocab_size)
    FLIGHT.clear()
    FLIGHT.arm(str(path))
    try:
        # Its own engine: the guard leaves requests in their slots.
        server = SlotServer(params, CFG, slots=1, cache_len=32,
                            kv_block=KV_BLOCK)
        with pytest.raises(RuntimeError, match="max_ticks"):
            server.serve(_as_requests(prompt, 8), max_ticks=3)
    finally:
        FLIGHT.disarm()
    data = json.loads(path.read_text())
    assert data["reason"] == "engine_error:RuntimeError"
    assert data["records"], "no ticks captured before the error"
    FLIGHT.clear()


def test_serve_report_slo_goodput_bounds(params):
    """SLO surface in ServeReport: generous targets -> goodput 1.0,
    unmeetable targets -> 0.0; window percentiles agree with the report's
    own TTFT/TBT accounting (same shared percentile definition)."""
    prompt = jax.random.randint(jax.random.PRNGKey(24), (2, 8), 0,
                                CFG.vocab_size)

    # Engines of its own: the window's counts are asserted below.
    relaxed = SlotServer(params, CFG, slots=2, cache_len=32,
                         kv_block=KV_BLOCK,
                         slo_ttft=3600.0, slo_tbt=3600.0)
    rep = relaxed.serve(_as_requests(prompt, 3))
    assert rep.slo["goodput"] == 1.0
    assert rep.slo["requests_retired"] == 2
    assert rep.slo["ttft_p95_s"] == pytest.approx(
        rep.latency_percentiles()["ttft_p95_s"], abs=1e-6  # 6-dp rounding
    )

    strict = SlotServer(params, CFG, slots=2, cache_len=32,
                        kv_block=KV_BLOCK,
                        slo_ttft=1e-12, slo_tbt=1e-12)
    rep = strict.serve(_as_requests(prompt, 3))
    assert rep.slo["goodput"] == 0.0
    assert rep.as_dict()["slo"]["slo"] == {"ttft_s": 1e-12, "tbt_s": 1e-12}


def test_slo_gauges_live_after_serve(engine):
    """serve() publishes the windowed SLO gauges when the registry is
    armed — what a /metrics scrape sees."""
    from tree_attention_tpu import obs

    obs.enable()
    try:
        server = engine(slots=2, cache_len=32, slo_ttft=3600.0,
                        slo_tbt=3600.0)
        prompt = jax.random.randint(jax.random.PRNGKey(25), (2, 8), 0,
                                    CFG.vocab_size)
        server.serve(_as_requests(prompt, 3))
        reg = obs.REGISTRY
        assert reg.get("serving_goodput_ratio").value() == 1.0
        assert reg.get("serving_slo_ttft_seconds").labels(
            q="p95").value() > 0
        assert reg.get("serving_slo_tbt_seconds").labels(
            q="p50").value() >= 0
        # And the Prometheus text a /metrics scrape would serve carries
        # the series.
        text = reg.to_prometheus()
        assert 'serving_slo_ttft_seconds{q="p95"}' in text
        assert "serving_goodput_ratio 1" in text
    finally:
        obs.disable()


def test_serving_metrics_flow(engine):
    """The four serving metrics record when the registry is armed."""
    from tree_attention_tpu import obs

    obs.enable()
    try:
        reg = obs.REGISTRY
        tokens0 = reg.counter("serving_tokens_total").value()
        server = engine(slots=2, cache_len=32)
        prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0,
                                    CFG.vocab_size)
        report = server.serve(_as_requests(prompt, 3))
        assert (
            reg.counter("serving_tokens_total").value() - tokens0
            == report.tokens_generated
        )
        done = reg.counter(
            "serving_requests_total", labels=("outcome",)
        ).labels(outcome="budget").value()
        assert done >= 2
        hist = reg.histogram("serving_queue_wait_seconds")
        assert hist._value_payload()["count"] >= 2
    finally:
        obs.disable()
