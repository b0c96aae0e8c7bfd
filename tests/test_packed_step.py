"""The packed step against the padded one (ISSUE 30).

``forward_packed_step`` computes a compact chunk group ``(C, Tq)`` beside one
decode row a slot; ``forward_step`` with ``n_tokens`` computes ``(S, Tq)``
rows for the same tick. Same tokens in, same state out: the logits of the row
each slot samples from, every byte of the pool (int8 scales included) and
``length``. The two run the same layer body (``_step_layers``) on the same
arithmetic per row; what differs is how many rows ride beside it, so the pool
is held bit for bit and the logits to rounding.

Off the TPU both take the hoisted reference view; what the TPU compiler makes
of the packed program is ``test_chip_compile.py``'s to check.
"""

import dataclasses
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.models import (
    TransformerConfig,
    forward_packed_step,
    forward_step,
    init_paged_cache,
    init_params,
)
from tree_attention_tpu.models.decode import cache_pools
from tree_attention_tpu.models.experts import init_block_params
from tree_attention_tpu.models.transformer import model_from_config

from tests.jitted import packed_step_stats, step_stats

S, TQ, BLK, CACHE_LEN = 4, 8, 4, 32
NB = CACHE_LEN // BLK


def _dense_cfg():
    return TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, dtype=jnp.float32, attn_block_size=BLK,
    )


def _latent_cfg():
    from tests.test_latent_moe import SMALL

    return model_from_config(SMALL)


@functools.lru_cache(maxsize=None)
def _setup(kind):
    """(cfg, params, a cache with every slot mapped and some history)."""
    if kind == "latent":
        cfg = _latent_cfg()
        params = init_block_params(jax.random.PRNGKey(0), cfg)
    else:
        cfg = _dense_cfg()
        params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_paged_cache(
        cfg, S, CACHE_LEN, S * NB, block=BLK, quantize=kind == "int8")
    # Slot i owns blocks [i·NB, (i+1)·NB), in a scrambled order.
    rng = np.random.default_rng(3)
    table = np.stack([i * NB + rng.permutation(NB) for i in range(S)])
    cache = dataclasses.replace(cache, table=jnp.asarray(table, jnp.int32))
    # History: a few exact steps, so decode rows attend real rows (and an
    # int8 pool has blocks with scales of their own).
    hist = jnp.asarray(rng.integers(0, cfg.vocab_size, (S, 6)), jnp.int32)
    n_hist = jnp.asarray([5, 3, 6, 2], jnp.int32)
    _, cache = forward_step(params, hist, cache, cfg, n_tokens=n_hist)
    return cfg, params, cache


# name -> (chunk members [(slot, n)], decode slots, reset {slot: value}, C)
TICKS = {
    # One full chunk beside three decode rows.
    "c1_full_chunk": ([(1, TQ)], [0, 2, 3], {}, 1),
    # A chunk tail (n < Tq) whose slot restarts at 0: the first chunk of a
    # prompt in a reused slot.
    "c1_tail_after_reset": ([(3, 5)], [0, 2], {3: 0}, 1),
    # Two members, one a tail; one decode row; one slot idle.
    "c2_two_members": ([(0, TQ), (2, 3)], [1], {}, 2),
    # C = 2 with one member only: the other is padding, and names a slot
    # that decodes (it must move nothing of that slot's).
    "c2_one_member_one_padding": ([(2, 7), (0, 0)], [0, 1, 3], {}, 2),
    # A prefix hit: the chunking slot's length is reset to a block
    # boundary below what its last occupant left.
    "c1_reset_to_a_hit": ([(2, 6)], [0, 1, 3], {2: BLK}, 1),
}


def _tick(cfg, cache, name):
    members, decode, reset, C = TICKS[name]
    rng = np.random.default_rng(11)
    chunk_tok = rng.integers(0, cfg.vocab_size, (C, TQ)).astype(np.int32)
    dec_tok = rng.integers(0, cfg.vocab_size, (S,)).astype(np.int32)
    length = np.array(cache.length)
    for slot, val in reset.items():
        length[slot] = val
    cache = dataclasses.replace(cache, length=jnp.asarray(length))
    chunk_slot = np.asarray([s for s, _ in members], np.int32)
    chunk_n = np.asarray([n for _, n in members], np.int32)
    dec_n = np.zeros((S,), np.int32)
    dec_n[decode] = 1
    # The padded tick of the same work.
    mat = np.zeros((S, TQ), np.int32)
    n_vec = dec_n.copy()
    mat[:, 0] = dec_tok
    for j, (slot, n) in enumerate(members):
        if n:
            mat[slot] = chunk_tok[j]
            n_vec[slot] = n
    return cache, (chunk_tok, chunk_slot, chunk_n, dec_tok, dec_n), \
        (mat, n_vec)


@pytest.mark.parametrize("tick", sorted(TICKS))
@pytest.mark.parametrize("kind", ["exact", "int8", "latent"])
def test_packed_step_matches_padded(kind, tick):
    cfg, params, cache = _setup(kind)
    cache, packed, (mat, n_vec) = _tick(cfg, cache, tick)
    # The tick's tokens and counts are operands: the ticks of one kind and
    # one ``C`` share a compiled program (``tests/jitted.py``).
    ref_logits, ref_cache, _ = step_stats(
        params, jnp.asarray(mat), cache, jnp.asarray(n_vec), cfg)
    got_logits, got_cache, _ = packed_step_stats(
        params, *(jnp.asarray(a) for a in packed), cache, cfg)
    np.testing.assert_array_equal(np.asarray(got_cache.length),
                                  np.asarray(ref_cache.length))
    for name, pool in cache_pools(ref_cache).items():
        got = np.asarray(cache_pools(got_cache)[name])
        if kind == "int8":
            # A row quantizes under its block's scale; the row itself is
            # the same to float rounding, so a level may flip by one.
            assert np.abs(got.astype(np.int32)
                          - np.asarray(pool).astype(np.int32)).max() <= 1, name
        else:
            np.testing.assert_allclose(got, np.asarray(pool), rtol=0,
                                       atol=2e-6, err_msg=name)
    if kind == "int8":
        for f in ("k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got_cache, f)),
                np.asarray(getattr(ref_cache, f)), err_msg=f)
    rows = np.maximum(n_vec - 1, 0)
    ref_last = np.asarray(ref_logits)[np.arange(S), rows]
    live = n_vec > 0
    np.testing.assert_allclose(np.asarray(got_logits)[live], ref_last[live],
                               rtol=2e-4, atol=2e-4)
    assert got_logits.shape == (S, cfg.vocab_size)


def test_packed_step_counts_the_rows_that_carry_a_token():
    """The expert layers' row counters see the packed rows' validity, not
    the padded matrix's: the same pairs, whichever way the tick is laid."""
    cfg, params, cache = _setup("latent")
    cache, packed, (mat, n_vec) = _tick(cfg, cache, "c2_two_members")
    a = np.asarray(step_stats(params, jnp.asarray(mat), cache,
                              jnp.asarray(n_vec), cfg)[2]["expert_rows"])
    b = np.asarray(packed_step_stats(
        params, *(jnp.asarray(x) for x in packed), cache,
        cfg)[2]["expert_rows"])
    layers = cfg.n_layers - cfg.n_dense_layers
    assert a.sum() == int(n_vec.sum()) * cfg.moe.per_token * layers
    np.testing.assert_array_equal(a, b)


def test_packed_step_refuses_a_contiguous_cache():
    from tree_attention_tpu.models import init_cache

    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, S, CACHE_LEN)
    z = jnp.zeros((S,), jnp.int32)
    with pytest.raises(ValueError, match="paged pool"):
        forward_packed_step(
            params, jnp.zeros((1, TQ), jnp.int32), z[:1], z[:1], z, z,
            cache, cfg)
