"""The conv layers' one-launch decode step (``ops/pallas_conv.py``
``conv_tail_step``) against the XLA path it replaces on a TPU
(``models/hybrid.py`` ``_tail_step``: the tails' gathers, the taps and the
scatter), in interpret mode: the output rows of every slot that writes and the
WHOLE pool, bit for bit. Every case runs the same two compiled programs (one
shape: 8 slots, 4 table entries of 4 positions, 3 conv layers of 32 blocks)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_attention_tpu.models.decode import _RowGroup
from tree_attention_tpu.models.hybrid import _tail_step
from tree_attention_tpu.ops.pallas_conv import (
    CUT,
    conv_tail_plan,
    conv_tail_step,
)

B, D, L, N, BLOCK, NB = 8, 128, 3, 32, 4, 4


@jax.jit
def _xla_step(flat, bcu, w, table, start, n, c):
    """What ``conv_mixer`` runs for a group of one row a slot off the TPU."""
    g = _RowGroup(lo=None, batch=B, tq=1, start=start, n=n, table=table,
                  tree_mask=None)
    flat, out, wrote = _tail_step(
        flat, bcu[:, None], w.astype(jnp.float32), g, c, N, BLOCK)
    return flat, out[:, 0], wrote


@jax.jit
def _kernel_step(flat, bcu, w, table, start, n, c):
    plan = conv_tail_plan(table, start, n, N, BLOCK)
    flat, out = conv_tail_step(flat, bcu, w, plan, c * N, interpret=True)
    return flat, out, plan.count, plan.ids


# Slot b's blocks, entry j: own[b][j] = 8 j + b puts the eight slots' blocks
# of one entry side by side in the pool (ONE cut of 8 rows an entry);
# spread[b][j] = 4 b + j keeps a slot's blocks together and two slots'
# current blocks 4 apart (two slots a cut).
OWN = np.arange(NB)[None, :] * B + np.arange(B)[:, None]
SPREAD = np.arange(B)[:, None] * NB + np.arange(NB)[None, :]
ONES = [1] * B


def _stale():
    # Slot 3 sits out; its first table entry is a stale name of the block
    # slot 2 writes in this very call.
    table = OWN.copy()
    table[3, 0] = table[2, 0]
    return table


def _past():
    # Slot 5's entry names a block past the pool, slot 6's none at all:
    # both rows are dropped; slot 1's position lies past its table.
    table = SPREAD.copy()
    table[5, 1], table[6, 1] = N, -1
    return table


CASES = {
    # name: (table, start, n, conv layer)
    "even_positions": (SPREAD, [2, 6, 10, 14, 2, 6, 10, 14], ONES, 0),
    "odd_positions": (SPREAD, [3, 7, 11, 15, 3, 7, 11, 15], ONES, 0),
    "block_starts_read_the_block_before":
        (SPREAD, [4, 5, 8, 9, 12, 13, 4, 5], ONES, 0),
    "nothing_before_positions_0_and_1":
        (SPREAD, [0, 1, 0, 1, 2, 3, 1, 0], ONES, 0),
    "a_slot_with_no_row_and_a_stale_entry":
        (_stale(), [1, 2, 3, 1, 2, 3, 2, 1], [1, 1, 1, 0, 1, 1, 0, 1], 0),
    "two_slots_a_cut": (SPREAD, [2, 3, 6, 7, 9, 10, 13, 14], ONES, 1),
    "eight_slots_one_cut": (OWN, [2, 3, 1, 0, 3, 2, 1, 2], ONES, 0),
    "eight_slots_one_cut_at_block_starts":
        (OWN, [4, 5, 4, 5, 6, 7, 5, 4], ONES, 2),
    "a_later_conv_layer": (SPREAD, [5, 2, 9, 14, 7, 0, 12, 3], ONES, 2),
    "blocks_past_the_pool_are_dropped":
        (_past(), [3, 16, 2, 7, 9, 5, 6, 1], ONES, 1),
}


@functools.lru_cache(maxsize=None)
def _operands(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    return mk((L * N, 2 * D)), mk((B, 3 * D)), mk((3, D))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_leaves_the_xla_paths_bits(case):
    table, start, n, c = CASES[case]
    flat, bcu, w = _operands()
    args = (jnp.asarray(table, jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.asarray(n, jnp.int32), jnp.int32(c))
    want_pool, want_out, want_n = _xla_step(flat, bcu, w, *args)
    got_pool, got_out, got_n, ids = _kernel_step(flat, bcu, w, *args)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    np.testing.assert_array_equal(f32(got_pool), f32(want_pool))
    writes = np.asarray(ids) >= 0
    np.testing.assert_array_equal(f32(got_out)[writes], f32(want_out)[writes])
    assert int(got_n) == int(want_n) == int(writes.sum())
    # The case is what its name says: the rows that changed are the
    # writers' rows of layer c and no other.
    changed = np.flatnonzero((f32(want_pool) != f32(flat)).any(axis=1))
    assert set(changed) <= {c * N + int(i) for i in np.asarray(ids)[writes]}
    if "one_cut" in case:
        assert len({(c * N + int(i)) // CUT for i in np.asarray(ids)}) == 1
    if "two_slots_a_cut" in case:
        cuts = [(c * N + int(i)) // CUT for i in np.asarray(ids)]
        assert sorted(cuts.count(x) for x in set(cuts)) == [2] * (B // 2)
    if "dropped" in case:
        assert int(want_n) == B - 3
    if "no_row" in case:
        assert int(want_n) == B - 2


# -- through the model: a decode step takes the kernel ------------------------

# The conv / attention hybrid at a small size in bf16 (the kernel's dtype):
# three conv layers of 16 blocks, 128 lanes a half row.
TINY = {
    "family": "lfm2_moe", "model_type": "lfm2_moe", "hidden_size": 128,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 2, "num_key_value_heads": 1,
    "num_hidden_layers": 4,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_dense_layers": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 64,
    "torch_dtype": "bfloat16", "tie_word_embeddings": True,
    "block": {"qk_norm": True, "router_scoring": "sigmoid"},
    "deployment": {"experts_total": 4, "expert_share": 0},
}


def test_a_decode_step_through_the_kernel_leaves_the_block_paths_cache(
        monkeypatch):
    """``forward_step`` at one row a slot with the pools' row path steered
    on (``pool_write_path``, as on a TPU) against the block path, step by
    step from the same cache: the tail pool, K, V and the logits bit for
    bit, the program's own count of tails the same, across a block
    boundary and with a slot that sits steps out."""
    import dataclasses

    from tree_attention_tpu.models import decode, hybrid
    from tree_attention_tpu.models.transformer import (
        init_params, model_from_config)

    cfg = model_from_config(TINY, max_seq_len=32)
    assert cfg.conv_layers == 3 and cfg.dtype == jnp.bfloat16
    params = init_params(jax.random.PRNGKey(3), cfg)
    slots, blk, nb = 4, 8, 4
    cache = decode.init_paged_cache(cfg, slots, nb * blk, slots * nb,
                                    block=blk)
    cache = dataclasses.replace(cache, table=jnp.arange(
        slots * nb, dtype=jnp.int32).reshape(nb, slots).T)   # neighbours

    def step(params, toks, cache, n):
        stats = {}
        logits, cache = decode.forward_step(
            params, toks, cache, cfg, n_tokens=n, stats=stats)
        return logits, cache, stats["tail_blocks"]

    by_block = jax.jit(step)
    row_path = lambda tq: "row" if tq == 1 else "block"
    assert hybrid.tail_write_path(1, cache.tail) == "block"     # a CPU
    monkeypatch.setattr(decode, "pool_write_path", row_path)
    monkeypatch.setattr(hybrid, "pool_write_path", row_path)
    assert hybrid.tail_write_path(1, cache.tail) == "row"
    assert hybrid.tail_write_path(1, cache.tail.astype(jnp.float32)) \
        == hybrid.tail_write_path(8, cache.tail) == "block"
    by_kernel = jax.jit(step)
    rng = np.random.default_rng(5)
    bits = lambda a: np.asarray(a.astype(jnp.float32))
    for t in range(11):
        toks = jnp.asarray(rng.integers(0, 64, size=(slots, 1)), jnp.int32)
        n = jnp.asarray([1, t % 3 != 1, 1, t < 9], jnp.int32)
        got = by_kernel(params, toks, cache, n)
        logits, cache, wrote = by_block(params, toks, cache, n)
        live = np.asarray(n) > 0
        np.testing.assert_array_equal(bits(got[0])[live], bits(logits)[live])
        for name in ("tail", "k", "v"):
            np.testing.assert_array_equal(
                bits(getattr(got[1], name)), bits(getattr(cache, name)), name)
        assert int(got[2]) == int(wrote) == 3 * int(live.sum())
    assert int(cache.length[0]) == 11      # past the first block's end
