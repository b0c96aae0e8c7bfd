"""The paged pool's one write, and the pool it leaves (ISSUE 25).

``forward_step`` carries a replicated paged pool through the layer loop and
writes each layer's new rows into it with ``_paged_pool_write``: the blocks
the rows touch are read, overlaid and scattered back into the pool viewed
flat, layer ``l`` reached by offset. Two contracts, both bit for bit:

(a) the write against a plain numpy loop, on every layer of a 3-layer pool
    (a row that drops must not land in another layer's block 0, which is
    what the parent's per-layer sentinel ``N`` names in a flat pool);
(b) the pool after ``forward_step`` against the same step with the parent's
    write (``pool.at[pb, :, off, :]`` on the layer's slice) put in its place:
    how the rows get there is all that changed.

What the TPU compiler makes of the write (no pool-sized copy) is
``test_chip_compile.py``'s to check; the CPU runs the hoisted view, which
takes the same write.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax

from tree_attention_tpu.models import (
    TransformerConfig,
    forward_step,
    init_paged_cache,
    init_params,
)
from tree_attention_tpu.models import decode
from tree_attention_tpu.models.decode import (
    PagedKVCache,
    PagedQuantKVCache,
    _paged_pool_write,
)

L, N, HKV, BLK, D = 3, 8, 2, 4, 8
NB = 3                      # a slot's table: capacity 12 tokens
TQ = 6

# name -> (table, start, n); one row per slot. Writable blocks are distinct
# between slots, as the allocator keeps them.
CASES = {
    # Two inert slots (n = 0) beside a full chunk and a single decode row.
    "ragged_with_idle_slots": (
        [[0, 1, 2], [3, 4, 5], [6, 7, 0], [0, 0, 0]],
        [0, 5, 2, 7], [0, 6, 1, 0]),
    # Rows 3..8: the chunk leaves block 0, fills block 1, enters block 2.
    "chunk_crosses_blocks": (
        [[5, 2, 7], [1, 3, 0]], [3, 1], [6, 5]),
    # Slot 0 reaches capacity (rows 9, 10, 11 land; 12, 13, 14 drop); slot
    # 1 starts at capacity and writes nothing.
    "reaches_capacity": (
        [[4, 6, 1], [2, 0, 3]], [9, 12], [6, 6]),
    # Both slots read the same two prefix blocks (5, 2), below ``start``;
    # each writes only its own third block.
    "shared_prefix_below_start": (
        [[5, 2, 3], [5, 2, 6]], [8, 9], [4, 3]),
    # A table entry outside the pool: the parent's scatter dropped it, and
    # a flat pool must not take it for a block of the next layer.
    "table_entry_past_the_pool": (
        [[1, N, 2], [3, -1, 4]], [2, 3], [6, 6]),
}


def _numpy_write(pool, rows, table, start, n, layer):
    out = pool.copy()
    for i in range(rows.shape[0]):
        for j in range(int(n[i])):
            pos = int(start[i]) + j
            if pos >= table.shape[1] * BLK:
                continue
            pb = int(table[i, pos // BLK])
            if 0 <= pb < N:
                out[layer, pb, :, pos % BLK, :] = rows[i, :, j, :]
    return out


def _bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_pool_write_matches_numpy(case, dtype, layer):
    table, start, n = (np.asarray(a, np.int32) for a in CASES[case])
    rng = np.random.default_rng(sorted(CASES).index(case))
    draw = lambda shape: jnp.asarray(
        rng.integers(-100, 100, size=shape), dtype)
    pool = draw((L, N, HKV, BLK, D))
    rows = draw((len(start), HKV, TQ, D))
    got = _paged_pool_write(pool, rows, jnp.asarray(table),
                            jnp.asarray(start), jnp.asarray(n),
                            jnp.int32(layer))
    want = _numpy_write(np.asarray(pool), np.asarray(rows), table, start, n,
                        layer)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    for other in set(range(L)) - {layer}:   # no row in layer l-1 or l+1
        np.testing.assert_array_equal(_bits(got[other]), _bits(pool[other]))
    if case == "reaches_capacity":
        assert (_bits(got[layer]) != _bits(pool[layer])).any()


# -- (b) the pool after forward_step ----------------------------------------

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=L, n_heads=4, n_kv_heads=HKV,
    d_head=D, d_ff=64, max_seq_len=64, dtype=jnp.bfloat16,
    attn_impl="blockwise", attn_block_size=BLK,
)


def _parent_write(pool, rows, table, start, n, layer):
    """The write as the parent commit had it, on layer ``layer``'s slice:
    block and row indexed apart, invalid rows sent to block ``N``."""
    one = lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    blocks, _, block, _ = one.shape
    B, Hkv, Tq, Dh = rows.shape
    pos = start[:, None] + jnp.arange(Tq, dtype=jnp.int32)[None, :]
    lb = jnp.clip(pos // block, 0, table.shape[1] - 1)
    pb = jnp.take_along_axis(table, lb, axis=1)
    valid = ((jnp.arange(Tq, dtype=jnp.int32)[None, :] < n[:, None])
             & (pos < table.shape[1] * block))
    pb = jnp.where(valid, pb, blocks)
    flat = jnp.moveaxis(rows, 2, 1).reshape(B * Tq, Hkv, Dh)
    one = one.at[pb.reshape(-1), :, (pos % block).reshape(-1), :].set(
        flat.astype(pool.dtype), mode="drop")
    return lax.dynamic_update_index_in_dim(pool, one, layer, 0)


def _filled_cache(int8, seed):
    """A paged cache mid-life: random pool contents (and scales), four slots
    at ragged lengths, slots 0 and 1 sharing their first block."""
    rng = np.random.default_rng(seed)
    cache = init_paged_cache(CFG, 4, NB * BLK, N + 3, block=BLK,
                             quantize=int8)
    shape = cache.k.shape
    if int8:
        fill = lambda: jnp.asarray(
            rng.integers(-127, 128, size=shape), jnp.int8)
        scale = lambda: jnp.asarray(
            rng.uniform(0.01, 0.05, size=shape[:3]), jnp.float32)
        kw = dict(k_scale=scale(), v_scale=scale())
        kind = PagedQuantKVCache
    else:
        fill = lambda: jnp.asarray(
            rng.normal(size=shape), jnp.bfloat16)
        kw, kind = {}, PagedKVCache
    table = jnp.asarray(
        [[9, 1, 2], [9, 3, 4], [5, 6, 0], [7, 8, 10]], jnp.int32)
    return kind(k=fill(), v=fill(), table=table,
                length=jnp.asarray([5, 4, 0, 11], jnp.int32), **kw)


# name -> (Tq, n_tokens): a decode tick with an idle slot; a mixed tick whose
# chunk crosses a block, beside a decode row, an idle slot and a slot that
# writes its last row.
STEPS = {
    "decode_tick": (1, [1, 1, 0, 1]),
    "mixed_tick": (5, [5, 1, 0, 1]),
}


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_forward_step_pool_is_the_parents(monkeypatch, int8, step):
    tq, n_tokens = STEPS[step]
    params = init_params(jax.random.PRNGKey(3), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, tq), 0, 64)
    n_tokens = jnp.asarray(n_tokens, jnp.int32)

    def run():
        fn = jax.jit(lambda c: forward_step(
            params, tokens, c, CFG, n_tokens=n_tokens))
        return fn(_filled_cache(int8, seed=7))

    logits, new = run()
    monkeypatch.setattr(decode, "_paged_pool_write", _parent_write)
    logits_p, parent = run()

    before = _filled_cache(int8, seed=7)
    fields = ("k", "v") + (("k_scale", "v_scale") if int8 else ())
    for name in fields:
        got, want = getattr(new, name), getattr(parent, name)
        np.testing.assert_array_equal(_bits(got), _bits(want), err_msg=name)
        assert got.shape == getattr(before, name).shape
    # The step wrote something, in every layer, and only there.
    changed = _bits(new.k) != _bits(before.k)
    assert changed.reshape(L, -1).any(axis=1).all()
    written = int(np.sum(np.asarray(n_tokens)))
    assert changed.any(axis=-1).sum() <= L * HKV * written
    np.testing.assert_array_equal(np.asarray(new.length),
                                  np.asarray(before.length + n_tokens))
    np.testing.assert_array_equal(_bits(logits), _bits(logits_p))
