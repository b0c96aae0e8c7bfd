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

(c) a group of ONE row a slot on a TPU takes another road to the same bits
    (ISSUE 39): ``paged_row_write``, a Pallas kernel that moves the sublane
    tile holding each slot's row and nothing else, K and V in one call. Here
    it runs in interpret mode, with ``pool_write_path`` steered to it, and is
    held to the numpy loop AND to ``_paged_pool_write`` on every layer, for
    bf16 and int8 pools (the tile is 8 rows of a block), at the latent pool's and
    the packed hybrid's shapes, under two tables, under ``shard_map``, and
    where an idle slot's stale table entry names the very block a live slot
    writes in the same call.

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
    blocks, blk = pool.shape[1], pool.shape[3]
    for i in range(rows.shape[0]):
        for j in range(int(n[i])):
            pos = int(start[i]) + j
            if pos >= table.shape[1] * blk:
                continue
            pb = int(table[i, pos // blk])
            if 0 <= pb < blocks:
                out[layer, pb, :, pos % blk, :] = rows[i, :, j, :]
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


# -- (c) one row a slot: the row kernel --------------------------------------

RBLK = 64                   # eight tiles of 8 rows (``row_write_tile``)
# name -> (table, start, n) at Tq = 1: one row per slot, ``n`` 0 or 1. The
# cases above cut to their first row, and what only this path can get wrong.
ROW_CASES = {
    name: (table, [s * RBLK // BLK for s in start], [min(x, 1) for x in n])
    for name, (table, start, n) in CASES.items()
}
ROW_CASES.update({
    # The last row of a slot's capacity lands; the position after it drops.
    "last_row_and_one_past": (
        [[4, 6, 1], [2, 0, 3]], [NB * RBLK - 1, NB * RBLK], [1, 1]),
    # Rows of one tile's every neighbourhood: first and last row of a tile,
    # of a block, and tiles apart in one block of two slots' own.
    "tile_edges": (
        [[1, 2, 3], [4, 5, 6], [7, 0, 0], [0, 0, 0]],
        [0, RBLK + 7, 8, 2 * RBLK + 63], [1, 1, 1, 1]),
    "all_slots_idle": ([[1, 2, 3], [4, 5, 6]], [7, 70], [0, 0]),
    # Slot 1 is idle and its table still names block 3 (a retired request's),
    # where live slot 0 writes row 5 in this call; slot 3, idle too, names
    # slot 2's block at slot 2's own position. A tile read for an idle slot
    # and written back as it was would undo the row: both must land.
    "idle_slot_names_a_live_slots_block": (
        [[3, 0, 0], [3, 0, 0], [0, 6, 0], [0, 6, 0]],
        [5, 6, RBLK + 40, RBLK + 40], [1, 0, 1, 0]),
})
# name -> (Hkv, D, pools of a layer): rotary GQA's K and V; the latent pool
# (one row of 640 lanes a token, no head axis); the hybrid's K and V with two
# 64-lane heads side by side (LFM2: 4 x 128 lanes).
ROW_SHAPES = {"gqa": (HKV, D, 2), "latent": (1, 640, 1), "packed": (4, 128, 2)}


def _draw(rng, shape, dtype):
    return jnp.asarray(rng.integers(-100, 100, size=shape), dtype)


def _row_path(monkeypatch):
    monkeypatch.setattr(
        decode, "pool_write_path", lambda tq: "row" if tq == 1 else "block")


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_row_write_matches_numpy_and_the_block_path(
        monkeypatch, case, dtype, layer):
    _check_row_write(monkeypatch, "gqa", case, dtype, layer)


@pytest.mark.parametrize("case", ["idle_slot_names_a_live_slots_block",
                                  "last_row_and_one_past",
                                  "ragged_with_idle_slots"])
@pytest.mark.parametrize("shape", ["latent", "packed"])
def test_row_write_at_the_latent_and_the_packed_shape(
        monkeypatch, shape, case):
    _check_row_write(monkeypatch, shape, case, jnp.bfloat16, L - 1)


def _check_row_write(monkeypatch, shape, case, dtype, layer):
    hkv, d, n_pools = ROW_SHAPES[shape]
    table, start, n = (np.asarray(a, np.int32) for a in ROW_CASES[case])
    rng = np.random.default_rng(sorted(ROW_CASES).index(case))
    pools = tuple(_draw(rng, (L, N, hkv, RBLK, d), dtype)
                  for _ in range(n_pools))
    rows = tuple(_draw(rng, (len(start), hkv, 1, d), dtype)
                 for _ in range(n_pools))
    args = (jnp.asarray(table), jnp.asarray(start), jnp.asarray(n),
            jnp.int32(layer))
    block_path = [_paged_pool_write(p, r, *args)
                  for p, r in zip(pools, rows)]
    _row_path(monkeypatch)
    got = jax.jit(decode._pool_write)(pools, rows, *args)
    assert len(got) == n_pools
    for p, r, g, b in zip(pools, rows, got, block_path):
        want = _numpy_write(np.asarray(p), np.asarray(r), table, start, n,
                            layer)
        np.testing.assert_array_equal(_bits(g), _bits(want))
        np.testing.assert_array_equal(_bits(g), _bits(b))
        rows_changed = (_bits(g) != _bits(p)).any(axis=(2, 4)).sum()
        assert rows_changed <= int(n.sum())
    if case in ("idle_slot_names_a_live_slots_block", "tile_edges"):
        assert (_bits(got[0][layer]) != _bits(pools[0][layer])).any(
            axis=(1, 3)).sum() == int(n.sum())


def test_row_write_counts_its_builds(monkeypatch):
    """The registry says how often the row path was built, by the heads and
    the slots a call takes."""
    from tree_attention_tpu import obs

    table, start, n = (jnp.asarray(a, jnp.int32)
                       for a in ROW_CASES["tile_edges"])
    rng = np.random.default_rng(0)
    # A shape no other test of this process builds (the call is jitted: a
    # cached program counts nothing).
    pools = tuple(_draw(rng, (L, N, HKV, RBLK, 3 * D), jnp.bfloat16)
                  for _ in range(2))
    rows = tuple(_draw(rng, (4, HKV, 1, 3 * D), jnp.bfloat16)
                 for _ in range(2))
    _row_path(monkeypatch)
    was = obs.REGISTRY.enabled
    obs.REGISTRY.enable()
    try:
        decode._pool_write(pools, rows, table, start, n, 0)
        text = obs.REGISTRY.to_prometheus()
    finally:
        if not was:
            obs.REGISTRY.disable()
    line = [l for l in text.splitlines()
            if l.startswith("pallas_decode_kernel_builds_total{")
            and 'kernel="paged_row_write"' in l]
    assert line and f'heads="{HKV}"' in line[0] \
        and 'entries="4"' in line[0], text


def test_row_write_under_two_tables(monkeypatch):
    """A window configuration's tick: the same rows under the full layers'
    table into pools of N blocks and under the window layers' table into
    pools of fewer. An entry that is a block of the full pools and past the
    window pools drops there; the block of a slot outside its window (a
    stale entry, -1 as the ledger leaves it) drops."""
    n_win = 5
    table = np.asarray([[1, 2, 3], [4, 5, 6], [7, 0, 0]], np.int32)
    wtable = np.asarray([[-1, 2, 0], [4, n_win, 6], [3, 0, 0]], np.int32)
    start = np.asarray([RBLK + 3, RBLK + 20, 9], np.int32)
    n = np.asarray([1, 1, 1], np.int32)
    rng = np.random.default_rng(11)
    rows = tuple(_draw(rng, (3, HKV, 1, D), jnp.bfloat16) for _ in range(2))
    _row_path(monkeypatch)
    for blocks, tbl, landed in ((N, table, 3), (n_win, wtable, 2)):
        pools = tuple(_draw(rng, (L, blocks, HKV, RBLK, D), jnp.bfloat16)
                      for _ in range(2))
        got = decode._pool_write(pools, rows, jnp.asarray(tbl),
                                 jnp.asarray(start), jnp.asarray(n), 1)
        for p, r, g in zip(pools, rows, got):
            want = _numpy_write(np.asarray(p), np.asarray(r), tbl, start, n,
                                1)
            np.testing.assert_array_equal(_bits(g), _bits(want))
            assert (_bits(g) != _bits(p)).any(axis=(2, 4)).sum() == landed


def test_row_write_under_shard_map_drops_rows_of_other_shards(monkeypatch):
    """The sequence-sharded pool's write is the local call under
    ``shard_map``: each shard is handed a block only for the rows whose
    blocks it owns, so a row lands once, on its owner, and the union is the
    replicated write."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tests.jitted import jitted

    shards = 4
    mesh = Mesh(np.asarray(jax.devices()[:shards]), ("seq",))
    # Global block ids over 4 shards of 2: slots 0..3 write on shards 1, 3,
    # 0 and (idle) 2; slot 4's entry lies outside the pool.
    table = np.asarray([[2, 3, 0], [0, 7, 0], [1, 0, 0], [5, 0, 0],
                        [N, 0, 0]], np.int32)
    start = np.asarray([5, RBLK + 17, 63, 8, 2], np.int32)
    n = np.asarray([1, 1, 1, 0, 1], np.int32)
    rng = np.random.default_rng(5)
    pools = tuple(_draw(rng, (N, HKV, RBLK, D), jnp.bfloat16)
                  for _ in range(2))
    rows = tuple(_draw(rng, (5, HKV, 1, D), jnp.bfloat16) for _ in range(2))
    _row_path(monkeypatch)
    sharded = tuple(
        jax.device_put(p, NamedSharding(mesh, P("seq"))) for p in pools)
    write = jitted(lambda pl, r, t, s, k: decode._pool_write_seq(
        pl, r, t, s, k, mesh=mesh, seq_axis="seq"))
    got = write(sharded, rows, jnp.asarray(table), jnp.asarray(start),
                jnp.asarray(n))
    for p, r, g in zip(pools, rows, got):
        want = _numpy_write(np.asarray(p)[None], np.asarray(r), table, start,
                            n, 0)[0]
        np.testing.assert_array_equal(_bits(g), _bits(want))
        assert (_bits(g) != _bits(p)).any(axis=(1, 3)).sum() == 3


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


@pytest.mark.parametrize("path", ["block", "row"])
@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_forward_step_pool_is_the_parents(monkeypatch, int8, step, path):
    """``path`` row: a group of one row a slot through ``paged_row_write``
    (interpret mode), as a TPU's decode tick takes it; the mixed tick's
    five rows a slot keep the block path either way."""
    tq, n_tokens = STEPS[step]
    if path == "row":
        _row_path(monkeypatch)
    params = init_params(jax.random.PRNGKey(3), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, tq), 0, 64)
    n_tokens = jnp.asarray(n_tokens, jnp.int32)

    def run():
        fn = jax.jit(lambda c: forward_step(
            params, tokens, c, CFG, n_tokens=n_tokens))
        return fn(_filled_cache(int8, seed=7))

    logits, new = run()
    monkeypatch.setattr(decode, "pool_write_path", lambda tq: "block")
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
