"""Hierarchical KV cache tests (ISSUE 13): host-RAM demotion tier +
per-block int8 scales.

Four contracts, layered like the subsystem:

(a) **Tier bookkeeping** — HostBlockPool's row accounting and the
    allocator's new ``demoted`` ownership state never lose or double-use
    a block across enqueue/flush/cancel/restore/drop arcs, and the
    reservation-soundness rule extends to staged blocks (demoted is NOT
    available until its D2H copy lands).
(b) **Bit-exact staging round trip** — the jitted demote gather →
    host commit → host read → restore scatter pipeline reproduces the
    original pool bytes exactly, exact dtype and int8 + per-block
    scales alike (restore is a copy, not a recompute).
(c) **Radix tier transitions** — driving PagedPrefixIndex directly
    (no engine): eviction demotes instead of freeing, a hit on a
    still-pending demotion cancels it (zero copies), the host tier's own
    LRU drops leaves when full, and restore consumes fresh device blocks
    with the tree's view consistent throughout.
(d) **Hit-vs-cold parity across forced demote/restore cycles** — the
    existing suites' contract, now through the tier: a revisit of a
    demoted prefix must emit exactly the cold pass's tokens (bit-exact
    restore on the exact tier; token-level parity for int8, whose
    per-block scales now publish/hit through the SHARED radix tree),
    single device AND compat ``cpu_mesh``. Demotion is forced with a
    deliberately tiny ``kv_blocks`` pool.

Frugal by the tier-1 budget: one engine per configuration, serves
reused, the unit layers engine-free.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.models import TransformerConfig, init_params
from tree_attention_tpu.models.decode import (
    gather_kv_blocks,
    quantize_paged_blocks,
    scatter_kv_blocks,
)
from tree_attention_tpu.parallel import cpu_mesh
from tree_attention_tpu.serving import Request, SlotServer
from tree_attention_tpu.serving.block_pool import BlockAllocator
from tree_attention_tpu.serving.host_pool import HostBlockPool
from tree_attention_tpu.serving.prefix_cache import (
    PagedPrefixIndex,
    TIER_DEVICE,
    TIER_HOST,
)

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

# chunk == block == 4 (the PR-5/6 alignment trick) and a pool of 12
# blocks against a working set of 4 published prompts x 3 blocks + 5
# in-flight: admissions MUST demote — the forced-cycle knob the module
# docstring names.
TIER_KW = dict(
    slots=2, cache_len=32, prefill_chunk=4, prefill_budget=4,
    prefix_cache=True, prefix_block=4, kv_block=4,
    kv_blocks=12, host_blocks=16,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _req(uid, prompt, n_new=5, tick=0):
    return Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=n_new, arrival_tick=tick)


def _prompt(seed, n=13):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)


def _assert_drained(server):
    leak = server.leak_report()
    assert leak["blocks_private"] == 0, leak
    assert leak["blocks_reserved"] == 0, leak
    assert leak["pins"] == 0, leak
    assert leak["blocks_used"] == leak["blocks_cached"], leak
    hp = server._host_pool
    if hp is not None:
        assert not hp.pending, "demotions left staged after drain"


# ---------------------------------------------------------------------------
# (a) tier bookkeeping
# ---------------------------------------------------------------------------


class TestHostPoolBookkeeping:
    def _hp(self, blocks=4, quantized=False):
        return HostBlockPool(blocks, n_layers=1, n_kv_heads=1, block=2,
                             d_head=2,
                             dtype=np.int8 if quantized else np.float32,
                             quantized=quantized)

    def test_alloc_enqueue_flush_release_cycle(self):
        hp = self._hp()
        rows = [hp.alloc() for _ in range(3)]
        assert hp.used == 3 and hp.free_count == 1
        for i, r in enumerate(rows):
            hp.enqueue(r, device_bid=10 + i)
        assert hp.demotions == 3
        items = hp.take_pending()
        assert items == sorted((r, 10 + i) for i, r in enumerate(rows))
        assert not hp.pending  # drained in one batch
        hp.commit([r for r, _ in items],
                  np.ones((3, 1, 1, 2, 2), np.float32),
                  np.ones((3, 1, 1, 2, 2), np.float32))
        hp.release(rows[0], restored=True)
        hp.release(rows[1], restored=False)
        assert hp.restores == 1 and hp.drops == 1 and hp.used == 1

    def test_cancel_pending_returns_device_block(self):
        hp = self._hp()
        r = hp.alloc()
        hp.enqueue(r, device_bid=7)
        assert hp.cancel_pending(r) == 7  # bytes never left the device
        assert hp.cancel_pending(r) is None  # idempotent: already freed
        assert hp.used == 0 and hp.restores == 1

    def test_drop_of_pending_returns_block_for_free(self):
        hp = self._hp(blocks=1)
        r = hp.alloc()
        assert hp.alloc() is None  # tier full
        hp.enqueue(r, device_bid=3)
        assert hp.drop(r) == 3  # copy never ran; caller must free it
        assert hp.used == 0 and hp.drops == 1

    def test_double_stage_asserts(self):
        hp = self._hp()
        r = hp.alloc()
        hp.enqueue(r, device_bid=1)
        with pytest.raises(AssertionError, match="double-staged"):
            hp.enqueue(r, device_bid=2)

    def test_quantized_pool_carries_scales(self):
        hp = self._hp(quantized=True)
        r = hp.alloc()
        hp.enqueue(r, device_bid=0)
        hp.take_pending()
        hp.commit([r], np.ones((1, 1, 1, 2, 2), np.int8),
                  np.ones((1, 1, 1, 2, 2), np.int8),
                  np.full((1, 1, 1), 0.5, np.float32),
                  np.full((1, 1, 1), 0.25, np.float32))
        k, v, ks, vs = hp.read([r])
        assert ks[0, 0, 0] == 0.5 and vs[0, 0, 0] == 0.25
        assert k.dtype == np.int8


class TestAllocatorDemotedState:
    def test_demote_flush_frees(self):
        alloc = BlockAllocator(2)
        assert alloc.reserve(1)
        bid = alloc.alloc()
        alloc.publish(bid)
        alloc.demote_cached(bid)
        # Staged: NOT reusable, NOT available — the soundness window.
        assert alloc.available() == 1
        gen = alloc.gen
        alloc.free_demoted(bid)
        assert alloc.available() == 2 and alloc.gen == gen + 1

    def test_undemote_hands_back_to_tree(self):
        alloc = BlockAllocator(2)
        assert alloc.reserve(1)
        bid = alloc.alloc()
        alloc.publish(bid)
        alloc.demote_cached(bid)
        alloc.undemote(bid)  # the cancelled-pending restore arc
        alloc.free_cached(bid)  # tree-owned again: normal eviction works
        assert alloc.available() == 2

    def test_demote_requires_tree_ownership(self):
        alloc = BlockAllocator(2)
        assert alloc.reserve(1)
        bid = alloc.alloc()  # private, not tree-owned
        with pytest.raises(AssertionError, match="not tree-owned"):
            alloc.demote_cached(bid)

    def test_dry_alloc_flushes_staged_demotions(self):
        """The mid-tick arc: a backed reservation finds the free list
        dry, eviction DEMOTES (no block frees), so alloc must force the
        registered flusher to complete the staged copy before it can
        hand a block out — the soundness invariant holds through the
        staging window."""
        alloc = BlockAllocator(1)
        assert alloc.reserve(1)
        bid = alloc.alloc()
        alloc.publish(bid)  # tree-owned: the one evictable block
        tree, staged, flushed = [bid], [], []

        def evict_one():
            if not tree:
                return False
            b = tree.pop()
            alloc.demote_cached(b)  # demotes, does NOT free
            staged.append(b)
            return True

        alloc.set_evictor(evict_one, lambda: len(tree))

        def flush():
            n = len(staged)
            for b in staged:
                alloc.free_demoted(b)
            flushed.extend(staged)
            staged.clear()
            return n

        alloc.set_demote_flusher(flush)
        assert alloc.reserve(1)  # backed by the evictable block
        assert alloc.alloc() == bid  # demote -> flush -> free -> alloc
        assert flushed == [bid]


# ---------------------------------------------------------------------------
# (b) bit-exact staging round trip
# ---------------------------------------------------------------------------


class TestStagingRoundTrip:
    def test_exact_gather_commit_read_scatter_bit_exact(self):
        rng = np.random.default_rng(0)
        L, N, Hkv, blk, D = 2, 6, 2, 4, 8
        pool_k = jnp.asarray(rng.standard_normal((L, N, Hkv, blk, D)),
                             jnp.float32)
        pool_v = jnp.asarray(rng.standard_normal((L, N, Hkv, blk, D)),
                             jnp.float32)
        hp = HostBlockPool(4, n_layers=L, n_kv_heads=Hkv, block=blk,
                           d_head=D, dtype=np.float32)
        bids = [1, 4, 5]
        rows = [hp.alloc() for _ in bids]
        ids = jnp.asarray(np.array(bids, np.int32))
        gk, gv = jax.jit(gather_kv_blocks)(pool_k, pool_v, ids)
        hp.commit(rows, np.asarray(gk), np.asarray(gv))
        # Zero the demoted blocks on-device (the flush frees them for
        # reuse — the restore must NOT depend on the device bytes).
        zeroed_k = pool_k.at[:, jnp.asarray(bids)].set(0.0)
        zeroed_v = pool_v.at[:, jnp.asarray(bids)].set(0.0)
        hk, hv = hp.read(rows)
        rk, rv = jax.jit(scatter_kv_blocks)(
            zeroed_k, zeroed_v, ids, jnp.asarray(hk), jnp.asarray(hv)
        )
        assert np.array_equal(np.asarray(rk), np.asarray(pool_k))
        assert np.array_equal(np.asarray(rv), np.asarray(pool_v))

    def test_int8_round_trip_carries_scales_bit_exact(self):
        rng = np.random.default_rng(1)
        L, N, Hkv, blk, D = 2, 5, 2, 4, 8
        pool_k = jnp.asarray(
            rng.integers(-127, 128, (L, N, Hkv, blk, D)), jnp.int8
        )
        pool_v = jnp.asarray(
            rng.integers(-127, 128, (L, N, Hkv, blk, D)), jnp.int8
        )
        ks = jnp.asarray(rng.uniform(0.01, 1.0, (L, N, Hkv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 1.0, (L, N, Hkv)), jnp.float32)
        hp = HostBlockPool(4, n_layers=L, n_kv_heads=Hkv, block=blk,
                           d_head=D, dtype=np.int8, quantized=True)
        bids = [0, 3]
        rows = [hp.alloc() for _ in bids]
        ids = jnp.asarray(np.array(bids, np.int32))
        out = jax.jit(gather_kv_blocks)(pool_k, pool_v, ids, ks, vs)
        hp.commit(rows, *[np.asarray(o) for o in out])
        zk = pool_k.at[:, jnp.asarray(bids)].set(0)
        zv = pool_v.at[:, jnp.asarray(bids)].set(0)
        zks = ks.at[:, jnp.asarray(bids)].set(1.0)
        zvs = vs.at[:, jnp.asarray(bids)].set(1.0)
        hk, hv, hks, hvs = hp.read(rows)
        rk, rv, rks, rvs = jax.jit(scatter_kv_blocks)(
            zk, zv, ids, jnp.asarray(hk), jnp.asarray(hv),
            zks, zvs, jnp.asarray(hks), jnp.asarray(hvs)
        )
        for got, want in ((rk, pool_k), (rv, pool_v), (rks, ks),
                          (rvs, vs)):
            assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_requantize_roundtrip_is_identity(self):
        """The no-rewrite contract of the int8 hit path: dequantizing a
        block (int8 · its scale) and re-quantizing at the same per-block
        granularity reproduces the identical int8 bytes and scale —
        shared blocks never need rewriting at final chunk."""
        rng = np.random.default_rng(2)
        L, Hkv, T, D, blk = 2, 2, 16, 8, 4
        k = jnp.asarray(rng.standard_normal((L, 1, Hkv, T, D)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((L, 1, Hkv, T, D)),
                        jnp.float32)
        kq, vq, ks, vs = quantize_paged_blocks(k, v, blk, T)
        # Dequantize per block, then re-quantize.
        sk = jnp.repeat(jnp.moveaxis(ks, 1, 2), blk, axis=2)[:, None]
        k_deq = kq.astype(jnp.float32) * sk[..., None]
        sv = jnp.repeat(jnp.moveaxis(vs, 1, 2), blk, axis=2)[:, None]
        v_deq = vq.astype(jnp.float32) * sv[..., None]
        kq2, vq2, ks2, vs2 = quantize_paged_blocks(k_deq, v_deq, blk, T)
        assert np.array_equal(np.asarray(kq2), np.asarray(kq))
        assert np.array_equal(np.asarray(vq2), np.asarray(vq))
        assert np.array_equal(np.asarray(ks2), np.asarray(ks))
        assert np.array_equal(np.asarray(vs2), np.asarray(vs))

    def test_zero_span_takes_fallback_scale(self):
        k = jnp.zeros((1, 1, 1, 8, 4), jnp.float32)
        _, _, ks, vs = quantize_paged_blocks(k, k, 4, 8)
        assert np.all(np.asarray(ks) == 1.0)
        assert np.all(np.asarray(vs) == 1.0)


# ---------------------------------------------------------------------------
# (c) radix tier transitions, engine-free
# ---------------------------------------------------------------------------


def _publish_chain(idx, alloc, prompt, block=4):
    """Admit-like flow: reserve, alloc private blocks, adopt them."""
    nb = len(prompt) // block
    assert alloc.reserve(nb)
    phys = {j: alloc.alloc() for j in range(nb)}
    path, _ = idx.adopt(np.asarray(prompt, np.int32), phys, [])
    return path


class TestRadixTierTransitions:
    def _build(self, kv_blocks=6, host_blocks=4):
        alloc = BlockAllocator(kv_blocks)
        hp = HostBlockPool(host_blocks, n_layers=1, n_kv_heads=1,
                           block=4, d_head=2, dtype=np.float32)
        idx = PagedPrefixIndex(block=4, alloc=alloc, host_pool=hp)
        return alloc, hp, idx

    def test_eviction_demotes_and_match_spans_tiers(self):
        alloc, hp, idx = self._build()
        p1 = list(range(8))
        path = _publish_chain(idx, alloc, p1)
        idx.release(path)
        # A second chain pins the tree; evicting now must DEMOTE p1's
        # LRU leaf (p2's path is pinned, p1's is refcount-0).
        p2 = [50 + t for t in range(8)]
        path2 = _publish_chain(idx, alloc, p2)
        assert idx.evict_one()
        assert hp.demotions >= 1
        # Probe with one suffix token (matching caps at len-1 tokens).
        matched, nodes = idx.match(np.asarray(p1 + [99], np.int32))
        assert matched == 8  # the DEMOTED path still matches fully
        assert any(n.tier == TIER_HOST for n in nodes)
        idx.release(nodes)
        idx.release(path2)

    def test_pending_hit_cancels_demotion_zero_copy(self):
        alloc, hp, idx = self._build()
        p1 = list(range(8))
        path = _publish_chain(idx, alloc, p1)
        idx.release(path)
        old_bids = [n.block_id for n in path]
        assert idx.evict_one()  # leaf demoted, still PENDING (no flush)
        matched, nodes = idx.match(np.asarray(p1 + [99], np.int32))
        demoted = idx.demoted_in(nodes)
        assert len(demoted) == 1
        rows, bids = idx.restore_nodes(demoted, lambda: (_ for _ in ())
                                       .throw(AssertionError("no alloc")))
        assert rows == [] and bids == []  # cancelled in place: no copy
        assert [n.block_id for n in nodes] == old_bids
        assert all(n.tier == TIER_DEVICE for n in nodes)
        assert hp.used == 0 and hp.restores == 1
        idx.release(nodes)

    def test_flushed_restore_consumes_fresh_blocks(self):
        alloc, hp, idx = self._build()
        p1 = list(range(8))
        idx.release(_publish_chain(idx, alloc, p1))
        assert idx.evict_one() and idx.evict_one()
        # Flush the staged copies: device blocks free for reuse.
        for row, bid in hp.take_pending():
            hp.commit([row], np.zeros((1, 1, 1, 4, 2), np.float32),
                      np.zeros((1, 1, 1, 4, 2), np.float32))
            alloc.free_demoted(bid)
        free0 = alloc.free_count
        matched, nodes = idx.match(np.asarray(p1 + [99], np.int32))
        demoted = idx.demoted_in(nodes)
        assert len(demoted) == 2
        assert alloc.reserve(2)
        rows, bids = idx.restore_nodes(demoted, alloc.alloc)
        assert len(rows) == 2 and len(bids) == 2
        for row in rows:
            hp.release(row, restored=True)
        assert alloc.free_count == free0 - 2
        assert all(n.tier == TIER_DEVICE for n in nodes)
        assert hp.used == 0
        idx.release(nodes)
        # Restored nodes are tree-owned again: evictable as usual.
        assert idx.evictable_blocks() == 2

    def test_full_host_tier_drops_its_lru_leaf(self):
        alloc, hp, idx = self._build(kv_blocks=8, host_blocks=1)
        p1, p2 = list(range(8)), [50 + t for t in range(8)]
        idx.release(_publish_chain(idx, alloc, p1))
        idx.release(_publish_chain(idx, alloc, p2))
        # 4 cached; demote 3: the 1-row tier must drop to make room.
        assert idx.evict_one() and idx.evict_one() and idx.evict_one()
        assert hp.drops >= 2 and hp.used == 1
        assert idx.stats()["host_blocks_used"] == 1

    def test_evictable_counts_device_tier_only(self):
        alloc, hp, idx = self._build()
        idx.release(_publish_chain(idx, alloc, list(range(8))))
        assert idx.evictable_blocks() == 2
        assert idx.evict_one()
        assert idx.evictable_blocks() == 1  # host node holds no device block


# ---------------------------------------------------------------------------
# (d) hit-vs-cold parity across forced demote/restore cycles
# ---------------------------------------------------------------------------


def _serve_rounds(server, prompts, uid0=0):
    """Serve each prompt in its own run (serial revisit order — the LRU
    worst case) and return {prompt_index: tokens} plus summed kv stats."""
    toks, demoted, restored = {}, 0, 0
    for i, p in enumerate(prompts):
        rep = server.serve([_req(uid0 + i, p)])
        toks[i] = rep.results[0].tokens
        demoted += rep.kv.get("demotions", 0)
        restored += rep.kv.get("restores", 0)
    return toks, demoted, restored


_REF: dict = {}


def _exact_ref(params):
    """Memoized exact tiered run (ONE engine for the whole file): cold
    pass + demoted-revisit pass over 4 prompts on the tiny pool."""
    if not _REF:
        prompts = [_prompt(s) for s in range(4)]
        server = SlotServer(params, CFG, **TIER_KW)
        cold, d1, _ = _serve_rounds(server, prompts)
        warm, d2, r2 = _serve_rounds(server, prompts, uid0=10)
        _REF.update(server=server, prompts=prompts, cold=cold,
                    warm=warm, demotions=d1 + d2, restores=r2)
    return _REF


class TestDemoteRestoreParity:
    def test_exact_hit_vs_cold_across_cycles(self, params):
        """The PR-5/6 hit-vs-cold contract THROUGH the tier: pass 2
        revisits prefixes whose blocks were forcibly demoted (tiny
        pool), restores them, and must emit exactly the cold tokens —
        restore is bit-exact on the exact tier, so the revisit's
        programs see literally the cold run's rows."""
        ref = _exact_ref(params)
        server = ref["server"]
        assert ref["demotions"] > 0, "pool sizing failed to force demotion"
        assert ref["restores"] > 0, "revisit failed to exercise restore"
        assert ref["warm"] == ref["cold"]
        assert server._host_pool.used > 0  # the tier is actually holding
        _assert_drained(server)
        # One more cycle for good measure: the tree must still be
        # consistent after demote->restore->demote churn.
        again, _, r3 = _serve_rounds(server, ref["prompts"], uid0=20)
        assert r3 > 0 and again == ref["cold"]
        _assert_drained(server)

    def test_int8_hit_vs_cold_through_shared_tree(self, params):
        """int8 prefix publish/hit rides the SHARED radix tree now
        (per-block scales): token-level parity across forced
        demote/restore cycles, and the hit must move dequant-gather
        bytes (the int8 staging cost the instant reports)."""
        prompts = [_prompt(s) for s in range(4)]
        server = SlotServer(params, CFG, quantize=True, **TIER_KW)
        cold, d1, _ = _serve_rounds(server, prompts)
        warm, d2, r2 = _serve_rounds(server, prompts, uid0=10)
        assert d1 + d2 > 0 and r2 > 0
        assert warm == cold
        _assert_drained(server)

    def test_cpu_mesh_parity(self, params):
        """The same forced demote/restore flow on a compat cpu_mesh
        reproduces the single-device tokens (the gather/scatter jits run
        over the replicated pool arrays)."""
        ref = _exact_ref(params)
        server = SlotServer(params, CFG, mesh=cpu_mesh(2), **TIER_KW)
        cold, _, _ = _serve_rounds(server, ref["prompts"])
        warm, _, r2 = _serve_rounds(server, ref["prompts"], uid0=10)
        assert r2 > 0
        assert cold == ref["cold"] and warm == ref["warm"]
        _assert_drained(server)

    def test_tiering_off_is_the_old_behavior(self, params):
        """host_blocks=0 keeps classic eviction: no tier state, no
        demotions reported, same tokens (the transparency baseline the
        bench's off arm relies on)."""
        ref = _exact_ref(params)
        server = SlotServer(
            params, CFG, **{**TIER_KW, "host_blocks": 0}
        )
        cold, _, _ = _serve_rounds(server, ref["prompts"])
        assert server._host_pool is None
        rep = server.serve([_req(40, ref["prompts"][0])])
        assert "demotions" not in rep.kv
        assert cold == ref["cold"]

    def test_tiering_requires_prefix(self, params):
        with pytest.raises(ValueError, match="prefix_cache"):
            SlotServer(params, CFG, slots=1, cache_len=32, host_blocks=4)


# ---------------------------------------------------------------------------
# obs: the tier's gauges/counters and flight fields
# ---------------------------------------------------------------------------


def test_tier_metrics_and_flight_fields(params):
    from tree_attention_tpu import obs
    from tree_attention_tpu.obs.flight import FLIGHT

    ref = _exact_ref(params)  # warm memoized engine: published + demoted
    server, prompts = ref["server"], ref["prompts"]
    obs.enable()
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        reg = obs.REGISTRY
        dem0 = reg.counter("serving_kv_demotions_total").value()
        res0 = reg.counter("serving_kv_restores_total").value()
        _serve_rounds(server, prompts, uid0=30)
        assert reg.counter("serving_kv_demotions_total").value() > dem0
        assert reg.counter("serving_kv_restores_total").value() > res0
        used = reg.gauge("serving_kv_host_blocks_used").value()
        assert used == server._host_pool.used
    finally:
        obs.disable()
        FLIGHT.disarm()
    recs = FLIGHT.snapshot()["records"]
    assert {"host_blocks_used", "restored_blocks"} <= set(recs[0])
    assert max(r["restored_blocks"] for r in recs) > 0
    FLIGHT.clear()
    rep = server.serve([_req(99, prompts[0])])
    for key in ("host_blocks", "host_blocks_used", "demotions",
                "restores", "host_drops"):
        assert key in rep.kv, rep.kv


# ---------------------------------------------------------------------------
# per-block-scale kernel oracles (interpret mode)
# ---------------------------------------------------------------------------


def _per_block_case(seed):
    """A fragmented int8 paged case with PER-BLOCK scale scalars: random
    pool, non-monotone table (rows share blocks), ragged lengths."""
    rng = np.random.default_rng(seed)
    B, Hq, Hkv, D = 2, 4, 2, 16
    N, NB, blk = 9, 4, 4
    k_q = rng.integers(-127, 128, size=(N, Hkv, blk, D)).astype(np.int8)
    v_q = rng.integers(-127, 128, size=(N, Hkv, blk, D)).astype(np.int8)
    ks = rng.uniform(0.005, 0.03, size=(N, Hkv)).astype(np.float32)
    vs = rng.uniform(0.005, 0.03, size=(N, Hkv)).astype(np.float32)
    table = rng.integers(0, N, size=(B, NB)).astype(np.int32)
    table[1] = table[0][::-1]  # shared blocks, reversed order
    lengths = rng.integers(1, NB * blk + 1, size=(B,)).astype(np.int32)
    q = rng.normal(size=(B, Hq, 1, D)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_q), jnp.asarray(v_q),
            jnp.asarray(ks), jnp.asarray(vs), jnp.asarray(table),
            jnp.asarray(lengths), blk)


def _dequant_ref(q, k_q, v_q, ks, vs, table, lengths, blk):
    """Exact kernel over the dequantized gathered view — the numeric
    truth the per-block kernels approximate (int8 resolution)."""
    from tree_attention_tpu.ops.decode import gather_paged_kv
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode,
    )

    k_deq = k_q.astype(jnp.float32) * ks[:, :, None, None]
    v_deq = v_q.astype(jnp.float32) * vs[:, :, None, None]
    kg, vg = gather_paged_kv(k_deq, v_deq, table)
    return attention_pallas_decode(q, kg, vg, causal=True,
                                   q_offset=lengths, block_size=blk)


@pytest.mark.parametrize("seed", [0, 1])
def test_paged_q8_per_block_scales_kernel(seed):
    """The q8 paged kernel with (N, Hkv) per-block scales (ISSUE 13:
    K's scalar rescales the score tile post-matmul, V's folds into p)
    tracks the dequantized exact reference to int8/bf16 resolution."""
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode_q8,
    )

    case = _per_block_case(seed)
    q, k_q, v_q, ks, vs, table, lengths, blk = case
    ref_o, ref_l = _dequant_ref(*case)
    out, lse = attention_pallas_decode_q8(
        q, k_q, v_q, ks, vs, causal=True, q_offset=lengths,
        block_table=table,
    )
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_o), atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_l),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("seed", [2, 3])
def test_paged_q8q_per_block_scales_kernel(seed):
    """Same contract for the int8-MXU q8q kernel: per-block K scalars
    join the per-row Q scale in the post-matmul rescale (the int8 x
    int8 -> int32 path is untouched), V's fold into p in-kernel — no
    per-channel epilogue remains."""
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode_q8q,
    )

    case = _per_block_case(seed)
    q, k_q, v_q, ks, vs, table, lengths, blk = case
    ref_o, ref_l = _dequant_ref(*case)
    out, lse = attention_pallas_decode_q8q(
        q, k_q, v_q, ks, vs, causal=True, q_offset=lengths,
        block_table=table,
    )
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_o), atol=6e-2, rtol=6e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_l),
                               atol=3e-2, rtol=3e-2)


def test_per_block_scale_shape_validation():
    """Misshapen per-block scales fail loudly on both kernels; the
    per-slot (B, Hkv, 1, D) contract still validates for the
    contiguous shape."""
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode_q8,
        attention_pallas_decode_q8q,
    )

    q, k_q, v_q, ks, vs, table, lengths, blk = _per_block_case(4)
    bad = jnp.ones((3, 2), jnp.float32)  # wrong N
    for fn in (attention_pallas_decode_q8, attention_pallas_decode_q8q):
        with pytest.raises(ValueError, match="per-block"):
            fn(q, k_q, v_q, bad, bad, causal=True, q_offset=lengths,
               block_table=table)


def test_int8_hit_with_non_divisible_cache_len(params):
    """Review regression: the int8 hit's dequant-gather bucket must
    FLOOR-cap at cache_len // kv_block — the ceil cap (table width)
    overhangs the staging cache when cache_len is not block-divisible
    and crashed every such hit."""
    server = SlotServer(params, CFG, slots=1, cache_len=28,
                        prefill_chunk=4, prefill_budget=4, quantize=True,
                        prefix_cache=True, prefix_block=8, kv_block=8)
    p = _prompt(11, n=26)
    cold = server.serve([_req(0, p, n_new=2)])
    hit = server.serve([_req(1, p, n_new=2)])
    assert hit.prefix["hits"] == 1
    assert hit.results[0].tokens == cold.results[0].tokens
