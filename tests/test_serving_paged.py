"""Paged KV tests (ISSUE 6): block-table kernels, allocator, parity.

Three contracts, mirroring the layered design:

(a) **Block-table kernel oracles** — the Pallas paged decode kernels
    (exact, q8, q8q) must be BIT-exact with gathering ``pool[table]``
    into a contiguous buffer and running the unpaged kernel at the same
    tile size, across ragged lengths, fragmented/non-monotone tables
    (including blocks shared between batch rows), and int8 pools. The
    eager chunked path gathers through the same helper, so eager and
    Pallas stay bit-exact too.
(b) **Allocator safety** — the unified pool's ownership ledger
    (free / slot-private / tree-cached), reservations, and LRU leaf
    eviction never double-free, leak, or touch a referenced block under
    hundreds of random admit/advance/publish/retire interleavings.
(c) **Serving parity** — the engine emits token-for-token what a
    reference that is not the engine emits (exact: lockstep ``generate``
    on a contiguous ``KVCache``; int8: a plain ``forward_step`` loop over
    a hand-built B=1 int8 pool, :func:`paged_int8_stream`), a radix hit moves ZERO device KV bytes (span args
    + pool counters prove it, not just code inspection), admissions
    DEFER when the pool is over-subscribed instead of corrupting state,
    and a request that can never fit fails with a clear message.

Bit-exactness in (c) holds at matched tiling: the configs pin
``attn_block_size == kv_block`` and a block-divisible ``cache_len``, so
the engine and its references fold identical KV tiles in identical
order (the same alignment trick the PR-5 hit-vs-cold suite uses for
chunk == block).

Everything is CPU-safe and fast-tier (interpret-mode kernels).
"""

import dataclasses
import functools
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.models import (
    TransformerConfig,
    generate,
    init_cache,
    init_paged_cache,
    forward_step,
    init_params,
)
from tree_attention_tpu.models.decode import quantize_paged_blocks
from tree_attention_tpu.ops.decode import flash_decode, gather_paged_kv
from tree_attention_tpu.ops.pallas_decode import (
    attention_pallas_decode,
    attention_pallas_decode_q8,
    attention_pallas_decode_q8q,
)
from tree_attention_tpu.serving import (
    BlockAllocator,
    PagedPrefixIndex,
    Request,
    SlotServer,
)

# attn_block_size == kv_block == 4 keeps the engine and its references
# folding identical tiles (see module docstring); cache_len 32 divides.
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
    attn_block_size=4,
)

PAGED_KW = dict(kv_block=4)
PREFIX_KW = dict(prefix_cache=True, prefix_block=4)
CHUNK_KW = dict(prefill_chunk=4, prefill_budget=8)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _req(uid, prompt, n_new=5, tick=0):
    return Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=n_new, arrival_tick=tick)


def _prompt(seed, n=13):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)


def _single_stream(params, prompt, n_new, cache_len=32):
    return np.asarray(
        generate(params, jnp.asarray(prompt)[None], n_new, CFG,
                 cache_len=cache_len)
    )[0].tolist()


@functools.lru_cache(maxsize=None)
def _jitted_step(cfg):
    return jax.jit(functools.partial(forward_step, cfg=cfg))


def paged_int8_stream(params, cfg, prompt, n_new, *, cache_len, kv_block):
    """What int8 serving promises, without the engine: the greedy tokens
    of a plain ``forward_step`` loop over a B=1 int8 pool. The prompt is
    prefilled exactly on a contiguous ``KVCache``; its rows, quantized
    block by block under each block's own scale
    (``quantize_paged_blocks``: the quantize-after-prefill contract at
    block granularity), are laid into the pool by hand through an
    identity table; every later row is appended by the model's own step
    (under the anchor block's scale)."""
    prompt = np.asarray(prompt, np.int32)
    plen = len(prompt)
    nb = -(-cache_len // kv_block)
    step = _jitted_step(cfg)
    exact = init_cache(cfg, 1, cache_len)
    logits, exact = step(params, jnp.asarray(prompt)[None], exact)
    live = (np.arange(cache_len) < plen)[None, None, None, :, None]
    kq, vq, ks, vs = quantize_paged_blocks(
        jnp.where(live, exact.k, 0), jnp.where(live, exact.v, 0),
        kv_block, plen,
    )

    def pool(rows):  # (L, 1, Hkv, T, D) -> (L, nb, Hkv, block, D)
        L, _, H, T, D = rows.shape
        flat = np.zeros((L, H, nb * kv_block, D), np.int8)
        flat[:, :, :T] = np.asarray(rows)[:, 0]
        return jnp.asarray(np.moveaxis(
            flat.reshape(L, H, nb, kv_block, D), 2, 1))

    cache = dataclasses.replace(
        init_paged_cache(cfg, 1, cache_len, nb, block=kv_block,
                         quantize=True),
        k=pool(kq), v=pool(vq), k_scale=ks, v_scale=vs,
        table=jnp.arange(nb, dtype=jnp.int32)[None],
        length=jnp.asarray([plen], jnp.int32),
    )
    toks = [int(jnp.argmax(logits[0, plen - 1]))]
    for _ in range(n_new - 1):
        logits, cache = step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), cache)
        toks.append(int(jnp.argmax(logits[0, 0])))
    return toks


# ---------------------------------------------------------------------------
# (a) block-table kernel oracles
# ---------------------------------------------------------------------------


def _random_pool_case(seed, *, int8=False):
    """A fragmented paged decode case: random pool, non-monotone tables
    (rows share blocks, ids repeat, nothing is sorted), ragged lengths."""
    rng = np.random.default_rng(seed)
    B, Hq, Hkv, D = 3, 4, 2, 8
    N, NB, blk = 11, 4, 4
    pool_k = rng.normal(size=(N, Hkv, blk, D)).astype(np.float32)
    pool_v = rng.normal(size=(N, Hkv, blk, D)).astype(np.float32)
    table = rng.integers(0, N, size=(B, NB)).astype(np.int32)
    table[1] = table[0][::-1]          # shared blocks, reversed order
    lengths = rng.integers(0, NB * blk + 1, size=(B,)).astype(np.int32)
    q = rng.normal(size=(B, Hq, 1, D)).astype(np.float32)
    if int8:
        k_q = np.clip(np.round(pool_k / 0.02), -127, 127).astype(np.int8)
        v_q = np.clip(np.round(pool_v / 0.02), -127, 127).astype(np.int8)
        scale = np.full((B, Hkv, 1, D), 0.02, np.float32)
        return (jnp.asarray(q), jnp.asarray(k_q), jnp.asarray(v_q),
                jnp.asarray(scale), jnp.asarray(table),
                jnp.asarray(lengths), blk)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(lengths), blk)


def _step_tile(pool, table, q):
    """The KV tile one grid step of the paged kernel folds: the pool block
    times the table entries a step takes (``tuning.paged_decode_step``;
    ISSUE 28). The contiguous kernel at this ``block_size`` folds the same
    columns in the same order."""
    from tree_attention_tpu.ops.tuning import paged_decode_step

    hkv, blk, d = pool.shape[1:]
    rows = -(-(q.shape[1] // hkv) * q.shape[2] // 8) * 8
    return blk * paged_decode_step(hkv, blk, d, pool.dtype.itemsize,
                                   table.shape[1], min(rows, 128))[1]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_kernel_bit_exact_vs_gathered(seed):
    """The exact paged kernel == gather + unpaged kernel at the same
    tile size, bit for bit, on fragmented non-monotone tables."""
    q, pk, pv, table, lengths, blk = _random_pool_case(seed)
    kg, vg = gather_paged_kv(pk, pv, table)
    ref_o, ref_l = attention_pallas_decode(
        q, kg, vg, causal=True, q_offset=lengths,
        block_size=_step_tile(pk, table, q)
    )
    pg_o, pg_l = attention_pallas_decode(
        q, pk, pv, causal=True, q_offset=lengths, block_table=table
    )
    assert (np.asarray(ref_o) == np.asarray(pg_o)).all()
    assert (np.asarray(ref_l) == np.asarray(pg_l)).all()


@pytest.mark.parametrize("kernel", ["q8", "q8q"])
def test_paged_kernel_bit_exact_int8(kernel):
    """Both int8 kernels stream paged pools bit-exactly too."""
    fn = (attention_pallas_decode_q8 if kernel == "q8"
          else attention_pallas_decode_q8q)
    q, kq, vq, scale, table, lengths, blk = _random_pool_case(3, int8=True)
    kg, vg = gather_paged_kv(kq, vq, table)
    ref_o, ref_l = fn(q, kg, vg, scale, scale, causal=True,
                      q_offset=lengths, block_size=_step_tile(kq, table, q))
    pg_o, pg_l = fn(q, kq, vq, scale, scale, causal=True,
                    q_offset=lengths, block_table=table)
    assert (np.asarray(ref_o) == np.asarray(pg_o)).all()
    assert (np.asarray(ref_l) == np.asarray(pg_l)).all()


def test_paged_eager_matches_pallas():
    """The eager chunked path (gather + vmap) agrees with the paged
    Pallas kernel — the eager/compiled contract serving relies on."""
    q, pk, pv, table, lengths, blk = _random_pool_case(4)
    e_o, e_l = flash_decode(q, pk, pv, q_position=lengths,
                            block_table=table, block_size=blk)
    p_o, p_l = attention_pallas_decode(
        q, pk, pv, causal=True, q_offset=lengths, block_table=table
    )
    np.testing.assert_allclose(np.asarray(e_o), np.asarray(p_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(e_l), np.asarray(p_l),
                               rtol=1e-5, atol=1e-5)


def test_paged_forward_step_matches_contiguous(params):
    """One mixed-Tq model step over a paged cache whose blocks hold the
    same rows as a contiguous cache (scattered to arbitrary pool blocks)
    produces bit-identical logits and writes the same KV rows."""
    rng = np.random.default_rng(5)
    B, cap, blk = 2, 32, 4
    nb = cap // blk
    lengths = np.asarray([9, 4], np.int32)
    # Prefill a contiguous cache to the target lengths.
    cache_c = init_cache(CFG, B, cap)
    warm = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(B, 12)))
    _, cache_c = forward_step(params, warm, cache_c, CFG,
                              n_tokens=jnp.asarray(lengths))
    # Mirror its rows into a paged pool through a fragmented table.
    N = 2 * nb + 3
    perm = rng.permutation(N)[:2 * nb]
    table = perm.reshape(B, nb).astype(np.int32)
    cache_p = init_paged_cache(CFG, B, cap, N, block=blk)
    pool_k = np.zeros(np.shape(cache_p.k), np.float32)
    pool_v = np.zeros(np.shape(cache_p.v), np.float32)
    kc = np.asarray(cache_c.k)  # (L, B, Hkv, cap, D)
    vc = np.asarray(cache_c.v)
    for b in range(B):
        for j in range(nb):
            pool_k[:, table[b, j], :, :, :] = kc[:, b, :, j*blk:(j+1)*blk]
            pool_v[:, table[b, j], :, :, :] = vc[:, b, :, j*blk:(j+1)*blk]
    import dataclasses
    cache_p = dataclasses.replace(
        cache_p, k=jnp.asarray(pool_k), v=jnp.asarray(pool_v),
        table=jnp.asarray(table), length=jnp.asarray(lengths),
    )
    cache_c = dataclasses.replace(cache_c, length=jnp.asarray(lengths))
    # One mixed step: slot 0 takes 3 rows, slot 1 one row.
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(B, 4)))
    n_tok = jnp.asarray([3, 1], jnp.int32)
    lc, cache_c2 = forward_step(params, toks, cache_c, CFG, n_tokens=n_tok)
    lp, cache_p2 = forward_step(params, toks, cache_p, CFG, n_tokens=n_tok)
    # Valid logits rows agree bit-for-bit (pad rows are garbage on both).
    for b, n in enumerate([3, 1]):
        assert (np.asarray(lc)[b, :n] == np.asarray(lp)[b, :n]).all()
    # The written KV agrees through the table view, over valid rows.
    kg, vg = gather_paged_kv(cache_p2.k[0], cache_p2.v[0],
                             cache_p2.table)
    for b, end in enumerate(np.asarray(lengths) + np.asarray([3, 1])):
        assert (np.asarray(kg)[b, :, :end]
                == np.asarray(cache_c2.k)[0, b, :, :end]).all()
        assert (np.asarray(vg)[b, :, :end]
                == np.asarray(cache_c2.v)[0, b, :, :end]).all()
    assert (np.asarray(cache_p2.length) == np.asarray(cache_c2.length)).all()


# ---------------------------------------------------------------------------
# (b) allocator + paged radix index property test
# ---------------------------------------------------------------------------


def _tree_nodes(idx):
    out = []
    stack = list(idx._root.children.values())
    while stack:
        n = stack.pop()
        out.append(n)
        stack.extend(n.children.values())
    return out


def test_block_allocator_property():
    """300+ random admit/advance/publish/retire interleavings over a tiny
    pool: block ownership stays a partition (free ∪ private ∪ cached),
    reservations are always honored, pinned nodes are never evicted, and
    draining every request leaks nothing."""
    rng = np.random.default_rng(42)
    blk = 2
    alloc = BlockAllocator(8)
    idx = PagedPrefixIndex(block=blk, alloc=alloc)
    live = []  # request mirrors of the engine's slot ledgers

    def check_invariants():
        nodes = _tree_nodes(idx)
        cached = {n.block_id for n in nodes}
        free = set(alloc._free)
        private = set()
        for req in live:
            assert not (req["private"] & private), "block owned twice"
            private |= req["private"]
        assert len(cached) == len(nodes)
        assert not (cached & free) and not (cached & private) \
            and not (free & private)
        assert cached | free | private == set(range(alloc.blocks)), \
            "pool blocks leaked or conjured"
        assert alloc.reserved == sum(r["reserve"] for r in live)
        assert idx.evictable_blocks() <= len(cached)

    for step in range(400):
        r = rng.random()
        if r < 0.45 or not live:
            # Admit: match (pin) + reserve worst case; defer on failure.
            plen = int(rng.integers(2, 11))
            prompt = rng.integers(0, 3, size=plen).astype(np.int32)
            total = -(-(plen + 2) // blk)
            matched, nodes = idx.match(prompt, record=False)
            needed = total - matched // blk
            if not alloc.reserve(needed):
                idx.release(nodes)  # deferred: pins roll back
            else:
                idx.record_match(matched)
                live.append(dict(
                    prompt=prompt, nodes=nodes, private=set(),
                    table=[n.block_id for n in nodes], reserve=needed,
                    published=False,
                ))
        elif r < 0.8:
            # Advance: allocate one reserved block; publish when the
            # prompt's span is covered (the engine's final chunk).
            req = live[int(rng.integers(0, len(live)))]
            if req["reserve"] > 0:
                bid = alloc.alloc()
                req["reserve"] -= 1
                req["private"].add(bid)
                req["table"].append(bid)
            nb_full = len(req["prompt"]) // blk
            if not req["published"] and len(req["table"]) >= nb_full:
                phys = {j: req["table"][j] for j in range(nb_full)
                        if req["table"][j] in req["private"]}
                path, adopted = idx.adopt(req["prompt"], phys,
                                          req["nodes"])
                for j in adopted:
                    req["private"].discard(req["table"][j])
                req["nodes"] = path  # admit pins carry over
                req["published"] = True
        else:
            # Retire: free privates, release pins, return reservations.
            req = live.pop(int(rng.integers(0, len(live))))
            idx.release(req["nodes"])
            for bid in req["private"]:
                alloc.free_private(bid)
            alloc.unreserve(req["reserve"])
        check_invariants()
        # Pinned paths survive every eviction the interleaving caused.
        current = {id(n) for n in _tree_nodes(idx)}
        for req in live:
            for node in req["nodes"]:
                assert id(node) in current, "pinned node was evicted"

    while live:
        req = live.pop()
        idx.release(req["nodes"])
        for bid in req["private"]:
            alloc.free_private(bid)
        alloc.unreserve(req["reserve"])
    check_invariants()
    assert alloc.reserved == 0
    assert all(n.refs == 0 for n in _tree_nodes(idx))


def test_adopt_budget_eviction_never_orphans():
    """Regression (review): adopt's retention-budget eviction must never
    take a node on the walk's own path — the just-walked unpinned leaf
    could previously be the LRU victim, attaching the new child under a
    detached parent (an orphaned subtree whose pool block leaks)."""
    alloc = BlockAllocator(4)
    idx = PagedPrefixIndex(block=2, alloc=alloc, max_cached=1)
    ok = alloc.reserve(1)
    assert ok
    a = alloc.alloc()
    p1, _ = idx.adopt(np.asarray([0, 1, 9], np.int32), {0: a}, [])
    idx.release(p1)  # request 1 retired: its leaf is unpinned
    # Request 2 shares block [0,1] and tries to publish [2,3] while the
    # 1-block retention budget is full: the only refcount-0 leaf is the
    # node the walk is standing ON — adoption must stop, not orphan it.
    ok = alloc.reserve(2)
    assert ok
    b, c = alloc.alloc(), alloc.alloc()
    p2, adopted = idx.adopt(np.asarray([0, 1, 2, 3, 9], np.int32),
                            {0: b, 1: c}, [])
    assert adopted == [] and p2 == []
    alloc.free_private(b)
    alloc.free_private(c)
    # Nothing leaked or orphaned: the walked leaf is still matchable and
    # still evictable, and the ledger balances (1 cached + 3 free).
    assert idx.evictable_blocks() == 1
    matched, nodes = idx.match(np.asarray([0, 1, 9], np.int32))
    assert matched == 2
    idx.release(nodes)
    assert alloc.used == 1 and alloc.free_count == 3


def test_paged_prefix_block_mismatch_rejected(params):
    """An explicit --prefix-block that disagrees with --kv-block is a
    clear error, never a silently-overridden granularity."""
    with pytest.raises(ValueError, match="kv_block"):
        SlotServer(params, CFG, slots=1, cache_len=32, prefix_cache=True,
                   prefix_block=8, kv_block=4)


def test_allocator_reserve_then_evict():
    """A reservation backed only by evictable tree leaves succeeds, the
    alloc recycles the LRU leaf when the free list runs dry, and a hit
    whose pins would strand an outstanding reservation is REFUSED (the
    engine releases the pins and defers the admission)."""
    alloc = BlockAllocator(2)
    idx = PagedPrefixIndex(block=2, alloc=alloc)
    assert alloc.reserve(2)
    a, b = alloc.alloc(), alloc.alloc()
    path, adopted = idx.adopt(np.asarray([0, 1, 2, 3], np.int32),
                              {0: a, 1: b}, [])
    assert adopted == [0, 1]
    idx.release(path)  # cached, unpinned: both evictable
    assert alloc.free_count == 0 and alloc.evictable() == 2
    assert alloc.reserve(2)  # backed purely by evictions
    c = alloc.alloc()
    assert idx.evictions == 1 and c == b  # the LRU leaf freed its block
    # One reservation still outstanding, backed by the remaining leaf: a
    # hit pinning that leaf would strand it — reserve() refuses even a
    # zero-block ask until the pins roll back.
    _, nodes = idx.match(np.asarray([0, 1, 9], np.int32))
    assert alloc.available() < 0
    assert not alloc.reserve(0)
    idx.release(nodes)
    assert alloc.available() == 0
    d = alloc.alloc()  # the outstanding reservation is still honored
    assert d == a and idx.evictions == 2


# ---------------------------------------------------------------------------
# (c) serving parity + admission control
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantize", [False, True], ids=["exact", "int8"])
def test_paged_serving_matches_reference(params, quantize):
    """The engine (chunked prefill, per-tick mixed step, retire) emits
    token-for-token what a reference that is not the engine emits:
    lockstep ``generate`` on a contiguous ``KVCache`` (exact), a plain
    ``forward_step`` loop over a hand-built int8 pool (int8)."""
    prompt = _prompt(11)
    server = SlotServer(params, CFG, slots=2, cache_len=32,
                        quantize=quantize, **CHUNK_KW, **PAGED_KW)
    # One request per serve: the multi-request/occupancy machinery is
    # pinned by test_serving.py — this cell pins the reference parity.
    rep = server.serve([_req(0, prompt)], max_ticks=400)
    if quantize:
        ref = paged_int8_stream(params, CFG, prompt, 5, cache_len=32,
                                kv_block=4)
    else:
        ref = _single_stream(params, prompt, 5)
    assert rep.results[0].tokens == ref
    assert rep.kv["layout"] == "paged"
    assert rep.kv["blocks_used"] == 0  # everything freed at retire


def test_paged_hit_moves_zero_bytes(params, tmp_path):
    """The headline contract: a radix hit is a host table update — the
    report's byte counter AND the trace instant both record 0 device KV
    bytes moved (an int8 hit's dequant gather shows up in the same
    counter — test_serving_tiered.py — so the 0 is measured)."""
    from tree_attention_tpu import obs

    prompt = _prompt(13)
    server = SlotServer(params, CFG, slots=2, cache_len=32,
                        **CHUNK_KW, **PREFIX_KW, **PAGED_KW)
    cold = server.serve([_req(0, prompt)])
    assert cold.prefix["misses"] == 1
    assert cold.prefix["pool_blocks_used"] == 3  # 13 tokens / block 4
    path = tmp_path / "paged_trace.jsonl"
    obs.TRACER.start(str(path))
    try:
        hit = server.serve([_req(1, prompt)])
    finally:
        obs.TRACER.close()
    assert hit.prefix["hits"] == 1
    assert hit.prefix["tokens_reused"] == 12
    assert hit.prefix["hit_bytes_moved"] == 0
    assert hit.results[0].tokens == cold.results[0].tokens
    assert hit.results[0].tokens == _single_stream(params, prompt, 5)
    events = [json.loads(l) for l in path.read_text().splitlines()]
    hits = [e for e in events
            if e["ph"] == "i" and e["name"] == "prefix_hit"]
    assert len(hits) == 1 and hits[0]["args"]["bytes_moved"] == 0


def test_paged_oversubscription_defers(params):
    """A pool smaller than the working set DEFERS admissions (requests
    wait their turn, FIFO) and still serves every request correctly."""
    prompt = _prompt(14)
    single = _single_stream(params, prompt, 5)
    # Each request needs ceil((13+5)/4) = 5 blocks; 6 admit one at a time.
    server = SlotServer(params, CFG, slots=3, cache_len=32,
                        prefill_chunk=4, prefill_budget=12,
                        kv_block=4, kv_blocks=6)
    report = server.serve([_req(i, prompt) for i in range(3)],
                          max_ticks=2000)
    assert len(report.results) == 3
    for r in report.results:
        assert r.tokens == single, f"uid {r.uid} corrupted under deferral"
    assert report.kv["peak_blocks_used"] <= 6


def test_paged_impossible_request_fails_clean(params):
    """Worst case beyond the WHOLE pool: a clear admission-time error
    naming the flag, never a shape error inside a jitted gather."""
    server = SlotServer(params, CFG, slots=1, cache_len=32,
                        kv_block=4, kv_blocks=4)
    with pytest.raises(ValueError, match="kv-blocks"):
        server.serve([_req(0, _prompt(15), n_new=4)])  # needs 5 > 4


def test_paged_sharing_is_capacity(params):
    """At a pool FAR below slots × cache_len, shared-prefix admissions
    still run concurrently — block sharing is real capacity, the claim
    the serving_paged_flood bench measures at scale."""
    rng = np.random.default_rng(16)
    shared = rng.integers(0, CFG.vocab_size, size=12).astype(np.int32)
    prompts = [
        np.concatenate([shared,
                        rng.integers(0, CFG.vocab_size, size=3)
                        .astype(np.int32)])
        for _ in range(3)
    ]
    # 3 slots × 8 blocks at full length = 24; pool holds 12.
    server = SlotServer(params, CFG, slots=3, cache_len=32,
                        kv_blocks=12, **CHUNK_KW, **PREFIX_KW, **PAGED_KW)
    reqs = [_req(i, p, n_new=4, tick=i * 8) for i, p in enumerate(prompts)]
    report = server.serve(reqs, max_ticks=800)
    assert report.prefix["hits"] == 2
    assert report.kv["peak_blocks_used"] <= 12
    for res in report.results:
        assert res.tokens == _single_stream(
            params, prompts[res.uid], 4
        ), f"request {res.uid} diverged on a shared paged block"


def test_paged_obs_gauges_and_flight(params):
    """The pool gauges publish while the registry records, and the
    flight recorder's per-tick records carry block occupancy +
    fragmentation — all silent when disarmed."""
    from tree_attention_tpu import obs
    from tree_attention_tpu.obs.flight import FLIGHT

    prompt = _prompt(17)
    server = SlotServer(params, CFG, slots=2, cache_len=32,
                        **CHUNK_KW, **PAGED_KW)
    obs.enable()
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        server.serve([_req(0, prompt)])
        used = obs.REGISTRY.gauge("serving_kv_blocks_used").value()
        free = obs.REGISTRY.gauge("serving_kv_blocks_free").value()
        assert used == 0 and free == server.kv_blocks
    finally:
        obs.disable()
        FLIGHT.disarm()
    recs = FLIGHT.snapshot()["records"]
    assert {"kv_blocks_used", "kv_blocks_free", "kv_frag"} <= set(recs[0])
    assert max(r["kv_blocks_used"] for r in recs) > 0
    assert all(0.0 <= r["kv_frag"] <= 1.0 for r in recs)
    FLIGHT.clear()


def test_paged_cli_flags_parse():
    """The paged/tiering flags parse; the PR-6-deprecated
    --prefix-pool-blocks alias is GONE (ISSUE 13) — --kv-blocks and
    --host-blocks express both budgets now."""
    import pytest

    from tree_attention_tpu.utils.config import parse_args

    cfg = parse_args(["--mode", "serve", "--kv-layout", "paged",
                      "--admission", "chunked",
                      "--kv-block", "32", "--kv-blocks", "64"])
    # accepted, read nowhere
    assert not hasattr(cfg, "kv_layout") and not hasattr(cfg, "admission")
    assert cfg.kv_block == 32 and cfg.kv_blocks == 64
    cfg = parse_args(["--mode", "serve", "--host-blocks", "16",
                      "--kv-tiering", "off"])
    assert cfg.host_blocks == 16 and cfg.kv_tiering == "off"
    assert parse_args(["--mode", "serve"]).kv_tiering == "on"
    with pytest.raises(SystemExit):
        parse_args(["--mode", "serve", "--prefix-pool-blocks", "8"])


@pytest.mark.parametrize("flag, gone, kept", [
    ("--kv-layout", "contiguous", "paged"),
    ("--admission", "whole", "chunked"),
])
def test_cli_a_path_that_is_gone_is_a_parse_error(capsys, flag, gone, kept):
    """Serving has one KV layout and one admission path: each flag
    survives for callers that pass the one value left
    (``benchmark/harness.py``), any other value exits at the parser, for
    every model, with a message that names the value left."""
    from tree_attention_tpu.utils.config import parse_args

    with pytest.raises(SystemExit) as e:
        parse_args(["--mode", "serve", flag, gone])
    assert e.value.code == 2
    said = capsys.readouterr().err.strip().splitlines()[-1]
    assert flag in said and f"'{gone}'" in said
    assert kept in said
