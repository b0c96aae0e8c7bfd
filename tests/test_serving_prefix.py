"""Radix prefix KV cache tests (ISSUE 5).

The reuse contract has two halves:

(a) **bit-exact parity** — a prefix-hit admission (the matched blocks
    referenced in place + suffix prefill) emits token-for-token what a
    cold full prefill of the same prompt emits, on the exact AND int8
    cache, single device and compat ``cpu_mesh``. The test configs
    align chunk and block boundaries so every compiled program a hit runs
    is literally the cold run's program over the same rows — any
    divergence is a real reuse bug, not float noise.
(b) **allocator safety** — the radix tree's ref-counting and LRU
    eviction never free a block a live request holds and never retain
    more than the tree's cap (``prefix_pool_blocks``), under random
    admit/retire interleavings of a ``PagedPrefixIndex`` over a
    ``BlockAllocator``.

Everything here is CPU-safe and fast-tier.
"""

import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.models import (
    TransformerConfig,
    generate,
    init_params,
)
from tree_attention_tpu.parallel import cpu_mesh
from tree_attention_tpu.serving import (
    BlockAllocator,
    PagedPrefixIndex,
    Request,
    SlotServer,
    synthetic_trace,
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

# chunk == block == 4 keeps every prefill boundary of a hit run aligned
# with the cold run's, so parity can demand bit-exactness (see module
# docstring).
PREFIX_KW = dict(prefix_cache=True, prefix_block=4, prefix_pool_blocks=16)
CHUNK_KW = dict(prefill_chunk=4, prefill_budget=4)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _single_stream(params, prompt, n_new, cache_len=64):
    return np.asarray(
        generate(params, jnp.asarray(prompt)[None], n_new, CFG,
                 cache_len=cache_len)
    )[0].tolist()


def _req(uid, prompt, n_new=5, tick=0):
    return Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=n_new, arrival_tick=tick)


def _prompt(seed, n=13):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# (a) bit-exact hit-vs-cold parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantize", [False, True],
                         ids=["exact", "int8"])
def test_prefix_hit_matches_cold(params, quantize):
    """Serve a prompt twice on one prefix-enabled server: the second
    admission must hit the pool (stats prove it) and emit exactly the
    first run's tokens — which are exactly a prefix-less server's."""
    prompt = _prompt(1)
    server = SlotServer(params, CFG, slots=2, cache_len=32,
                        quantize=quantize, **CHUNK_KW, **PREFIX_KW)
    cold = server.serve([_req(0, prompt)])
    assert cold.prefix["hits"] == 0 and cold.prefix["misses"] == 1
    # 13 tokens at block 4 -> 3 published blocks (12 tokens).
    assert cold.prefix["pool_blocks_used"] == 3
    hit = server.serve([_req(1, prompt)])
    assert hit.prefix["hits"] == 1 and hit.prefix["tokens_reused"] == 12
    assert hit.results[0].tokens == cold.results[0].tokens
    ref = SlotServer(params, CFG, slots=2, cache_len=32,
                     quantize=quantize, **CHUNK_KW)
    base = ref.serve([_req(0, prompt)])
    assert hit.results[0].tokens == base.results[0].tokens
    if not quantize:
        assert hit.results[0].tokens == _single_stream(params, prompt, 5,
                                                       cache_len=32)


def test_prefix_full_block_prompt_keeps_one_suffix_token(params):
    """A prompt that is ENTIRELY whole blocks can never match fully — the
    last block is held back so at least one token remains to prefill
    (sampling needs a forward row). 12 tokens / block 4 -> match 8."""
    prompt = _prompt(3, n=12)
    server = SlotServer(params, CFG, slots=1, cache_len=32,
                        **CHUNK_KW, **PREFIX_KW)
    server.serve([_req(0, prompt)])
    hit = server.serve([_req(1, prompt)])
    assert hit.prefix["hits"] == 1
    assert hit.prefix["tokens_reused"] == 8
    assert hit.results[0].tokens == _single_stream(params, prompt, 5,
                                                   cache_len=32)


def test_prefix_shared_prefix_diverging_suffixes(params):
    """Requests sharing a long prefix but diverging after it each match
    the shared blocks and still decode their OWN continuation — pinned
    against per-request single-stream decode."""
    rng = np.random.default_rng(4)
    shared = rng.integers(0, CFG.vocab_size, size=12).astype(np.int32)
    prompts = [
        np.concatenate([shared,
                        rng.integers(0, CFG.vocab_size, size=k).astype(
                            np.int32)])
        for k in (3, 5, 2)
    ]
    server = SlotServer(params, CFG, slots=2, cache_len=32,
                        **CHUNK_KW, **PREFIX_KW)
    # Stagger arrivals so the publisher finishes before the others admit.
    reqs = [_req(i, p, n_new=4, tick=i * 8) for i, p in enumerate(prompts)]
    report = server.serve(reqs, max_ticks=500)
    assert report.prefix["hits"] == 2  # requests 1 and 2 reuse request 0's
    assert report.prefix["tokens_reused"] == 24
    for res in report.results:
        assert res.tokens == _single_stream(
            params, prompts[res.uid], 4, cache_len=32
        ), f"request {res.uid} diverged on a shared-prefix hit"


def test_prefix_mesh_parity(params):
    """Prefix reuse under a mesh (replicated pool) reproduces the
    single-device tokens, exact and int8."""
    mesh = cpu_mesh(2)
    prompt = _prompt(5)
    for quantize in (False, True):
        kw = dict(slots=2, cache_len=32, quantize=quantize,
                  **CHUNK_KW, **PREFIX_KW)
        ref = SlotServer(params, CFG, **kw)
        r1, r2 = ref.serve([_req(0, prompt)]), ref.serve([_req(1, prompt)])
        got = SlotServer(params, CFG, mesh=mesh, **kw)
        g1, g2 = got.serve([_req(0, prompt)]), got.serve([_req(1, prompt)])
        assert g2.prefix["hits"] == 1
        assert g1.results[0].tokens == r1.results[0].tokens
        assert g2.results[0].tokens == r2.results[0].tokens


def test_prefix_under_eviction_pressure(params):
    """A pool far smaller than the working set still serves every request
    correctly — publishes stop when the pool is pinned, eviction recycles
    refcount-0 leaves, and tokens stay single-stream-identical."""
    rng = np.random.default_rng(6)
    reqs = [
        _req(i, rng.integers(0, CFG.vocab_size,
                             size=int(rng.integers(2, 14))).astype(np.int32),
             n_new=3, tick=i)
        for i in range(8)
    ]
    server = SlotServer(params, CFG, slots=2, cache_len=32,
                        prefill_chunk=4, prefix_cache=True, prefix_block=4,
                        prefix_pool_blocks=2)
    report = server.serve(reqs, max_ticks=800)
    assert report.prefix["pool_blocks_used"] <= 2
    for res in report.results:
        req = next(r for r in reqs if r.uid == res.uid)
        assert res.tokens == _single_stream(
            params, req.prompt, req.max_new_tokens, cache_len=32
        ), f"request {res.uid} corrupted under eviction pressure"


def test_prefix_hit_trace_instants(params, tmp_path):
    """A hit emits a ``prefix_hit`` instant and the request span carries
    ``prefix_hit_len`` — the per-request reuse truth in Perfetto."""
    from tree_attention_tpu import obs

    prompt = _prompt(7)
    server = SlotServer(params, CFG, slots=1, cache_len=32,
                        **CHUNK_KW, **PREFIX_KW)
    server.serve([_req(0, prompt)])
    path = tmp_path / "prefix_trace.jsonl"
    obs.TRACER.start(str(path))
    try:
        server.serve([_req(1, prompt)])
    finally:
        obs.TRACER.close()
    events = [json.loads(l) for l in path.read_text().splitlines()]
    hits = [e for e in events if e["ph"] == "i"
            and e["name"] == "prefix_hit"]
    assert len(hits) == 1
    assert hits[0]["args"]["rid"] == 1
    assert hits[0]["args"]["matched_tokens"] == 12
    spans = [e for e in events if e["ph"] == "X"
             and e["name"] == "request:1"]
    assert spans and spans[0]["args"]["prefix_hit_len"] == 12


def test_prefix_metrics_flow(params):
    """The prefix counters and the pool gauge record when the registry is
    armed (and ServeReport carries the same truths either way)."""
    from tree_attention_tpu import obs

    prompt = _prompt(8)
    obs.enable()
    try:
        reg = obs.REGISTRY
        hits0 = reg.counter("serving_prefix_hits_total").value()
        misses0 = reg.counter("serving_prefix_misses_total").value()
        reused0 = reg.counter("serving_prefix_tokens_reused_total").value()
        server = SlotServer(params, CFG, slots=1, cache_len=32,
                            **CHUNK_KW, **PREFIX_KW)
        server.serve([_req(0, prompt)])
        server.serve([_req(1, prompt)])
        assert reg.counter("serving_prefix_hits_total").value() \
            - hits0 == 1
        assert reg.counter("serving_prefix_misses_total").value() \
            - misses0 == 1
        assert reg.counter("serving_prefix_tokens_reused_total").value() \
            - reused0 == 12
        assert reg.gauge("serving_prefix_pool_blocks_used").value() == 3
    finally:
        obs.disable()


def test_prefix_flight_fields(params):
    """The flight recorder's per-tick records carry the reuse fields."""
    from tree_attention_tpu.obs.flight import FLIGHT

    prompt = _prompt(9)
    server = SlotServer(params, CFG, slots=1, cache_len=32,
                        **CHUNK_KW, **PREFIX_KW)
    server.serve([_req(0, prompt)])
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        server.serve([_req(1, prompt)])
    finally:
        FLIGHT.disarm()
    recs = FLIGHT.snapshot()["records"]
    assert {"prefix_hits", "prefix_reused"} <= set(recs[0])
    assert sum(r["prefix_hits"] for r in recs) == 1
    assert sum(r["prefix_reused"] for r in recs) == 12
    FLIGHT.clear()


# ---------------------------------------------------------------------------
# (b) radix allocator: ref-counting + LRU under random interleavings
# ---------------------------------------------------------------------------



def _index(blocks, max_cached=None, block=2):
    alloc = BlockAllocator(blocks)
    return PagedPrefixIndex(block=block, alloc=alloc,
                            max_cached=max_cached), alloc


def _publish(idx, alloc, prompt):
    """One request's life up to its publish, as the engine drives the
    index: match (pin the path), reserve and allocate private blocks for
    the unmatched full blocks, hand them over (``adopt``), free what the
    tree did not take. Returns ``(matched, path, adopted)`` with ``path``
    pinned until the caller releases it."""
    matched, held = idx.match(prompt)
    nb_full = len(prompt) // idx.block
    new = range(len(held), nb_full)
    assert alloc.reserve(len(new))
    phys = {j: alloc.alloc() for j in new}
    path, adopted = idx.adopt(prompt, phys, held)
    for j, bid in phys.items():
        if j not in adopted:
            alloc.free_private(bid)
    return matched, path, adopted


def _tree_nodes(idx):
    out = []
    stack = list(idx._root.children.values())
    while stack:
        n = stack.pop()
        out.append(n)
        stack.extend(n.children.values())
    return out


def _check_invariants(idx, alloc):
    nodes = _tree_nodes(idx)
    held = {n.block_id for n in nodes}
    free = set(alloc._free)
    # The tree never over-commits: it owns exactly the blocks that are
    # not free (every private block was adopted or given back), one node
    # a block, within its retention cap.
    assert not held & free
    assert held | free == set(range(alloc.blocks))
    assert len(held) == len(nodes) == idx.blocks_used
    assert idx.blocks_used <= idx.max_cached
    assert alloc.reserved == 0
    assert all(n.refs >= 0 for n in nodes)


def test_radix_refcount_lru_property():
    """Random admit/retire interleavings over a tiny retention cap:
    referenced blocks are never freed, the tree never outgrows its cap,
    and matches always return true prefixes of what was published."""
    rng = np.random.default_rng(42)
    idx, alloc = _index(16, max_cached=5)
    live = []  # (held_nodes, prompt)
    for step in range(300):
        action = rng.random()
        if action < 0.55 or not live:
            # "Admit": match then publish a random prompt built from a
            # tiny alphabet so prefixes collide often.
            plen = int(rng.integers(1, 13))
            prompt = rng.integers(0, 3, size=plen).astype(np.int32)
            matched, path, adopted = _publish(idx, alloc, prompt)
            assert matched % idx.block == 0
            assert matched <= max(plen - 1, 0)
            assert len(path) <= plen // idx.block
            # The held nodes must spell the prompt's own prefix.
            for j, node in enumerate(path):
                assert node.key == tuple(
                    int(t) for t in prompt[j * 2:(j + 1) * 2]
                )
            live.append((path, prompt))
        else:
            # "Retire" a random live request.
            path, _ = live.pop(int(rng.integers(0, len(live))))
            idx.release(path)
        _check_invariants(idx, alloc)
        # No node held by a live request was evicted: it must still be
        # in the tree, on the device tier.
        current = {id(n) for n in _tree_nodes(idx)}
        for path, _ in live:
            for node in path:
                assert id(node) in current, "pinned node was evicted"
    # Drain everything: all blocks become evictable, none leak.
    for path, _ in live:
        idx.release(path)
    assert idx.total_pins() == 0
    assert idx.evictable_blocks() == idx.blocks_used
    _check_invariants(idx, alloc)


def test_radix_lru_evicts_least_recently_used_leaf():
    idx, alloc = _index(8, max_cached=2)
    a = np.asarray([0, 0, 9], np.int32)   # one full block [0,0]
    b = np.asarray([1, 1, 9], np.int32)   # one full block [1,1]
    c = np.asarray([2, 2, 9], np.int32)   # forces an eviction
    for prompt in (a, b):
        idx.release(_publish(idx, alloc, prompt)[1])
    # Touch A (a match refreshes recency) -> B is the LRU victim.
    _, path = idx.match(a)
    idx.release(path)
    idx.release(_publish(idx, alloc, c)[1])
    matched, path = idx.match(a)
    assert matched == 2  # A survived
    idx.release(path)
    assert idx.match(b)[0] == 0  # B was evicted
    assert idx.evictions == 1
    assert alloc.used == idx.blocks_used == 2  # B's block went back


def test_radix_pinned_pool_stops_publish():
    """When every retained block is referenced, adopt() stops early
    instead of evicting pinned data — partial paths are valid prefixes —
    and the blocks the tree did not take stay the request's own."""
    idx, alloc = _index(8, max_cached=2)
    long = np.asarray([0, 1, 2, 3, 4, 5, 6, 7], np.int32)  # 4 blocks
    _, path, adopted = _publish(idx, alloc, long)
    assert adopted == [0, 1] and len(path) == 2  # cap-bound, not prompt-bound
    assert alloc.used == 2  # the two refused blocks were given back
    # Still pinned: a second long publish gets nothing.
    other = np.asarray([7, 6, 5, 4], np.int32)
    _, p2, ids2 = _publish(idx, alloc, other)
    assert ids2 == [] and p2 == []
    idx.release(path)
    # Released: now the other prompt can claim (evict) the retention.
    _, p3, ids3 = _publish(idx, alloc, other)
    assert ids3 == [0, 1]
    assert idx.match(long)[0] == 0
    idx.release(p3)


def test_prefix_block_must_be_pow2(params):
    with pytest.raises(ValueError, match="power of two"):
        _index(4, block=3)
    with pytest.raises(ValueError, match="power of two"):
        SlotServer(params, CFG, slots=1, cache_len=32, prefix_cache=True,
                   prefix_block=3)


# ---------------------------------------------------------------------------
# synthetic_trace prefix params (satellite)
# ---------------------------------------------------------------------------


def test_synthetic_trace_prefix_share():
    trace = synthetic_trace(
        8, prompt_len=12, prompt_jitter=0, max_new_tokens=2,
        prefix_share=1.0, prefix_len=8, seed=3,
    )
    head = trace[0].prompt[:8].tolist()
    assert all(r.prompt[:8].tolist() == head for r in trace)
    # Suffixes still differ (the trace is not 8 identical requests).
    assert len({tuple(r.prompt[8:].tolist()) for r in trace}) > 1
    assert all(len(r.prompt) == 12 for r in trace)


def test_synthetic_trace_prefix_share_partial_and_clamped():
    # share 0 -> no two prompts share an 8-token head (random 256-vocab).
    cold = synthetic_trace(6, prompt_len=12, prompt_jitter=0,
                           max_new_tokens=2, prefix_share=0.0,
                           prefix_len=8, seed=4)
    heads = {tuple(r.prompt[:8].tolist()) for r in cold}
    assert len(heads) == len(cold)
    # prefix_len >= prompt_len clamps to plen - 1 (one free suffix token).
    clamped = synthetic_trace(4, prompt_len=6, prompt_jitter=0,
                              max_new_tokens=2, prefix_share=1.0,
                              prefix_len=32, seed=5)
    head5 = clamped[0].prompt[:5].tolist()
    assert all(r.prompt[:5].tolist() == head5 for r in clamped)
    assert all(len(r.prompt) == 6 for r in clamped)
    with pytest.raises(ValueError, match="prefix_share"):
        synthetic_trace(2, prefix_share=1.5, prefix_len=4)
