"""Speculative decoding subsystem tests (ISSUE 8).

The hard contract: **token-for-token parity with greedy non-speculative
decode** — whatever the drafter proposes, however much gets rejected, the
committed stream is identical; speculation may only change *when* tokens
arrive, never *which*. Pinned here across exact/int8 ×
single-device/compat-cpu_mesh, with free (n-gram), tree, and adversarial
oracle drafters.

Plus the layers underneath:

- the tree-attention verify mask (ops level): packed-tree logits equal a
  sequential decode along each node's root path, on the chunked-vmap path
  and the Pallas interpret kernels (exact and int8-MXU), with the
  lower-triangular mask reproducing plain causal BIT-FOR-BIT;
- commit compaction (`compact_decode_window`) on synthetic buffers and
  through real caches, contiguous and paged;
- rollback edge cases: rejection at the slot-capacity boundary, EOS
  inside a committed burst, a drafter proposing past ``max_new_tokens``,
  and a randomized accept/reject property test asserting cache bytes
  inside the committed prefix are bit-identical to sequential stepping;
- the paged pool invariant: rolled-back blocks unmap without leaking
  capacity (used == 0, reserved == 0 after every serve).

Everything is CPU-safe fast-tier (Pallas in interpret mode, meshes from
``cpu_mesh``).
"""

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
from tree_attention_tpu.models.decode import (
    compact_decode_window,
    init_paged_cache,
    PagedKVCache,
)
from tree_attention_tpu.ops.decode import flash_decode, gather_paged_kv
from tree_attention_tpu.ops.reference import attention_naive
from tree_attention_tpu.parallel import cpu_mesh
from tree_attention_tpu.serving import Request, SlotServer
from tree_attention_tpu.serving.block_pool import BlockAllocator
from tree_attention_tpu.serving.speculation import (
    Drafter,
    DraftProposal,
    PromptLookupDrafter,
    PromptLookupTreeDrafter,
    DraftModelDrafter,
    accept_longest_path,
    accept_stochastic_path,
    make_drafter,
    pack_proposal,
    pack_siblings,
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

# A prompt whose greedy continuation settles into a loop after a short
# wander — the workload prompt-lookup drafting exists for (the tiny
# random model collapses to a repeating attractor; the drafter then
# predicts it perfectly). Verified below by the acceptance assertions.
LOOP_PROMPT = np.tile(np.array([7, 9, 4], np.int32), 6)[:16]
ALT_PROMPT = np.tile(np.array([3, 5], np.int32), 8)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _reqs(n_new=24, eos=None):
    return [
        Request(uid=0, prompt=LOOP_PROMPT, max_new_tokens=n_new, eos_id=eos),
        Request(uid=1, prompt=ALT_PROMPT, max_new_tokens=n_new, eos_id=eos),
    ]


_REF_CACHE = {}
_SERVERS = {}


@pytest.fixture(scope="module", autouse=True)
def _servers_go_with_the_module():
    yield
    _SERVERS.clear()
    _REF_CACHE.clear()


def _server(params, drafter=None, **kw):
    """ONE two-slot server a shape for the cases that only differ in what
    they serve or in who drafts: the constructor jits bound methods, so
    every new server compiles its tick programs again whatever JAX has
    cached. ``drafter`` (a name or an object; ``None``: no speculation) is
    host state the verify tick asks each tick, put in place per case; the
    report's ``spec`` block counts from the start of its own ``serve``.
    The pool is checked clean before a case gets the server, so no case
    sees another's blocks. A case that reads process-wide counters or the
    flight record over a whole run builds its own."""
    key = (drafter is not None, tuple(sorted(kw.items())))
    if key not in _SERVERS:
        spec = dict(speculate=True, draft_k=5) if drafter is not None else {}
        _SERVERS[key] = SlotServer(params, CFG, slots=2, cache_len=64,
                                   **spec, **kw)
    s = _SERVERS[key]
    if drafter is not None:
        s._drafter = (make_drafter(drafter) if isinstance(drafter, str)
                      else drafter)
    assert s._pool.used == 0 == s._pool.reserved
    return s


def _ref_tokens(params, n_new=24, eos=None, **kw):
    """Non-speculative reference streams, memoized per server shape and
    request budget: several parity tests share the same reference run."""
    key = (n_new, eos, tuple(sorted(kw.items())))
    if key not in _REF_CACHE:
        rep = _server(params, **kw).serve(_reqs(n_new, eos))
        _REF_CACHE[key] = {r.uid: r.tokens for r in rep.results}
    return _REF_CACHE[key]


# ---------------------------------------------------------------------------
# speculation.py host logic
# ---------------------------------------------------------------------------


class TestProposalAndAccept:
    def test_proposal_validates_topological_order(self):
        with pytest.raises(ValueError, match="topological"):
            DraftProposal(np.array([1, 2], np.int32),
                          np.array([1, 0], np.int32))
        with pytest.raises(ValueError, match="topological"):
            DraftProposal(np.array([1], np.int32), np.array([-2], np.int32))

    def test_chain_detection_truncation_and_chain_prefix(self):
        tree = DraftProposal(
            np.array([5, 6, 7, 8], np.int32),
            np.array([-1, -1, 1, 0], np.int32),  # two root branches
        )
        assert not tree.is_chain
        chain = tree.chain_prefix()  # first children: 0 -> 3
        assert chain.is_chain
        assert chain.tokens.tolist() == [5, 8]
        trunc = tree.truncated(2)
        assert trunc.tokens.tolist() == [5, 6]
        assert trunc.parents.tolist() == [-1, -1]
        lin = DraftProposal(np.array([1, 2], np.int32),
                            np.array([-1, 0], np.int32))
        assert lin.is_chain

    def test_pack_chain_is_causal_shape(self):
        pack = pack_proposal(9, DraftProposal(
            np.array([1, 2, 3], np.int32), np.array([-1, 0, 1], np.int32)
        ))
        assert pack.row_tokens.tolist() == [9, 1, 2, 3]
        assert pack.depth.tolist() == [0, 1, 2, 3]
        np.testing.assert_array_equal(pack.anc, np.tril(np.ones((4, 4),
                                                                bool)))

    def test_pack_tree_depths_and_ancestors(self):
        # tip -> {a, b}; a -> c
        pack = pack_proposal(9, DraftProposal(
            np.array([1, 2, 3], np.int32), np.array([-1, -1, 0], np.int32)
        ))
        assert pack.depth.tolist() == [0, 1, 1, 2]
        assert pack.anc[3].tolist() == [True, True, False, True]
        assert pack.anc[2].tolist() == [True, False, True, False]

    def test_accept_walk_full_partial_none_and_tree(self):
        chain = pack_proposal(9, DraftProposal(
            np.array([1, 2, 3], np.int32), np.array([-1, 0, 1], np.int32)
        ))
        # full accept: every row's argmax names its packed child
        kept, com = accept_longest_path(chain, [1, 2, 3, 4])
        assert kept == [1, 2, 3] and com == [1, 2, 3, 4]
        # partial: diverges after one
        kept, com = accept_longest_path(chain, [1, 7, 3, 4])
        assert kept == [1] and com == [1, 7]
        # none: the bonus token still commits
        kept, com = accept_longest_path(chain, [5, 0, 0, 0])
        assert kept == [] and com == [5]
        # tree: the walk picks the matching branch
        tree = pack_proposal(9, DraftProposal(
            np.array([1, 2, 3], np.int32), np.array([-1, -1, 1], np.int32)
        ))
        kept, com = accept_longest_path(tree, [2, 0, 3, 8])
        assert kept == [2, 3] and com == [2, 3, 8]

    def test_stochastic_accept_is_the_same_walk_over_samples(self):
        # The point-mass coupling (ISSUE 20): with SAMPLES in place of
        # argmaxes the ratio test degenerates to the same child walk —
        # accept iff the target's draw names the draft, else the draw
        # itself is the residual emission.
        chain = pack_proposal(9, DraftProposal(
            np.array([1, 2, 3], np.int32), np.array([-1, 0, 1], np.int32)
        ))
        kept, com = accept_stochastic_path(chain, [1, 2, 7, 4])
        assert kept == [1, 2] and com == [1, 2, 7]
        kept, com = accept_stochastic_path(chain, [4, 0, 0, 0])
        assert kept == [] and com == [4]

    def test_pack_siblings_shape_and_limits(self):
        pack = pack_siblings([[3, 4], [5, 6], [3, 7]])
        assert pack.rows == 6
        assert pack.row_tokens.tolist() == [3, 4, 5, 6, 3, 7]
        assert pack.depth.tolist() == [0, 1, 0, 1, 0, 1]
        assert pack.row_parents.tolist() == [-1, 0, -1, 2, -1, 4]
        # Per-branch lower-triangular blocks, nothing across branches.
        tril2 = np.tril(np.ones((2, 2), bool))
        for r in range(3):
            o = 2 * r
            np.testing.assert_array_equal(pack.anc[o:o + 2, o:o + 2],
                                          tril2)
        off = ~np.kron(np.eye(3, dtype=bool), np.ones((2, 2), bool))
        assert not pack.anc[off].any()
        with pytest.raises(ValueError, match="equal length"):
            pack_siblings([[1, 2], [3]])
        with pytest.raises(ValueError, match=">= 1"):
            pack_siblings([])
        with pytest.raises(AssertionError, match="32-row"):
            pack_siblings([list(range(11))] * 3)  # 33 rows

    def test_prompt_lookup_prefers_full_k_continuation(self):
        # tail [1, 2] recurs at position 0 (long continuation) and at
        # position 6 (3 tokens to the end). The most recent match wins
        # while its continuation is a full k; once k outgrows it, the
        # drafter reaches back for the full-k match instead of freezing
        # speculation depth at the distance-to-end.
        h = np.array([1, 2, 3, 4, 5, 9, 1, 2, 8, 1, 2], np.int32)
        prop = PromptLookupDrafter().propose(h, 3)
        assert prop is not None and prop.is_chain
        assert prop.tokens.tolist() == [8, 1, 2]  # recent, still full-k
        prop = PromptLookupDrafter().propose(h, 4)
        assert prop.tokens.tolist() == [3, 4, 5, 9]  # older full-k match

    def test_prompt_lookup_miss_returns_none(self):
        assert PromptLookupDrafter().propose(
            np.arange(10, dtype=np.int32), 4
        ) is None

    def test_tree_drafter_branches_on_divergent_continuations(self):
        # "5 1" continued by 7 once and by 8 once -> two branches.
        h = np.array([5, 1, 7, 9, 5, 1, 8, 2, 5, 1], np.int32)
        prop = PromptLookupTreeDrafter(width=2).propose(h, 4)
        assert prop is not None and not prop.is_chain
        roots = [int(t) for t, p in zip(prop.tokens, prop.parents)
                 if p == -1]
        assert sorted(roots) == [7, 8]

    def test_tree_drafter_budget_smaller_than_width(self):
        # k < width: the branch list clamps to the budget — a negative
        # primary share (review finding) must never slice backwards and
        # overshoot the k-node budget.
        h = np.array([5, 1, 7, 9, 5, 1, 8, 2, 5, 1], np.int32)
        for k in (1, 2, 3):
            prop = PromptLookupTreeDrafter(width=4).propose(h, k)
            assert prop is not None and len(prop) <= k

    def test_draft_model_drafter_proposes_its_own_greedy_chain(self, params):
        d = DraftModelDrafter(params, CFG)
        hist = LOOP_PROMPT
        prop = d.propose(hist, 4)
        assert prop is not None and prop.is_chain and len(prop) == 4
        ref = np.asarray(generate(
            params, jnp.asarray(hist)[None], 4, CFG, cache_len=32
        ))[0]
        np.testing.assert_array_equal(prop.tokens, ref)

    def test_make_drafter_registry(self):
        assert isinstance(make_drafter("ngram"), PromptLookupDrafter)
        assert isinstance(make_drafter("ngram-tree"),
                          PromptLookupTreeDrafter)
        with pytest.raises(ValueError, match="unknown drafter"):
            make_drafter("nope")
        with pytest.raises(ValueError, match="needs params"):
            make_drafter("model")


# ---------------------------------------------------------------------------
# ops level: the tree verify mask
# ---------------------------------------------------------------------------


def _random_tree_mask(rng, B, Tq):
    """Random ancestor-closed masks (diag always set, strictly lower
    bits random but transitively closed — the shape packing produces)."""
    anc = np.zeros((B, Tq, Tq), bool)
    for b in range(B):
        parents = [-1] + [int(rng.integers(-1, i)) for i in range(1, Tq)]
        for i in range(Tq):
            anc[b, i, i] = True
            if parents[i] >= 0:
                anc[b, i] |= anc[b, parents[i]]
    return anc


def test_tree_mask_chunked_matches_naive_oracle():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, cap, Tq = 2, 4, 2, 16, 96, 5
    q = jnp.asarray(rng.standard_normal((B, Hq, Tq, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    pos = jnp.asarray([10, 63], jnp.int32)
    tm = jnp.asarray(_random_tree_mask(rng, B, Tq))
    out, lse = flash_decode(q, k, v, q_position=pos, num_splits=4,
                            tree_mask=tm)
    for b in range(B):
        o_ref, l_ref = attention_naive(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], causal=True,
            q_offset=int(pos[b]), tree_mask=tm[b:b + 1],
        )
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(o_ref[0]),
                                   atol=2e-6)
        np.testing.assert_allclose(np.asarray(lse[b]), np.asarray(l_ref[0]),
                                   atol=2e-6)


def test_tree_mask_tril_is_causal_bit_for_bit():
    """The load-bearing equivalence: a lower-triangular tree mask IS the
    causal rule — chain spec slots in a tree tick must not perturb a
    single bit vs the pure-causal program."""
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode,
        attention_pallas_decode_q8q,
        quantize_kv_channelwise,
    )

    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, cap, Tq = 2, 4, 2, 16, 64, 4
    q = jnp.asarray(rng.standard_normal((B, Hq, Tq, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, cap, D), np.float32))
    pos = jnp.asarray([7, 40], jnp.int32)
    tril = jnp.asarray(np.broadcast_to(np.tril(np.ones((Tq, Tq), bool)),
                                       (B, Tq, Tq)))
    oc, lc = flash_decode(q, k, v, q_position=pos, num_splits=4)
    ot, lt = flash_decode(q, k, v, q_position=pos, num_splits=4,
                          tree_mask=tril)
    assert bool(jnp.all(oc == ot)) and bool(jnp.all(lc == lt))
    oc, lc = attention_pallas_decode(q, k, v, causal=True, q_offset=pos,
                                     interpret=True)
    ot, lt = attention_pallas_decode(q, k, v, causal=True, q_offset=pos,
                                     tree_mask=tril, interpret=True)
    assert bool(jnp.all(oc == ot)) and bool(jnp.all(lc == lt))
    qb = q.astype(jnp.bfloat16)
    k_q, v_q, k_s, v_s = quantize_kv_channelwise(
        k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    )
    oc, lc = attention_pallas_decode_q8q(qb, k_q, v_q, k_s, v_s,
                                         causal=True, q_offset=pos,
                                         interpret=True)
    ot, lt = attention_pallas_decode_q8q(qb, k_q, v_q, k_s, v_s,
                                         causal=True, q_offset=pos,
                                         tree_mask=tril, interpret=True)
    assert bool(jnp.all(oc == ot)) and bool(jnp.all(lc == lt))


def test_tree_mask_pallas_matches_chunked_paged_and_contiguous():
    from tree_attention_tpu.ops.pallas_decode import attention_pallas_decode

    rng = np.random.default_rng(2)
    B, Hq, Hkv, D, Tq, blk, NB, N = 2, 4, 2, 16, 5, 16, 4, 10
    q = jnp.asarray(rng.standard_normal((B, Hq, Tq, D), np.float32))
    pool_k = jnp.asarray(rng.standard_normal((N, Hkv, blk, D), np.float32))
    pool_v = jnp.asarray(rng.standard_normal((N, Hkv, blk, D), np.float32))
    table = jnp.asarray(rng.permutation(N)[:B * NB].reshape(B, NB)
                        .astype(np.int32))
    pos = jnp.asarray([11, 37], jnp.int32)
    tm = jnp.asarray(_random_tree_mask(rng, B, Tq))
    k, v = gather_paged_kv(pool_k, pool_v, table)
    o_ref, l_ref = flash_decode(q, k, v, q_position=pos, num_splits=2,
                                tree_mask=tm)
    # contiguous pallas interpret
    o1, l1 = attention_pallas_decode(q, k, v, causal=True, q_offset=pos,
                                     tree_mask=tm, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o_ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l_ref), atol=2e-6)
    # paged pallas interpret (table-driven split-KV grid)
    o2, l2 = attention_pallas_decode(q, pool_k, pool_v, causal=True,
                                     q_offset=pos, block_table=table,
                                     tree_mask=tm, interpret=True)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o_ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(l2), np.asarray(l_ref), atol=2e-6)


def test_sibling_mask_rows_equal_independent_branches():
    """The ISSUE-20 packing oracle: a ``pack_siblings`` bundle's rows
    through the EXISTING tree-mask kernels equal k independent causal
    decodes — branch r's rows see the frozen ancestors ``[0, pos)``
    plus its own suffix only, exactly as if that suffix sat alone at
    ``[pos, pos+s)``. No new kernel; the block-diagonal mask is the
    whole mechanism."""
    from tree_attention_tpu.ops.pallas_decode import attention_pallas_decode

    rng = np.random.default_rng(5)
    Hq, Hkv, D, cap, pos = 4, 2, 16, 64, 19
    k_br, s = 3, 4
    Tq = k_br * s
    pack = pack_siblings([[0] * s] * k_br)  # tokens unused at ops level
    q = rng.standard_normal((1, Hq, Tq, D)).astype(np.float32)
    kv_k = rng.standard_normal((1, Hkv, cap, D)).astype(np.float32)
    kv_v = rng.standard_normal((1, Hkv, cap, D)).astype(np.float32)
    tm = jnp.asarray(pack.anc)[None]
    out, lse = flash_decode(
        jnp.asarray(q), jnp.asarray(kv_k), jnp.asarray(kv_v),
        q_position=jnp.asarray([pos], jnp.int32), num_splits=2,
        tree_mask=tm,
    )
    op, lp = attention_pallas_decode(
        jnp.asarray(q), jnp.asarray(kv_k), jnp.asarray(kv_v),
        causal=True, q_offset=jnp.asarray([pos], jnp.int32),
        tree_mask=tm, interpret=True,
    )
    for r in range(k_br):
        o = r * s
        # The branch alone: its suffix KV moved to the contiguous
        # window [pos, pos+s), everything behind pos untouched.
        bk, bv = kv_k.copy(), kv_v.copy()
        bk[:, :, pos:pos + s] = kv_k[:, :, pos + o:pos + o + s]
        bv[:, :, pos:pos + s] = kv_v[:, :, pos + o:pos + o + s]
        o_ref, l_ref = flash_decode(
            jnp.asarray(q[:, :, o:o + s]), jnp.asarray(bk),
            jnp.asarray(bv),
            q_position=jnp.asarray([pos], jnp.int32), num_splits=2,
        )
        for got_o, got_l in ((out, lse), (op, lp)):
            np.testing.assert_allclose(
                np.asarray(got_o[:, :, o:o + s]), np.asarray(o_ref),
                atol=2e-6,
            )
            np.testing.assert_allclose(
                np.asarray(got_l[:, :, o:o + s]), np.asarray(l_ref),
                atol=2e-6,
            )


def test_forward_step_tree_rows_equal_per_path_sequential(params):
    """THE verify-mask semantics: a packed tree's logits row j equals a
    sequential decode along j's root path — on both layouts."""
    prompt = np.asarray(LOOP_PROMPT[:7])
    toks = np.array([5, 11, 23, 7, 9, 23], np.int32)
    par = np.array([-1, 0, 0, 1, 1, 2], np.int32)
    Tq = len(toks)
    pack = pack_proposal(int(toks[0]), DraftProposal(toks[1:], par[1:] - 1))
    import dataclasses as dc

    def mk_paged():
        c = init_paged_cache(CFG, 1, 32, 10, block=4)
        perm = np.array([7, 2, 9, 0, 5, 1, 8, 3], np.int32)  # fragmented
        return dc.replace(c, table=jnp.asarray(perm)[None])

    # One program a (layout, token width): the prompt, the packed tree, and
    # every root path at the deepest path's width with its length in
    # ``n_tokens``, so that paths of 1, 2 and 3 rows share one.
    step = jax.jit(lambda p, t, c, **kw: forward_step(p, t, c, CFG, **kw))
    deepest = int(pack.depth.max()) + 1
    for mk in (lambda: init_cache(CFG, 1, 32), mk_paged):
        _, cache = step(params, jnp.asarray(prompt)[None], mk())
        logits, _ = step(
            params, jnp.asarray(pack.row_tokens)[None], cache,
            n_tokens=jnp.asarray([Tq], jnp.int32),
            positions=jnp.asarray(7 + pack.depth)[None],
            tree_mask=jnp.asarray(pack.anc)[None],
        )
        for i in range(Tq):
            path, j = [], i
            while j >= 0:
                path.append(j)
                j = int(pack.row_parents[j])
            path = path[::-1]
            rows = np.zeros((1, deepest), np.int32)
            rows[0, :len(path)] = pack.row_tokens[path]
            # ``cache`` is the untouched prefilled base (functional
            # updates): every path replays from it directly.
            lr, _ = step(params, jnp.asarray(rows), cache,
                         n_tokens=jnp.asarray([len(path)], jnp.int32))
            np.testing.assert_allclose(
                np.asarray(lr[0, len(path) - 1]), np.asarray(logits[0, i]),
                atol=2e-4
            )


def test_compact_decode_window_paged_unit():
    """Synthetic pool: dst j takes src[j] through a fragmented table,
    rows past n untouched, n=0 slots bit-identical."""
    L, N, Hkv, blk, D = 1, 6, 1, 4, 2
    pool = jnp.arange(L * N * Hkv * blk * D, dtype=jnp.float32).reshape(
        L, N, Hkv, blk, D
    )
    table = jnp.asarray([[3, 1, 4, 0]], jnp.int32)
    cache = PagedKVCache(k=pool, v=pool + 1000, table=table,
                         length=jnp.asarray([13], jnp.int32))

    def logical(c, pos):
        b = int(table[0, pos // blk])
        return np.asarray(c.k[0, b, 0, pos % blk])

    before = {p: logical(cache, p) for p in range(16)}
    out = compact_decode_window(
        cache, jnp.asarray([7], jnp.int32),
        jnp.asarray([[0, 2, 5, 3, 4, 5]], jnp.int32),
        jnp.asarray([3], jnp.int32),
    )
    exp = dict(before)
    exp[8] = before[9]   # dst 1 <- src 2
    exp[9] = before[12]  # dst 2 <- src 5
    for p in range(16):
        np.testing.assert_array_equal(logical(out, p), exp[p])
    # n = 0 is a bit-exact no-op
    out0 = compact_decode_window(
        cache, jnp.asarray([7], jnp.int32),
        jnp.asarray([[0, 1, 2, 3, 4, 5]], jnp.int32),
        jnp.asarray([0], jnp.int32),
    )
    assert bool(jnp.all(out0.k == cache.k)) and bool(
        jnp.all(out0.v == cache.v)
    )


# ---------------------------------------------------------------------------
# engine parity: the hard contract
# ---------------------------------------------------------------------------


class OracleDrafter(Drafter):
    """Knows each request's true continuation (the non-speculative
    reference) and proposes it with controlled poison — the adversarial
    fixture that drives acceptance (and rejection) deterministically."""

    def __init__(self, prompts, refs, wrong_every=0, tree=False,
                 always_k=None):
        self.prompts = prompts
        self.full = {
            uid: np.concatenate([np.asarray(prompts[uid], np.int32),
                                 np.asarray(refs[uid], np.int32)])
            for uid in refs
        }
        self.wrong_every = wrong_every
        self.tree = tree
        self.always_k = always_k
        self.calls = 0

    def _uid(self, history):
        for uid, p in self.prompts.items():
            if len(history) >= len(p) and np.array_equal(
                history[:len(p)], np.asarray(p, np.int32)
            ):
                return uid
        raise AssertionError("history matches no request")

    def propose(self, history, k):
        self.calls += 1
        if self.always_k is not None:
            k = self.always_k  # adversarial: ignore the engine's budget
        full = self.full[self._uid(history)]
        cont = full[len(history):len(history) + k].copy()
        if len(cont) == 0:
            # Past the reference: propose garbage (must all reject).
            cont = np.full((max(k, 1),), 3, np.int32)
        if self.wrong_every and self.calls % self.wrong_every == 0 \
                and len(cont) > 1:
            cont[1] = (cont[1] + 1) % CFG.vocab_size
        if not self.tree:
            return DraftProposal(
                cont, np.arange(-1, len(cont) - 1, dtype=np.int32)
            )
        # A decoy branch packed BEFORE the true chain: an accepted path
        # through the tree is then never contiguous rows — exercises the
        # commit compaction every single tick.
        tokens = [int((cont[0] + 1) % CFG.vocab_size)]
        parents = [-1]
        prev = -1
        for t in cont[:max(len(cont) - 1, 1)]:
            parents.append(prev)
            prev = len(tokens)
            tokens.append(int(t))
        return DraftProposal(np.asarray(tokens, np.int32),
                             np.asarray(parents, np.int32))


def _assert_parity(params, server_kw, drafter, n_new=24, eos=None,
                   min_accept=None):
    ref = _ref_tokens(params, n_new, eos, **server_kw)
    s = _server(params, drafter, **server_kw)
    rep = s.serve(_reqs(n_new, eos))
    for r in rep.results:
        assert r.tokens == ref[r.uid], (
            f"uid {r.uid}: spec {r.tokens} != ref {ref[r.uid]}"
        )
    assert s._pool.used == 0, "spec serve leaked pool blocks"
    assert s._pool.reserved == 0, "spec serve leaked reservations"
    if min_accept is not None:
        assert rep.spec["acceptance_rate"] >= min_accept, rep.spec
    return rep


def _lookup_walk(prompt, stream, draft_k):
    """What a verify loop must count when it commits ``stream`` under
    the prompt-lookup drafter: walk the reference as the engine does —
    draft from everything committed so far, within the request's
    remaining budget; the longest matching prefix is accepted and the
    model's own next token rides along. Returns (proposed, accepted)."""
    drafter = PromptLookupDrafter()
    proposed = accepted = 0
    i = 1  # stream[0] is the prefill's sample
    while i < len(stream):
        budget = min(draft_k, len(stream) - i - 1)
        prop = drafter.propose(
            np.concatenate([prompt, stream[:i]]).astype(np.int32), budget
        ) if budget >= 1 else None
        draft = [] if prop is None else list(prop.tokens[:budget])
        a = 0
        while a < len(draft) and draft[a] == stream[i + a]:
            a += 1
        proposed += len(draft)
        accepted += a
        i += a + 1
    return proposed, accepted


@pytest.mark.parametrize("kw", [{}, {"quantize": True}],
                         ids=["paged", "paged-int8"])
def test_spec_parity_ngram_all_combos(params, kw):
    """A workload the n-gram drafter MUST be accepted on, made by the
    model itself: the non-speculative engine's greedy continuation of
    each prompt is replayed as the prompt's tail, so the loop the tiny
    model settles into is already in the history when speculation
    starts. The acceptance the engine reports is held to the walk of
    that non-speculative reference (no constant floor)."""
    wander = _ref_tokens(params, **kw)
    prompts = {
        0: np.concatenate([LOOP_PROMPT, wander[0]]).astype(np.int32),
        1: np.concatenate([ALT_PROMPT, wander[1]]).astype(np.int32),
    }
    n_new, draft_k = 24, 5

    def reqs():
        return [Request(uid=u, prompt=p, max_new_tokens=n_new)
                for u, p in prompts.items()]

    ref = _server(params, **kw).serve(reqs())
    ref = {r.uid: np.asarray(r.tokens, np.int32) for r in ref.results}
    s = _server(params, "ngram", **kw)
    rep = s.serve(reqs())
    for r in rep.results:
        assert r.tokens == ref[r.uid].tolist()
    assert s._pool.used == 0 and s._pool.reserved == 0
    walks = [_lookup_walk(prompts[u], ref[u], draft_k) for u in prompts]
    proposed, accepted = (sum(w[j] for w in walks) for j in (0, 1))
    assert accepted > 0, "the replayed loop must be drafted and accepted"
    assert (rep.spec["proposed"], rep.spec["accepted"]) == (proposed,
                                                            accepted)


def test_spec_parity_ngram_tree(params):
    rep = _assert_parity(params, {}, "ngram-tree")
    assert rep.spec["proposed"] > 0


@pytest.mark.parametrize("kw", [{}, {"quantize": True}],
                         ids=["paged", "int8"])
def test_spec_parity_mesh(params, kw):
    """compat cpu_mesh: spec == non-spec on the SAME mesh topology (the
    int8 case exercises the chain fallback — its dequantized view rides
    the tree merge, which has no mask plumbing)."""
    mesh = cpu_mesh(2)
    ref = SlotServer(params, CFG, slots=2, cache_len=64, mesh=mesh,
                     **kw).serve(_reqs())
    rt = {r.uid: r.tokens for r in ref.results}
    s = SlotServer(params, CFG, slots=2, cache_len=64, mesh=mesh,
                   speculate=True, draft_k=5, drafter="ngram-tree", **kw)
    rep = s.serve(_reqs())
    for r in rep.results:
        assert r.tokens == rt[r.uid]


def test_spec_parity_oracle_chain_and_tree(params):
    """Deterministic accept/reject mixtures, including tree decoys that
    force a compaction every commit."""
    prompts = {0: LOOP_PROMPT, 1: ALT_PROMPT}
    refs = _ref_tokens(params)
    for tree in (False, True):
        for wrong_every in (0, 2, 3):
            d = OracleDrafter(prompts, refs, wrong_every=wrong_every,
                              tree=tree)
            rep = _assert_parity(params, {}, d)
            if wrong_every == 0 and not tree:
                assert rep.spec["acceptance_rate"] == 1.0


def test_spec_oracle_tree_int8(params):
    prompts = {0: LOOP_PROMPT, 1: ALT_PROMPT}
    kw = {"quantize": True}
    refs = _ref_tokens(params, **kw)
    d = OracleDrafter(prompts, refs, wrong_every=2, tree=True)
    _assert_parity(params, kw, d)


# ---------------------------------------------------------------------------
# rollback edge cases (the satellite checklist)
# ---------------------------------------------------------------------------


def test_tree_draft_coexists_with_wide_prefill_chunk(params):
    """A tick can carry a live slot's TREE draft AND another slot's
    prefill chunk wider than 32 tokens (the int32 bitmask limit): the
    tree falls back to its root-path chain for that tick instead of
    building an over-wide mask (review finding — used to raise
    ``Tq exceeds 32`` mid-serve). Parity still holds."""
    prompt_a = np.tile(np.array([7, 9, 4], np.int32), 8)   # 24 tokens
    prompt_b = np.tile(np.array([3, 5], np.int32), 50)     # 100 tokens
    reqs = lambda: [
        Request(uid=0, prompt=prompt_a, max_new_tokens=24,
                arrival_tick=0),
        # Arrives once slot 0 is live and drafting: its 64-token chunks
        # share verify ticks with slot 0's tree proposals.
        Request(uid=1, prompt=prompt_b, max_new_tokens=8,
                arrival_tick=4),
    ]
    kw = dict(slots=2, cache_len=256, prefill_chunk=64)
    ref = SlotServer(params, CFG, **kw).serve(reqs())
    rt = {r.uid: r.tokens for r in ref.results}
    s = SlotServer(params, CFG, speculate=True, draft_k=5,
                   drafter="ngram-tree", **kw)
    rep = s.serve(reqs())
    for r in rep.results:
        assert r.tokens == rt[r.uid]


class _NeverDrafter(Drafter):
    def propose(self, history, k):
        return None


def test_draftless_ticks_run_narrow_and_match(params):
    """A drafter that never proposes: every tick is a tip-only (Tq=1)
    verify — the engine must not pay the padded verify bucket (review
    finding) and the stream stays identical."""
    ref = _ref_tokens(params, n_new=10)
    s = _server(params, _NeverDrafter())
    rep = s.serve(_reqs(10))
    for r in rep.results:
        assert r.tokens == ref[r.uid]
    assert rep.spec["proposed"] == 0


def test_rejection_at_slot_capacity_boundary(params):
    """prompt + max_new == cache_len exactly: the verify window brushes
    the clamp-and-shift machinery at the cache edge; every reject rolls
    back correctly and the final token lands at the last row."""
    n_new = 64 - len(LOOP_PROMPT)  # fills cache_len=64 to the brim
    prompts = {0: LOOP_PROMPT, 1: ALT_PROMPT}
    refs = _ref_tokens(params, n_new=n_new)
    d = OracleDrafter(prompts, refs, wrong_every=2, tree=False)
    _assert_parity(params, {}, d, n_new=n_new)


def test_eos_inside_committed_burst_retires_same_tick(params):
    """EOS commits mid-burst: the burst truncates AT the EOS token, the
    slot retires the same tick, and tokens match the non-spec run
    (which also stops at EOS)."""
    base = _ref_tokens(params, n_new=24)
    # Pick a token the reference actually emits mid-stream for uid 0.
    eos = base[0][len(base[0]) // 2]
    ref = _ref_tokens(params, n_new=24, eos=eos)
    prompts = {0: LOOP_PROMPT, 1: ALT_PROMPT}
    # The oracle drafts the NO-EOS continuation, so the EOS can land
    # anywhere inside an accepted burst.
    d = OracleDrafter(prompts, base)
    s = _server(params, d)
    rep = s.serve(_reqs(24, eos))
    for r in rep.results:
        assert r.tokens == ref[r.uid]
        if eos in r.tokens:
            assert r.outcome == "eos"
            assert r.tokens[-1] == eos  # truncated AT the EOS
    assert s._pool.used == 0 and s._pool.reserved == 0


def test_drafter_proposing_past_max_new_tokens_is_clamped(params):
    """An adversarial drafter that always proposes 31 tokens regardless
    of the engine's budget: commits never exceed max_new_tokens and
    parity holds."""
    prompts = {0: LOOP_PROMPT, 1: ALT_PROMPT}
    refs = _ref_tokens(params, n_new=10)
    d = OracleDrafter(prompts, refs, always_k=31)
    rep = _assert_parity(params, {}, d, n_new=10)
    for r in rep.results:
        assert len(r.tokens) == 10


def test_randomized_accept_reject_cache_bytes_property(params):
    """The device-state contract under random accept/reject, run by hand
    on forward_step (chain drafts, a random poison position per round)
    against a token-by-token reference cache, on both layouts:

    - bytes OUTSIDE the verify window (everything at or past
      ``start + n``, and everything below ``start``) are BIT-identical
      across the verify step — speculation never touches state it did
      not commit;
    - bytes inside the committed prefix equal sequential stepping to
      float-association tolerance (a Tq=k chunk and k Tq=1 steps batch
      the same row math differently — the parity of chunked
      admission with a whole-prompt prefill is token-level for the
      same reason);
    - the committed token stream is the reference stream by
      construction of the accept rule (asserted via the argmax walk).
    """
    rng = np.random.default_rng(7)
    prompt = np.asarray(LOOP_PROMPT[:8])
    ref_toks = np.asarray(generate(
        params, jnp.asarray(prompt)[None], 24, CFG, cache_len=64
    ))[0]
    stream = np.concatenate([prompt, ref_toks])

    def view_kv(cache):
        if isinstance(cache, PagedKVCache):
            ks = [gather_paged_kv(cache.k[l], cache.v[l], cache.table)
                  for l in range(CFG.n_layers)]
            return (jnp.stack([a for a, _ in ks]),
                    jnp.stack([b for _, b in ks]))
        return cache.k, cache.v

    import dataclasses as dc

    def mk_paged():
        c = init_paged_cache(CFG, 1, 64, 16, block=4)
        return dc.replace(
            c, table=jnp.asarray(rng.permutation(16).astype(np.int32))[None]
        )

    # Jitted steppers (one compile per layout each — eager op dispatch
    # would dominate the test): the verify step runs at a fixed padded
    # width with per-call n_tokens, exactly the engine's bucket shape.
    W = 8
    ref_step = jax.jit(lambda p, t, c: forward_step(p, t, c, CFG))
    verify_step = jax.jit(
        lambda p, t, c, n: forward_step(p, t, c, CFG, n_tokens=n)
    )

    for mk in (lambda: init_cache(CFG, 1, 64), mk_paged):
        _, spec_cache = forward_step(params, jnp.asarray(prompt)[None],
                                     mk(), CFG)
        _, ref_cache = forward_step(params, jnp.asarray(prompt)[None],
                                    mk(), CFG)
        clen = len(prompt)  # committed rows in spec_cache
        pos = len(prompt)   # next stream index (tip = stream[pos])
        while pos + 1 < len(stream) and clen < 48:
            k = int(rng.integers(1, 6))
            draft = stream[pos + 1:pos + 1 + k].copy()
            poison = int(rng.integers(0, len(draft) + 1))
            if poison < len(draft):
                draft[poison] = (draft[poison] + 1) % CFG.vocab_size
            rows = np.concatenate([[stream[pos]], draft])
            n = len(rows)
            mat = np.zeros((1, W), np.int32)
            mat[0, :n] = rows
            spec_cache = dc.replace(
                spec_cache, length=jnp.asarray([clen], jnp.int32)
            )
            pre_k, pre_v = view_kv(spec_cache)
            logits, spec_cache = verify_step(
                params, jnp.asarray(mat), spec_cache,
                jnp.asarray([n], jnp.int32),
            )
            sk, sv = view_kv(spec_cache)
            # BIT-identity outside the verify window: below start and at
            # or past start + n, the step wrote nothing.
            for pre, post in ((pre_k, sk), (pre_v, sv)):
                assert bool(jnp.all(pre[..., :clen, :]
                                    == post[..., :clen, :])), \
                    f"bytes below the window changed at clen={clen}"
                assert bool(jnp.all(pre[..., clen + n:, :]
                                    == post[..., clen + n:, :])), \
                    f"bytes past the window changed at clen={clen}"
            am = np.asarray(jnp.argmax(logits[0, :n], axis=-1))
            a = 0
            while a < len(draft) and draft[a] == am[a]:
                a += 1
            # the accept walk reproduces the reference stream exactly
            # (beyond the generated reference there is no ground truth)
            if pos + a + 2 <= len(stream):
                np.testing.assert_array_equal(
                    am[:a + 1], stream[pos + 1:pos + a + 2]
                )
            # reference advances the same committed tokens one by one
            for j in range(a + 1):
                _, ref_cache = ref_step(
                    params, jnp.asarray([[stream[pos + j]]]), ref_cache
                )
            clen += a + 1
            pos += a + 1
            rk, rv = view_kv(ref_cache)
            # committed-prefix bytes equal sequential stepping to float
            # association (different Tq batch the same row math).
            np.testing.assert_allclose(
                np.asarray(sk[..., :clen, :]),
                np.asarray(rk[..., :clen, :]), atol=1e-5,
                err_msg=f"K diverged inside committed prefix, clen={clen}",
            )
            np.testing.assert_allclose(
                np.asarray(sv[..., :clen, :]),
                np.asarray(rv[..., :clen, :]), atol=1e-5,
                err_msg=f"V diverged inside committed prefix, clen={clen}",
            )
            assert int(ref_cache.length[0]) == clen


# ---------------------------------------------------------------------------
# block pool rollback + engine validation
# ---------------------------------------------------------------------------


def test_block_allocator_unmap_private_restores_reservation():
    a = BlockAllocator(4)
    assert a.reserve(3)
    b1, b2, b3 = a.alloc(), a.alloc(), a.alloc()
    assert a.reserved == 0 and a.free_count == 1
    gen = a.gen
    a.unmap_private(b3)  # rollback: free + re-reserved, gen unchanged
    assert a.reserved == 1 and a.free_count == 2
    assert a.gen == gen
    assert a.alloc() == b3  # the reservation backs the re-allocation
    a.free_private(b1)
    a.free_private(b2)
    a.free_private(b3)
    assert a.used == 0 and a.reserved == 0


def test_speculate_allows_sampling_rejects_bad_draft_k(params):
    # The pure-argmax restriction is LIFTED (ISSUE 20): a sampling
    # spec engine constructs fine and serves via the stochastic
    # accept walk (distribution parity tested below).
    SlotServer(params, CFG, slots=1, cache_len=32, speculate=True,
               temperature=0.5)
    with pytest.raises(ValueError, match="draft_k"):
        SlotServer(params, CFG, slots=1, cache_len=32, speculate=True,
                   draft_k=0)
    with pytest.raises(ValueError, match="draft_k"):
        SlotServer(params, CFG, slots=1, cache_len=32, speculate=True,
                   draft_k=32)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_spec_metrics_flight_and_report(params):
    from tree_attention_tpu import obs
    from tree_attention_tpu.obs.flight import FLIGHT

    obs.REGISTRY.enable()
    FLIGHT.arm()
    FLIGHT.clear()
    try:
        prompts = {0: LOOP_PROMPT, 1: ALT_PROMPT}
        refs = _ref_tokens(params)
        d = OracleDrafter(prompts, refs, wrong_every=3)
        s = SlotServer(params, CFG, slots=2, cache_len=64, speculate=True,
                       draft_k=5, drafter=d)
        p0 = obs.REGISTRY.get("serving_spec_proposed_total").value()
        a0 = obs.REGISTRY.get("serving_spec_accepted_total").value()
        rep = s.serve(_reqs())
        prop = obs.REGISTRY.get("serving_spec_proposed_total").value() - p0
        acc = obs.REGISTRY.get("serving_spec_accepted_total").value() - a0
        assert prop == rep.spec["proposed"] > 0
        assert acc == rep.spec["accepted"] > 0
        ratio = obs.REGISTRY.get("serving_spec_acceptance_ratio").value()
        assert 0.0 < ratio <= 1.0
        # report block + as_dict round trip
        assert 0.0 < rep.spec["acceptance_rate"] <= 1.0
        assert rep.spec["tokens_per_verify"] > 1.0
        assert rep.as_dict()["spec"] == rep.spec
        # flight records carry the per-tick spec_verify fields
        recs = FLIGHT.snapshot()["records"]
        spec_recs = [r for r in recs if "spec_verify" in r]
        assert spec_recs, "no spec_verify flight fields recorded"
        assert sum(r["spec_verify"]["proposed"] for r in spec_recs) == prop
        assert sum(r["spec_verify"]["accepted"] for r in spec_recs) == acc
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()


def test_spec_disabled_off_path_untouched(params):
    """speculate=False engines never touch the spec machinery: no spec
    block in the report, no spec fields in flight records."""
    from tree_attention_tpu.obs.flight import FLIGHT

    FLIGHT.arm()
    FLIGHT.clear()
    try:
        s = SlotServer(params, CFG, slots=2, cache_len=64)
        rep = s.serve(_reqs(8))
        assert rep.spec == {}
        assert "spec" not in rep.as_dict()
        assert all("spec_verify" not in r
                   for r in FLIGHT.snapshot()["records"])
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()


# ---------------------------------------------------------------------------
# CLI flags
# ---------------------------------------------------------------------------


def test_cli_flags_parse():
    from tree_attention_tpu.utils.config import parse_args

    cfg = parse_args([
        "--mode", "serve", "--speculate", "--draft-k", "7",
        "--drafter", "ngram-tree",
    ])
    assert cfg.speculate and cfg.draft_k == 7
    assert cfg.drafter == "ngram-tree"
    cfg = parse_args(["--mode", "serve"])
    assert not cfg.speculate and cfg.drafter == "ngram"
