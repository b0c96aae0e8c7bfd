"""Sequence-sharded paged serving (ISSUE 18): seq == replicated, exactly.

The sharded pool is a LAYOUT change, not an algorithm change: shard ``s``
of ``W`` owns global block ids ``[s·N/W, (s+1)·N/W)``, each shard runs
the flash partial over only its local blocks, and the decode merge is
the tree-attention monoid — one MAX and two SUM collectives on
``(res, lse)``.  Every test here pins one face of that equivalence on
the compat ``cpu_mesh(2)``:

- the host ledger (``ShardedBlockAllocator``) splits soundly and hands
  blocks out richest-shard-first so placement stays balanced;
- the Pallas local-blocks kernel honors the signed local-table
  convention (negative = remote → culled; all-remote row → the merge
  identity ``(0, -inf)``) against the reference partial;
- ``paged_tree_decode`` equals the unsharded reference and costs
  EXACTLY three collectives (asserted through the accounting counters,
  the same artifact the serving bench gates on);
- end-to-end ``SlotServer`` parity: seq-sharded serving is
  token-for-token the replicated oracle, exact and int8, including a
  randomized admit/retire/prefix-hit
  interleaving (the property the layout must survive: ANY allocation
  history maps to the same logical attention).

Tier-1 keeps two engine combos and one small property seed; the
remaining combos ride the ``slow`` lane (the engine parity serves cost
~10s each — the tier-1 budget is tight).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tree_attention_tpu import obs
from tree_attention_tpu.models import init_params
from tree_attention_tpu.ops.decode import paged_local_partial
from tree_attention_tpu.ops.pallas_decode import attention_pallas_decode
from tree_attention_tpu.parallel.accounting import PAYLOAD_BYTES
from tree_attention_tpu.parallel.mesh import cpu_mesh
from tree_attention_tpu.parallel.tree import paged_tree_decode
from tree_attention_tpu.serving import Request, SlotServer
from tree_attention_tpu.serving.block_pool import ShardedBlockAllocator

from tests.test_serving_paged import (
    CFG, CHUNK_KW, PAGED_KW, PREFIX_KW, _prompt, _req,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def mesh():
    return cpu_mesh(2)


# ---------------------------------------------------------------------------
# (a) host ledger
# ---------------------------------------------------------------------------


class TestShardedAllocator:
    def test_rejects_unsplittable_pool(self):
        with pytest.raises(ValueError):
            ShardedBlockAllocator(10, 4)

    def test_range_partition_ownership(self):
        a = ShardedBlockAllocator(8, 2)
        assert a.shard_blocks == 4
        assert [a.shard_of(b) for b in range(8)] == [0] * 4 + [1] * 4

    def test_richest_first_keeps_shards_balanced(self):
        a = ShardedBlockAllocator(8, 2)
        assert a.reserve(6)
        held = []
        for _ in range(6):
            held.append(a.alloc())
            used = a.used_per_shard()
            assert max(used) - min(used) <= 1, used
        # round-trip: free and re-alloc lands back in balance
        for b in held:
            a.free_private(b)
        assert a.free_per_shard() == [4, 4]
        assert a.free_count == 8

    def test_global_reservations_span_shards(self):
        # Reservations are deliberately global: any block serves any
        # slot through the table indirection, so a reservation larger
        # than one shard's slice must still be grantable.
        a = ShardedBlockAllocator(8, 2)
        assert a.reserve(6)
        got = [a.alloc() for _ in range(6)]
        assert len({a.shard_of(b) for b in got}) == 2


# ---------------------------------------------------------------------------
# (b) local-blocks kernel vs the reference partial
# ---------------------------------------------------------------------------


def test_pallas_local_blocks_matches_reference_partial():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, blk, Nl, NB = 3, 4, 2, 16, 4, 6, 4
    pool_k = jnp.asarray(rng.normal(size=(Nl, Hkv, blk, D)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(Nl, Hkv, blk, D)), jnp.float32)
    # signed local table: owned rows mixed with -1 (remote) entries;
    # row 1 is ALL-remote — the kernel must emit the merge identity.
    tbl = jnp.asarray([[0, -1, 3, -1],
                       [-1, -1, -1, -1],
                       [5, 2, -1, 1]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    q_pos = jnp.asarray([9, 4, 15], jnp.int32)

    ref_o, ref_l = paged_local_partial(q, pool_k, pool_v, tbl,
                                       q_position=q_pos)
    ker_o, ker_l = attention_pallas_decode(
        q, pool_k, pool_v, causal=True, q_offset=q_pos, kv_offset=0,
        block_table=tbl, local_blocks=True, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ref_o), np.asarray(ker_o),
                               atol=2e-6)
    assert np.all(np.isneginf(np.asarray(ker_l)[1]))
    live = ~np.isneginf(np.asarray(ref_l))
    np.testing.assert_allclose(np.asarray(ref_l)[live],
                               np.asarray(ker_l)[live], atol=2e-5)
    # empty rows agree on the merge identity exactly
    assert np.all(np.isneginf(np.asarray(ker_l)[~live]))


# ---------------------------------------------------------------------------
# (c) the sharded merge: value and collective cost
# ---------------------------------------------------------------------------


def test_paged_tree_decode_matches_reference_in_three_collectives(mesh):
    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, blk, N, NB = 2, 4, 2, 8, 4, 8, 3
    pool_k = jnp.asarray(rng.normal(size=(N, Hkv, blk, D)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(N, Hkv, blk, D)), jnp.float32)
    # global ids straddling both shards' ranges [0,4) and [4,8)
    tbl = jnp.asarray([[0, 5, 2], [7, 1, 4]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    q_pos = jnp.asarray([11, 7], jnp.int32)

    ref_o, ref_l = paged_local_partial(q, pool_k, pool_v, tbl,
                                       q_position=q_pos)
    was_enabled = obs.REGISTRY.enabled
    obs.REGISTRY.enable()
    try:
        out, lse = paged_tree_decode(q, pool_k, pool_v, tbl, mesh=mesh,
                                     q_position=q_pos)
        colls = sorted(key[1] for key in PAYLOAD_BYTES._children
                       if key[0] == "paged_tree_decode")
        # exactly the monoid: one MAX, two SUMs — nothing else
        assert colls == ["pmax", "psum_den", "psum_num"]
    finally:
        if not was_enabled:
            obs.REGISTRY.disable()
    np.testing.assert_allclose(np.asarray(ref_o), np.asarray(out),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref_l), np.asarray(lse),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# (d) engine parity: seq-sharded serving vs the replicated oracle
# ---------------------------------------------------------------------------


def _serve_tokens(server, reqs):
    rep = server.serve([_clone(r) for r in reqs], max_ticks=400)
    return {r.uid: r.tokens for r in rep.results}


def _clone(r):
    return Request(uid=r.uid, prompt=r.prompt.copy(),
                   max_new_tokens=r.max_new_tokens,
                   arrival_tick=r.arrival_tick)


@pytest.mark.parametrize("quantize", [False, True], ids=["exact", "int8"])
def test_seq_sharded_matches_replicated_oracle(params, mesh, quantize):
    kw = dict(slots=2, cache_len=32, quantize=quantize, **CHUNK_KW,
              **PAGED_KW)
    reqs = [_req(0, _prompt(11))]
    rep = SlotServer(params, CFG, mesh=mesh, **kw)
    seq = SlotServer(params, CFG, mesh=mesh, kv_shard="seq", **kw)
    assert _serve_tokens(seq, reqs) == _serve_tokens(rep, reqs)


def test_random_interleaving_property(params, mesh):
    """Any admit/retire/prefix-hit history → the replicated tokens.

    Randomized small workload: shared prefixes (radix hits pin blocks),
    staggered arrivals over 2 slots (admissions interleave with
    retirements), ragged lengths — one seed in tier-1, more in slow.
    """
    _interleaving_case(params, mesh, seed=3)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [4, 5, 6])
def test_random_interleaving_property_more_seeds(params, mesh, seed):
    _interleaving_case(params, mesh, seed=seed)


def _interleaving_case(params, mesh, *, seed):
    rng = np.random.default_rng(seed)
    base = _prompt(7, n=8)
    reqs = []
    for i in range(4):
        kind = int(rng.integers(0, 3))
        if kind == 0:        # exact prefix re-serve → radix hit
            prompt = base.copy()
        elif kind == 1:      # shared prefix + fresh tail
            tail = _prompt(100 + seed * 10 + i, n=int(rng.integers(1, 6)))
            prompt = np.concatenate([base, tail])
        else:                # unrelated prompt
            prompt = _prompt(200 + seed * 10 + i,
                             n=int(rng.integers(4, 14)))
        reqs.append(Request(
            uid=i, prompt=np.asarray(prompt, np.int32),
            max_new_tokens=int(rng.integers(2, 5)),
            arrival_tick=int(rng.integers(0, 5)),
        ))
    kw = dict(slots=2, cache_len=32, **CHUNK_KW, **PAGED_KW, **PREFIX_KW)
    rep = SlotServer(params, CFG, mesh=mesh, **kw)
    seq = SlotServer(params, CFG, mesh=mesh, kv_shard="seq", **kw)
    assert _serve_tokens(seq, reqs) == _serve_tokens(rep, reqs)
