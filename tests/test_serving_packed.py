"""The packed mixed tick in the engine (ISSUE 30).

A tick that carries a prompt chunk computes a chunk group of ``C`` members
beside one decode row a slot. ``C`` is fixed when the engine is built
(``ceil(prefill_budget / prefill_chunk)``), so:

- the set of tick programs depends on the Tq buckets alone: serving eight
  prompts at once builds no program that one prompt served alone did not
  (what the benchmark's warm-up relies on: it serves one prompt alone, and a
  compile inside the measured window fails the run);
- the chunk plan stops at ``C`` slots, in FIFO order;
- the flight record says what the tick computed: ``chunk_group`` members,
  ``rows_computed == C·tq + S``.

Tokens stay those of each request's own single-stream decode.
"""

import numpy as np
import pytest
import jax

from tree_attention_tpu.models import init_params
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.serving.engine import Request, SlotServer

from tests.test_serving import CFG, KV_BLOCK, _single_stream

SLOTS, CHUNK = 8, 16
# One prompt that alone takes every bucket the engine has (16, then a tail
# of 5 rows in the bucket of 8), as the harness's warm-up prompt does.
WARM_LEN = CHUNK + 5


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _requests(lens, n_new=3, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, max_new_tokens=n_new,
                    prompt=rng.integers(0, CFG.vocab_size, n).astype(np.int32))
            for i, n in enumerate(lens)]


def _programs(server):
    return {name: getattr(server, name)._cache_size()
            for name in ("_mixed", "_packed")}


@pytest.mark.parametrize("group", [1, 2])
def test_a_full_house_builds_no_program_one_prompt_did_not(params, group):
    server = SlotServer(params, CFG, slots=SLOTS, cache_len=64,
                        kv_block=KV_BLOCK, prefill_chunk=CHUNK,
                        prefill_budget=group * CHUNK)
    assert server._chunk_group == group
    server.serve(_requests([WARM_LEN]))
    warm = _programs(server)
    # The decode tick; one packed program a bucket (16 and 8).
    assert warm == {"_mixed": 1, "_packed": 2}

    lens = [WARM_LEN, 7, 16, 30, 3, 21, 12, 25]
    reqs = _requests(lens, seed=9)
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        report = server.serve(reqs)
    finally:
        FLIGHT.disarm()
    recs = FLIGHT.snapshot()["records"]
    FLIGHT.clear()
    assert _programs(server) == warm

    for res in report.results:
        req = reqs[res.uid]
        assert res.tokens == _single_stream(
            params, req.prompt, req.max_new_tokens, cache_len=64), res.uid

    # All eight are admitted on the first tick, slot i to request i, so
    # FIFO order is slot order: every plan is the first `group` slots
    # still prefilling.
    mixed = [r for r in recs if r["kind"] == "mixed"]
    assert mixed and any(len(r["chunk_plan"]) == group for r in mixed)
    left = {i: n for i, n in enumerate(lens)}
    for r in mixed:
        plan = r["chunk_plan"]
        want = sorted(left)[:group]
        assert [s for s, _, _ in plan] == want, (r["tick"], plan)
        for s, n, last in plan:
            assert n == min(CHUNK, left[s])
            left[s] -= n
            assert last == (left[s] == 0)
            if last:
                del left[s]
        assert r["chunk_group"] == group
        assert r["rows_computed"] == group * r["tq"] + SLOTS
        assert r["rows_useful"] == r["chunk_tokens"] + r["occupancy"]
    assert not left
    for r in recs:
        if r["kind"] == "decode":
            assert r["chunk_group"] == 0 and r["rows_computed"] == SLOTS


def test_the_default_budget_is_one_chunk(params):
    server = SlotServer(params, CFG, slots=4, cache_len=64,
                        kv_block=KV_BLOCK, prefill_chunk=CHUNK)
    assert server.prefill_budget == CHUNK and server._chunk_group == 1
    # The group never outgrows the slots, whatever the budget.
    wide = SlotServer(params, CFG, slots=2, cache_len=64,
                      kv_block=KV_BLOCK, prefill_chunk=CHUNK,
                      prefill_budget=10 * CHUNK)
    assert wide._chunk_group == 2


def test_kv_steps_are_what_the_kernels_lists_hold(params, monkeypatch):
    """The flight record's ``kv_steps_run`` / ``kv_steps_grid`` (ISSUE 37):
    the host counts, from the lengths it packs, what the paged decode
    kernels' work lists hold on the device. Each tick program's own
    operands say the truth: its slots' lengths after the resets, through
    the function the kernels build their lists with."""
    import jax.numpy as jnp

    from tree_attention_tpu.models.decode import paged_step_tokens
    from tree_attention_tpu.ops import tuning
    from tree_attention_tpu.ops.pallas_decode import paged_plan

    # One table entry a grid step: lengths of a few blocks tell steps apart.
    monkeypatch.setattr(tuning, "PAGED_STEP_ENTRIES", (1,))
    server = SlotServer(params, CFG, slots=SLOTS, cache_len=128,
                        kv_block=KV_BLOCK, prefill_chunk=CHUNK)
    truth = []

    def count(tq, lengths, table):
        step = paged_step_tokens(server.cache, CFG, tq)
        plan = paged_plan(lengths, 0, table, tq=tq,
                          entries=step // KV_BLOCK, block=KV_BLOCK)
        return np.array([int(plan.count), table.size * KV_BLOCK // step])

    mixed, packed = server._mixed, server._packed

    def spy_mixed(p, tokens, n_tok, reset, reset_val, emit, cache, *rest):
        length = jnp.where(reset, reset_val, cache.length)
        truth.append(count(tokens.shape[1], length, cache.table))
        return mixed(p, tokens, n_tok, reset, reset_val, emit, cache, *rest)

    def spy_packed(p, chunk_tok, chunk_slot, chunk_n, dec_tok, dec_n, reset,
                   reset_val, emit, cache, *rest):
        length = jnp.where(reset, reset_val, cache.length)
        truth.append(
            count(chunk_tok.shape[1], length[chunk_slot],
                  cache.table[chunk_slot])
            + count(1, length, cache.table))
        return packed(p, chunk_tok, chunk_slot, chunk_n, dec_tok, dec_n,
                      reset, reset_val, emit, cache, *rest)

    server._mixed, server._packed = spy_mixed, spy_packed
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, max_new_tokens=int(rng.integers(2, 12)),
                    prompt=rng.integers(0, CFG.vocab_size, int(n))
                    .astype(np.int32))
            for i, n in enumerate(rng.integers(3, 100, size=20))]
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        server.serve(reqs)
    finally:
        FLIGHT.disarm()
    recs = [r for r in FLIGHT.snapshot()["records"]
            if r["kind"] in ("decode", "mixed")]
    FLIGHT.clear()
    got = [(r["kv_steps_run"], r["kv_steps_grid"]) for r in recs]
    assert got == [tuple(t) for t in truth] and len(got) > 40
    # Ragged slots fill a part of the rectangle, never none of it: a slot
    # with nothing to attend to still holds one entry.
    assert all(SLOTS <= run <= grid for run, grid in got)
    assert sum(run for run, _ in got) < 0.5 * sum(g for _, g in got)
    decode = [r for r in recs if r["kind"] == "decode"]
    assert all(r["kv_steps_grid"] == SLOTS * 128 // KV_BLOCK for r in decode)


@pytest.mark.parametrize("path", ["block", "row"])
def test_pool_rows_are_what_the_device_wrote(params, monkeypatch, path):
    """The flight record's ``pool_rows_row`` / ``pool_rows_block``
    (ISSUE 39): the host counts, from the rows it packs, the token rows a
    tick program writes into the pool (x the layers that cache one) by the
    write they take. The device's truth is the pool itself: the rows of the
    K pool that a program changed. ``path`` row: a group of one row a slot
    goes through ``paged_row_write`` (interpret mode here; what a TPU's
    programs do), so a decode tick's rows all count there and a packed
    tick's chunk rows stay on the block path."""
    from tree_attention_tpu import obs
    from tree_attention_tpu.models import decode

    if path == "row":
        monkeypatch.setattr(
            decode, "pool_write_path",
            lambda tq: "row" if tq == 1 else "block")
        from tree_attention_tpu.serving import engine
        monkeypatch.setattr(engine, "pool_write_path", decode.pool_write_path)
    server = SlotServer(params, CFG, slots=SLOTS, cache_len=128,
                        kv_block=KV_BLOCK, prefill_chunk=CHUNK)
    truth = []

    def spied(program):
        def call(*args):
            cache = next(a for a in args if hasattr(a, "table"))
            before = np.asarray(cache.k[-1]).copy()
            out = program(*args)
            after = np.asarray(next(
                o for o in out if hasattr(o, "table")).k[-1])
            # (block, row) positions whose K row changed, in the last
            # layer: there a row is its whole context's, where the first
            # layer's is its token's and position's alone and a reused
            # block may be handed the very bits it held.
            truth.append(
                CFG.n_layers * int((before != after).any(axis=(1, 3)).sum()))
            return out
        return call

    server._mixed, server._packed = spied(server._mixed), \
        spied(server._packed)
    rng = np.random.default_rng(4)
    reqs = [Request(uid=i, max_new_tokens=int(rng.integers(2, 10)),
                    prompt=rng.integers(0, CFG.vocab_size, int(n))
                    .astype(np.int32))
            for i, n in enumerate(rng.integers(3, 60, size=14))]
    def counter():
        return {p: obs.REGISTRY.get(
            "serving_kv_pool_rows_written_total").labels(path=p).value()
            for p in ("row", "block")}

    FLIGHT.clear()
    FLIGHT.arm()
    obs.REGISTRY.enable()
    try:
        before = counter()      # another test of this process may have served
        server.serve(reqs)
        counted = {p: n - before[p] for p, n in counter().items()}
    finally:
        FLIGHT.disarm()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()
    recs = [r for r in FLIGHT.snapshot()["records"]
            if r["kind"] in ("decode", "mixed")]
    FLIGHT.clear()
    got = [r["pool_rows_row"] + r["pool_rows_block"] for r in recs]
    assert got == truth and len(got) > 20
    layers = CFG.n_layers
    for r in recs:
        if path == "block":
            assert r["pool_rows_row"] == 0
        elif r["kind"] == "decode":
            assert r["pool_rows_block"] == 0
            assert r["pool_rows_row"] == r["occupancy"] * layers
        else:
            assert r["pool_rows_row"] == r["occupancy"] * layers
            assert r["pool_rows_block"] == r["chunk_tokens"] * layers
    assert counted == {
        "row": sum(r["pool_rows_row"] for r in recs),
        "block": sum(r["pool_rows_block"] for r in recs)}
    assert (counted["row"] > 0) == (path == "row")
