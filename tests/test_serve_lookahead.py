"""The serve loop dispatches tick t+1 before it fetches tick t (ISSUE 32).

``SlotServer.serve`` defers a program's tail (fetch, emit, account, flight
record) by one tick whenever the next program can be planned from counts
alone, so the host's part of a tick and the fetch's round trip run under
the device's work. These cases hold the look-ahead to the synchronous
order it replaced (``_lookahead = False``, a private switch no option
reaches): the same tokens, logprobs, outcomes and prefix hits; a row
dispatched for a request that has since left is thrown away; every operand
of a program in flight is a snapshot; a flight record describes one
program; and the loop drains the pending tail before it idles or raises.

CPU toy engines, memoized per flag shape (each instance pays its own jit
compiles): the whole file stays under ~40 s.
"""

import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmark import ticks as bench_ticks
from tree_attention_tpu.models import TransformerConfig, init_params
from tree_attention_tpu.models.transformer import model_from_config
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.serving import Request, SlotServer
from tree_attention_tpu.serving.engine import (
    OUTCOME_BUDGET,
    OUTCOME_CANCELLED,
    OUTCOME_DEADLINE,
    OUTCOME_EOS,
)

CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, max_seq_len=256, dtype=jnp.float32,
    attn_impl="blockwise", attn_block_size=4,
)
BLOCK = 4
# A pool the radix tree fills: three slots' worst case is 21 blocks, so
# every later admission evicts what earlier prompts published.
BASE_KW = dict(slots=3, cache_len=32, kv_block=BLOCK, prefill_chunk=BLOCK,
               kv_blocks=24, prefix_cache=True, prefix_block=BLOCK)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


_ENGINES = {}


def engine(params, lookahead=True, **kw):
    """One engine a flag shape and order; ``lookahead=False`` is the
    synchronous order, reached through the private switch."""
    key = (lookahead,) + tuple(sorted(kw.items()))
    if key not in _ENGINES:
        server = SlotServer(params, CFG, **{**BASE_KW, **kw})
        server._lookahead = lookahead
        _ENGINES[key] = server
    return _ENGINES[key]


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=n).astype(np.int32)


def _recorded(server, reqs, **kw):
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        report = server.serve(reqs, **kw)
    finally:
        FLIGHT.disarm()
    recs = [r for r in FLIGHT.snapshot()["records"] if "t_s" in r]
    FLIGHT.clear()
    return report, recs


def _forget_prefixes(server):
    """A drained engine's radix tree, emptied: the next trace starts cold."""
    while server._prefix.evict_one():
        pass


def assert_drained(server):
    lr = server.leak_report()
    assert lr["blocks_private"] == 0 and lr["blocks_reserved"] == 0, lr
    assert lr["blocks_shared"] == 0 and lr["pins"] == 0, lr
    assert lr["blocks_used"] == lr["blocks_cached"], lr
    assert server._tail is None


# -- (a) the same streams as the synchronous order ---------------------------


def _trace(sampling, eos=None):
    """Chunked admissions between decode ticks (8 requests through 3
    slots), budget ends, a one-token request, a prompt served twice (the
    second is a prefix hit on the blocks the first one's thrown-away row
    reads and writes beside) and, with ``eos``, two streams that end
    mid-way."""
    kw = dict(temperature=0.9, top_k=12) if sampling else {}
    shape = [(17, 6), (13, 1), (18, 8), (17, 5), (18, 7), (14, 4), (9, 6),
             (16, 3)]
    reqs = []
    for uid, (plen, n_new) in enumerate(shape):
        # Request 3 repeats request 0's prompt: 16 of its 17 tokens hit.
        seed = 40 + (0 if uid == 3 else uid)
        reqs.append(Request(
            uid=uid, prompt=_prompt(seed, plen), max_new_tokens=n_new,
            eos_id=None if eos is None else eos.get(uid),
            seed=None if not sampling else 7 * uid + 1, **kw))
    return reqs


def _streams(report):
    return {(r.uid, r.index): (r.tokens, r.outcome, r.prefix_hit_tokens,
                               round(r.cum_logprob, 5))
            for r in report.results}


@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "temperature-topk"])
def test_streams_equal_the_synchronous_order(params, sampling):
    sync = engine(params, lookahead=False)
    ahead = engine(params)
    # Where two streams end: their third token becomes their EOS.
    probe = {r.uid: r.tokens for r in sync.serve(_trace(sampling)).results}
    eos = {0: probe[0][2], 6: probe[6][2]}
    want = {uid: probe[uid][:probe[uid].index(tok) + 1]
            for uid, tok in eos.items()}
    _forget_prefixes(sync)
    _forget_prefixes(ahead)
    rep_s = sync.serve(_trace(sampling, eos))
    rep_a, recs = _recorded(ahead, _trace(sampling, eos))
    _forget_prefixes(sync)
    _forget_prefixes(ahead)

    assert _streams(rep_a) == _streams(rep_s)
    got = {r.uid: r for r in rep_a.results}
    for uid, toks in want.items():
        # No token after an EOS, although a row was already out for one.
        assert got[uid].tokens == toks and got[uid].outcome == OUTCOME_EOS
    assert got[1].tokens == probe[1] and len(got[1].tokens) == 1
    assert {r.outcome for r in rep_a.results} == {OUTCOME_EOS,
                                                  OUTCOME_BUDGET}
    assert got[3].prefix_hit_tokens == 16
    for key in ("hits", "misses", "tokens_reused"):
        assert rep_a.prefix[key] == rep_s.prefix[key], key
    assert rep_a.prefix["evictions"] > 0          # the pool was full
    assert rep_a.tokens_generated == rep_s.tokens_generated
    # finish_tick and admit_tick may read one later, nothing else differs.
    for a, s in zip(rep_a.results, rep_s.results):
        assert 0 <= a.finish_tick - s.finish_tick <= len(rep_a.results)
        assert a.admit_tick >= s.admit_tick
    # It engaged: all but the first program went out ahead.
    assert [r["ahead"] for r in recs] == [False] + [True] * (len(recs) - 1)
    assert_drained(ahead)
    assert_drained(sync)


# -- (b) a row in flight for a request that leaves ---------------------------


@pytest.mark.parametrize("how, outcome", [
    ("cancel", OUTCOME_CANCELLED), ("deadline", OUTCOME_DEADLINE)])
def test_a_request_that_leaves_with_a_row_in_flight(params, how, outcome,
                                                    monkeypatch):
    """The client goes (or its deadline passes) as its third token
    arrives; the sweep of the next tick retires the slot while a program
    holds a row for it. Outcome, tokens and the block ledger as in the
    synchronous order; the row is thrown away."""
    results = {}
    for lookahead in (False, True):
        server = engine(params, lookahead=lookahead)
        used0 = server._pool.used
        flying = []
        retire = server._retire

        def spy(slot, tick, why, res, server=server, retire=retire):
            tail = server._tail
            flying.append((why, tail is not None
                           and tail.flying(slot, server._slot_req[slot])))
            return retire(slot, tick, why, res)

        monkeypatch.setattr(server, "_retire", spy)
        reqs = [Request(uid=i, prompt=_prompt(60 + i, 10 + i),
                        max_new_tokens=8) for i in range(3)]
        seen = []

        def on_token(tok, server=server, seen=seen, victim=reqs[1]):
            seen.append(tok)
            if len(seen) == 3:
                if how == "cancel":
                    server.cancel(victim.uid)
                else:
                    victim.deadline_s = 0.0   # long past, from now on
        reqs[1].on_token = on_token
        report = server.serve(reqs)
        _forget_prefixes(server)
        assert server._pool.used == used0 == 0
        assert_drained(server)
        results[lookahead] = _streams(report)
        assert (outcome, lookahead) in flying
        got = {r.uid: r for r in report.results}
        assert got[1].outcome == outcome and got[1].tokens == seen
        assert len(seen) == 3                 # nothing after it left
    assert results[True] == results[False]


# -- (c) the order of dispatch and fetch -------------------------------------


class _Fetch:
    """Stands in for a program's fetch vehicle: says when the host reads."""

    def __init__(self, arr, log, n):
        self.arr, self.log, self.n = arr, log, n

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.n))
        return np.asarray(self.arr)


def _wrapped(server, monkeypatch, operands=None):
    """Wrap the two tick programs: log each dispatch and each fetch."""
    log = []

    def wrap(name, kind):
        fn = getattr(server, name)

        def call(*args):
            n = sum(1 for what, _ in log if what != "fetch")
            log.append((kind, n))
            if operands is not None:
                operands.append(args)
            tok, lp, fused, last, cache = fn(*args)
            return tok, lp, _Fetch(fused, log, n), last, cache

        monkeypatch.setattr(server, name, call)

    wrap("_mixed", "decode")
    wrap("_packed", "packed")
    return log


def _mixed_trace(n=4):
    return [Request(uid=i, prompt=_prompt(70 + i, 9 + 2 * i),
                    max_new_tokens=4 + i) for i in range(n)]


def test_dispatch_runs_ahead_of_the_fetch_before_it(params, monkeypatch):
    server = engine(params)
    log = _wrapped(server, monkeypatch)
    report, recs = _recorded(server, _mixed_trace())
    _forget_prefixes(server)
    at = {ev: i for i, ev in enumerate(log)}
    kinds = {n: kind for kind, n in log if kind != "fetch"}
    assert len(kinds) == report.ticks == len(recs)
    assert {"decode", "packed"} <= set(kinds.values())
    ahead_after = set()
    for n in range(1, len(kinds)):
        if ("fetch", n - 1) not in at:
            continue                          # chunks only: nothing to fetch
        # Program n is out before the host waits for program n - 1.
        assert at[(kinds[n], n)] < at[("fetch", n - 1)]
        ahead_after.add(kinds[n - 1])
        if ("fetch", n) in at:
            assert at[("fetch", n - 1)] < at[("fetch", n)]
    assert ahead_after == {"decode", "packed"}

    sync = engine(params, lookahead=False)
    log = _wrapped(sync, monkeypatch)
    _, recs = _recorded(sync, _mixed_trace())
    _forget_prefixes(sync)
    order = [ev for ev in log if ev[0] == "fetch" or ("fetch", ev[1]) in log]
    assert [what == "fetch" for what, _ in order] \
        == [False, True] * (len(order) // 2)
    assert not any(r["ahead"] for r in recs)
    assert [r["sync_reason"] for r in recs] \
        == ["first"] + ["drain"] * (len(recs) - 1)


@pytest.mark.parametrize("kw, req_kw, reasons, some_ahead", [
    (dict(speculate=True, draft_k=3, prefix_cache=False), {}, {"spec"},
     False),
    (dict(temperature=1.0, prefix_cache=False), dict(n=2), {"tree"}, True),
    (dict(temperature=1.0, tree_sampling=False, prefix_cache=False),
     dict(n=2), {"fork"}, True),
    (dict(quantize=True, prefix_cache=False), {}, {"staged"}, True),
], ids=["speculation", "tree-family", "fork-family", "staged-int8"])
def test_ticks_that_need_token_values_stay_synchronous(
        params, kw, req_kw, reasons, some_ahead):
    """Which ticks look ahead follows from the engine's own state: a tick
    whose plan reads token values lands the pending tail first and says
    why in its record; the plain ticks round it still run ahead."""
    server = engine(params, **kw)
    reqs = [Request(uid=0, prompt=_prompt(80, 9), max_new_tokens=4,
                    **req_kw),
            Request(uid=1, prompt=_prompt(81, 7), max_new_tokens=6,
                    arrival_tick=2)]
    report, recs = _recorded(server, reqs)
    assert len(recs) == report.ticks
    said = {r["sync_reason"] for r in recs if not r["ahead"]}
    assert reasons <= said <= reasons | {"first", "drain"}
    assert any(r["ahead"] for r in recs) == some_ahead
    for r in recs:
        if r["kind"] in ("staged", "verify"):
            assert not r["ahead"]
        assert ("sync_reason" in r) != r["ahead"]
    assert sum(r["tokens_emitted"] for r in recs) == report.tokens_generated
    assert server._tail is None


# -- (d) every operand of a program in flight is a snapshot ------------------


def test_arrays_written_after_a_dispatch_do_not_reach_it(params,
                                                          monkeypatch):
    """``jnp.asarray`` of a numpy array may alias it on the CPU backend.
    With two programs in flight the host writes its table and its
    per-request vectors (a retire, an admission) before the device has
    read them: what was uploaded must be a copy."""
    server = engine(params)
    operands = []
    _wrapped(server, monkeypatch, operands)
    host = [server._host_table, server._temp_np, server._topk_np,
            server._salt_np]
    seen = []

    def holds(dev):
        """``dev`` keeps its values whatever the host writes next."""
        view = np.asarray(dev)
        assert not any(np.shares_memory(view, h) for h in host)
        before = view.copy()
        saved = [h.copy() for h in host]
        for h in host:                        # a retire, an admission
            h[...] = 1
        np.testing.assert_array_equal(np.asarray(dev), before)
        for h, was in zip(host, saved):
            h[...] = was
        seen.append(dev.shape)

    sync_table = server._sync_table

    def spy_table():
        dirty = server._table_dirty
        sync_table()
        if dirty:                     # the pool is donated: look now
            holds(server.cache.table)

    monkeypatch.setattr(server, "_sync_table", spy_table)
    server.serve(_mixed_trace(3))
    _forget_prefixes(server)
    tables = len(seen)
    for args in operands:
        for a in args:
            if isinstance(a, jax.Array) and not a.is_deleted() \
                    and a.shape == (server.slots,):
                holds(a)
    assert tables > 0 and len(seen) > tables + 4 * len(operands)


# -- (e) a flight record describes one program --------------------------------


MOE = {
    "family": "deepseek_mla_moe", "model_type": "deepseek_v2",
    "hidden_size": 32, "intermediate_size": 64, "kv_lora_rank": 16,
    "q_lora_rank": 24, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 2,
    "topk_group": 1, "topk_method": "group_limited_greedy",
    "routed_scaling_factor": 1, "norm_topk_prob": False,
    "scoring_func": "softmax", "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "num_hidden_layers": 2, "vocab_size": 64,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "torch_dtype": "float32",
    "deployment": {"experts_total": 4, "expert_share": 0},
}


def test_a_record_holds_its_own_programs_numbers(monkeypatch):
    """An expert model's record carries the router's counts off the
    program's own fetch: ``routed_rows`` is the rows that carried a token
    in THAT program (a chunk's included) times the expert layers, so a
    mixed program's counts can never stand in a decode record. The
    records are in dispatch order, ``t_s`` rises, and the benchmark's
    spans are one a program."""
    tcfg = model_from_config(MOE, max_seq_len=64)
    server = SlotServer(init_params(jax.random.PRNGKey(1), tcfg), tcfg,
                        slots=2, cache_len=32, kv_block=8, prefill_chunk=8,
                        prefix_cache=True, prefix_block=8)
    operands = []
    log = _wrapped(server, monkeypatch, operands)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, max_new_tokens=3 + i,
                    prompt=rng.integers(0, 64, size=8 + 3 * i)
                    .astype(np.int32)) for i in range(3)]
    emitted = []
    for r in reqs:
        r.on_token = lambda tok, log=log: emitted.append(len(log))
    report, recs = _recorded(server, reqs)
    programs = [(kind, n) for kind, n in log if kind != "fetch"]
    assert len(recs) == len(programs) == report.ticks
    assert [r["tick"] for r in recs] == list(range(len(recs)))
    layers = tcfg.n_layers - 1                          # layer 0 is dense
    fetches = [i for i, ev in enumerate(log) if ev[0] == "fetch"]
    for rec, (kind, n), args in zip(recs, programs, operands):
        if kind == "packed":
            chunk_n, dec_n = np.asarray(args[3]), np.asarray(args[5])
            assert rec["kind"] == "mixed"
            assert rec["chunk_tokens"] == int(chunk_n.sum()) > 0
        else:
            chunk_n, dec_n = np.zeros(1, int), np.asarray(args[2])
            assert rec["kind"] == "decode" and rec["chunk_tokens"] == 0
        rows = int(chunk_n.sum() + dec_n.sum())
        assert rec["rows_useful"] == rows
        assert rec["occupancy"] == int(dec_n.sum())
        if rec["host_sync"]:
            assert rec["routed_rows"] == rows * layers
            assert rec["routed_pairs"] == rows * layers * 2
            # The tokens between this program's fetch and the next one's.
            at = log.index(("fetch", n))
            nxt = min([f for f in fetches if f > at], default=len(log))
            assert rec["tokens_emitted"] == sum(
                1 for e in emitted if at < e <= nxt)
        else:
            assert "routed_rows" not in rec and rec["tokens_emitted"] == 0
    stamps = [r["t_s"] for r in recs]
    assert stamps == sorted(set(stamps))
    spans = bench_ticks.spans(recs, 0.0, float("inf"))
    assert len(spans) == len(recs) - 1       # the last has no next stamp
    assert [s[2] for s in spans] == [r["chunk_tokens"] for r in recs[:-1]]
    assert [s[3] for s in spans] == [r["occupancy"] for r in recs[:-1]]
    assert sum(r["tokens_emitted"] for r in recs) == report.tokens_generated


# -- (f) the pending tail is drained ------------------------------------------


def test_the_tail_lands_before_the_loop_ends_drains_or_raises(
        params, tmp_path):
    server = engine(params)
    # Exhaustion: the last program's tokens and its record are there.
    report, recs = _recorded(server, _mixed_trace(2))
    _forget_prefixes(server)
    assert len(recs) == report.ticks and recs[-1]["tokens_emitted"] > 0
    assert all(r.outcome == OUTCOME_BUDGET and
               len(r.tokens) == 4 + r.uid for r in report.results)
    assert_drained(server)

    # Drain: asked for as a token arrives, with a program in flight. The
    # queue is shed, what is in its slot runs to its end.
    reqs = _mixed_trace(4)
    reqs[0].on_token = lambda tok: server.request_drain()
    report, recs = _recorded(server, reqs)
    _forget_prefixes(server)
    got = {r.uid: r for r in report.results}
    assert got[0].outcome == OUTCOME_BUDGET and len(got[0].tokens) == 4
    assert got[3].outcome == "shed" and any(r["draining"] for r in recs)
    assert len(recs) == report.ticks
    assert_drained(server)

    # An error at the loop's top, a program in flight: its tail lands
    # first, so the black box holds the last program and its tokens.
    own = SlotServer(params, CFG, **BASE_KW)
    path = tmp_path / "flight.json"
    FLIGHT.clear()
    FLIGHT.arm(str(path))
    seen = []
    reqs = _mixed_trace(2)
    reqs[0].on_token = seen.append
    try:
        with pytest.raises(RuntimeError, match="max_ticks"):
            own.serve(reqs, max_ticks=5)
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
    dumped = [r for r in json.loads(path.read_text())["records"]
              if "t_s" in r]
    assert [r["tick"] for r in dumped] == [0, 1, 2, 3, 4]
    assert dumped[-1]["ahead"] and dumped[-1]["tokens_emitted"] > 0
    # Ticks 2, 3 and 4 each sampled a token for request 0; the third was
    # in flight when the loop raised.
    assert len(seen) == sum(r["tokens_emitted"] for r in dumped) == 3
    assert own._tail is None
