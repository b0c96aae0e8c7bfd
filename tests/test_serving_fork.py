"""Copy-on-write forking tests (ISSUE 15): n>1 sampling, best-of-n,
mid-generation branching on shared KV blocks.

Five contracts, mirroring the layered design:

(a) **Allocator CoW arcs** — ``fork_shared``/``release_shared`` refcount
    full ancestor blocks between branches: first fork shares a private
    block at two owners, sibling forks add owners, the LAST release
    frees (and grows availability), and sharing a free/cached block is
    an audited error, not corruption.
(b) **Sampling** — ``sample_slots`` is exact argmax at temperature 0
    (value-identical to the legacy greedy path), honors per-slot
    temperature/top-k, and derives randomness as
    ``fold_in(request_key, stream_index)`` — the reproducibility root.
(c) **Parity** — a temperature-0 ``n = k`` family is token-for-token
    identical to k independent greedy requests, across exact/int8 ×
    single-device/compat cpu_mesh (all on the paged layout — forking is
    a paged feature); fixed-seed SAMPLED runs
    are bit-identical across two serves. Mid-generation forks
    (``fork_at`` / the ``fork(uid)`` mailbox) share the stream prefix
    and diverge after it.
(d) **Leaks** — every fork arc (family, mid-gen, cancel-before-fork,
    cancel-mid-family) drains the allocator to 0 private / 0 shared /
    0 reserved / 0 pins; a 300-event random fork/cancel property test
    hammers the interleavings.
(e) **Surfaces** — OpenAI-shaped ``n``/``best_of`` on the live HTTP
    ingress (per-index SSE events, n finishes, best-of streams only the
    winner), trace-field plumbing, and the REGISTRY/TRACER/FLIGHT-
    guarded fork telemetry.

Engines are memoized per flag shape (each instance pays its own jit
compiles) and the test configs stay tiny — the tier-1 budget rule.
"""

import http.client
import json
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu.models import TransformerConfig, init_params
from tree_attention_tpu.models.decode import sample_slots
from tree_attention_tpu.parallel import cpu_mesh
from tree_attention_tpu.serving import Request, SlotServer
from tree_attention_tpu.serving.block_pool import BlockAllocator
from tree_attention_tpu.serving.engine import (
    OUTCOME_BUDGET,
    OUTCOME_CANCELLED,
    OUTCOME_EOS,
    RequestSource,
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
    attn_block_size=4,
)
CACHE_LEN = 32
BASE_KW = dict(cache_len=CACHE_LEN, kv_block=4, prefill_chunk=4)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


_ENGINES = {}


def engine(params, **kw):
    key = tuple(sorted(kw.items()))
    if key not in _ENGINES:
        merged = dict(BASE_KW)
        merged.update(kw)
        _ENGINES[key] = SlotServer(params, CFG, **merged)
    return _ENGINES[key]


def greedy(params):
    return engine(params, slots=6, prefix_cache=True, prefix_block=4)


def sampled(params):
    return engine(params, slots=6, temperature=1.0)


def _prompt(seed, n=13):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)


def _req(uid, prompt, n_new=5, **kw):
    return Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=n_new, **kw)


def assert_drained(eng):
    lr = eng.leak_report()
    assert lr["blocks_private"] == 0, lr
    assert lr["blocks_shared"] == 0, lr
    assert lr["blocks_reserved"] == 0, lr
    assert lr["pins"] == 0, lr
    assert lr["blocks_used"] == lr["blocks_cached"], lr


# ---------------------------------------------------------------------------
# (a) allocator CoW arcs
# ---------------------------------------------------------------------------


def _allocator_with_private(n_private):
    pool = BlockAllocator(8)
    assert pool.reserve(n_private)
    return pool, [pool.alloc() for _ in range(n_private)]


def test_fork_shared_refcounts_and_last_release_frees():
    pool, (a, b) = _allocator_with_private(2)
    assert pool.fork_shared([a, b]) == [a, b]
    assert pool.shared_refs(a) == 2 and pool.shared_refs(b) == 2
    assert pool.shared_count == 2
    # A second sibling shares the same ancestors: one more owner each.
    pool.fork_shared([a, b])
    assert pool.shared_refs(a) == 3
    used0, gen0 = pool.used, pool.gen
    pool.release_shared(a)
    pool.release_shared(a)
    assert pool.shared_refs(a) == 1 and pool.used == used0
    assert pool.gen == gen0  # nothing freed yet
    pool.release_shared(a)  # the last owner
    assert pool.shared_refs(a) == 0 and pool.used == used0 - 1
    assert pool.gen > gen0  # availability grew: deferred admits retry
    for _ in range(3):
        pool.release_shared(b)
    assert pool.shared_count == 0 and pool.used == 0


def test_fork_shared_audits_ownership():
    pool, (a,) = _allocator_with_private(1)
    pool.free_private(a)
    with pytest.raises(AssertionError):
        pool.fork_shared([a])  # sharing a FREE block would double-own it
    pool2, (c,) = _allocator_with_private(1)
    pool2.publish(c)  # now radix-owned
    with pytest.raises(AssertionError):
        pool2.fork_shared([c])
    pool3, (d,) = _allocator_with_private(1)
    with pytest.raises(AssertionError):
        pool3.release_shared(d)  # never shared


# ---------------------------------------------------------------------------
# (b) sampling
# ---------------------------------------------------------------------------


def _keys(n, seed=0):
    return jax.vmap(
        lambda i: jax.random.fold_in(jax.random.PRNGKey(seed), i)
    )(jnp.arange(n))


def test_sample_slots_greedy_is_exact_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(1), (5, 32))
    tok, lp = sample_slots(
        logits, jnp.zeros((5,)), jnp.zeros((5,), jnp.int32),
        _keys(5), jnp.arange(5, dtype=jnp.int32),
    )
    assert np.array_equal(np.asarray(tok),
                          np.asarray(jnp.argmax(logits, axis=-1)))
    ref_lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    got = np.asarray(lp)
    for i in range(5):
        assert got[i] == pytest.approx(ref_lp[i, int(tok[i])])


def test_sample_slots_topk_restricts_support_and_reproduces():
    logits = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
    temp = jnp.full((4,), 0.9)
    topk = jnp.asarray([1, 3, 8, 0], jnp.int32)
    keys = _keys(4, seed=7)
    draws = set()
    for idx in range(40):
        tok, _ = sample_slots(logits, temp, topk,
                              keys, jnp.full((4,), idx, jnp.int32))
        t = np.asarray(tok)
        for i, k in enumerate((1, 3, 8, 0)):
            if k:
                allowed = np.argsort(np.asarray(logits[i]))[-k:]
                assert int(t[i]) in allowed.tolist()
        draws.add(tuple(t.tolist()))
    assert len(draws) > 1  # temperature 0.9 actually samples
    # top_k=1 is argmax even at temperature > 0
    tok, _ = sample_slots(logits, temp, jnp.full((4,), 1, jnp.int32),
                          keys, jnp.zeros((4,), jnp.int32))
    assert np.array_equal(np.asarray(tok),
                          np.asarray(jnp.argmax(logits, axis=-1)))


def test_sample_slots_randomness_is_key_and_index_only():
    """The reproducibility root: the draw depends only on (key, index) —
    not on batch position or what other slots do."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (3, 32))
    temp = jnp.full((3,), 1.0)
    topk = jnp.zeros((3,), jnp.int32)
    keys = _keys(3, seed=9)
    a, _ = sample_slots(logits, temp, topk, keys,
                        jnp.asarray([4, 5, 6], jnp.int32))
    # Same rows, same keys, same indices → same draws (twice).
    b, _ = sample_slots(logits, temp, topk, keys,
                        jnp.asarray([4, 5, 6], jnp.int32))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # Row 0 in a different batch position with the same (key, idx):
    c, _ = sample_slots(
        jnp.stack([logits[2], logits[0]]), temp[:2], topk[:2],
        jnp.stack([keys[2], keys[0]]), jnp.asarray([6, 4], jnp.int32),
    )
    assert int(c[1]) == int(a[0]) and int(c[0]) == int(a[2])


# ---------------------------------------------------------------------------
# (c) parity
# ---------------------------------------------------------------------------


def _family_vs_independent(eng, prompt, k, n_new=5):
    fam = eng.serve([_req(0, prompt, n_new=n_new, n=k)])
    assert sorted(r.index for r in fam.results) == list(range(k))
    branches = {r.index: r.tokens for r in fam.results}
    ref = eng.serve([_req(100 + j, prompt, n_new=n_new)
                     for j in range(k)])
    for r in ref.results:
        j = r.uid - 100
        assert branches[j] == r.tokens, (
            f"branch {j} diverged from an independent greedy request: "
            f"{branches[j]} != {r.tokens}"
        )
    assert_drained(eng)
    return branches


def test_greedy_family_matches_independent_exact(params):
    _family_vs_independent(greedy(params), _prompt(1), 3)


def test_greedy_family_matches_independent_unaligned_prompt(params):
    # A prompt length crossing a block boundary mid-block: the CoW tail
    # copy is exercised (plen % kv_block != 0) and parity still holds.
    _family_vs_independent(greedy(params), _prompt(2, n=10), 4)


def test_greedy_family_matches_independent_int8(params):
    eng = engine(params, slots=5, quantize=True)
    _family_vs_independent(eng, _prompt(3), 3)


def test_greedy_family_mesh_parity(params):
    """The family on a compat cpu_mesh reproduces the single-device
    branches token-for-token, exact and int8."""
    mesh = cpu_mesh(2)
    prompt = _prompt(5)
    single = _family_vs_independent(greedy(params), prompt, 2)
    m_exact = SlotServer(params, CFG, slots=4, mesh=mesh, **BASE_KW)
    got = m_exact.serve([_req(0, prompt, n_new=5, n=2)])
    assert {r.index: r.tokens for r in got.results} == single
    assert_drained(m_exact)
    single_q = _family_vs_independent(
        engine(params, slots=5, quantize=True), prompt, 2
    )
    m_q = SlotServer(params, CFG, slots=4, mesh=mesh, quantize=True,
                     **BASE_KW)
    got_q = m_q.serve([_req(0, prompt, n_new=5, n=2)])
    assert {r.index: r.tokens for r in got_q.results} == single_q
    assert_drained(m_q)


def test_family_prefix_hit_parity_and_pins(params):
    """A family whose prompt is already radix-published forks on top of
    CACHED ancestors (repin, not CoW) — parity holds and every branch's
    pins release at retire."""
    eng = greedy(params)
    prompt = _prompt(6, n=12)
    eng.serve([_req(50, prompt, n_new=3)])  # publish the prompt
    _family_vs_independent(eng, prompt, 3)


def test_sampled_family_reproducible_and_diverse(params):
    eng = sampled(params)
    prompt = _prompt(7)
    r1 = eng.serve([_req(0, prompt, n_new=6, n=4)])
    b1 = {r.index: tuple(r.tokens) for r in r1.results}
    r2 = eng.serve([_req(0, prompt, n_new=6, n=4)])
    b2 = {r.index: tuple(r.tokens) for r in r2.results}
    assert b1 == b2, "fixed-seed sampled family not bit-reproducible"
    assert len(set(b1.values())) >= 2, (
        "sampled siblings never diverged — per-branch keys broken"
    )
    for r in r1.results:
        assert r.cum_logprob < 0.0  # real model logprobs accumulated
    assert_drained(eng)


def test_request_seed_pins_the_stream(params):
    """Two different uids with the same explicit seed sample the same
    stream; without a seed, uid salts the key and they differ."""
    eng = sampled(params)
    prompt = _prompt(8)
    rep = eng.serve([
        _req(0, prompt, n_new=6, seed=42),
        _req(1, prompt, n_new=6, seed=42),
        _req(2, prompt, n_new=6),
    ])
    toks = {r.uid: r.tokens for r in rep.results}
    assert toks[0] == toks[1]
    assert toks[2] != toks[0]
    assert_drained(eng)


def test_per_request_temperature_zero_is_greedy(params):
    """temperature=0 on a sampling engine rides the exact argmax path —
    identical tokens to the greedy engine's."""
    eng = sampled(params)
    prompt = _prompt(9)
    got = eng.serve([_req(0, prompt, n_new=5, temperature=0.0)])
    ref = greedy(params).serve([_req(1, prompt, n_new=5)])
    assert got.results[0].tokens == ref.results[0].tokens


def test_fork_at_branches_share_prefix_then_diverge(params):
    eng = sampled(params)
    prompt = _prompt(10)
    rep = eng.serve([_req(0, prompt, n_new=8, fork_at=3)])
    res = {r.index: r.tokens for r in rep.results}
    assert sorted(res) == [0, 1]
    assert res[0][:3] == res[1][:3], "fork did not share the prefix"
    assert res[0] != res[1], "fork branches never diverged"
    assert rep.kv["forks"] == 1
    assert_drained(eng)


def test_fork_mailbox_unknown_uid_ages_out(params):
    eng = greedy(params)
    eng.fork(987654)  # nothing live with this uid — must age out
    rep = eng.serve([_req(0, _prompt(11), n_new=4)])
    assert rep.results[0].outcome == OUTCOME_BUDGET
    assert not eng._fork_carry
    assert_drained(eng)


def test_fork_issued_while_prefilling_waits_until_live(params):
    """A fork aimed at a request still queued/prefilling must WAIT (at
    full carry) until the request goes live — not burn its scarcity
    retries and expire while a long prompt chunks through."""
    eng = greedy(params)
    eng.fork(0)  # lands in the mailbox before the request even admits
    rep = eng.serve([_req(0, _prompt(21, n=24), n_new=6)])
    res = {r.index: r.tokens for r in rep.results}
    assert sorted(res) == [0, 1], res
    assert res[0] == res[1]  # greedy branches stay identical
    assert not eng._fork_carry
    assert_drained(eng)


def test_best_of_streams_only_the_winner(params):
    eng = sampled(params)
    prompt = _prompt(12)
    got = {"tok": [], "fin": []}
    rep = eng.serve([_req(
        0, prompt, n_new=5, best_of=3,
        on_branch_token=lambda i, t: got["tok"].append((i, t)),
        on_branch_finish=lambda i, r: got["fin"].append((i, r)),
    )])
    assert len(rep.results) == 3  # the report keeps every branch
    assert len(got["fin"]) == 1 and got["fin"][0][0] == 0
    winner = got["fin"][0][1]
    best = max(rep.results, key=lambda r: (r.cum_logprob, -r.index))
    assert winner.tokens == best.tokens
    assert [t for _, t in got["tok"]] == winner.tokens
    assert all(i == 0 for i, _ in got["tok"])  # winner streams as idx 0
    assert_drained(eng)


def test_validation_rejects_unforkable_shapes(params):
    eng = greedy(params)
    with pytest.raises(ValueError, match="n must be >= 1"):
        eng.serve([_req(0, _prompt(13), n=0)])
    with pytest.raises(ValueError, match="requires n == 1"):
        eng.serve([_req(0, _prompt(13), n=2, best_of=3)])
    with pytest.raises(ValueError, match="exceed the engine"):
        eng.serve([_req(0, _prompt(13), n=eng.slots + 1)])
    with pytest.raises(ValueError, match="fork_at must be >= 1"):
        eng.serve([_req(0, _prompt(13), fork_at=0)])
    # The disaggregated pair's workers reject families via _fork_ok.
    eng2 = engine(params, slots=4, temperature=0.5)
    eng2._fork_ok = False
    try:
        with pytest.raises(ValueError, match="not supported on this"):
            eng2.serve([_req(0, _prompt(13), n=2)])
    finally:
        eng2._fork_ok = True


def test_spec_engine_rejects_fork_allows_sampling(params):
    eng = engine(params, slots=2, speculate=True, draft_k=3)
    with pytest.raises(ValueError, match="speculate"):
        eng.serve([_req(0, _prompt(14), n=2)])
    # The pure-argmax restriction is LIFTED (ISSUE 20): sampled serving
    # under speculation walks the stochastic accept path.
    rep = eng.serve([_req(0, _prompt(14), n_new=4, temperature=0.7)])
    assert rep.results[0].outcome == OUTCOME_BUDGET
    assert len(rep.results[0].tokens) == 4


# ---------------------------------------------------------------------------
# (d) leaks
# ---------------------------------------------------------------------------


class ScriptedSource(RequestSource):
    """Deterministic driver: arrivals by tick plus cancel/fork actions
    through the engine's thread-safe mailboxes."""

    def __init__(self, eng, arrivals, cancels=None, forks=None):
        self.eng = eng
        self._arr = sorted(arrivals, key=lambda r: (r.arrival_tick, r.uid))
        self._pos = 0
        self._cancels = dict(cancels or {})
        self._forks = dict(forks or {})

    def poll(self, tick):
        for t in sorted(k for k in self._cancels if k <= tick):
            for uid in self._cancels.pop(t):
                self.eng.cancel(uid)
        for t in sorted(k for k in self._forks if k <= tick):
            for uid in self._forks.pop(t):
                self.eng.fork(uid)
        out = []
        while (self._pos < len(self._arr)
               and self._arr[self._pos].arrival_tick <= tick):
            out.append(self._arr[self._pos])
            self._pos += 1
        return out

    def next_arrival(self):
        ticks = []
        if self._pos < len(self._arr):
            ticks.append(self._arr[self._pos].arrival_tick)
        ticks.extend(self._cancels)
        ticks.extend(self._forks)
        return min(ticks) if ticks else None

    @property
    def exhausted(self):
        return (self._pos >= len(self._arr) and not self._cancels
                and not self._forks)


def test_cancel_before_family_forks_releases_everything(params):
    """Cancel the parent while its family is still prefilling: the
    fpend sibling slots free, the family block hold unreserves, and
    every requested completion still gets a result."""
    eng = greedy(params)
    long_prompt = _prompt(15, n=24)
    req = _req(0, long_prompt, n_new=4, n=3)
    src = ScriptedSource(eng, [req], cancels={1: [0]})
    rep = eng.serve(src, max_ticks=500)
    assert len(rep.results) == 3
    assert {r.outcome for r in rep.results} == {OUTCOME_CANCELLED}
    assert sorted(r.index for r in rep.results) == [0, 1, 2]
    assert not eng._families
    assert all(st == "free" for st in eng._slot_state)
    assert_drained(eng)


def test_cancel_mid_family_retires_every_branch(params):
    """A cancel landing while all branches decode kills the whole
    family (one uid = one client connection) leak-free."""
    eng = greedy(params)
    req = _req(0, _prompt(16), n_new=12, n=3)
    src = ScriptedSource(eng, [req], cancels={6: [0]})
    rep = eng.serve(src, max_ticks=500)
    assert len(rep.results) == 3
    assert all(r.outcome in (OUTCOME_CANCELLED, OUTCOME_EOS,
                             OUTCOME_BUDGET) for r in rep.results)
    assert rep.outcomes.get(OUTCOME_CANCELLED, 0) >= 1
    assert_drained(eng)


def test_property_random_fork_join_cancel_drains_clean(params):
    """The ISSUE-15 leak gate: 300 random events — family admissions
    (n up to 3, occasional best_of), plain requests with fork_at
    self-branches, mailboxed fork(uid)s aimed at anything, cancels
    aimed at anything — then drain to 0 private / 0 shared / 0
    reserved / 0 pins."""
    eng = greedy(params)
    prng = np.random.default_rng(4321)
    arrivals, cancels, forks = [], {}, {}
    uid, tick = 0, 0
    for _ in range(300):
        r = prng.random()
        tick += int(prng.integers(0, 3))
        if r < 0.5 or uid == 0:
            kw = {}
            style = prng.random()
            if style < 0.35:
                kw["n"] = int(prng.integers(2, 4))
            elif style < 0.5:
                kw["best_of"] = int(prng.integers(2, 4))
            elif style < 0.7:
                kw["fork_at"] = int(prng.integers(1, 4))
            arrivals.append(_req(
                uid,
                prng.integers(0, 128,
                              size=int(prng.integers(2, 14)))
                .astype(np.int32),
                n_new=int(prng.integers(2, 7)),
                arrival_tick=tick, **kw,
            ))
            uid += 1
        elif r < 0.8:
            victim = int(prng.integers(0, uid + 3))
            cancels.setdefault(tick, []).append(victim)
        else:
            victim = int(prng.integers(0, uid + 3))
            forks.setdefault(tick, []).append(victim)
    rep = eng.serve(ScriptedSource(eng, arrivals, cancels, forks),
                    max_ticks=40_000)
    uids = sorted(set(r.uid for r in rep.results))
    assert uids == list(range(uid))
    assert rep.outcomes.get(OUTCOME_CANCELLED, 0) > 0  # chaos happened
    assert not eng._families and not eng._fork_carry
    assert_drained(eng)


# ---------------------------------------------------------------------------
# (e) surfaces: traces, telemetry, HTTP
# ---------------------------------------------------------------------------


def test_trace_fields_plumb_through():
    reqs = synthetic_trace(3, prompt_len=8, max_new_tokens=4,
                           n=2, best_of=0, fork_at=2)
    assert all(r.n == 2 and r.best_of is None and r.fork_at == 2
               for r in reqs)
    reqs = synthetic_trace(2, prompt_len=8, max_new_tokens=4, best_of=3)
    assert all(r.n == 1 and r.best_of == 3 for r in reqs)
    from tree_attention_tpu.bench.serving import heavy_tail_trace

    events = heavy_tail_trace(4, cache_len=64, n=2, fork_at=1, seed=3)
    assert all(e["n"] == 2 and e["fork_at"] == 1 for e in events)
    assert all("best_of" not in e for e in events)
    events = heavy_tail_trace(2, cache_len=64, best_of=2, seed=3)
    assert all(e["best_of"] == 2 for e in events)


def test_fork_telemetry_counters_flight_and_instants(params, tmp_path):
    from tree_attention_tpu import obs
    from tree_attention_tpu.obs.flight import FLIGHT

    eng = greedy(params)
    trace_file = tmp_path / "trace.jsonl"
    obs.enable()
    obs.TRACER.start(str(trace_file))
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        reg = obs.REGISTRY
        forks0 = reg.counter("serving_forks_total").value()
        shared0 = reg.counter("serving_fork_blocks_shared_total").value()
        eng.serve([_req(0, _prompt(17), n_new=4, n=3)])
        assert reg.counter("serving_forks_total").value() - forks0 == 2
        assert reg.counter(
            "serving_fork_blocks_shared_total").value() - shared0 >= 2
        recs = FLIGHT.snapshot()["records"]
        assert {"forks", "shared_blocks"} <= set(recs[0])
        assert sum(r["forks"] for r in recs) == 2
        assert sum(r["shared_blocks"] for r in recs) >= 2
    finally:
        obs.disable()
        obs.TRACER.close()
        FLIGHT.disarm()
        FLIGHT.clear()
    events = [json.loads(line)
              for line in open(trace_file) if line.strip()]
    fork_events = [e for e in events
                   if e["ph"] == "i" and e["name"] == "fork"]
    assert len(fork_events) == 2
    assert {e["args"]["index"] for e in fork_events} == {1, 2}
    assert all(e["args"]["shared_blocks"] >= 1 for e in fork_events)


@pytest.fixture(scope="module")
def live(params):
    from tree_attention_tpu.serving.ingress import IngressServer

    eng = SlotServer(params, CFG, slots=6, temperature=0.8, seed=5,
                     **BASE_KW)
    srv = IngressServer(eng, max_queue=8, default_max_tokens=4,
                        keepalive_s=0.05)
    srv.start()
    yield srv
    if srv.running:
        srv.stop()


def _post(port, body, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def _read_sse_indexed(resp):
    tokens, finishes = {}, {}
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        if line[6:] == b"[DONE]":
            break
        ch = json.loads(line[6:])["choices"][0]
        idx = ch["index"]
        tokens.setdefault(idx, []).extend(ch["token_ids"])
        if ch["finish_reason"] is not None:
            finishes[idx] = ch["finish_reason"]
    return tokens, finishes


def _settled(eng, timeout=15.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        lr = eng.leak_report()
        if (eng.all_slots_free and lr["blocks_private"] == 0
                and lr["blocks_shared"] == 0
                and lr["blocks_reserved"] == 0 and lr["pins"] == 0):
            return True
        time.sleep(0.05)
    return False


def test_http_n3_streams_indexed_branches(params, live):
    prompt = [int(t) for t in _prompt(18)]
    conn, resp = _post(live.port, {
        "prompt": prompt, "max_tokens": 4, "n": 3, "seed": 7,
    })
    assert resp.status == 200
    tokens, finishes = _read_sse_indexed(resp)
    conn.close()
    assert sorted(tokens) == [0, 1, 2]
    assert sorted(finishes) == [0, 1, 2]
    assert all(len(t) == 4 for t in tokens.values())
    assert all(f == "length" for f in finishes.values())
    # Same seed → bit-identical on a re-POST (the wire-level
    # reproducibility contract).
    conn, resp = _post(live.port, {
        "prompt": prompt, "max_tokens": 4, "n": 3, "seed": 7,
    })
    tokens2, _ = _read_sse_indexed(resp)
    conn.close()
    assert tokens2 == tokens
    assert _settled(live.engine)


def test_http_best_of_streams_one_winner(params, live):
    prompt = [int(t) for t in _prompt(19)]
    conn, resp = _post(live.port, {
        "prompt": prompt, "max_tokens": 4, "best_of": 3, "seed": 8,
    })
    assert resp.status == 200
    tokens, finishes = _read_sse_indexed(resp)
    conn.close()
    assert sorted(tokens) == [0] and sorted(finishes) == [0]
    assert len(tokens[0]) == 4
    assert _settled(live.engine)


def test_http_whole_body_n2_choices(params, live):
    prompt = [int(t) for t in _prompt(20)]
    conn, resp = _post(live.port, {
        "prompt": prompt, "max_tokens": 3, "n": 2, "stream": False,
        "temperature": 0.0,
    })
    assert resp.status == 200
    body = json.loads(resp.read())
    conn.close()
    assert [c["index"] for c in body["choices"]] == [0, 1]
    # temperature 0: both branches are the same greedy stream.
    assert body["choices"][0]["token_ids"] == body["choices"][1]["token_ids"]
    assert body["usage"]["completion_tokens"] == 6
    assert _settled(live.engine)


def test_http_rejects_bad_fork_fields(params, live):
    prompt = [1, 2, 3]
    for bad in ({"n": 0}, {"n": "x"}, {"best_of": 0},
                {"temperature": -1.0}, {"n": 2, "best_of": 3}):
        conn, resp = _post(live.port, {
            "prompt": prompt, "max_tokens": 2, **bad,
        })
        assert resp.status in (400, 200), bad
        if resp.status == 200:
            # engine-side validation (n with best_of) finishes the
            # stream with an error frame instead of a 400.
            _, finishes = _read_sse_indexed(resp)
            assert finishes.get(0) == "error", (bad, finishes)
        conn.close()
    assert _settled(live.engine)
