"""ISSUE 10: the hardened ingress — cancellation, deadlines, drain, HTTP.

Two layers, one no-leak contract:

- **Engine layer** — the request-source loop's robustness arcs, driven
  single-threaded and deterministically by a :class:`ScriptedSource`
  (submissions, cancels, and drains keyed by tick) and by per-request
  ``on_token`` callbacks that fire mid-stream on the engine thread (the
  exact reentrancy a disconnect produces). Covers the edges the ISSUE
  names: cancel during prefill chunks, cancel mid-staging under int8,
  cancel between verify and commit under speculation, deadline expiry
  racing EOS, and a 300-event random cancel/admit property test ending
  at allocator ``used == cached`` with every radix pin released.
- **HTTP layer** — one live loopback :class:`IngressServer` (module-
  scoped; jits paid once) for SSE streaming, stream-vs-whole parity,
  429 + Retry-After backpressure, deadline shedding over the wire,
  disconnect-cancellation, and the drain lifecycle.

Frugality (the tier-1 budget): ONE tiny model config, module-scoped
params, engines memoized per flag-shape, reference streams memoized —
every fresh SlotServer pays its own jit compiles.
"""

from __future__ import annotations

import http.client
import json
import time

import numpy as np
import pytest

import jax

from tree_attention_tpu.bench.serving import serving_model_config
from tree_attention_tpu.models import init_params
from tree_attention_tpu.serving import (
    Request,
    RequestSource,
    SlotServer,
)
from tree_attention_tpu.serving.engine import (
    OUTCOME_BUDGET,
    OUTCOME_CANCELLED,
    OUTCOME_DEADLINE,
    OUTCOME_EOS,
    OUTCOME_ERROR,
    OUTCOME_SHED,
)

CFG = serving_model_config(d_model=64, vocab_size=128, max_seq_len=64)
CACHE_LEN = 64
SLOTS = 2

rng = np.random.default_rng(11)
SHORT_PROMPT = rng.integers(0, 128, size=8).astype(np.int32)
LONG_PROMPT = rng.integers(0, 128, size=40).astype(np.int32)
LOOP_PROMPT = np.tile(np.array([7, 9, 4], np.int32), 8)  # spec-friendly


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


_ENGINES = {}


def engine(params, **kw):
    """Memoized engines per flag shape — each instance pays its own jit
    compiles, so tests sharing a shape share one."""
    key = tuple(sorted(kw.items()))
    if key not in _ENGINES:
        _ENGINES[key] = SlotServer(
            params, CFG, slots=SLOTS, cache_len=CACHE_LEN,
            prefill_chunk=8, **kw,
        )
    return _ENGINES[key]


def base_engine(params):
    return engine(params, prefix_cache=True, prefix_block=16)


_REFS = {}


def ref_tokens(params, prompt, n_new, eos=None):
    """Memoized single-request greedy reference stream."""
    key = (tuple(int(t) for t in prompt), n_new, eos)
    if key not in _REFS:
        rep = base_engine(params).serve(
            [Request(uid=900, prompt=np.asarray(prompt, np.int32),
                     max_new_tokens=n_new, eos_id=eos)]
        )
        _REFS[key] = rep.results[0].tokens
    return _REFS[key]


def assert_leak_free(eng):
    lr = eng.leak_report()
    assert lr["blocks_private"] == 0, lr
    assert lr["blocks_reserved"] == 0, lr
    assert lr["pins"] == 0, lr
    assert lr["blocks_used"] == lr["blocks_cached"], lr


class ScriptedSource(RequestSource):
    """Deterministic single-threaded driver: arrivals by tick, plus
    cancel/drain actions applied at their tick through the engine's
    thread-safe mailboxes (exactly what an ingress handler thread would
    do, minus the thread)."""

    def __init__(self, eng, arrivals, cancels=None, drain_at=None):
        self.eng = eng
        self._arr = sorted(arrivals, key=lambda r: (r.arrival_tick, r.uid))
        self._pos = 0
        self._cancels = dict(cancels or {})  # tick -> [uids]
        self._drain_at = drain_at

    def poll(self, tick):
        for t in sorted(k for k in self._cancels if k <= tick):
            for uid in self._cancels.pop(t):
                self.eng.cancel(uid)
        if self._drain_at is not None and tick >= self._drain_at:
            self._drain_at = None
            self.eng.request_drain()
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
        if self._drain_at is not None:
            ticks.append(self._drain_at)
        return min(ticks) if ticks else None

    @property
    def exhausted(self):
        return (self._pos >= len(self._arr) and not self._cancels
                and self._drain_at is None)


# ---------------------------------------------------------------------------
# engine layer: cancellation


def test_cancel_mid_prefill_releases_everything(params):
    """Cancel while the victim's prompt is still chunk-prefilling: the
    slot frees, its pinned radix path releases, its paged blocks (and
    unspent worst-case reservation) return to the pool — and the engine
    keeps serving the other slot untouched."""
    # Two chunks a tick: B's one-chunk prompt finishes beside A's first.
    eng = engine(params, prefix_cache=True, prefix_block=16,
                 prefill_budget=16)
    a = Request(uid=0, prompt=LONG_PROMPT, max_new_tokens=8)
    b = Request(uid=1, prompt=SHORT_PROMPT, max_new_tokens=6,
                on_token=lambda t: eng.cancel(0))  # fires mid-A-prefill
    rep = eng.serve(ScriptedSource(eng, [a, b]))
    by_uid = {r.uid: r for r in rep.results}
    assert by_uid[0].outcome == OUTCOME_CANCELLED
    assert by_uid[0].tokens == []  # died before its first token
    assert by_uid[1].outcome == OUTCOME_BUDGET
    assert by_uid[1].tokens == ref_tokens(params, SHORT_PROMPT, 6)
    assert_leak_free(eng)
    # The engine stays serviceable after a cancellation.
    rep2 = eng.serve([Request(uid=2, prompt=SHORT_PROMPT,
                              max_new_tokens=6)])
    assert rep2.results[0].tokens == ref_tokens(params, SHORT_PROMPT, 6)
    assert_leak_free(eng)


def test_cancel_mid_decode_keeps_partial_stream(params):
    """A client that walks away after 3 tokens: the request retires
    'cancelled' having streamed exactly what the result records, and the
    partial stream is a prefix of the uncancelled reference."""
    eng = base_engine(params)
    streamed = []

    def on_tok(t):
        streamed.append(t)
        if len(streamed) == 3:
            eng.cancel(5)

    rep = eng.serve(ScriptedSource(eng, [
        Request(uid=5, prompt=SHORT_PROMPT, max_new_tokens=24,
                on_token=on_tok),
    ]))
    res = rep.results[0]
    assert res.outcome == OUTCOME_CANCELLED
    assert res.tokens == streamed
    assert 3 <= len(res.tokens) < 24
    ref = ref_tokens(params, SHORT_PROMPT, 24)
    assert res.tokens == ref[:len(res.tokens)]
    assert_leak_free(eng)


def test_cancel_mid_staging_releases_int8_latch(params):
    """int8 chunked admission stages ONE prompt at a time; cancelling
    the staging request must release that latch (and its blocks) so the
    queued request behind it admits and serves correctly."""
    eng = engine(params, quantize=True)
    a = Request(uid=0, prompt=LONG_PROMPT, max_new_tokens=4)
    b = Request(uid=1, prompt=SHORT_PROMPT, max_new_tokens=4)
    # Tick 2: A is mid-staging (5 chunks of 8), B still queued (the
    # staging latch holds admission); the cancel must free both.
    rep = eng.serve(ScriptedSource(eng, [a, b], cancels={2: [0]}))
    by_uid = {r.uid: r for r in rep.results}
    assert by_uid[0].outcome == OUTCOME_CANCELLED
    assert by_uid[0].tokens == []
    assert by_uid[1].outcome == OUTCOME_BUDGET
    assert len(by_uid[1].tokens) == 4
    assert_leak_free(eng)
    # Same engine, same prompt, no cancellation: the staged path still
    # produces the canonical int8 stream (the latch release left no
    # stale staged rows behind).
    rep2 = eng.serve([Request(uid=2, prompt=SHORT_PROMPT,
                              max_new_tokens=4)])
    assert rep2.results[0].tokens == by_uid[1].tokens


def test_cancel_under_speculation_unmaps_rollback(params):
    """Cancel landing between a verify commit and the next tick under
    --speculate: the committed burst stands, rolled-back blocks were
    unmapped (not leaked), and the partial stream is a prefix of the
    non-speculative reference — cancellation must not break the parity
    contract for what WAS emitted."""
    eng = engine(params, speculate=True, draft_k=4)
    streamed = []

    def on_tok(t):
        streamed.append(t)
        if len(streamed) == 6:  # mid-burst: fires inside the commit walk
            eng.cancel(3)

    rep = eng.serve(ScriptedSource(eng, [
        Request(uid=3, prompt=LOOP_PROMPT, max_new_tokens=24,
                on_token=on_tok),
    ]))
    res = rep.results[0]
    assert res.outcome == OUTCOME_CANCELLED
    assert 6 <= len(res.tokens) < 24
    ref = ref_tokens(params, LOOP_PROMPT, 24)
    assert res.tokens == ref[:len(res.tokens)]
    lr = eng.leak_report()
    assert lr["blocks_private"] == 0 and lr["blocks_reserved"] == 0, lr
    assert lr["blocks_used"] == 0, lr  # no prefix cache on this engine


# ---------------------------------------------------------------------------
# engine layer: deadlines


def test_deadline_expired_in_queue_is_rejected_unserved(params):
    """One slot busy, a deadline the queue wait must blow: the queued
    request sheds with outcome 'deadline', admit_tick == -1, no tokens
    — and it counts as a goodput miss, not a latency sample."""
    eng = engine(params, prefix_cache=True, prefix_block=16,
                 kv_blocks=2)  # room for one in-flight request: B must queue
    retired0 = eng.slo.snapshot()["requests_retired"]
    a = Request(uid=0, prompt=SHORT_PROMPT, max_new_tokens=20)
    b = Request(uid=1, prompt=SHORT_PROMPT, max_new_tokens=4,
                deadline_s=time.monotonic() + 0.001)
    rep = eng.serve(ScriptedSource(eng, [a, b]))
    by_uid = {r.uid: r for r in rep.results}
    assert by_uid[0].outcome == OUTCOME_BUDGET
    assert by_uid[1].outcome == OUTCOME_DEADLINE
    assert by_uid[1].admit_tick == -1 and by_uid[1].tokens == []
    assert eng.slo.snapshot()["requests_retired"] == retired0 + 2
    assert_leak_free(eng)


def test_sweep_only_tick_still_records_flight_counters(params):
    """Review finding (ISSUE 14): a sweep that retired work but left the
    tick idle (every queued request dead on arrival, no slots in flight)
    broke out of the loop BEFORE the flight record — the counters were
    zeroed at the next tick top and the storm vanished from the black
    box."""
    from tree_attention_tpu.obs.flight import FLIGHT

    eng = base_engine(params)
    req = Request(uid=610, prompt=SHORT_PROMPT, max_new_tokens=4,
                  deadline_s=time.monotonic() - 1.0)  # dead on arrival
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        rep = eng.serve([req])
    finally:
        FLIGHT.disarm()
    recs = FLIGHT.snapshot()["records"]
    FLIGHT.clear()
    assert rep.results[0].outcome == OUTCOME_DEADLINE
    swept = [r for r in recs if r.get("sweep_only")]
    assert len(swept) == 1 and swept[0]["deadline_expired"] == 1
    assert_leak_free(eng)


def test_deadline_expired_in_flight_retires_midstream(params):
    """A live request whose deadline passes mid-decode retires with
    outcome 'deadline'; the tokens already streamed stand."""
    eng = base_engine(params)
    req = Request(uid=7, prompt=SHORT_PROMPT, max_new_tokens=50)

    def on_tok(t, _req=req):
        if len(_req_tokens) >= 3:
            _req.deadline_s = 0.0  # engine thread: sweep sees it next tick
        _req_tokens.append(t)

    _req_tokens = []
    req.on_token = on_tok
    rep = eng.serve(ScriptedSource(eng, [req]))
    res = rep.results[0]
    assert res.outcome == OUTCOME_DEADLINE
    assert 3 <= len(res.tokens) < 50
    assert_leak_free(eng)


def test_deadline_and_eos_same_tick_eos_wins(params):
    """EOS processed at a tick's end beats a deadline that expires the
    same instant: the request already finished, so the sweep finds a
    free slot and the outcome stays 'eos'."""
    eng = base_engine(params)
    ref = ref_tokens(params, SHORT_PROMPT, 12)
    eos = int(ref[4])
    k = ref.index(eos)  # first occurrence (may be < 4)
    req = Request(uid=8, prompt=SHORT_PROMPT, max_new_tokens=12,
                  eos_id=eos)

    def on_tok(t, _req=req):
        if t == eos:
            _req.deadline_s = 0.0  # expires on the EOS tick itself

    req.on_token = on_tok
    rep = eng.serve(ScriptedSource(eng, [req]))
    res = rep.results[0]
    assert res.outcome == OUTCOME_EOS
    assert res.tokens == ref[:k + 1]
    assert_leak_free(eng)


def test_deadline_beats_eos_when_it_expires_first(params):
    """The mirror case: the deadline expires one tick BEFORE the EOS
    token would land — shedding wins, the stream truncates before EOS."""
    eng = base_engine(params)
    ref = ref_tokens(params, SHORT_PROMPT, 12)
    eos = int(ref[6])
    k = ref.index(eos)
    req = Request(uid=9, prompt=SHORT_PROMPT, max_new_tokens=12,
                  eos_id=eos)
    seen = []

    def on_tok(t, _req=req):
        seen.append(t)
        if len(seen) == k:  # the tick before EOS would be sampled
            _req.deadline_s = 0.0

    req.on_token = on_tok
    rep = eng.serve(ScriptedSource(eng, [req]))
    res = rep.results[0]
    assert res.outcome == OUTCOME_DEADLINE
    assert len(res.tokens) < k + 1
    assert eos not in res.tokens[k - 1:]
    assert_leak_free(eng)


# ---------------------------------------------------------------------------
# engine layer: drain, validation, report plumbing


def test_drain_sheds_queue_and_finishes_inflight(params):
    """request_drain(): in-flight requests complete, queued ones shed
    with outcome 'shed' — the SIGTERM contract, minus the signal."""
    eng = engine(params, prefix_cache=True, prefix_block=16,
                 kv_blocks=2)  # B queues behind A on pool pressure
    a = Request(uid=0, prompt=SHORT_PROMPT, max_new_tokens=10)
    b = Request(uid=1, prompt=SHORT_PROMPT, max_new_tokens=4)
    rep = eng.serve(ScriptedSource(eng, [a, b], drain_at=3))
    by_uid = {r.uid: r for r in rep.results}
    assert by_uid[0].outcome == OUTCOME_BUDGET
    assert len(by_uid[0].tokens) == 10  # finished, not truncated
    assert by_uid[1].outcome == OUTCOME_SHED
    assert by_uid[1].tokens == []
    assert rep.outcomes == {OUTCOME_BUDGET: 1, OUTCOME_SHED: 1}
    assert_leak_free(eng)


def test_invalid_live_request_finishes_with_error_outcome(params):
    """A live source's invalid request must not kill the loop serving
    everyone else: it finishes unserved with outcome 'error' while the
    valid request streams normally (static lists still raise)."""
    eng = base_engine(params)
    bad = Request(uid=0, prompt=SHORT_PROMPT, max_new_tokens=1000)
    good = Request(uid=1, prompt=SHORT_PROMPT, max_new_tokens=4)
    rep = eng.serve(ScriptedSource(eng, [bad, good]))
    by_uid = {r.uid: r for r in rep.results}
    assert by_uid[0].outcome == OUTCOME_ERROR
    assert by_uid[1].outcome == OUTCOME_BUDGET
    with pytest.raises(ValueError):
        eng.serve([bad])  # the pre-validated static path still raises
    assert_leak_free(eng)


def test_cancel_unknown_uid_is_noop(params):
    """Cancelling a finished/unknown uid (a client disconnecting after
    its stream completed) changes nothing."""
    eng = base_engine(params)
    eng.cancel(424242)
    rep = eng.serve([Request(uid=0, prompt=SHORT_PROMPT,
                             max_new_tokens=4)])
    assert rep.results[0].outcome == OUTCOME_BUDGET
    # NOTE: serve() clears stale mailboxes at start, so even uid 0 above
    # was safe — pin that contract too.
    eng.cancel(0)
    rep2 = eng.serve([Request(uid=0, prompt=SHORT_PROMPT,
                              max_new_tokens=4)])
    assert rep2.results[0].outcome == OUTCOME_BUDGET


# ---------------------------------------------------------------------------
# engine layer: the 300-event property test


def test_property_random_cancel_admit_drains_clean(params):
    """300 random scripted events — admissions (some sharing radix
    prefixes), cancels aimed at queued/active/finished/unknown uids,
    scattered deadlines — then drain: every submitted request gets
    exactly one result, and the engine holds zero slot-private blocks,
    zero reservations, zero radix pins (used == cached)."""
    eng = base_engine(params)
    prng = np.random.default_rng(1234)
    prefixes = [prng.integers(0, 128, size=16).astype(np.int32)
                for _ in range(3)]
    arrivals = []
    cancels = {}
    uid = 0
    tick = 0
    for _ in range(300):
        r = prng.random()
        tick += int(prng.integers(0, 3))
        if r < 0.55 or uid == 0:
            suffix = prng.integers(
                0, 128, size=int(prng.integers(2, 9))
            ).astype(np.int32)
            prompt = np.concatenate(
                [prefixes[int(prng.integers(0, 3))], suffix]
            ) if prng.random() < 0.7 else suffix
            req = Request(
                uid=uid, prompt=prompt,
                max_new_tokens=int(prng.integers(2, 7)),
                arrival_tick=tick,
                deadline_s=(time.monotonic() + float(prng.uniform(0.2, 30))
                            if prng.random() < 0.2 else None),
            )
            arrivals.append(req)
            uid += 1
        else:
            # Aim at anything: queued, live, finished, or never-existing.
            victim = int(prng.integers(0, uid + 3))
            cancels.setdefault(tick, []).append(victim)
    rep = eng.serve(ScriptedSource(eng, arrivals, cancels=cancels),
                    max_ticks=20_000)
    assert sorted(r.uid for r in rep.results) == list(range(uid))
    assert_leak_free(eng)
    allowed = {OUTCOME_BUDGET, OUTCOME_CANCELLED, OUTCOME_DEADLINE}
    assert set(rep.outcomes) <= allowed, rep.outcomes
    assert rep.outcomes.get(OUTCOME_CANCELLED, 0) > 0  # chaos happened


# ---------------------------------------------------------------------------
# HTTP layer: one live loopback server for the whole module


@pytest.fixture(scope="module")
def live(params):
    from tree_attention_tpu.serving.ingress import IngressServer

    eng = SlotServer(params, CFG, slots=SLOTS, cache_len=CACHE_LEN,
                     prefill_chunk=8, prefix_cache=True, prefix_block=16)
    srv = IngressServer(eng, max_queue=8, default_max_tokens=6,
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


def _read_sse(resp):
    tokens, finish = [], None
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
        tokens.extend(ch["token_ids"])
        if ch["finish_reason"] is not None:
            finish = ch["finish_reason"]
    return tokens, finish


def _settled(eng, timeout=15.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        lr = eng.leak_report()
        if (eng.all_slots_free and lr["blocks_private"] == 0
                and lr["blocks_reserved"] == 0 and lr["pins"] == 0):
            return True
        time.sleep(0.05)
    return False


def test_http_sse_stream_and_whole_agree(params, live):
    """The SSE stream and the stream:false JSON body report the same
    greedy tokens and finish_reason (and match the engine's reference)."""
    prompt = [int(t) for t in SHORT_PROMPT]
    conn, resp = _post(live.port, {"prompt": prompt, "max_tokens": 6})
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    toks, finish = _read_sse(resp)
    conn.close()
    assert finish == "length"
    conn, resp = _post(live.port, {"prompt": prompt, "max_tokens": 6,
                                   "stream": False})
    body = json.loads(resp.read())
    conn.close()
    assert resp.status == 200
    assert body["choices"][0]["token_ids"] == toks
    assert body["choices"][0]["finish_reason"] == "length"
    assert body["usage"] == {"prompt_tokens": len(prompt),
                             "completion_tokens": 6,
                             "prefix_hit_tokens": 0}
    assert toks == ref_tokens(params, SHORT_PROMPT, 6)


def test_http_bad_requests_rejected(live):
    for body, frag in [
        ({"prompt": "a string"}, "token ids"),
        ({"prompt": []}, "non-empty"),
        ({}, "non-empty"),
        # Malformed numerics must 400 at parse time — after the queue
        # unit is taken they would leak admission depth on the way out.
        ({"prompt": [1], "max_tokens": "abc"}, "non-numeric"),
        ({"prompt": [1], "deadline_s": "soon"}, "non-numeric"),
    ]:
        conn, resp = _post(live.port, body)
        assert resp.status == 400
        assert frag in json.loads(resp.read())["error"]["message"]
        conn.close()


def test_http_disconnect_cancels_and_frees(live):
    """Close the socket after the first token: the keepalive/write probe
    detects it, the engine cancels mid-flight, and the pool returns to a
    leak-free state while the server keeps serving others."""
    prompt = [int(t) for t in LONG_PROMPT]
    conn, resp = _post(live.port, {"prompt": prompt, "max_tokens": 20})
    assert resp.status == 200
    while True:  # read up to the first token event, then vanish
        line = resp.readline()
        if line.startswith(b"data: "):
            break
    resp.close()
    conn.close()  # vanish: the server's next write/keepalive probe fails
    assert _settled(live.engine), live.engine.leak_report()
    # Liveness after the cancel: a fresh request still streams.
    conn, resp = _post(live.port, {"prompt": [1, 2, 3], "max_tokens": 3})
    toks, finish = _read_sse(resp)
    conn.close()
    assert finish == "length" and len(toks) == 3


def test_http_deadline_sheds_over_the_wire(live):
    """A deadline the request cannot meet comes back as finish_reason
    'deadline' on the stream (expired in queue or in flight)."""
    conn, resp = _post(live.port, {
        "prompt": [int(t) for t in LONG_PROMPT],
        "max_tokens": 20, "deadline_s": 0.001,
    })
    assert resp.status == 200
    toks, finish = _read_sse(resp)
    conn.close()
    assert finish == "deadline"
    assert _settled(live.engine)


def test_http_429_backpressure_with_retry_after(live):
    """Past max_queue waiting requests, submissions get 429 and a
    Retry-After derived from queue depth x windowed TTFT."""
    import threading

    live.max_queue = 1
    conns = []
    results = []

    def fire():
        c, r = _post(live.port, {
            "prompt": [int(t) for t in LONG_PROMPT], "max_tokens": 16,
        })
        results.append((r.status, r.getheader("Retry-After")))
        conns.append((c, r))

    try:
        threads = [threading.Thread(target=fire) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        codes = [s for s, _ in results]
        assert 429 in codes, codes
        for status, retry in results:
            if status == 429:
                assert retry is not None and int(retry) >= 1
    finally:
        live.max_queue = 8
        for c, r in conns:
            if r.status == 200:
                _read_sse(r)  # let the 200s finish cleanly
            c.close()
    assert _settled(live.engine)


def test_http_stats_endpoint(live):
    conn = http.client.HTTPConnection("127.0.0.1", live.port, timeout=10)
    conn.request("GET", "/ingress/stats")
    body = json.loads(conn.getresponse().read())
    conn.close()
    assert body["max_queue"] == 8 and body["draining"] is False
    assert body["slots"] == SLOTS


def test_zz_http_drain_lifecycle(live):
    """LAST (zz): drain stops admission (503), finishes in-flight, and
    the collected report carries the outcome vocabulary; the engine ends
    leak-free. Runs last because the module server cannot un-drain."""
    live.drain()
    conn, resp = _post(live.port, {"prompt": [1, 2], "max_tokens": 2})
    assert resp.status == 503
    conn.close()
    report = live.join(timeout=60)
    assert report is not None
    assert set(report.outcomes) <= {
        OUTCOME_BUDGET, OUTCOME_EOS, OUTCOME_CANCELLED, OUTCOME_DEADLINE,
        OUTCOME_SHED, OUTCOME_ERROR,
    }
    assert report.outcomes.get(OUTCOME_CANCELLED, 0) >= 1  # the disconnect
    assert report.outcomes.get(OUTCOME_DEADLINE, 0) >= 1
    lr = live.engine.leak_report()
    assert lr["blocks_private"] == 0 and lr["pins"] == 0
    live.stop()
