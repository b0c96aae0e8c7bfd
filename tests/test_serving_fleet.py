"""ISSUE 11: the prefix-affinity serving fleet — router, supervisor, bench.

Three layers, matching the subsystem's own:

- **Scoring layer** — :class:`ReplicaTree` and the router's
  :meth:`FleetRouter.choose`/:meth:`finish` policy driven directly, no
  HTTP: affinity vs least-loaded vs hysteresis, round-robin tie-breaks,
  failover exclusion, stale-tree TTL decay, and feedback truncation
  (the replica reported fewer hit tokens than predicted -> the router
  forgets the stale path). Plus :func:`federate_metrics` as pure
  text-to-text.
- **Trace layer** — the multi-tenant Zipf shared-prefix mixture in
  :func:`heavy_tail_trace` (per-tenant populations, skew, the
  ``prefix_seed`` population decoupling the fleet bench arms lean on).
- **HTTP layer** — ONE module-scoped loopback fleet (2 replicas, tiny
  config; the replica-0 engine doubles as the direct-serve parity
  reference BEFORE the fleet starts, so no extra engine pays compiles):
  routed streams token-identical to direct serving, per-request
  ``usage.prefix_hit_tokens`` reporting, ``/router/stats`` and
  federated ``/metrics``, the ``POST /admin/drain`` handshake, and a
  rolling restart under live traffic with zero dropped accepted
  requests and leak-free drained allocators.

Frugality (the tier-1 budget): exactly two SlotServer instances are
built for the whole file, shared by every HTTP test; everything else is
HTTP-free.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np
import pytest

import jax

from tree_attention_tpu.bench.serving import (
    _wait_engine_settled,
    heavy_tail_trace,
    replay_trace_http,
    serving_model_config,
)
from tree_attention_tpu.models import init_params
from tree_attention_tpu.serving import Request, SlotServer
from tree_attention_tpu.serving.fleet import FleetSupervisor, LocalReplica
from tree_attention_tpu.serving.router import (
    REASON_AFFINITY,
    REASON_FAILOVER,
    REASON_LEAST_LOADED,
    FleetRouter,
    ReplicaTree,
    federate_metrics,
)

BLOCK = 8
CFG = serving_model_config(d_model=64, vocab_size=128, max_seq_len=64)
CACHE_LEN = 64
SLOTS = 2


# ---------------------------------------------------------------------------
# ReplicaTree: the approximate radix tree
# ---------------------------------------------------------------------------


class TestReplicaTree:
    def test_match_is_block_granular(self):
        t = ReplicaTree(block=4)
        t.insert(list(range(10)), now=1.0)  # 2 full blocks; tail ignored
        assert t.blocks == 2
        assert t.match(list(range(10))) == 8
        assert t.match(list(range(4)) + [99, 99, 99, 99]) == 4
        assert t.match([77, 77, 77, 77]) == 0
        assert t.match(list(range(3))) == 0  # partial block never matches

    def test_lru_cap_evicts_oldest_leaf(self):
        t = ReplicaTree(block=2, max_blocks=3)
        t.insert([1, 1, 2, 2], now=1.0)   # 2 nodes
        t.insert([3, 3], now=2.0)         # 3 nodes — at cap
        t.insert([4, 4], now=3.0)         # over cap: LRU LEAF evicted
        assert t.blocks == 3
        # [1,1]'s child (2,2) was the LRU leaf; its interior parent stays.
        assert t.match([1, 1, 2, 2]) == 2
        assert t.match([3, 3]) == 2 and t.match([4, 4]) == 2

    def test_ttl_decay_drops_stale_subtrees(self):
        t = ReplicaTree(block=2, ttl_s=10.0)
        t.insert([1, 1, 2, 2], now=0.0)
        t.insert([5, 5], now=8.0)
        assert t.decay(now=11.0) == 2  # the untouched [1,1] subtree
        assert t.match([1, 1, 2, 2]) == 0
        assert t.match([5, 5]) == 2
        assert t.blocks == 1

    def test_feedback_truncation(self):
        t = ReplicaTree(block=2)
        t.insert([1, 1, 2, 2, 3, 3], now=1.0)
        t.truncate([1, 1, 2, 2, 3, 3], keep_tokens=2)
        assert t.match([1, 1, 2, 2, 3, 3]) == 2
        assert t.blocks == 1
        # keep >= tracked length is a no-op
        t.truncate([1, 1], keep_tokens=6)
        assert t.match([1, 1]) == 2

    def test_clear(self):
        t = ReplicaTree(block=2)
        t.insert([1, 1, 2, 2], now=1.0)
        t.clear()
        assert t.blocks == 0 and t.match([1, 1]) == 0


# ---------------------------------------------------------------------------
# Routing policy (no HTTP — choose()/finish() driven directly)
# ---------------------------------------------------------------------------


def scoring_router(**kw) -> FleetRouter:
    """A router used purely as a scoring object (never .start()ed)."""
    kw.setdefault("block", 4)
    r = FleetRouter(**kw)
    r.add_replica("r0", 1001)
    r.add_replica("r1", 1002)
    r.add_replica("r2", 1003)
    return r


PROMPT_A = list(range(16))           # 4 full blocks
PROMPT_B = [99] * 8 + list(range(8))  # distinct head


def finish_ok(router, name, prompt, reason, predicted,
              hit_tokens=None) -> None:
    router.finish(name, prompt, reason=reason, predicted=predicted,
                  hit_tokens=predicted if hit_tokens is None
                  else hit_tokens)


class TestRoutingPolicy:
    def test_cold_prompts_round_robin_then_affinity(self):
        r = scoring_router()
        n0, why0, m0 = r.choose(PROMPT_A, now=1.0)
        assert why0 == REASON_LEAST_LOADED and m0 == 0
        finish_ok(r, n0, PROMPT_A, why0, m0)
        # The chosen replica's tree learned the prompt: the next sharer
        # routes by affinity, to the same replica.
        n1, why1, m1 = r.choose(PROMPT_A, now=2.0)
        assert (n1, why1) == (n0, REASON_AFFINITY) and m1 == 16
        finish_ok(r, n1, PROMPT_A, why1, m1)

    def test_round_robin_cycles_cold_ties(self):
        r = scoring_router(affinity=False)
        picks = []
        for i in range(3):
            n, why, _ = r.choose([50 + i] * 8, now=float(i))
            assert why == REASON_LEAST_LOADED
            finish_ok(r, n, [50 + i] * 8, why, 0)
            picks.append(n)
        assert sorted(picks) == ["r0", "r1", "r2"]  # ties cycle, no pile-up

    def test_affinity_off_ignores_matches(self):
        r = scoring_router(affinity=False)
        n0, _, _ = r.choose(PROMPT_A, now=1.0)
        finish_ok(r, n0, PROMPT_A, REASON_LEAST_LOADED, 0)
        seen = set()
        for i in range(3):
            n, why, m = r.choose(PROMPT_A, now=2.0 + i)
            assert why == REASON_LEAST_LOADED and m == 0
            finish_ok(r, n, PROMPT_A, why, 0)
            seen.add(n)
        assert len(seen) == 3  # scattered — the dilution baseline

    def test_hysteresis_overrides_hot_affinity(self):
        r = scoring_router(hysteresis=2)
        n0, _, _ = r.choose(PROMPT_A, now=1.0)  # r_aff learns the prefix
        finish_ok(r, n0, PROMPT_A, REASON_LEAST_LOADED, 0)
        # Pile in-flight work onto the affinity replica (no finish).
        held = [r.choose(PROMPT_A, now=2.0 + i) for i in range(3)]
        assert all(h[0] == n0 and h[1] == REASON_AFFINITY for h in held)
        # Excess is now 3 > hysteresis=2: least-loaded overrides.
        n4, why4, _ = r.choose(PROMPT_A, now=6.0)
        assert n4 != n0 and why4 == REASON_LEAST_LOADED

    def test_min_match_floor(self):
        r = scoring_router(min_match=8)
        n0, _, _ = r.choose(PROMPT_A[:4] + [7, 7, 7, 7], now=1.0)
        finish_ok(r, n0, PROMPT_A[:4] + [7, 7, 7, 7], REASON_LEAST_LOADED,
                  0)
        # Only ONE block (4 tokens) would match — below min_match.
        n1, why1, m1 = r.choose(PROMPT_A[:4] + [8, 8, 8, 8], now=2.0)
        assert why1 == REASON_LEAST_LOADED and m1 == 0
        finish_ok(r, n1, PROMPT_A[:4] + [8, 8, 8, 8], why1, 0)

    def test_exclude_is_failover(self):
        r = scoring_router()
        n0, _, _ = r.choose(PROMPT_A, now=1.0)
        finish_ok(r, n0, PROMPT_A, REASON_LEAST_LOADED, 0)
        n1, why1, _ = r.choose(PROMPT_A, exclude={n0}, now=2.0)
        assert n1 != n0 and why1 == REASON_FAILOVER
        finish_ok(r, n1, PROMPT_A, why1, 0)

    def test_draining_and_down_not_routable_rejoin_resets_tree(self):
        r = scoring_router()
        n0, _, _ = r.choose(PROMPT_A, now=1.0)
        finish_ok(r, n0, PROMPT_A, REASON_LEAST_LOADED, 0)
        r.set_draining(n0)
        n1, why1, _ = r.choose(PROMPT_A, now=2.0)
        assert n1 != n0 and why1 == REASON_LEAST_LOADED
        finish_ok(r, n1, PROMPT_A, why1, 0)
        r.mark_down(n1)
        n2, _, _ = r.choose(PROMPT_A, now=3.0)
        assert n2 not in (n0, n1)
        finish_ok(r, n2, PROMPT_A, REASON_LEAST_LOADED, 0)
        # Rejoin clears the affinity view: the restarted cache is empty.
        r.rejoin(n0)
        assert r.stats()["replicas"][n0]["tree_blocks"] == 0
        # All excluded -> no pick at all.
        none, _, _ = r.choose(PROMPT_A, exclude={n0, n1, n2}, now=4.0)
        assert none is None

    def test_stale_tree_ttl_decay_in_choose(self):
        r = scoring_router(tree_ttl_s=10.0)
        n0, _, _ = r.choose(PROMPT_A, now=1.0)
        finish_ok(r, n0, PROMPT_A, REASON_LEAST_LOADED, 0)
        n1, why1, _ = r.choose(PROMPT_A, now=5.0)  # fresh: affinity
        assert (n1, why1) == (n0, REASON_AFFINITY)
        finish_ok(r, n1, PROMPT_A, why1, 16)
        n2, why2, m2 = r.choose(PROMPT_A, now=60.0)  # decayed: cold
        assert why2 == REASON_LEAST_LOADED and m2 == 0
        finish_ok(r, n2, PROMPT_A, why2, 0)

    def test_feedback_truncates_on_partial_hit(self):
        r = scoring_router()
        n0, _, _ = r.choose(PROMPT_A, now=1.0)
        finish_ok(r, n0, PROMPT_A, REASON_LEAST_LOADED, 0)
        _, _, m = r.choose(PROMPT_A, now=2.0)
        assert m == 16
        # The replica reports it only matched 4 tokens (evicted the
        # rest): the router's tree truncates to the report.
        r.finish(n0, PROMPT_A, reason=REASON_AFFINITY, predicted=16,
                 hit_tokens=4)
        _, _, m2 = r.choose(PROMPT_A, now=3.0)
        assert m2 == 4
        finish_ok(r, n0, PROMPT_A, REASON_AFFINITY, m2)

    def test_inflight_accounting_via_stats(self):
        r = scoring_router()
        n0, why0, m0 = r.choose(PROMPT_A, now=1.0)
        assert r.stats()["replicas"][n0]["inflight"] == 1
        finish_ok(r, n0, PROMPT_A, why0, m0)
        assert r.stats()["replicas"][n0]["inflight"] == 0
        assert r.stats()["routed"][REASON_LEAST_LOADED] == 1


# ---------------------------------------------------------------------------
# Metrics federation (pure text)
# ---------------------------------------------------------------------------


class TestFleetLifecycleGuards:
    def test_timed_out_drain_blocks_restart_until_loop_returns(self):
        # A wedged engine loop past the drain timeout must NOT be
        # restartable: a second serve() on the same engine would
        # corrupt slot/pool state. await_drained(False) keeps the
        # guard up; once the loop actually returns, restart is legal.
        release = threading.Event()

        class WedgedEngine:
            slots = 1

            def serve(self, source):
                release.wait(10.0)
                return "report"

            def request_drain(self):
                pass

        rep = LocalReplica("w", WedgedEngine)
        rep.start()
        rep.begin_drain()
        assert rep.await_drained(timeout_s=0.2) is False
        with pytest.raises(RuntimeError, match="restart before drain"):
            rep.restart()
        release.set()
        assert rep.await_drained(timeout_s=5.0) is True
        assert rep.restart() > 0  # loop returned: restart legal again
        rep.stop()


class TestFederation:
    def test_labels_injected_and_meta_deduped(self):
        out = federate_metrics({
            "r0": "# HELP x_total help\n# TYPE x_total counter\n"
                  'x_total{a="b"} 1\nplain 2\n',
            "r1": "# HELP x_total help\nx_total{a=\"b\"} 3\n",
        })
        lines = out.splitlines()
        assert lines.count("# HELP x_total help") == 1
        # TYPE must survive its sibling HELP (dedup is per-directive).
        assert lines.count("# TYPE x_total counter") == 1
        assert 'x_total{replica="r0",a="b"} 1' in lines
        assert 'x_total{replica="r1",a="b"} 3' in lines
        assert 'plain{replica="r0"} 2' in lines

    def test_empty(self):
        assert federate_metrics({}) == ""

    def test_malformed_lines_dropped_not_fatal(self):
        # A truncated scrape or an error page behind a metrics_url must
        # not kill the fleet-wide /metrics response.
        out = federate_metrics({
            "r0": "<html>\nx_total 1\ngarbage-no-space\n",
        })
        lines = out.splitlines()
        assert 'x_total{replica="r0"} 1' in lines
        assert all("garbage" not in ln and "html" not in ln
                   for ln in lines)


# ---------------------------------------------------------------------------
# Multi-tenant heavy-tail trace
# ---------------------------------------------------------------------------


class TestMultiTenantTrace:
    def test_tenant_prefixes_shared_and_zipf_skewed(self):
        evs = heavy_tail_trace(
            200, cache_len=128, tenants=4, tenant_prefix_len=16,
            tenant_zipf=1.5, vocab_size=128, seed=5,
        )
        heads = {}
        counts = {}
        for e in evs:
            t = e["tenant"]
            counts[t] = counts.get(t, 0) + 1
            head = tuple(e["prompt"][:16])
            heads.setdefault(t, head)
            # every event of one tenant shares that tenant's prefix
            assert head == heads[t]
            assert len(e["prompt"]) + e["max_tokens"] <= 128
        assert len(heads) == 4
        assert len(set(heads.values())) == 4  # distinct populations
        assert counts[0] > counts[3]  # Zipf skew: rank 0 dominates

    def test_prefix_seed_decouples_population_from_trace(self):
        a = heavy_tail_trace(20, cache_len=128, tenants=2,
                             tenant_prefix_len=16, seed=7, prefix_seed=1)
        b = heavy_tail_trace(20, cache_len=128, tenants=2,
                             tenant_prefix_len=16, seed=7, prefix_seed=2)
        # identical arrivals/lengths/suffixes, disjoint prefix heads
        assert [e["t_s"] for e in a] == [e["t_s"] for e in b]
        assert [e["tenant"] for e in a] == [e["tenant"] for e in b]
        assert [e["prompt"][16:] for e in a] == [e["prompt"][16:] for e in b]
        assert a[0]["prompt"][:16] != b[0]["prompt"][:16]

    def test_no_tenants_is_the_legacy_shape(self):
        evs = heavy_tail_trace(5, cache_len=64, seed=3)
        assert all("tenant" not in e for e in evs)


# ---------------------------------------------------------------------------
# CLI flags
# ---------------------------------------------------------------------------


class TestCLIFlags:
    def test_fleet_flags_parse(self):
        from tree_attention_tpu.utils.config import parse_args

        cfg = parse_args(["--mode", "serve", "--serve-fleet",
                          "--replicas", "4", "--router-port", "8123",
                          "--affinity", "off"])
        assert cfg.serve_fleet and cfg.replicas == 4
        assert cfg.router_port == 8123 and cfg.affinity == "off"

    def test_fleet_defaults(self):
        from tree_attention_tpu.utils.config import parse_args

        cfg = parse_args(["--mode", "serve"])
        assert not cfg.serve_fleet
        assert cfg.replicas == 2 and cfg.affinity == "on"

    def test_serve_fleet_excludes_serve_http(self):
        from tree_attention_tpu.cli import _run_serve
        from tree_attention_tpu.utils.config import parse_args

        cfg = parse_args(["--mode", "serve", "--serve-fleet",
                          "--serve-http", "0"])
        with pytest.raises(SystemExit, match="exclusive"):
            _run_serve(cfg, None)

    def test_replicas_floor(self):
        from tree_attention_tpu.cli import _run_serve
        from tree_attention_tpu.utils.config import parse_args

        cfg = parse_args(["--mode", "serve", "--serve-fleet",
                          "--replicas", "0"])
        with pytest.raises(SystemExit, match="--replicas"):
            _run_serve(cfg, None)


# ---------------------------------------------------------------------------
# Router hardening (review fixes) — no engines, fake/absent replicas
# ---------------------------------------------------------------------------


class TestRouterHardening:
    def test_invalid_bodies_reject_before_any_accounting(self):
        # Validation failures after choose() would leak the replica's
        # in-flight count forever (the ingress's brick-the-server
        # class): every reject must happen BEFORE routing accounting.
        router = FleetRouter(block=4)
        router.add_replica("r0", 1)  # never contacted
        port = router.start()
        try:
            for body in (
                {"prompt": [1, 2], "deadline_s": "soon"},  # non-numeric
                {"prompt": [1, 2], "deadline_s": {}},
                {"prompt": ["a", "b"]},                    # non-int ids
                {"prompt": [True, False]},                 # bools lie
                {"prompt": []},
                {"prompt": "text"},
            ):
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=10.0)
                try:
                    conn.request("POST", "/v1/completions",
                                 json.dumps(body),
                                 {"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    assert resp.status == 400, body
                    resp.read()
                finally:
                    conn.close()
            st = router.stats()
            assert st["replicas"]["r0"]["inflight"] == 0
            assert sum(st["routed"].values()) == 0
            assert st["replicas"]["r0"]["tree_blocks"] == 0
        finally:
            router.stop()

    def test_replica_lost_mid_stream_errors_out_and_marks_down(self):
        # A replica that dies AFTER streaming a token (abrupt socket
        # close, no finish/[DONE]) must end the client stream with the
        # SSE error frame + [DONE] — not a silent cut — and be marked
        # down so it takes no new routes.
        import socket

        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)

        def serve_once():
            c, _ = srv.accept()
            c.recv(65536)
            c.sendall(b"HTTP/1.0 200 OK\r\n"
                      b"Content-Type: text/event-stream\r\n\r\n")
            c.sendall(b'data: {"id": "cmpl-0", "object": '
                      b'"text_completion", "choices": [{"index": 0, '
                      b'"text": "5 ", "token_ids": [5], '
                      b'"finish_reason": null}]}\n\n')
            time.sleep(0.1)
            c.close()  # vanish: no finish event, no [DONE]

        threading.Thread(target=serve_once, daemon=True).start()
        router = FleetRouter(block=4)
        router.add_replica("mort", srv.getsockname()[1])
        port = router.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=20.0)
            try:
                conn.request("POST", "/v1/completions",
                             json.dumps({"prompt": [1, 2, 3],
                                         "max_tokens": 4}),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 200
                payloads = []
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    line = line.strip()
                    if not line.startswith(b"data: "):
                        continue
                    if line[6:] == b"[DONE]":
                        break
                    payloads.append(json.loads(line[6:]))
            finally:
                conn.close()
            assert payloads[0]["choices"][0]["token_ids"] == [5]
            assert payloads[-1].get("finish_reason") == "error"
            # The router's handler thread closes its books (finish():
            # inflight back to 0) AFTER it wrote [DONE]; the client saw
            # [DONE] first, so wait for the books, bounded.
            deadline = time.monotonic() + 5.0
            while (router.stats()["replicas"]["mort"]["inflight"]
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert router.stats()["replicas"]["mort"]["state"] == "down"
            assert router.stats()["replicas"]["mort"]["inflight"] == 0
        finally:
            router.stop()
            srv.close()


# ---------------------------------------------------------------------------
# The loopback fleet (ONE module-scoped instance; 2 engines total)
# ---------------------------------------------------------------------------


N_PARITY = 6


def _mt_trace(n, prefix_seed, gap=0.005):
    return heavy_tail_trace(
        n, cache_len=CACHE_LEN, mean_gap_s=gap, vocab_size=128,
        seed=21, tenants=3, tenant_prefix_len=2 * BLOCK,
        prefix_seed=prefix_seed,
    )


@pytest.fixture(scope="module")
def fleet():
    params = init_params(jax.random.PRNGKey(0), CFG)

    def make_engine():
        return SlotServer(
            params, CFG, slots=SLOTS, cache_len=CACHE_LEN,
            prefill_chunk=BLOCK, prefix_cache=True, prefix_block=BLOCK,
            kv_blocks=SLOTS * (CACHE_LEN // BLOCK) + 16,
        )

    reps = [LocalReplica(f"r{i}", make_engine, max_queue=64,
                         default_max_tokens=6, keepalive_s=0.1)
            for i in range(2)]
    router = FleetRouter(block=BLOCK, affinity=True, hysteresis=2)
    sup = FleetSupervisor(reps, router=router, monitor_interval_s=0)

    # Direct-serve parity reference on replica 0's engine BEFORE the
    # fleet starts — the same instance the fleet then reuses, so the
    # file builds exactly two engines.
    trace = _mt_trace(N_PARITY, prefix_seed=31)
    report = reps[0].engine.serve([
        Request(uid=i, prompt=np.asarray(e["prompt"], np.int32),
                max_new_tokens=e["max_tokens"])
        for i, e in enumerate(trace)
    ])
    refs = {i: list(r.tokens) for i, r in
            enumerate(sorted(report.results, key=lambda r: r.uid))}
    port = sup.start()
    yield {"sup": sup, "router": router, "port": port,
           "trace": trace, "refs": refs}
    sup.stop()


def _settle(sup, router=None):
    for eng in sup.engines:
        _wait_engine_settled(eng)
    if router is not None:
        # Router-side inflight decrements on the handler threads a beat
        # after the client sees [DONE] — poll it down before reading
        # load-sensitive routing state.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if all(v["inflight"] == 0
                   for v in router.stats()["replicas"].values()):
                return
            time.sleep(0.02)


class TestFleetHTTP:
    def test_routed_streams_token_identical_to_direct(self, fleet):
        res = replay_trace_http(fleet["port"], fleet["trace"])
        _settle(fleet["sup"], fleet["router"])
        for i, r in enumerate(res):
            assert r["finish_reason"] in ("stop", "length"), res[i]
            assert r["tokens"] == fleet["refs"][i], (
                f"routed stream {i} diverged from direct serving"
            )
        stats = fleet["router"].stats()
        assert sum(stats["routed"].values()) >= N_PARITY
        assert stats["dropped"] == 0

    def test_affinity_routes_repeat_prefixes_and_reports_hits(self, fleet):
        # A fresh tenant population, two waves of the same prompt: wave
        # one is cold (least-loaded), wave two must ride affinity to the
        # SAME replica and report prefix_hit_tokens upstream.
        ev = _mt_trace(1, prefix_seed=47)[0]
        ev["max_tokens"] = 4
        conn = http.client.HTTPConnection("127.0.0.1", fleet["port"],
                                          timeout=30.0)
        hits = []
        try:
            for _ in range(2):
                conn.request(
                    "POST", "/v1/completions",
                    json.dumps({"prompt": ev["prompt"], "max_tokens": 4,
                                "stream": False}),
                    {"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                body = json.loads(resp.read())
                assert resp.status == 200
                hits.append(body["usage"]["prefix_hit_tokens"])
        finally:
            conn.close()
        _settle(fleet["sup"], fleet["router"])
        assert hits[0] == 0  # cold population: no replica had it
        # Second wave: the router sent it back to the warmed replica,
        # which reports >= the full-block span of the prompt's head.
        plen = len(ev["prompt"])
        assert hits[1] >= BLOCK
        assert hits[1] <= plen - 1  # matched is capped below the prompt
        st = fleet["router"].stats()
        assert st["routed"][REASON_AFFINITY] >= 1

    def test_router_stats_and_federated_metrics_endpoints(self, fleet):
        from tree_attention_tpu import obs

        was = obs.REGISTRY.enabled
        obs.REGISTRY.enable()
        try:
            # One routed request so the labeled router families carry
            # samples the exposition prints.
            ev = dict(fleet["trace"][0], t_s=0.0)
            replay_trace_http(fleet["port"], [ev])
            _settle(fleet["sup"], fleet["router"])
            conn = http.client.HTTPConnection("127.0.0.1", fleet["port"],
                                              timeout=10.0)
            try:
                conn.request("GET", "/router/stats")
                st = json.loads(conn.getresponse().read())
                assert set(st["replicas"]) == {"r0", "r1"}
                conn.request("GET", "/metrics")
                text = conn.getresponse().read().decode()
            finally:
                conn.close()
        finally:
            if not was:
                obs.REGISTRY.disable()
        assert "serving_router_requests_total" in text
        assert "serving_router_replica_healthy" in text
        assert "serving_router_replica_inflight" in text

    def test_rolling_restart_under_traffic_drops_nothing(self, fleet):
        sup, router = fleet["sup"], fleet["router"]
        trace = _mt_trace(10, prefix_seed=53)
        roll_out: dict = {}

        def do_roll():
            time.sleep(0.1)
            roll_out.update(sup.rolling_restart())

        th = threading.Thread(target=do_roll, daemon=True)
        th.start()
        res = replay_trace_http(fleet["port"], trace)
        th.join(timeout=60.0)
        _settle(sup, router)
        assert len(roll_out) == 2, f"rolling restart incomplete: {roll_out}"
        # Zero dropped accepted requests: everything got in and finished.
        assert all(r["status"] == 200 for r in res)
        assert all(r["finish_reason"] in ("stop", "length") for r in res)
        # Each drained replica's allocator was clean at its drain point.
        for name, info in roll_out.items():
            assert info["drained"], (name, info)
            lk = info["leak"]
            assert lk["blocks_private"] == 0, (name, lk)
            assert lk["blocks_reserved"] == 0, (name, lk)
            assert lk["pins"] == 0, (name, lk)
        assert router.stats()["dropped"] == 0
        # Both replicas routable again after the roll.
        states = [v["state"] for v in
                  router.stats()["replicas"].values()]
        assert states == ["up", "up"]

    def test_post_roll_parity_and_admin_drain_handshake(self, fleet):
        # Streams stay token-identical after the roll (ports moved,
        # trees reset — the answers must not).
        res = replay_trace_http(fleet["port"], fleet["trace"])
        _settle(fleet["sup"], fleet["router"])
        for i, r in enumerate(res):
            assert r["tokens"] == fleet["refs"][i]
        # The HTTP drain handshake on a live replica: POST /admin/drain
        # -> 202, stats flip to draining, engine drains. Deliberately
        # WITHOUT telling the router (the mid-drain race a rolling
        # restart can hit): requests the router still sends to r0 get
        # its 503 and must requeue onto r1 — the failover arc, live.
        sup, router = fleet["sup"], fleet["router"]
        rep = sup.replicas["r0"]
        conn = http.client.HTTPConnection("127.0.0.1", rep.port,
                                          timeout=10.0)
        try:
            conn.request("POST", "/admin/drain", b"")
            resp = conn.getresponse()
            assert resp.status == 202
            assert json.loads(resp.read())["draining"] is True
            conn.request("GET", "/ingress/stats")
            st = json.loads(conn.getresponse().read())
            assert st["draining"] is True and st["ready"] is False
        finally:
            conn.close()
        requeued0 = router.stats()["requeued"]
        rng = np.random.default_rng(67)
        evs = [{"t_s": 0.0,
                "prompt": rng.integers(0, 128, size=9).tolist(),
                "max_tokens": 3}
               for _ in range(4)]
        res = replay_trace_http(fleet["port"], evs)
        _settle(sup, router)
        # Every request still finishes (r1 absorbed the refused ones)...
        assert all(r["status"] == 200 for r in res)
        assert all(r["finish_reason"] in ("stop", "length") for r in res)
        # ...and at least one rode the 503 -> failover requeue (cold
        # round-robin ties alternate, so some MUST have tried r0 first).
        assert router.stats()["requeued"] > requeued0
        assert rep.await_drained(timeout_s=30.0)
        port = rep.restart()
        router.rejoin("r0", port=port)
        assert rep.ready()
