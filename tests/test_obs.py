"""Telemetry subsystem: registry semantics, exports, tracer, overhead.

Fast tier (no ``slow`` marker). Covers the ISSUE-1 contracts:

- counter/gauge/histogram semantics and label handling;
- Prometheus/JSON export agreement (round-trip through a minimal text
  parser);
- span nesting and JSONL validity (every emitted line ``json.loads``);
- the disabled fast path is allocation-free (the guard that keeps hot-path
  instrumentation overhead-free when telemetry is off);
- integration: a CPU decode CLI run with ``--metrics-out``/``--trace-events``
  emits nonzero token + collective-payload counters and well-formed trace
  events.

And the ISSUE-4 serving-observability contracts:

- ``Histogram.quantile`` monotone bucket interpolation + the shared
  ``percentile`` definition;
- flight-recorder ring semantics, dumps, and liveness age;
- SLO window math vs oracle percentiles, window sliding, and goodput;
- the live HTTP endpoints (``/metrics`` ``/metrics.json`` ``/healthz``
  ``/flight``) against a real loopback server;
- crash-safe telemetry: a SIGTERM'd process still flushes metrics, trace,
  and flight-recorder sinks (subprocess test);
- the disabled-path zero-allocation guard extended to the new hooks.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import tracemalloc
import urllib.error
import urllib.request

import pytest

from tree_attention_tpu.obs.flight import FlightRecorder, TickPhases
from tree_attention_tpu.obs.http import MetricsHTTPServer
from tree_attention_tpu.obs.metrics import (
    MetricsRegistry,
    percentile,
)
from tree_attention_tpu.obs.slo import SLOMonitor
from tree_attention_tpu.obs.tracing import SpanTracer, _NOOP_SPAN

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _enabled_registry():
    reg = MetricsRegistry()
    reg.enable()
    return reg


class TestCounter:
    def test_inc_and_value(self):
        reg = _enabled_registry()
        c = reg.counter("steps_total", "steps")
        c.inc()
        c.inc(41)
        assert c.value() == 42

    def test_negative_increment_rejected(self):
        c = _enabled_registry().counter("c_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_disabled_is_noop(self):
        reg = MetricsRegistry()  # starts disabled
        c = reg.counter("c_total")
        c.inc(100)
        assert c.value() == 0
        reg.enable()
        c.inc(1)
        assert c.value() == 1
        reg.disable()
        c.inc(100)
        assert c.value() == 1

    def test_thread_safety(self):
        reg = _enabled_registry()
        c = reg.counter("c_total")

        def work():
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == 8000


class TestGauge:
    def test_set_inc_dec(self):
        g = _enabled_registry().gauge("fill")
        g.set(10)
        g.inc(5)
        g.dec(3)
        assert g.value() == 12


class TestHistogram:
    def test_bucket_counts_cumulative_export(self):
        reg = _enabled_registry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        (sample,) = _find(reg.snapshot(), "lat_seconds")["samples"]
        assert sample["count"] == 5
        assert sample["sum"] == pytest.approx(56.05)
        # Cumulative per the Prometheus le convention.
        assert sample["buckets"] == [
            [0.1, 1], [1.0, 3], [10.0, 4], ["+Inf", 5],
        ]

    def test_boundary_lands_in_its_bucket(self):
        reg = _enabled_registry()
        h = reg.histogram("h", buckets=(1.0, 2.0))
        h.observe(1.0)  # le="1.0" includes the bound
        (sample,) = _find(reg.snapshot(), "h")["samples"]
        assert sample["buckets"][0] == [1.0, 1]

    def test_empty_buckets_rejected(self):
        with pytest.raises(ValueError):
            _enabled_registry().histogram("h", buckets=())


class TestLabels:
    def test_children_are_independent(self):
        reg = _enabled_registry()
        c = reg.counter("x_total", labels=("impl",))
        c.labels(impl="pallas").inc(2)
        c.labels(impl="naive").inc(3)
        assert c.labels(impl="pallas").value() == 2
        assert c.labels(impl="naive").value() == 3

    def test_labels_cached(self):
        c = _enabled_registry().counter("x_total", labels=("a",))
        assert c.labels(a="1") is c.labels(a="1")

    def test_wrong_label_names_raise(self):
        c = _enabled_registry().counter("x_total", labels=("a",))
        with pytest.raises(ValueError):
            c.labels(b="1")
        with pytest.raises(ValueError):
            c.labels(a="1", b="2")

    def test_mutating_labeled_parent_raises(self):
        c = _enabled_registry().counter("x_total", labels=("a",))
        with pytest.raises(ValueError):
            c.inc()

    def test_invalid_names_rejected(self):
        reg = _enabled_registry()
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("ok_total", labels=("bad-label",))


class TestRegistry:
    def test_reregistration_idempotent(self):
        reg = _enabled_registry()
        a = reg.counter("c_total", labels=("x",))
        b = reg.counter("c_total", labels=("x",))
        assert a is b

    def test_conflicting_redeclaration_raises(self):
        reg = _enabled_registry()
        reg.counter("c_total")
        with pytest.raises(ValueError):
            reg.gauge("c_total")
        with pytest.raises(ValueError):
            reg.counter("c_total", labels=("x",))

    def test_reset_keeps_registrations(self):
        reg = _enabled_registry()
        c = reg.counter("c_total")
        c.inc(5)
        reg.reset()
        assert c.value() == 0
        c.inc(1)
        assert c.value() == 1


def _find(snapshot, name):
    (m,) = [m for m in snapshot["metrics"] if m["name"] == name]
    return m


def _parse_prometheus(text):
    """Minimal text-format parser: {series_name: {frozen_labels: value}}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, value = line.rsplit(" ", 1)
        if "{" in head:
            name, _, rest = head.partition("{")
            labels = {}
            for pair in filter(None, rest.rstrip("}").split(",")):
                k, _, v = pair.partition("=")
                labels[k] = v.strip('"')
            key = frozenset(labels.items())
        else:
            name, key = head, frozenset()
        out.setdefault(name, {})[key] = float(value)
    return out


class TestExports:
    def test_json_prometheus_round_trip(self):
        reg = _enabled_registry()
        c = reg.counter("tok_total", "tokens", labels=("mode",))
        c.labels(mode="decode").inc(7)
        g = reg.gauge("cap")
        g.set(4096)
        h = reg.histogram("lat_seconds", buckets=(0.5, 5.0))
        h.observe(0.1)
        h.observe(1.0)

        snap = json.loads(reg.to_json())  # JSON export parses
        prom = _parse_prometheus(reg.to_prometheus())

        assert prom["tok_total"][frozenset({("mode", "decode")})] == 7
        assert prom["cap"][frozenset()] == 4096
        # Histogram series agree with the JSON cumulative buckets
        # (normalise the le spelling: text format prints 5.0 as "5").
        def le_key(le):
            return le if le == "+Inf" else float(le)

        prom_buckets = {}
        for key, v in prom["lat_seconds_bucket"].items():
            (le_val,) = [lv for lk, lv in key if lk == "le"]
            prom_buckets[le_key(le_val)] = v
        (sample,) = _find(snap, "lat_seconds")["samples"]
        for le, cum in sample["buckets"]:
            assert prom_buckets[le_key(le)] == cum
        assert prom["lat_seconds_count"][frozenset()] == sample["count"]
        assert prom["lat_seconds_sum"][frozenset()] == pytest.approx(
            sample["sum"]
        )

    def test_label_value_escaping(self):
        reg = _enabled_registry()
        c = reg.counter("c_total", labels=("err",))
        c.labels(err='oops "quoted"\nnewline\\slash').inc()
        text = reg.to_prometheus()
        # One line per sample even with an embedded newline in the value.
        (line,) = [
            ln for ln in text.splitlines() if ln.startswith("c_total{")
        ]
        assert '\\"quoted\\"' in line and "\\n" in line

    def test_write_json(self, tmp_path):
        reg = _enabled_registry()
        reg.counter("c_total").inc()
        path = tmp_path / "metrics.json"
        reg.write_json(str(path))
        data = json.loads(path.read_text())
        assert _find(data, "c_total")["samples"][0]["value"] == 1
        assert "process_index" in data


class TestTracer:
    def test_span_nesting_and_jsonl_validity(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        tracer = SpanTracer()
        tracer.start(str(path))
        with tracer.span("outer", args={"phase": 1}):
            with tracer.span("inner"):
                pass
        tracer.instant("verdict", args={"guard": "clean"})
        tracer.close()

        events = [json.loads(line) for line in path.read_text().splitlines()]
        assert events, "no events emitted"
        complete = {e["name"]: e for e in events if e["ph"] == "X"}
        assert set(complete) == {"outer", "inner"}
        outer, inner = complete["outer"], complete["inner"]
        for e in (outer, inner):
            assert {"ts", "dur", "pid", "tid"} <= set(e)
        # Nesting: inner lies within outer on the same track.
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["args"] == {"phase": 1}
        (instant,) = [e for e in events if e["ph"] == "i"]
        assert instant["args"] == {"guard": "clean"}
        # Metadata names the process for Perfetto's track grouping.
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in events)

    def test_exception_annotates_span(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        tracer = SpanTracer()
        tracer.start(str(path))
        with pytest.raises(RuntimeError):
            with tracer.span("failing"):
                raise RuntimeError("boom")
        tracer.close()
        (event,) = [
            json.loads(l) for l in path.read_text().splitlines()
            if json.loads(l)["ph"] == "X"
        ]
        assert event["args"]["error"] == "RuntimeError"

    def test_inactive_tracer_returns_shared_noop(self):
        tracer = SpanTracer()
        assert tracer.span("a") is tracer.span("b") is _NOOP_SPAN
        tracer.instant("nothing")  # must not raise


class TestPercentileAndQuantile:
    """Satellite: one shared nearest-rank percentile + monotone bucket
    interpolation on histograms (the SLO plane's two estimators)."""

    def test_percentile_nearest_rank(self):
        vals = [1.0, 2.0, 3.0, 4.0, 5.0]
        assert percentile(vals, 0.0) == 1.0
        assert percentile(vals, 0.5) == 3.0
        assert percentile(vals, 1.0) == 5.0
        assert percentile(vals, 0.95) == 5.0
        assert percentile([], 0.5) == 0.0
        assert percentile([7.0], 0.99) == 7.0

    def test_percentile_matches_serving_report_definition(self):
        # The engine's old hand-rolled _pct was exactly this formula; the
        # dedup must not shift any report's percentile.
        vals = sorted([0.3, 0.1, 0.9, 0.5, 0.7, 0.2])
        for p in (0.0, 0.25, 0.5, 0.9, 0.95, 1.0):
            expect = vals[min(len(vals) - 1, int(p * (len(vals) - 1) + 0.5))]
            assert percentile(vals, p) == expect

    def test_quantile_interpolates_within_bucket(self):
        reg = _enabled_registry()
        h = reg.histogram("q_seconds", buckets=(1.0, 2.0, 4.0))
        # 4 samples in (1, 2]: quantiles interpolate linearly across it.
        for _ in range(4):
            h.observe(1.5)
        assert h.quantile(0.5) == pytest.approx(1.5)
        assert h.quantile(1.0) == pytest.approx(2.0)
        assert h.quantile(0.25) == pytest.approx(1.25)

    def test_quantile_monotone_across_buckets(self):
        reg = _enabled_registry()
        h = reg.histogram("q_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 5.0, 5.0):
            h.observe(v)
        qs = [h.quantile(p) for p in (0.0, 0.2, 0.4, 0.6, 0.8, 1.0)]
        assert qs == sorted(qs)
        # The first bucket (1 of 6 samples) interpolates from 0; the top
        # stays finite.
        assert 0.0 < h.quantile(0.1) <= 0.1
        assert qs[-1] <= 10.0

    def test_quantile_inf_bucket_clamps_to_highest_bound(self):
        reg = _enabled_registry()
        h = reg.histogram("q_seconds", buckets=(1.0, 2.0))
        h.observe(100.0)  # lands in +Inf
        assert h.quantile(0.99) == 2.0

    def test_quantile_empty_and_bad_p(self):
        reg = _enabled_registry()
        h = reg.histogram("q_seconds", buckets=(1.0,))
        assert h.quantile(0.5) == 0.0
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_quantile_labeled_parent_raises(self):
        reg = _enabled_registry()
        h = reg.histogram("q_seconds", labels=("x",), buckets=(1.0,))
        with pytest.raises(ValueError):
            h.quantile(0.5)
        assert h.labels(x="a").quantile(0.5) == 0.0


class TestFlightRecorder:
    def test_disabled_record_is_noop(self):
        fr = FlightRecorder(capacity=4)
        fr.record({"tick": 0})
        assert fr.ticks_recorded == 0
        assert fr.last_tick_age() is None
        assert fr.snapshot()["records"] == []

    def test_ring_keeps_last_capacity_records_in_order(self):
        fr = FlightRecorder(capacity=3)
        fr.arm()
        for i in range(7):
            fr.record({"tick": i})
        snap = fr.snapshot()
        assert snap["ticks_recorded"] == 7
        assert [r["tick"] for r in snap["records"]] == [4, 5, 6]
        assert snap["capacity"] == 3
        assert snap["last_tick_age_s"] is not None

    def test_dump_writes_valid_json(self, tmp_path):
        fr = FlightRecorder(capacity=8)
        fr.arm()
        fr.record({"tick": 0, "states": ["live"]})
        path = tmp_path / "sub" / "flight.json"  # parent dir created
        fr.dump(str(path), reason="test")
        data = json.loads(path.read_text())
        assert data["reason"] == "test"
        assert data["records"] == [{"tick": 0, "states": ["live"]}]

    def test_dump_if_armed_needs_a_sink(self, tmp_path):
        fr = FlightRecorder()
        fr.arm()  # memory-only
        fr.record({"tick": 0})
        assert fr.dump_if_armed("x") is None
        path = str(tmp_path / "f.json")
        fr.arm(path)
        assert fr.dump_if_armed("err") == path
        assert json.loads(open(path).read())["reason"] == "err"
        fr.disarm()
        assert fr.dump_if_armed("late") is None

    def test_clear_resets_liveness(self):
        fr = FlightRecorder()
        fr.arm()
        fr.record({"tick": 0})
        fr.clear()
        assert fr.ticks_recorded == 0
        assert fr.last_tick_age() is None

    def test_bad_capacity_rejected(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)


class TestSLOMonitor:
    def test_window_percentiles_match_oracle(self):
        import random

        rng = random.Random(3)
        mon = SLOMonitor(ttft_slo=1.0, tbt_slo=0.1, window=64)
        vals = [rng.uniform(0.0, 2.0) for _ in range(64)]
        for v in vals:
            mon.observe_ttft(v)
            mon.observe_tbt(v)
            mon.observe_queue_wait(v)
        snap = mon.snapshot()
        s = sorted(vals)
        for p, tag in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            oracle = percentile(s, p)
            assert snap[f"ttft_{tag}_s"] == pytest.approx(oracle, abs=1e-6)
            assert snap[f"tbt_{tag}_s"] == pytest.approx(oracle, abs=1e-6)
            assert snap[f"queue_wait_{tag}_s"] == pytest.approx(
                oracle, abs=1e-6)

    def test_window_slides(self):
        mon = SLOMonitor(window=4)
        for v in (9.0, 9.0, 9.0, 1.0, 1.0, 1.0, 1.0):
            mon.observe_ttft(v)
        # Only the last 4 observations (all 1.0) remain visible.
        assert mon.snapshot()["ttft_p99_s"] == 1.0

    def test_goodput_verdicts(self):
        mon = SLOMonitor(ttft_slo=1.0, tbt_slo=0.1, window=8)
        assert mon.goodput() == 1.0  # idle server: not failing its SLO
        assert mon.observe_request(0.5, 0.05) is True
        assert mon.observe_request(2.0, 0.05) is False   # TTFT miss
        assert mon.observe_request(0.5, 0.50) is False   # TBT miss
        assert mon.observe_request(1.0, 0.1) is True     # inclusive bound
        assert mon.goodput() == pytest.approx(0.5)
        snap = mon.snapshot()
        assert snap["goodput"] == pytest.approx(0.5)
        assert snap["requests_in_window"] == 4
        assert snap["requests_retired"] == 4

    def test_goodput_window_slides(self):
        mon = SLOMonitor(ttft_slo=1.0, tbt_slo=0.1, window=2)
        mon.observe_request(9.0, 9.0)  # bad, slides out below
        mon.observe_request(0.1, 0.01)
        mon.observe_request(0.1, 0.01)
        assert mon.goodput() == 1.0
        assert mon.snapshot()["requests_retired"] == 3

    def test_gauges_export_when_registry_enabled(self):
        from tree_attention_tpu.obs import REGISTRY

        mon = SLOMonitor(ttft_slo=1.0, tbt_slo=0.1, window=8)
        mon.observe_ttft(0.25)
        mon.observe_request(0.25, 0.0)
        was = REGISTRY.enabled
        REGISTRY.enable()
        try:
            mon.export_gauges()
            g = REGISTRY.get("serving_slo_ttft_seconds")
            assert g.labels(q="p50").value() == pytest.approx(0.25)
            assert REGISTRY.get("serving_goodput_ratio").value() == 1.0
            assert REGISTRY.get("serving_slo_window_requests").value() == 1
        finally:
            if not was:
                REGISTRY.disable()

    def test_lifetime_quantiles_from_histograms(self):
        # Histogram.quantile reuse: snapshot carries run-lifetime TTFT/TBT
        # quantiles interpolated from the cumulative histograms.
        from tree_attention_tpu import obs
        import tree_attention_tpu.serving.engine  # registers the hists

        obs.enable()
        try:
            obs.REGISTRY.get("serving_ttft_seconds").observe(0.3)
            snap = SLOMonitor().snapshot()
            assert "ttft_lifetime_p50_s" in snap
            assert snap["ttft_lifetime_p50_s"] > 0
        finally:
            obs.disable()

    def test_bad_args_rejected(self):
        with pytest.raises(ValueError):
            SLOMonitor(ttft_slo=0.0)
        with pytest.raises(ValueError):
            SLOMonitor(tbt_slo=-1.0)
        with pytest.raises(ValueError):
            SLOMonitor(window=0)


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.status, r.read().decode()


class TestHTTPEndpoints:
    """The live exporter against a real loopback server (port 0 = OS
    pick), over a dedicated registry + flight recorder."""

    @pytest.fixture()
    def server(self):
        reg = MetricsRegistry(enabled=True)
        reg.counter("http_test_total", "h").inc(7)
        reg.gauge("http_cap").set(4)
        fr = FlightRecorder(capacity=4)
        fr.arm()
        srv = MetricsHTTPServer(
            0, registry=reg, flight=fr, stall_after=30.0
        )
        srv.start()
        yield srv, reg, fr
        srv.stop()

    def test_metrics_text_matches_registry(self, server):
        srv, reg, _ = server
        status, body = _get(srv.port, "/metrics")
        assert status == 200
        assert body == reg.to_prometheus()
        assert "http_test_total 7" in body

    def test_metrics_json_matches_snapshot(self, server):
        srv, reg, _ = server
        status, body = _get(srv.port, "/metrics.json")
        assert status == 200
        data = json.loads(body)
        assert {m["name"] for m in data["metrics"]} == {
            m["name"] for m in reg.snapshot()["metrics"]
        }

    def test_metrics_live_not_cached(self, server):
        srv, reg, _ = server
        reg.counter("http_test_total").inc(5)
        _, body = _get(srv.port, "/metrics")
        assert "http_test_total 12" in body

    def test_healthz_idle_then_ok(self, server):
        srv, _, fr = server
        status, body = _get(srv.port, "/healthz")
        assert status == 200 and json.loads(body)["status"] == "idle"
        fr.record({"tick": 0})
        status, body = _get(srv.port, "/healthz")
        body = json.loads(body)
        assert status == 200 and body["status"] == "ok"
        assert body["ticks_recorded"] == 1
        assert body["last_tick_age_s"] < 30.0

    def test_healthz_stalled_returns_503(self, server):
        srv, _, fr = server
        fr.record({"tick": 0})
        fr._last_tick_t = time.monotonic() - 120.0
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(srv.port, "/healthz")
        assert err.value.code == 503
        assert json.loads(err.value.read().decode())["status"] == "stalled"

    def test_healthz_idle_again_after_drain(self, server):
        """A drained serve() run (mark_idle) must not age into 'stalled' —
        finished is not wedged, however old the last tick gets."""
        srv, _, fr = server
        fr.record({"tick": 0})
        fr.mark_idle()
        fr._last_tick_t = time.monotonic() - 120.0  # long past stall_after
        status, body = _get(srv.port, "/healthz")
        assert status == 200 and json.loads(body)["status"] == "idle"

    def test_flight_endpoint_serves_ring(self, server):
        srv, _, fr = server
        fr.record({"tick": 0, "occupancy": 2})
        status, body = _get(srv.port, "/flight")
        assert status == 200
        data = json.loads(body)
        assert data["records"] == [{"tick": 0, "occupancy": 2}]

    def test_unknown_path_404(self, server):
        srv, _, _ = server
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(srv.port, "/nope")
        assert err.value.code == 404

    def test_index_lists_endpoints(self, server):
        srv, _, _ = server
        status, body = _get(srv.port, "/")
        assert status == 200 and "/healthz" in body


_CRASH_SCRIPT = """
import os, sys, time
sys.path.insert(0, {repo!r})
from tree_attention_tpu import obs

obs.configure(metrics_out={metrics!r}, trace_events={trace!r},
              flight_out={flight!r})
assert obs.install_crash_handlers()
obs.counter("crash_test_total").inc(3)
with obs.span("crash_phase"):
    pass
for i in range(5):
    obs.FLIGHT.record({{"tick": i}})
print("READY", flush=True)
time.sleep(60)  # killed long before this returns
"""


def test_sigterm_flushes_all_sinks(tmp_path):
    """Crash-safe telemetry (ISSUE-4 satellite): SIGTERM mid-run still
    writes the metrics snapshot, flushes the span trace, and dumps the
    flight ring — and the process still dies by SIGTERM."""
    metrics = str(tmp_path / "m.json")
    trace = str(tmp_path / "t.jsonl")
    flight = str(tmp_path / "f.json")
    proc = subprocess.Popen(
        [sys.executable, "-c", _CRASH_SCRIPT.format(
            repo=REPO, metrics=metrics, trace=trace, flight=flight)],
        stdout=subprocess.PIPE, text=True, cwd=str(tmp_path),
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
    finally:
        proc.kill()
    assert rc == -signal.SIGTERM  # the kill stayed a kill
    data = json.loads(open(metrics).read())
    (c,) = [m for m in data["metrics"] if m["name"] == "crash_test_total"]
    assert c["samples"][0]["value"] == 3
    events = [json.loads(l) for l in open(trace).read().splitlines()]
    assert any(e.get("name") == "crash_phase" for e in events)
    fdata = json.loads(open(flight).read())
    assert [r["tick"] for r in fdata["records"]] == [0, 1, 2, 3, 4]
    assert fdata["reason"] == "flush"


def test_sigusr1_dumps_and_keeps_running(tmp_path):
    """SIGUSR1 is the live poke: dump the armed sinks, do NOT exit."""
    flight = str(tmp_path / "f.json")
    script = _CRASH_SCRIPT.format(
        repo=REPO, metrics=None, trace=None, flight=flight,
    ) + "\n"
    # Replace the tail: after READY, wait for the dump then exit cleanly.
    script = script.replace(
        "time.sleep(60)  # killed long before this returns",
        "t0 = time.time()\n"
        "while not os.path.exists({flight!r}) and time.time() - t0 < 30:\n"
        "    time.sleep(0.05)\n"
        "print('DUMPED' if os.path.exists({flight!r}) else 'TIMEOUT',"
        " flush=True)\n".format(flight=flight),
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        stdout=subprocess.PIPE, text=True, cwd=str(tmp_path),
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGUSR1)
        assert proc.stdout.readline().strip() == "DUMPED"
        assert proc.wait(timeout=30) == 0  # survived the signal
    finally:
        proc.kill()
    assert json.loads(open(flight).read())["reason"] == "flush"


class TestDisabledOverhead:
    """The hot-path guard: telemetry off must mean no-op AND no per-call
    allocation — the contract that lets heartbeat()/inc() sit on timing
    paths unconditionally."""

    def test_no_per_call_allocation_when_disabled(self):
        reg = MetricsRegistry()  # disabled
        c = reg.counter("c_total")
        child = reg.counter("l_total", labels=("a",)).labels(a="x")
        g = reg.gauge("g")
        h = reg.histogram("h_seconds")
        tracer = SpanTracer()  # inactive
        flight = FlightRecorder()  # disarmed
        tick_rec = {"tick": 0}  # prebuilt, as the engine's guard requires
        # The tick-phase stamper (ISSUE 24) keeps the same contract: off,
        # begin() latches and mark()/finish() return at one check.
        phases = TickPhases()
        # The speculative-decoding hooks (ISSUE 8) ride the same guard:
        # the engine's verify commit calls these module-level metrics
        # only under REGISTRY.enabled — exercised here through the real
        # objects (registered on the global, disabled registry).
        # The copy-on-write fork hooks (ISSUE 15) ride the same guard:
        # _fork_child bumps these only under REGISTRY.enabled.
        # The token-tree sibling hooks (ISSUE 20) too: the branch gauge
        # and the stochastic accept-sample counter.
        from tree_attention_tpu.serving.engine import (
            _FORKS, _FORK_SHARED,
            _SPEC_ACCEPTED, _SPEC_ACCEPT_RATIO, _SPEC_PROPOSED,
            _SPEC_ACCEPT_SAMPLES, _TREE_BRANCHES,
        )

        def hot_path():
            c.inc()
            child.inc(3)
            g.set(2.0)
            h.observe(0.5)
            _SPEC_PROPOSED.inc(4)
            _SPEC_ACCEPTED.inc(2)
            _SPEC_ACCEPT_RATIO.set(0.5)
            _FORKS.inc()
            _FORK_SHARED.inc(7)
            _TREE_BRANCHES.set(8)
            _SPEC_ACCEPT_SAMPLES.inc(4)
            with tracer.span("phase"):
                pass
            tracer.instant("event")
            flight.record(tick_rec)
            flight.record(None)  # the disabled-guard calling shape
            phases.begin(0.0)
            phases.mark("sweep")
            phases.mark("dispatch", 7, "mixed", 256)  # positional: no dict
            phases.finish(None)

        hot_path()  # warm any lazy caches before measuring
        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            for _ in range(5000):
                hot_path()
            grown = tracemalloc.get_traced_memory()[0] - base
        finally:
            tracemalloc.stop()
        # Zero net allocation modulo interpreter noise: 5000 iterations
        # with even ONE surviving allocation each would grow tens of KB.
        assert grown < 4096, f"disabled hot path allocated {grown} B"
        assert c.value() == 0 and child.value() == 0

    def test_instrumented_modules_keep_registry_disabled_by_default(self):
        # Importing instrumented layers must register metrics without
        # enabling anything (telemetry is opt-in per run).
        import tree_attention_tpu.host_runtime  # noqa: F401
        import tree_attention_tpu.utils.profiling  # noqa: F401
        from tree_attention_tpu.obs import REGISTRY, TRACER

        assert not REGISTRY.enabled
        assert not TRACER.active
        assert REGISTRY.get("heartbeat_ticks_total") is not None
        assert REGISTRY.get("timing_guard_verdicts_total") is not None

    def test_heartbeat_disabled_records_nothing(self):
        from tree_attention_tpu.host_runtime import heartbeat
        from tree_attention_tpu.obs import REGISTRY

        ticks = REGISTRY.get("heartbeat_ticks_total")
        before = ticks.value()
        was_enabled = REGISTRY.enabled
        REGISTRY.disable()
        try:
            heartbeat()
        finally:
            if was_enabled:
                REGISTRY.enable()
        assert ticks.value() == before


@pytest.mark.parametrize("mesh", [True])
def test_cli_decode_emits_telemetry(tmp_path, mesh):
    """Integration (ISSUE 1 acceptance): a CPU decode run with
    --metrics-out + --trace-events produces (a) a metrics JSON with
    nonzero decode-token and collective-payload counters and (b) a
    Chrome-trace JSONL that json.loads cleanly per line."""
    metrics = tmp_path / "metrics.json"
    trace = tmp_path / "trace.jsonl"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the CLI sets its own virtual-device flags
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "tree_attention_tpu",
         "--device", "cpu", "--n-virtual-cpu", "8", "--mesh", "seq=8",
         "--seq-len", "256", "--heads", "2", "--head-dim", "16",
         "--dtype", "float32", "--impl", "blockwise", "--block-size", "32",
         "--causal", "--iters", "2", "--warmup", "1",
         "--metrics-out", str(metrics), "--trace-events", str(trace)],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]

    data = json.loads(metrics.read_text())
    by_name = {m["name"]: m for m in data["metrics"]}

    def total(name, **labels):
        return sum(
            s["value"] for s in by_name[name]["samples"]
            if all(s["labels"].get(k) == v for k, v in labels.items())
        )

    # (a) nonzero decode-token and collective-payload counters.
    assert total("decode_tokens_total") > 0
    assert total("decode_kv_tokens_total") > 0
    assert total("decode_steps_total") > 0
    assert total("collective_payload_bytes_total", algorithm="tree_decode") > 0
    assert total("parallel_dispatch_total", algorithm="tree_decode") > 0
    # The hygiene guards filed a verdict for the run.
    assert total("timing_guard_verdicts_total") > 0

    # (b) every trace line parses; the run produced real spans with the
    # process-index pid contract.
    events = [json.loads(line) for line in trace.read_text().splitlines()]
    complete = [e for e in events if e.get("ph") == "X"]
    assert complete, "no complete spans in the trace"
    names = {e["name"] for e in complete}
    assert "mode:decode" in names and "time_fn" in names
    assert all(e["pid"] == 0 for e in complete)
    assert all(
        isinstance(e["ts"], int) and isinstance(e["dur"], int)
        and e["dur"] >= 0 for e in complete
    )
