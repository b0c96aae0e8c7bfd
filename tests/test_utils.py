"""Utils layer: config parsing, logging sinks, fenced timing."""

import json
import logging
import os

import jax.numpy as jnp
import pytest

from tree_attention_tpu.utils import (
    RunConfig,
    TimingStats,
    device_memory_stats,
    get_logger,
    parse_args,
    parse_mesh_spec,
    setup_logging,
    time_fn,
    trace,
)


class TestConfig:
    def test_defaults_reproduce_reference_workload(self):
        # /root/reference/model.py:140-145,51-53 — seq 64000, 16 heads,
        # head_dim 128, B=1, single-query decode.
        cfg = parse_args([])
        assert (cfg.seq_len, cfg.heads, cfg.head_dim, cfg.batch, cfg.q_len) == (
            64000, 16, 128, 1, 1,
        )
        assert cfg.mode == "decode" and not cfg.causal

    def test_flags_roundtrip(self):
        cfg = parse_args(
            "--mode bench --seq-len 4096 --heads 8 --kv-heads 2 --head-dim 64 "
            "--causal --dtype float32 --mesh data=2,seq=4 --comparator ring "
            "--impl blockwise --iters 3".split()
        )
        assert cfg.mode == "bench" and cfg.seq_len == 4096
        assert cfg.resolved_kv_heads() == 2 and cfg.causal
        assert cfg.mesh_axes() == {"data": 2, "seq": 4}
        assert cfg.comparator == "ring" and cfg.iters == 3

    def test_kv_heads_default_is_mha(self):
        assert RunConfig(heads=12).resolved_kv_heads() == 12

    def test_mesh_spec_errors(self):
        with pytest.raises(ValueError):
            parse_mesh_spec("seq")
        with pytest.raises(ValueError):
            parse_mesh_spec("seq=2,seq=4")
        with pytest.raises(ValueError):
            parse_mesh_spec("")
        assert parse_mesh_spec("data=2, seq=-1") == {"data": 2, "seq": -1}


class TestLogging:
    def test_process_prefix_and_file_sink(self, tmp_path):
        log = tmp_path / "run.log"
        setup_logging(logging.DEBUG, log_file=str(log))
        get_logger("kernel").info("block %d done", 7)
        text = log.read_text()
        assert "[p0]" in text and "block 7 done" in text
        assert "tree_attention_tpu.kernel" in text

    def test_nonzero_process_clamped_to_warning(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_PROCESS_INDEX", "3")
        # jax is imported in this test process, so fake its process_index too.
        import jax

        monkeypatch.setattr(jax, "process_index", lambda: 3)
        log = tmp_path / "p3.log"
        setup_logging(logging.INFO, log_file=str(log))
        get_logger().info("chatty")
        get_logger().warning("important")
        text = log.read_text()
        assert "chatty" not in text and "important" in text
        assert "[p3]" in text

    def test_setup_idempotent(self):
        r1 = setup_logging()
        r2 = setup_logging()
        assert r1 is r2 and len(r2.handlers) == 1


class TestProfiling:
    def test_time_fn_stats(self):
        calls = []

        def f(x):
            calls.append(1)
            return jnp.asarray(x) * 2

        stats = time_fn(f, 3, iters=4, warmup=1)
        assert isinstance(stats, TimingStats)
        assert stats.iters == 4 and len(calls) == 5
        assert stats.minimum <= stats.median <= stats.maximum
        assert stats.tokens_per_sec(1000) == 1000 / stats.median
        assert set(stats.as_dict()) == {
            "median_s", "mean_s", "min_s", "max_s", "iters",
        }
        json.dumps(stats.as_dict())  # JSON-serialisable for bench records

    def test_time_per_step_slope(self):
        import time as _time

        from tree_attention_tpu.utils.profiling import time_per_step

        def make(n):
            def run():
                _time.sleep(0.010 + 0.003 * n)  # fixed 10ms + 3ms/step

            return run

        per, s_small, s_large = time_per_step(
            make, n_small=2, n_large=10, iters=3, warmup=0, fetch=False
        )
        # The slope recovers ~3ms/step, not the 10ms fixed cost; bounds are
        # wide because time.sleep oversleeps under load.
        assert 0.001 < per < 0.010
        assert s_small.iters == 3 and s_large.median > s_small.median

    def test_time_per_step_validates_range(self):
        from tree_attention_tpu.utils.profiling import time_per_step

        with pytest.raises(ValueError):
            time_per_step(lambda n: (lambda: None), n_small=8, n_large=8)

    def test_time_per_step_min_stat(self):
        # min-stat slope survives large positive RPC-style spikes that
        # would flip the median-based slope negative: simulate durations by
        # advancing a fake clock inside the timed call.
        import itertools

        import tree_attention_tpu.utils.profiling as prof
        from tree_attention_tpu.utils.profiling import time_per_step

        # Spikes drive the small side's MEDIAN above the large side's
        # (median slope would be negative and raise); the min picks the one
        # clean call per side and recovers the true 3 ms/step slope.
        base = {2: 0.010 + 0.003 * 2, 10: 0.010 + 0.003 * 10}
        spikes = {2: [0.5, 0.5, 0.0], 10: [0.0, 0.0, 0.5]}
        state = {"t": 0.0}

        def fake_fn(n):
            seq = itertools.count()

            def run():
                i = next(seq)
                state["t"] += base[n] + (spikes[n][i] if i < 3 else 0.0)

            return run

        real = prof.time.perf_counter
        prof.time.perf_counter = lambda: state["t"]
        try:
            per, _, _ = time_per_step(
                fake_fn, n_small=2, n_large=10, iters=3, warmup=0,
                fetch=False, stat="min",
            )
        finally:
            prof.time.perf_counter = real
        assert abs(per - 0.003) < 1e-9

        with pytest.raises(ValueError):
            time_per_step(lambda n: (lambda: None), n_small=2, n_large=4,
                          stat="p99")

    def test_slope_per_step_repeats_takes_min_cycle_and_reports_spread(self):
        # A contended first measurement window inflates BOTH sides' minima
        # together, which a single cycle cannot detect (the r4 driver
        # capture read decode_64k 33 points low this way). Repeats re-time
        # the same compiled programs; the min positive cycle slope recovers
        # the clean number and the spread records the contention.
        import tree_attention_tpu.utils.profiling as prof
        from tree_attention_tpu.utils.profiling import slope_per_step

        state = {"t": 0.0, "calls": 0}
        base = {2: 0.010 + 0.003 * 2, 10: 0.010 + 0.003 * 10}
        made = []

        def fake_fn(n):
            made.append(n)

            def run():
                # Cycle 1 (first 4 timed calls at iters=2): 1.6x contended.
                factor = 1.6 if state["calls"] < 4 else 1.0
                state["calls"] += 1
                state["t"] += base[n] * factor

            return run

        real = prof.time.perf_counter
        prof.time.perf_counter = lambda: state["t"]
        try:
            s = slope_per_step(
                fake_fn, n_small=2, n_large=10, iters=2, warmup=0,
                fetch=False, stat="min", repeats=3,
            )
        finally:
            prof.time.perf_counter = real
        assert made == [2, 10]  # programs built once, reused across cycles
        assert len(s.slopes) == 3
        assert abs(s.per_step - 0.003) < 1e-9          # min = clean cycles
        assert abs(s.slopes[0] - 0.0048) < 1e-9        # contended cycle
        assert abs(s.spread_pct - 60.0) < 1e-6         # (4.8-3)/3

    def test_slope_per_step_all_nonpositive_cycles_raise(self):
        # Fake clock: every call costs exactly the same regardless of n,
        # so the slope is exactly 0 in every cycle (a real clock would
        # make this flaky — scheduling jitter can tip a zero slope
        # positive by chance).
        import tree_attention_tpu.utils.profiling as prof
        from tree_attention_tpu.utils.profiling import slope_per_step

        state = {"t": 0.0}

        def flat_fn(n):
            def run():
                # n-independent: zero marginal cost. 2^-6 is binary-exact,
                # so every perf_counter delta is bitwise identical and the
                # slope is exactly 0 (0.010 left 1e-19 of representation
                # error, enough to read as a "positive" slope).
                state["t"] += 0.015625

            return run

        real = prof.time.perf_counter
        prof.time.perf_counter = lambda: state["t"]
        try:
            with pytest.raises(RuntimeError, match="non-positive"):
                slope_per_step(flat_fn, n_small=2, n_large=10, iters=1,
                               warmup=0, fetch=False, stat="min", repeats=2)
        finally:
            prof.time.perf_counter = real
        with pytest.raises(ValueError):
            slope_per_step(flat_fn, n_small=2, n_large=10, repeats=0)

    def test_time_fn_fetch_fence(self):
        stats = time_fn(lambda: jnp.arange(8.0) * 2, iters=2, warmup=1,
                        fetch=True)
        assert stats.iters == 2

    def test_deflation_suspect_rules(self):
        # The min-stat estimator assumes contention only inflates a cycle;
        # deflation_suspect is the defence for the counterexample: a fence
        # that resolves early deflates a cycle while staying under the
        # physical ceilings.
        from tree_attention_tpu.utils.profiling import (
            SlopeStats,
            deflation_suspect,
            time_fn,
        )

        ts = time_fn(lambda: None, iters=1, warmup=0, fetch=False)

        def stats(slopes):
            pos = [s for s in slopes if s > 0]
            return SlopeStats(
                per_step=min(pos), slopes=tuple(slopes),
                spread_pct=(max(pos) - min(pos)) / min(pos) * 100,
                small=ts, large=ts,
            )

        # Deflated min among >= 3 cycles: flagged.
        assert "deflation" in deflation_suspect(stats((0.5, 1.0, 1.02)))
        # Genuine contention (min == median): quiet.
        assert deflation_suspect(stats((1.0, 1.0, 1.4))) is None
        # Two cycles can't distinguish the cases: quiet even at 2.5x
        # (the caller chose repeats < 3; that is its documented contract).
        assert deflation_suspect(stats((1.0, 2.5))) is None
        # ANY non-positive cycle is hard evidence of a faulty window —
        # a chain cannot cost nothing — and flags the record even when
        # enough clean-looking siblings survive ("could not check" must
        # not read as "checked and clean").
        for slopes in ((-0.1, 0.5, 1.0, 1.02), (-0.1, -0.2, 1.0),
                       (-0.1, 1.0, 1.0, 1.02)):
            reason = deflation_suspect(stats(slopes))
            assert reason is not None and "non-positive" in reason

    def test_time_fn_rejects_zero_iters(self):
        with pytest.raises(ValueError):
            time_fn(lambda: None, iters=0)

    def test_memory_stats_none_or_dict(self):
        stats = device_memory_stats()
        assert stats is None or (
            isinstance(stats, dict)
            and all(isinstance(v, int) for v in stats.values())
        )

    def test_trace_noop_and_capture(self, tmp_path):
        with trace(None):
            pass
        d = tmp_path / "prof"
        with trace(str(d)):
            jnp.ones((4,)).sum().block_until_ready()
        assert os.path.isdir(d)
