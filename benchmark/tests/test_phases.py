"""The readers of the engine's phase stamps, on hand-made flight records and
device events, and once end to end in a CPU rehearsal."""

import json
import os
import types

import pytest

from benchmark import phases, run
from benchmark import trace_reduce as tr
from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "BENCHMARK.json")
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal")
OFFSET = 1000.0                # the trace's clock minus time.monotonic()


def record(top, spent, **more):
    """A flight record whose phases last ``spent`` (name, seconds) in turn
    from ``top``, on the host's clock."""
    marks, t = [], top
    for name, seconds in spent:
        marks.append([name, t])
        t += seconds
    return dict({"t_s": top, "phases": marks, "t_end": t}, **more)


DECODE = [("ingest", 0.001), ("sweep", 0.001), ("admit", 0.002),
          ("plan", 0.001), ("pack", 0.002), ("table_sync", 0.001),
          ("dispatch", 0.004), ("publish", 0.0), ("fetch", 0.020),
          ("emit", 0.003), ("account", 0.001)]          # 36 ms, 16 the host's


def a_run(flight, trace=None, t_open=0.0, t_end=100.0):
    return types.SimpleNamespace(flight=flight, trace=trace, t_open=t_open,
                                 t_end=t_end)


def a_trace(device_events, t0, t1):
    """What ``Recording.reduce`` hands the readers, from hand-made events
    on the trace's clock."""
    data = {"host": {"python": [(tr.BEGIN_MARK, t0, 0.0),
                                (tr.END_MARK, t1, 0.0)]},
            "devices": device_events}
    return dict(tr.reduce_events(data), offset_s=OFFSET)


def test_a_phase_lasts_until_the_next_and_the_last_until_t_end():
    rec = record(5.0, DECODE)
    got = phases.tick_phases(rec)
    assert [p[0] for p in got] == [name for name, _ in DECODE]
    assert got[0][1:] == (5.0, pytest.approx(5.001))
    assert got[-1][2] == rec["t_end"] == pytest.approx(5.036)
    for (_, _, end), (_, start, _) in zip(got, got[1:]):
        assert end == start


def test_tick_medians_leave_out_fetch_and_ticks_without_one():
    flight = [record(1.0 + i, DECODE) for i in range(9)]
    # A tick of nothing but mid-prompt chunks fetches nothing: not counted.
    flight.append(record(20.0, [("ingest", 0.5), ("dispatch", 0.5),
                                ("account", 0.5)]))
    run_ = a_run(flight)
    assert len(phases.fetched_ticks(run_)) == 9
    assert phases.percentile_ms(run_, None, 0.5, but=("fetch",)) \
        == pytest.approx(16.0)
    assert phases.percentile_ms(run_, ("ingest", "sweep", "admit"), 0.99) \
        == pytest.approx(4.0)
    assert phases.percentile_ms(
        run_, ("plan", "pack", "table_sync", "dispatch"), 0.5) \
        == pytest.approx(8.0)
    assert phases.percentile_ms(run_, ("emit", "account"), 0.5) \
        == pytest.approx(4.0)


def test_ticks_that_end_after_the_profiler_started_are_left_out():
    slow = [(n, 10 * s) for n, s in DECODE]      # what the tracer costs
    flight = [record(1.0, DECODE), record(2.0, DECODE),
              record(49.99, DECODE),             # ends after the start
              record(51.0, slow), record(52.0, slow)]
    trace = a_trace({"/device:TPU:0": [("fusion.1", OFFSET + 50.0, 1.0)]},
                    OFFSET + 50.0, OFFSET + 53.0)
    run_ = a_run(flight, trace)
    assert phases.profiler_start(run_) == pytest.approx(50.0)
    assert len(phases.fetched_ticks(run_)) == 2
    assert phases.percentile_ms(run_, None, 0.5, but=("fetch",)) \
        == pytest.approx(16.0)
    # The same ticks inside the traced part, asked for by name.
    inside = phases.percentile_ms(run_, None, 0.5, but=("fetch",),
                                  t0=50.0, t1=run_.t_end)
    assert inside == pytest.approx(160.0)
    # Before the window opened: not the window's.
    assert len(phases.fetched_ticks(a_run(flight, trace, t_open=1.5))) == 1
    # Without a trace the whole window counts.
    assert len(phases.fetched_ticks(a_run(flight))) == 5


def test_a_program_without_the_stamps_reads_as_nothing():
    old = [{"tick": i, "t_s": float(i), "occupancy": 2, "chunk_tokens": 0}
           for i in range(5)]
    trace = a_trace({"/device:TPU:0": [("fusion.1", OFFSET + 1.0, 1.0)]},
                    OFFSET, OFFSET + 4.0)
    for run_ in (a_run(old, trace), a_run(None, trace), a_run([], None)):
        assert phases.fetched_ticks(run_) == []
        assert phases.percentile_ms(run_, None, 0.5) is None
        assert phases.rows(run_) is None
        assert phases.idle_shares(run_) is None
    spec = Spec(BENCH)
    for name in NEW:
        reader = spec.load_module("layer_metrics", name + ".py")
        assert reader.read(a_run(old, trace)) is None, name


def test_rows_are_summed_over_the_windows_ticks():
    flight = [record(float(i), DECODE, rows_computed=8 * tq, rows_useful=u)
              for i, (tq, u) in enumerate([(1, 8), (256, 263), (1, 7),
                                           (256, 100)])]
    assert phases.rows(a_run(flight)) == (8 * 514, 378)
    assert phases.rows(a_run(flight, t_open=1.0, t_end=3.0)) \
        == (8 * 257, 270)


def test_an_idle_interval_that_straddles_two_phases_is_split_by_overlap():
    spans = [("pack", 10.0, 12.0), ("dispatch", 12.0, 13.0),
             ("fetch", 13.0, 20.0), ("ingest", 21.0, 22.0)]
    got = phases.apportion([(11.0, 14.5), (19.0, 21.5)], spans)
    assert got[phases.HOST] == pytest.approx(1.0 + 0.5)
    assert got[phases.DISPATCH] == pytest.approx(1.0)
    assert got[phases.FETCH] == pytest.approx(1.5 + 1.0)
    assert got[phases.WAIT] == pytest.approx(1.0)     # 20..21: no phase
    assert sum(got.values()) == pytest.approx(3.5 + 2.5)
    # Nothing under any phase: all of it waited.
    assert phases.apportion([(30.0, 31.0)], spans)[phases.WAIT] == 1.0


def test_the_four_idle_shares_add_up_to_the_traces_idle_share():
    # Two ticks on the host's clock; the device trails the host and idles
    # while the host packs, uploads, and between the ticks.
    flight = [record(10.0, DECODE), record(10.040, DECODE)]
    dev0 = [("fusion.1", OFFSET + 9.990, 0.012),       # before the window
            ("flash_decode_paged.9", OFFSET + 10.013, 0.015),
            ("fusion.2", OFFSET + 10.029, 0.004),
            ("fusion.1", OFFSET + 10.055, 0.020)]
    dev1 = [("fusion.1", OFFSET + 10.0, 0.080)]        # never idle
    trace = a_trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                    OFFSET + 10.0, OFFSET + 10.080)
    run_ = a_run(flight, trace)
    shares = phases.idle_shares(run_)
    idle_pct = 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    assert sum(shares.values()) == pytest.approx(idle_pct)
    # Device 0 is idle 10.002..10.013 (admit 2, plan 1, pack 2, table_sync
    # 1, dispatch 4, fetch 1 ms), 10.028..10.029 (fetch), 10.033..10.055
    # (emit 2, account 1, no phase 4, then the next tick: ingest 1, sweep
    # 1, admit 2, plan 1, pack 2, table_sync 1, dispatch 4, fetch 3) and
    # 10.075..10.080 (account 1, no phase 4). Over two devices and 80 ms.
    per_ms = 100.0 / (2 * 80.0)
    assert shares[phases.WAIT] == pytest.approx((4 + 4) * per_ms)
    assert shares[phases.DISPATCH] == pytest.approx((5 + 5) * per_ms)
    assert shares[phases.FETCH] == pytest.approx((1 + 1 + 3) * per_ms)
    assert shares[phases.HOST] == pytest.approx((5 + 3 + 7 + 1) * per_ms)
    spec = Spec(BENCH)
    read = {name: spec.load_module("layer_metrics", name + ".py").read(run_)
            for name in NEW if name.startswith("idle_in_")}
    assert sum(read.values()) == pytest.approx(idle_pct)
    assert read["idle_in_wait_pct"] == pytest.approx(5.0)


NEW = ("tick_host_ms_p50", "tick_admit_ms_p99", "tick_pack_dispatch_ms_p50",
       "tick_emit_ms_p50", "tick_rows_useful_pct", "idle_in_fetch_pct",
       "idle_in_dispatch_pct", "idle_in_host_pct", "idle_in_wait_pct")


def test_the_benchmark_file_lists_the_new_metrics_last_and_by_their_cells():
    with open(BENCH) as f:
        bench = json.load(f)
    assert tuple(m["name"] for m in bench["per_layer"][-len(NEW):]) == NEW
    spec = Spec(BENCH)
    listed = {w["name"]: {m["name"] for m in spec.cell(w["name"]).per_layer}
              for w in bench["workloads"]}
    everywhere = set(NEW) - {"tick_admit_ms_p99", "tick_rows_useful_pct"}
    for cell, names in listed.items():
        assert everywhere <= names, cell
        assert ("tick_admit_ms_p99" in names) == cell.startswith("yi6b")
        assert ("tick_rows_useful_pct" in names) == cell.endswith("_sat")


def test_a_cpu_rehearsal_reads_the_tick_metrics_and_no_idle_share(
        capsys, tmp_path):
    """The rehearsal's own benchmark file with this PR's entries laid over
    it (the file itself is not edited): the program's stamps reach the
    readers through ``run.py``; the CPU has no device plane, so the
    ``idle_in_*`` find nothing and are left out."""
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(BENCH) as f:
        new = [m for m in json.load(f)["per_layer"] if m["name"] in NEW]
    known = {m["name"] for m in bench["end_to_end"]}
    for m in new:
        m.pop("workloads", None)
        if m["moves"] not in known:
            m["moves"] = "tbt_p50_ms"
    bench["per_layer"] += new
    bench["paths"] = [REHEARSAL, os.path.join(ROOT, "benchmark")]
    for c in bench["configs"]:
        c["file"] = os.path.join(REHEARSAL, c["file"])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    rc = run.main(["--benchmark", str(path), "--workload", "tiny_sat",
                   "--seed", "7", "--seconds", "2", "--trace", "1",
                   "--rehearse-on-cpu"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True
    read = set(lines[-1]["rehearsal"]["metrics_not_reported"])
    assert {n for n in NEW if n.startswith("tick_")} <= read
    assert not {n for n in NEW if n.startswith("idle_in_")} & read
    assert "decode_tick_p50_ms" in read       # and what was read before
