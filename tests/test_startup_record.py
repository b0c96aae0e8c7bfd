"""Start-up from inside (ISSUE 50): the start-up record of ``obs/flight.py``.

One tiny engine a shape a module, as ``tests/test_tick_phases.py`` does. The
record is the process's (``obs.STARTUP``) and other modules of this worker
have written to it, so a test reads what it added: the spans from a stamp
of its own on.
"""

import json
import os
import threading
import time
import types
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu import obs
from tree_attention_tpu.models import TransformerConfig, init_params
from tree_attention_tpu.obs import flight as flight_mod
from tree_attention_tpu.obs.flight import (
    FLIGHT, STARTUP, STARTUP_SPANS, FlightRecorder, StartupRecord,
    TickPhases,
)
from tree_attention_tpu.serving import Request, SlotServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, max_seq_len=256, dtype=jnp.float32,
    attn_impl="blockwise", attn_block_size=16,
)
ENGINE_KW = dict(slots=2, cache_len=32, kv_block=CFG.attn_block_size)
SETUP_METRICS = (
    "setup_import_s", "setup_engine_s", "setup_programs_s",
    "setup_programs_cached_pct", "setup_serve_s", "setup_unseen_s",
    "programs_built_in_window")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _requests(n, prompt_len, n_new, key=31, arrival_tick=0, uid0=0):
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(key), (n, prompt_len), 0, CFG.vocab_size))
    return [Request(uid=uid0 + i, prompt=prompt[i], max_new_tokens=n_new,
                    arrival_tick=arrival_tick) for i in range(n)]


def _since(snapshot, t):
    return [s for s in snapshot["spans"] if s[1] >= t]


def _named(spans, phase):
    return [s for s in spans if s[0] == "startup:" + phase]


@pytest.fixture(scope="module")
def twice(params):
    """A fresh engine (chunk buckets 8 and 16) served twice with the flight
    recorder armed. The first run's second request arrives mid-run and
    needs the wider bucket. Returns what the tests read."""
    t_before = time.monotonic()
    server = SlotServer(params, CFG, prefill_chunk=16, **ENGINE_KW)
    counter = obs.REGISTRY.get("serving_tick_programs_built_total")

    def counts():
        return {(p, src): counter.labels(program=p, source=src).value()
                for p in ("_mixed", "_packed") for src in ("compiled", "cache")}

    FLIGHT.clear()
    FLIGHT.arm()
    obs.REGISTRY.enable()
    try:
        base = counts()
        first = server.serve(
            _requests(1, 5, 6) + _requests(1, 14, 3, key=32, arrival_tick=4,
                                           uid0=1))
        dump = FLIGHT.snapshot()
        second = server.serve(_requests(2, 5, 3, key=33))
        counted = {k: v - base[k] for k, v in counts().items()}
    finally:
        obs.REGISTRY.disable()
        FLIGHT.disarm()
        FLIGHT.clear()
    t_after = time.monotonic()
    return types.SimpleNamespace(
        server=server, first=first, second=second, dump=dump,
        counted=counted, t_before=t_before, t_after=t_after)


# -- the record of a tiny engine served twice --------------------------------


def test_spans_come_in_order_and_never_overlap_in_the_thread(twice):
    spans = _since(twice.second.startup, twice.t_before)
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
    assert {s[0] for s in spans} <= {"startup:" + p for p in STARTUP_SPANS}
    # A program or a table is built inside a serve() call; nothing else
    # nests, and the spans of one level lie one after another.
    outer = [s for s in spans
             if s[0] not in ("startup:program", "startup:tables")]
    inner = [s for s in spans if s not in outer]
    for level in (outer, inner):
        for a, b in zip(level, level[1:]):
            assert a[2] is not None and a[2] <= b[1], (a, b)
    for s in inner:
        assert any(o[0] == "startup:serve" and o[1] <= s[1]
                   and (o[2] is None or s[2] <= o[2]) for o in outer), s
    closed = sum(s[2] - s[1] for s in outer if s[2] is not None)
    assert closed <= twice.t_after - twice.t_before
    # Always on: the recorder being armed is not what keeps the record.
    assert _named(spans, "engine") and _named(spans, "params")


def test_the_engine_span_sums_the_pools_and_the_params_span_the_relaid(twice):
    spans = _since(twice.first.startup, twice.t_before)
    engine, = _named(spans, "engine")
    cache = twice.server.cache
    assert engine[3] == {
        "pool_bytes": sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(cache)),
        "state_pool_bytes": 0}
    relaid, = _named(spans, "params")       # SlotServer's serving_params
    lay = twice.server.params["layers"]
    assert relaid[3] == {"bytes": lay["wqkv_t"].size * 4}
    assert relaid[2] <= engine[1]


def test_each_program_is_listed_once_on_the_serve_that_built_it(twice):
    first = _since(twice.first.startup, twice.t_before)
    second = _since(twice.second.startup, twice.t_before)
    built = [(s[3]["program"], s[3]["tq"]) for s in _named(first, "program")]
    assert sorted(built) == [("_mixed", 1), ("_packed", 8), ("_packed", 16)]
    assert sorted(built) == sorted(twice.server._tick_programs)
    # The second serve built nothing: the same three spans, now beside two
    # serve spans, the first closed with its fields and the second open.
    assert _named(second, "program") == _named(first, "program")
    one, two = _named(second, "serve")
    assert one[2] is not None and two[2] is None
    assert one[3] == {"ticks": twice.first.ticks, "prompt_tokens": 19,
                      "tokens_generated": 9}
    assert two[3] == {"ticks": twice.second.ticks, "prompt_tokens": 10,
                      "tokens_generated": 6}
    for s in _named(second, "program"):
        assert one[1] <= s[1] and s[2] <= one[2]
        f = s[3]
        assert f["trace_s"] > 0 and f["lower_s"] > 0 and f["compile_s"] > 0
        assert f["fetch_s"] == 0.0 and f["from_cache"] is False
        assert f["trace_s"] + f["lower_s"] + f["compile_s"] <= s[2] - s[1]


def test_a_bucket_built_mid_run_says_its_tick_and_only_that_record_has_built(
        twice):
    spans = _since(twice.first.startup, twice.t_before)
    wide, = [s for s in _named(spans, "program") if s[3]["tq"] == 16]
    assert wide[3]["tick"] >= 4             # the late request's first chunk
    recs = [r for r in twice.dump["records"] if "t_s" in r]
    built = {r["tick"]: r["built"] for r in recs if "built" in r}
    assert built == {
        s[3]["tick"]: [s[3]["program"], s[3]["tq"], round(s[2] - s[1], 6),
                       False]
        for s in _named(spans, "program")}
    by_tick = {r["tick"]: r for r in recs}
    assert by_tick[wide[3]["tick"]]["kind"] == "mixed"
    assert by_tick[wide[3]["tick"]]["tq"] == 16
    # The registry counted the same three builds while it was enabled.
    assert twice.counted == {
        ("_mixed", "compiled"): 1, ("_packed", "compiled"): 2,
        ("_mixed", "cache"): 0, ("_packed", "cache"): 0}
    # The tables of the programs the run built were read inside it.
    assert sum(s[3]["programs"] for s in _named(spans, "tables")) == 3


def test_the_report_the_dump_and_healthz_carry_the_record(twice):
    from tree_attention_tpu.obs.http import MetricsHTTPServer, flight_health

    rec = twice.second.as_dict()["startup"]
    assert rec == twice.second.startup
    assert set(rec) == {"t_process", "spans", "seconds", "dropped"}
    json.dumps(rec)                         # what a sink writes
    assert twice.dump["startup"]["t_process"] == rec["t_process"]
    assert _named(_since(twice.dump["startup"], twice.t_before), "program")
    assert not FLIGHT.enabled               # whether or not the ring is armed
    _, body = flight_health(FLIGHT)
    assert _named(body["startup"]["spans"], "serve")
    http = MetricsHTTPServer(0)
    port = http.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            live = json.load(resp)
    finally:
        http.stop()
    assert live["startup"]["t_process"] == rec["t_process"]
    assert live["startup"]["seconds"]["program"] > 0


def test_the_one_line_record_keeps_the_phases_seconds_alone(twice):
    from tree_attention_tpu import cli

    line = cli._report_record(twice.second)
    assert line["startup"] == twice.second.startup["seconds"]
    assert set(line["startup"]) <= set(STARTUP_SPANS)


def test_a_warm_loop_reads_no_clock_for_the_record(twice, monkeypatch):
    """The off path: serve() hands the record the two stamps it takes
    anyway, and a tick that builds no program touches nothing of it. The
    record's module reads an injected clock that counts its reads."""
    assert not FLIGHT.enabled and not obs.TRACER.active
    reads = []

    def counting():
        reads.append(1)
        return time.monotonic()

    monkeypatch.setattr(flight_mod, "time", types.SimpleNamespace(
        monotonic=counting, strftime=time.strftime))
    n_before = len(STARTUP.snapshot()["spans"])
    short = twice.server.serve(_requests(2, 5, 3, key=34))
    long = twice.server.serve(_requests(2, 5, 12, key=35))
    assert long.ticks > short.ticks + 5
    assert reads == []
    assert len(STARTUP.snapshot()["spans"]) == n_before + 2   # the serves
    assert not _named(_since(long.startup, twice.t_after), "program")


# -- the persistent cache, the registry and the tracer ------------------------


@pytest.fixture
def disk_cache(tmp_path):
    """JAX's persistent compile cache, on for one test, in its own
    directory (``tests/conftest.py`` turns it off for the process)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        yield
    finally:
        for n, v in old.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_a_rebuilt_program_comes_from_the_cache_and_the_registry_counts_both(
        params, disk_cache):
    t_before = time.monotonic()
    obs.REGISTRY.enable()
    try:
        counter = obs.REGISTRY.get("serving_tick_programs_built_total")
        gauge = obs.REGISTRY.get("serving_startup_seconds")

        def count(source):
            return counter.labels(program="_packed", source=source).value()

        base = count("compiled"), count("cache")
        server = SlotServer(params, CFG, prefill_chunk=8, **ENGINE_KW)
        server.serve(_requests(1, 5, 2, key=36))
        jax.clear_caches()
        report = server.serve(_requests(1, 5, 2, key=37))
        assert (count("compiled"), count("cache")) \
            == (base[0] + 1, base[1] + 1)
        seconds = STARTUP.snapshot()["seconds"]
        for phase in ("params", "engine", "program", "serve"):
            assert gauge.labels(phase=phase).value() \
                == pytest.approx(seconds[phase], abs=1e-5)
    finally:
        obs.REGISTRY.disable()
    packed = [s for s in _named(_since(report.startup, t_before), "program")
              if s[3]["program"] == "_packed"]
    assert [s[3]["from_cache"] for s in packed] == [False, True]
    cold, warm = (s[3] for s in packed)
    assert cold["fetch_s"] == 0.0 and cold["compile_s"] > 0
    assert warm["fetch_s"] > 0 and warm["compile_s"] >= 0
    assert warm["trace_s"] > 0 and warm["lower_s"] > 0


def test_the_tracer_gets_every_span_once_under_its_name(tmp_path):
    rec = StartupRecord(t_process=10.0)
    rec.add("import", 10.0, 12.5)
    rec.add("backend", 12.5, 13.0, platform="cpu", devices=1)
    path = tmp_path / "trace.jsonl"
    obs.TRACER.start(str(path))
    try:
        rec.publish()                       # what closed before the tracer
        rec.publish()                       # ... and not twice
        rec.add("engine", 13.0, 13.25, pool_bytes=7, state_pool_bytes=0)
    finally:
        obs.TRACER.close()
    events = [json.loads(ln) for ln in path.read_text().splitlines()]
    events = [e for e in events if e.get("cat") == "startup"]
    assert [(e["name"], e["ph"], e["ts"], e["dur"]) for e in events] == [
        ("startup:import", "X", 10_000_000, 2_500_000),
        ("startup:backend", "X", 12_500_000, 500_000),
        ("startup:engine", "X", 13_000_000, 250_000)]
    assert events[1]["args"] == {"platform": "cpu", "devices": 1}


# -- the record alone ---------------------------------------------------------


def test_the_record_is_bounded_and_keeps_its_head():
    class Small(StartupRecord):
        HEAD, RING = 3, 2

    rec = Small(t_process=0.0)
    for i in range(8):
        rec.add("serve", float(i), i + 0.5, ticks=i)
    snap = rec.snapshot()
    assert [s[3]["ticks"] for s in snap["spans"]] == [0, 1, 2, 6, 7]
    assert snap["dropped"] == 3
    assert snap["seconds"] == {"serve": 4.0}   # of all eight


def test_an_open_span_shows_with_no_end_and_its_fields_so_far():
    rec = StartupRecord(t_process=0.0)
    span = rec.begin("serve", 1.0)
    span[3].update(ticks=3)
    shown, = rec.snapshot()["spans"]
    assert shown == ["startup:serve", 1.0, None, {"ticks": 3}]
    rec.end(span, 2.0, tokens_generated=9)
    rec.end(span, 5.0)                      # twice: nothing
    assert shown[2] is None                 # the snapshot was a copy
    assert rec.snapshot()["spans"] == [
        ["startup:serve", 1.0, 2.0, {"ticks": 3, "tokens_generated": 9}]]
    assert rec.snapshot()["seconds"] == {"serve": 1.0}


def test_the_first_stamp_is_the_process_start_or_the_packages_import(
        monkeypatch):
    import tree_attention_tpu

    now = time.monotonic()
    assert tree_attention_tpu._T_IMPORT <= now
    start = flight_mod._process_start()
    if start is not None:                   # a /proc that says when
        assert start <= tree_attention_tpu._T_IMPORT + 0.011
        assert now - start < 7 * 86400
        assert StartupRecord().t_process == pytest.approx(start, abs=0.05)

    def no_proc(*a, **k):
        raise OSError("no /proc here")

    monkeypatch.setattr("builtins.open", no_proc)
    assert flight_mod._process_start() is None
    assert StartupRecord().t_process == tree_attention_tpu._T_IMPORT


def test_a_build_counts_only_its_own_programs_durations():
    """The stages: what the traced body compiles eagerly on the way is not
    the tick program's, and the backend's duration holds the cache's."""
    rec, done = StartupRecord(t_process=0.0), []
    build = rec.building("_packed", 16, 7, done.append)
    me = rec._builds[threading.get_ident()]
    assert me is build
    for event, s in ((flight_mod._EV_TRACE, 9.0), (flight_mod._EV_LOWER, 9.0),
                     (flight_mod._EV_COMPILE, 9.0)):
        rec._on_event(event, s)             # inside the body: another's
    build.stage = 1
    rec._on_event(flight_mod._EV_TRACE, 0.5)
    rec._on_event(flight_mod._EV_TRACE, 9.0)    # not while lowering
    rec._on_event(flight_mod._EV_LOWER, 0.25)
    rec._on_event(flight_mod._EV_FETCH, 0.125)
    assert not done and rec.snapshot()["spans"][0][2] is None
    rec._on_event(flight_mod._EV_COMPILE, 0.25)
    span, = done
    assert span is rec.snapshot()["spans"][0] and span[2] is not None
    assert span[3] == {
        "program": "_packed", "tq": 16, "tick": 7, "trace_s": 0.5,
        "lower_s": 0.25, "compile_s": 0.125, "fetch_s": 0.125,
        "from_cache": True}
    assert not rec._builds
    rec._on_event(flight_mod._EV_COMPILE, 1.0)  # outside a build: nothing
    assert len(rec.snapshot()["spans"]) == 1


def test_a_trace_that_raises_leaves_no_span(params):
    server = SlotServer(params, CFG, prefill_chunk=8, **ENGINE_KW)

    def broken(*args):
        raise RuntimeError("no such model")

    program = jax.jit(server._noting("_mixed", broken))
    t = time.monotonic()
    with pytest.raises(RuntimeError, match="no such model"):
        program(jnp.zeros(()), jnp.zeros((2, 1), jnp.int32))
    assert not _named(_since(STARTUP.snapshot(), t), "program")
    assert threading.get_ident() not in STARTUP._builds


def test_a_build_left_half_done_is_forgotten_with_the_next():
    rec = StartupRecord(t_process=0.0)
    rec.building("_mixed", 1, None)         # lowered, say, never compiled
    build = rec.building("_packed", 8, None)
    shown, = rec.snapshot()["spans"]
    assert shown[3]["program"] == "_packed"
    rec.abandon(build)
    assert rec.snapshot()["spans"] == [] and not rec._builds


def test_the_dispatch_annotation_says_what_its_call_built():
    said = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            said.append((self.name, kw))

    ph = TickPhases()
    ph.built(["_mixed", 1, 0.5, False])     # off: nothing, no annotation
    assert said == []
    FLIGHT.arm()
    try:
        ph._annotation = Annotation
        ph.begin(0.0)
        ph.mark("dispatch", 3, "decode", 1, False)
        ph.built(["_mixed", 1, 0.5, False])
        ph.abandon()
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
    assert said == [("tick:dispatch",
                     {"built": "['_mixed', 1, 0.5, False]"})]


def test_a_recorder_of_ones_own_keeps_a_record_of_its_own():
    mine = FlightRecorder(capacity=4, startup=StartupRecord(t_process=1.0))
    mine.startup.add("import", 1.0, 2.0)
    assert mine.snapshot()["startup"]["seconds"] == {"import": 1.0}
    mine.clear()                            # the ring's, not the record's
    assert mine.snapshot()["startup"]["seconds"] == {"import": 1.0}
    assert FLIGHT.startup is STARTUP is obs.STARTUP


def test_build_serve_engine_is_four_kinds_of_span_end_to_end():
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    cfg = parse_args([
        "--mode", "serve", "--device", "cpu", "--slots", "2",
        "--prompt-len", "8", "--prompt-jitter", "0", "--max-new-tokens", "4",
        "--model-dim", "32",
        "--heads", "2", "--vocab-size", "64", "--n-layers", "1",
        "--dtype", "float32", "--prefix-block", "8"])
    t_before = time.monotonic()
    setup = cli.build_serve_engine(cfg, None)
    t_built = time.monotonic()
    setup.make_engine()
    spans = _since(STARTUP.snapshot(), t_before)
    assert [s[0][len("startup:"):] for s in spans] == [
        "backend", "engine", "params", "params", "engine",      # the call
        "params", "engine"]                                     # the engine
    assert spans[0][3] == {"platform": "cpu", "devices": jax.device_count()}
    call = [s for s in spans if s[2] <= t_built]
    assert len(call) == 5
    for a, b in zip(call, call[1:]):
        assert a[2] <= b[1] and b[1] - a[2] < 0.05
    assert t_before <= call[0][1] and t_built - call[-1][2] < 0.05
    # cli's module body ended with the import's span, from the process's
    # own start.
    first = STARTUP.snapshot()["spans"][0]
    assert first[0] == "startup:import" and first[1] == STARTUP.t_process


# -- the readers ----------------------------------------------------------------


@pytest.fixture(scope="module")
def spec():
    import sys

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.spec import Spec

    return Spec(os.path.join(ROOT, "BENCHMARK.json"))


def _run(spec, startup):
    """A hand-made run: the process started at 100, the window is
    [150, 200)."""
    cell = types.SimpleNamespace(spec=spec)
    return types.SimpleNamespace(
        cell=cell, t_open=150.0, t_end=200.0,
        report={} if startup is None else {"startup": startup})


def _program(t0, t1, tick, cached, **durations):
    f = dict(program="_packed", tq=8, tick=tick, trace_s=0.0, lower_s=0.0,
             compile_s=0.0, fetch_s=0.0, from_cache=cached)
    f.update(durations)
    return ["startup:program", t0, t1, f]


HAND_MADE = {"t_process": 100.0, "seconds": {}, "dropped": 0, "spans": [
    ["startup:import", 100.0, 106.0, {}],
    ["startup:backend", 110.0, 110.5, {"platform": "tpu", "devices": 1}],
    ["startup:engine", 110.5, 111.0, {}],
    ["startup:params", 111.0, 118.0, {}],
    ["startup:params", 118.0, 119.0, {"bytes": 5}],
    ["startup:engine", 119.0, 121.0, {"pool_bytes": 1, "state_pool_bytes": 0}],
    ["startup:serve", 122.0, 130.0, {"ticks": 2}],
    _program(122.5, 126.0, 0, True, trace_s=1.0, lower_s=0.5, compile_s=0.25,
             fetch_s=1.25),
    _program(126.5, 129.5, 1, False, trace_s=0.5, lower_s=0.5, compile_s=1.5),
    ["startup:serve", 131.0, None, {"ticks": 900}],
    ["startup:tables", 131.0, 132.0, {"programs": 2}],
    # Built in the window: no part of set-up, and counted there.
    _program(160.0, 163.0, 55, False, trace_s=1.0, lower_s=1.0, compile_s=1.0),
    _program(201.0, 202.0, 999, False, compile_s=1.0),   # after it
]}
# import 6 + 0.5; engine 0.5 + 7 + 1 + 2; programs 3 + 2.5; serve
# (8 + 19) - 5.5 - 1 (the tables); unseen 50 - 6.5 - 10.5 - 5.5 - 20.5 - 1.
WANT = {"setup_import_s": 6.5, "setup_engine_s": 10.5,
        "setup_programs_s": 5.5, "setup_programs_cached_pct": 50.0,
        "setup_serve_s": 20.5, "setup_unseen_s": 6.0,
        "programs_built_in_window": 1.0}


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_a_reader_sums_its_spans_and_cuts_them_at_the_window(spec, name):
    reader = spec.load_module("layer_metrics", name + ".py")
    assert reader.read(_run(spec, HAND_MADE)) == pytest.approx(WANT[name])
    # A span that runs on into the window counts up to its opening.
    if name == "setup_engine_s":
        late = dict(HAND_MADE, spans=HAND_MADE["spans"] + [
            ["startup:engine", 149.0, 170.0, {}]])
        assert reader.read(_run(spec, late)) == pytest.approx(11.5)


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_a_reader_gives_nothing_for_a_program_without_the_record(spec, name):
    reader = spec.load_module("layer_metrics", name + ".py")
    assert reader.read(_run(spec, None)) is None
    if name == "setup_programs_cached_pct":
        # ... nor a share of no programs.
        none_built = dict(HAND_MADE, spans=HAND_MADE["spans"][:6])
        assert reader.read(_run(spec, none_built)) is None


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_the_benchmark_lists_the_metric_in_all_ten_cells(spec, name):
    entry, = [m for m in spec.data["per_layer"] if m["name"] == name]
    cells = [w["name"] for w in spec.data["workloads"]]
    assert len(cells) == 11 and entry["workloads"] == cells
    assert entry["layer"] == "set-up"
    assert entry["moves"] == ("tbt_p99_ms" if name.startswith("programs")
                              else "setup_s")
    assert entry["source"] == ("program_counter" if name in (
        "setup_programs_cached_pct", "programs_built_in_window")
        else "program_span")
    for cell in cells:
        assert name in [m["name"] for m in spec.cell(cell).per_layer]
    # (PR 52's four, for its own cell, came after them.)
    assert [m["name"] for m in spec.data["per_layer"][-11:-4]] \
        == list(SETUP_METRICS)
