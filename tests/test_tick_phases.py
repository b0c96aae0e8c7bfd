"""The tick from inside (ISSUE 24): phase stamps in ``SlotServer.serve``.

One set of stamps, three sinks — the tick's flight record (``phases``,
``t_end``, ``kind``, ``tq``, ``rows_computed``, ``rows_useful``), the
profiler's ``tick:<phase>`` annotations, and ``tick:<phase>`` complete
events in the ``--trace-events`` JSONL. CPU toy engine on the paged pool
at pages of ``attn_block_size`` tokens; the cases of the chunked shape
share one engine (a drained engine serves the next trace clean).
"""

import json
import time
import tracemalloc
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu import obs
from tree_attention_tpu.models import TransformerConfig, init_params
from tree_attention_tpu.obs import flight as flight_mod
from tree_attention_tpu.obs.flight import FLIGHT, TICK_PHASES, TickPhases
from tree_attention_tpu.serving import Request, SlotServer

CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, max_seq_len=256, dtype=jnp.float32,
    attn_impl="blockwise", attn_block_size=16,
)
SLOTS, CHUNK = 2, 4
ENGINE_KW = dict(slots=SLOTS, cache_len=32, kv_block=CFG.attn_block_size)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def chunked(params):
    return SlotServer(params, CFG, prefill_chunk=CHUNK, **ENGINE_KW)


def _requests(n, prompt_len, n_new, key=31):
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(key), (n, prompt_len), 0, CFG.vocab_size))
    return [Request(uid=i, prompt=prompt[i], max_new_tokens=n_new)
            for i in range(n)]


def _recorded(server, reqs, executed_only=True):
    """Serve with the flight recorder armed; the executed ticks' records."""
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        report = server.serve(reqs)
    finally:
        FLIGHT.disarm()
    recs = [r for r in FLIGHT.snapshot()["records"] if "t_s" in r]
    FLIGHT.clear()
    if executed_only:                    # no fast-forward in the trace
        assert len(recs) == report.ticks
    return report, recs


@pytest.fixture(scope="module")
def served(chunked, tmp_path_factory):
    """One chunked run, 3 requests through 2 slots, with the recorder AND
    the span tracer on: the records and the JSONL of the same ticks."""
    path = tmp_path_factory.mktemp("phases") / "trace.jsonl"
    server = chunked
    obs.TRACER.start(str(path))
    try:
        t_before = time.monotonic()
        report, recs = _recorded(server, _requests(3, 9, 4))
    finally:
        obs.TRACER.close()
    events = [json.loads(ln) for ln in path.read_text().splitlines()]
    return server, report, recs, events, t_before


def test_phases_are_named_in_loop_order_and_tile_the_tick(served):
    _, _, recs, _, t_before = served
    order = {name: i for i, name in enumerate(TICK_PHASES)}
    for rec, nxt in zip(recs, recs[1:] + [None]):
        names = [p[0] for p in rec["phases"]]
        starts = [p[1] for p in rec["phases"]]
        assert set(names) <= set(TICK_PHASES)
        # Loop order, each phase at most once.
        assert [order[n] for n in names] == sorted({order[n] for n in names})
        assert names[0] == "ingest" and names[-1] == "account"
        assert starts == sorted(starts)
        # Absolute monotonic stamps, at or after the tick's own top.
        assert starts[0] >= t_before
        assert rec["t_end"] >= starts[-1]
        if nxt is not None:
            assert rec["t_end"] <= nxt["phases"][0][1]
        # A tick that fetched has the fetch and the emit pass behind it.
        assert ("fetch" in names) == rec["host_sync"] == ("emit" in names)


def test_t_s_is_when_the_program_before_it_was_fetched(chunked, monkeypatch):
    """A record is one program's and is written by the iteration that
    lands its tail, one later than the one that dispatched it (ISSUE 32).
    ``t_s`` of a program dispatched ahead is the moment the program
    before it came back: the clock read that follows the ``emit`` mark of
    the record before. The first program of a busy spell has the tick's
    top, which precedes every stamp in its record. The engine and the
    stamper read an injected clock that counts its reads, so the order of
    the stamps is compared, not two reads of the wall clock."""
    from tree_attention_tpu.serving import engine as engine_mod

    reads = iter(range(1, 1 << 30))
    clock = types.SimpleNamespace(monotonic=lambda: float(next(reads)),
                                  strftime=time.strftime)
    monkeypatch.setattr(engine_mod, "time", clock)
    monkeypatch.setattr(flight_mod, "time", clock)
    _, recs = _recorded(chunked, _requests(3, 9, 4))
    assert [r["tick"] for r in recs] == list(range(len(recs)))
    assert recs[0]["ahead"] is False and recs[0]["sync_reason"] == "first"
    assert all(r["ahead"] and "sync_reason" not in r for r in recs[1:])
    stamps = [r["t_s"] for r in recs]
    assert stamps == sorted(set(stamps))
    # serve()'s own zero, from each pair: ``t_s`` is the read after the
    # ``emit`` mark, so every pair gives the same value, to the read.
    zero = {dict(before["phases"])["emit"] + 1.0 - rec["t_s"]
            for before, rec in zip(recs, recs[1:]) if before["host_sync"]}
    assert len(zero) == 1
    t0 = zero.pop()
    for before, rec in zip(recs, recs[1:]):
        # Behind the dispatch of its own program, inside the iteration
        # that landed the one before.
        at = rec["t_s"] + t0
        assert dict(before["phases"])["dispatch"] < at <= before["t_end"]
        assert at == float(int(at))          # a read of the injected clock
    assert recs[0]["t_s"] + t0 <= recs[0]["phases"][0][1]


def test_kind_and_tq_agree_with_the_chunk_plan(served):
    server, _, recs, _, _ = served
    kinds = {r["kind"] for r in recs}
    assert kinds == {"mixed", "decode"}
    for r in recs:
        if r["kind"] == "mixed":
            assert r["chunk_tokens"] > 0
            assert r["tq"] == server._chunk_bucket(
                max(n for _, n, _ in r["chunk_plan"]))
            # A tick with a chunk is packed: the chunk group's rows and
            # one decode row a slot, never a Tq-row matrix a slot.
            assert r["chunk_group"] == server._chunk_group
            assert r["rows_computed"] == \
                r["chunk_group"] * r["tq"] + SLOTS
        else:
            assert r["chunk_tokens"] == 0 and r["tq"] == 1
            assert r["chunk_group"] == 0
            assert r["rows_computed"] == SLOTS


def test_rows_useful_counts_the_rows_that_carried_a_token(served):
    _, _, recs, _, _ = served
    for r in recs:
        assert 0 < r["rows_useful"] <= r["rows_computed"]
        if r["kind"] == "decode":
            assert r["rows_useful"] == r["occupancy"]
        else:
            assert r["rows_useful"] == r["chunk_tokens"] + r["occupancy"]


def test_trace_events_hold_the_phases_inside_the_tick_span(served):
    _, report, recs, events, _ = served
    spans = [e for e in events if e["ph"] == "X"]
    ticks = [e for e in spans if e["name"] == "serving:tick"]
    drains = [e for e in ticks if e["args"].get("drain")]
    assert len(ticks) - len(drains) == report.ticks and len(drains) == 1
    # The span a record's phases lie in is the iteration that landed it:
    # the next tick's, or the drain span the loop opens for the last one.
    by_tick = {e["args"]["tick"] - 1: e for e in ticks
               if not e["args"].get("drain")}
    by_tick[drains[0]["args"]["tick"]] = drains[0]
    phase_events = [e for e in spans if e["name"].startswith("tick:")]
    assert phase_events and {e["cat"] for e in phase_events} == {"serving"}
    # The first iteration dispatched and landed nothing: its stamps are
    # in the trace and in no record.
    first = round(recs[0]["phases"][0][1] * 1e9) // 1000
    primer = [e for e in phase_events if e["ts"] < first]
    assert [e["name"] for e in primer][-2:] == ["tick:dispatch",
                                                "tick:publish"]
    phase_events = phase_events[len(primer):]
    assert len(phase_events) == sum(len(r["phases"]) for r in recs)
    it = iter(phase_events)
    for rec in recs:
        tick = by_tick[rec["tick"]]
        for name, start in rec["phases"]:
            e = next(it)                 # written in order, tick by tick
            assert e["name"] == "tick:" + name
            assert e["ts"] == round(start * 1e9) // 1000   # the same stamp
            assert e["pid"] == tick["pid"] and e["tid"] == tick["tid"]
            assert tick["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= tick["ts"] + tick["dur"]


@pytest.mark.parametrize("kw, want", [
    (dict(prefill_chunk=CHUNK, quantize=True), {"staged", "decode"}),
    (dict(prefill_chunk=CHUNK, speculate=True, draft_k=3), {"verify"}),
], ids=["staged", "verify"])
def test_the_other_tick_kinds(params, kw, want):
    server = SlotServer(params, CFG, **ENGINE_KW, **kw)
    _, recs = _recorded(server, _requests(2, 9, 4, key=33))
    assert {r["kind"] for r in recs} == want
    for r in recs:
        assert r["rows_useful"] <= r["rows_computed"] == SLOTS * r["tq"]
        if r["kind"] == "staged":
            # The stage programs run under 'pack'; tq is the decode
            # program's, if one ran in the same tick.
            assert r["chunk_tokens"] > 0 and r["tq"] in (0, 1)
        if r["kind"] == "verify":
            assert r["tq"] >= 1 and "dispatch" in dict(r["phases"])


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/leave."""

    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.kwargs))

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("leave", self.name, self.kwargs))

    def set_metadata(self, **kwargs):
        """What a phase learns while it is open (``built``, of a dispatch
        that built its program: ``tests/test_startup_record.py``)."""
        self.late = kwargs


def _iterations(names):
    """Annotation names grouped by loop iteration (each opens 'ingest')."""
    groups = []
    for n in names:
        if n == "tick:ingest":
            groups.append([])
        groups[-1].append(n)
    return groups


def test_each_phase_is_a_profiler_annotation_left_at_the_next_mark(
        chunked, monkeypatch):
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.log = []
    _, recs = _recorded(chunked, _requests(2, 6, 3, key=34))
    log = _FakeAnnotation.log
    # Strictly alternating enter/leave of the same annotation: never two
    # open at once, none left open at the end.
    assert len(log) % 2 == 0
    for (a, name_a, _), (b, name_b, _) in zip(log[0::2], log[1::2]):
        assert (a, b) == ("enter", "leave") and name_a == name_b
    entered = [(n, kw) for what, n, kw in log if what == "enter"]
    groups = _iterations([n for n, _ in entered])
    # The first iteration dispatches and lands nothing; each one after it
    # dispatches its program and lands the one before (that record's
    # phases); the drained exit lands the last, looks again for work and
    # abandons the stamps it began at ``admit``.
    assert groups[0][-2:] == ["tick:dispatch", "tick:publish"]
    assert "tick:fetch" not in groups[0]
    assert groups[1:-1] == [["tick:" + p[0] for p in r["phases"]]
                            for r in recs[:-1]]
    assert groups[-1] == ["tick:" + p[0] for p in recs[-1]["phases"]] \
        + ["tick:admit"]
    assert [p[0] for p in recs[-1]["phases"]] == [
        "ingest", "sweep", "admit", "fetch", "emit", "account"]
    dispatches = [kw for n, kw in entered if n == "tick:dispatch"]
    assert dispatches == [
        {"tick": r["tick"], "kind": r["kind"], "tq": r["tq"],
         "ahead": r["ahead"]} for r in recs]
    assert all(kw == {} for n, kw in entered if n != "tick:dispatch")


def test_the_real_annotation_takes_the_arguments_the_stamper_gives():
    """No fake: the stamper drives ``jax.profiler.TraceAnnotation`` itself
    (no session runs, so nothing is kept — it must just not raise)."""
    FLIGHT.arm()
    try:
        ph = TickPhases()
        ph.begin(1.0)
        ph.mark("dispatch", 7, "mixed", 256)
        rec = {}
        ph.finish(rec)
    finally:
        FLIGHT.disarm()
    assert [p[0] for p in rec["phases"]] == ["ingest", "dispatch"]
    assert rec["phases"][0][1] == 1.0 and rec["t_end"] >= rec["phases"][1][1]


def test_a_repeated_mark_and_an_abandoned_tick():
    _FakeAnnotation.log = []
    FLIGHT.arm()
    try:
        ph = TickPhases()
        ph._annotation = _FakeAnnotation
        ph.begin(0.5)
        ph.mark("pack")
        ph.mark("pack")                  # names the open phase: no new one
        ph.abandon()                     # an idle iteration: nothing kept
        assert not ph.on
        assert [what for what, _, _ in _FakeAnnotation.log] == [
            "enter", "leave", "enter", "leave"]
        ph.finish({})                    # off again: no-ops
        ph.mark("plan")
        ph.abandon()
        ph.begin(0.75)
        rec = {}
        ph.finish(rec)
    finally:
        FLIGHT.disarm()
    assert [p[0] for p in rec["phases"]] == ["ingest"]


def test_an_idle_iteration_leaves_no_record_and_no_open_annotation(
        chunked, monkeypatch):
    """Arrival ticks far apart: the loop fast-forwards between them, and
    those iterations stamp ingest..admit and then abandon."""
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.log = []
    reqs = _requests(2, 5, 2, key=35)
    reqs[1].arrival_tick = 50
    report, recs = _recorded(chunked, reqs, executed_only=False)
    assert len(recs) < report.ticks      # the clock jumped to tick 50
    assert all(r["phases"][-1][0] == "account" for r in recs)
    log = _FakeAnnotation.log
    assert [w for w, _, _ in log[0::2]] == ["enter"] * (len(log) // 2)
    assert [w for w, _, _ in log[1::2]] == ["leave"] * (len(log) // 2)
    groups = _iterations([n for w, n, _ in log if w == "enter"])
    # Two busy spells: each opens with an iteration that dispatches and
    # lands nothing, and closes with one that lands the last program,
    # finds nothing more (the fast-forward to tick 50, the drained exit)
    # and abandons the stamps it began again at ``admit``.
    primers = [g for g in groups if "tick:account" not in g]
    idle = [g for g in groups if g[-1] == "tick:admit"]
    assert len(primers) == 2 and len(groups) == len(recs) + 2
    assert all(g[-1] == "tick:publish" for g in primers)
    assert len(idle) == 2 and all(
        g[:3] == ["tick:ingest", "tick:sweep", "tick:admit"]
        and g[-2] == "tick:account" for g in idle)


def test_off_means_no_clock_read_and_no_allocation(monkeypatch):
    """The disabled path: begin() latches off with two attribute checks;
    mark(), abandon() and finish() are one check and a return."""
    assert not FLIGHT.enabled and not obs.TRACER.active

    def no_clock():
        raise AssertionError("the stamper read the clock while off")

    ph = TickPhases()

    def tick():
        ph.begin(0.0)
        ph.mark("sweep")
        ph.mark("dispatch", 3, "decode", 1)
        ph.abandon()
        ph.finish(None)

    tick()
    assert ph._annotation is None        # JAX's profiler never imported
    monkeypatch.setattr(flight_mod, "time",
                        types.SimpleNamespace(monotonic=no_clock))
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        for _ in range(5000):
            tick()
        grown = tracemalloc.get_traced_memory()[0] - base
    finally:
        tracemalloc.stop()
    assert grown < 4096, f"the disabled stamper allocated {grown} B"
    assert ph._marks is None and ph._open is None


def test_the_engine_serves_the_same_tokens_stamped_or_not(chunked):
    reqs = lambda: _requests(3, 9, 4, key=36)          # noqa: E731
    plain = chunked.serve(reqs())
    stamped, _ = _recorded(chunked, reqs())
    assert ({r.uid: r.tokens for r in plain.results}
            == {r.uid: r.tokens for r in stamped.results})
    assert plain.ticks == stamped.ticks
