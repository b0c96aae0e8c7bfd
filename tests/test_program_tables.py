"""The engine's tables from its tick programs' operations to the parts of the
model (``SlotServer.program_tables``, ISSUE 35): made only while tracing is
on, before the first tick, from the loop's own executables; in the report and
the flight recorder's dump. CPU toy engine, as ``tests/test_tick_phases.py``.
"""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tree_attention_tpu import obs
from tree_attention_tpu.models import TransformerConfig, init_params
from tree_attention_tpu.obs import scopes
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.serving import Request, SlotServer

from tests.conftest import instruments_left_on, instruments_off

CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, max_seq_len=256, dtype=jnp.float32,
    attn_impl="blockwise", attn_block_size=16,
)
SLOTS, CHUNK = 2, 4
# What a program is built or fetched by: the events the benchmark's
# ``compiles_in_window`` counts, and the lowering before them.
BUILDS = ("/jax/core/compile/backend_compile_duration",
          "/jax/compilation_cache/cache_retrieval_time_sec",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")


class Watch:
    """Every compile, cache fetch and lowering JAX reports, stamped."""

    def __init__(self):
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in BUILDS:
            self.seen.append((time.monotonic(), event))

    def between(self, t0, t1):
        return [e for t, e in self.seen if t0 <= t <= t1]


@pytest.fixture(scope="module")
def watch():
    return Watch()


@pytest.fixture(scope="module")
def server():
    params = init_params(jax.random.PRNGKey(0), CFG)
    return SlotServer(params, CFG, prefill_chunk=CHUNK, slots=SLOTS,
                      cache_len=32, kv_block=CFG.attn_block_size)


def _requests(n=3, prompt_len=9, n_new=4, key=31):
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(key), (n, prompt_len), 0, CFG.vocab_size))
    return [Request(uid=i, prompt=prompt[i], max_new_tokens=n_new)
            for i in range(n)]


@pytest.fixture(scope="module")
def runs(server, watch):
    """The same engine three times: tracing off (it builds its programs),
    off again (nothing left to build), then with the recorder armed. "Off"
    is this fixture's to establish: a worker runs other files before this
    one, and ``conftest.py`` holds each of them to the same state at its
    end."""
    instruments_off()
    assert instruments_left_on() == []
    out = {}
    for name in ("cold", "off", "on"):
        if name == "on":
            FLIGHT.clear()
            FLIGHT.arm()
        t0 = time.monotonic()
        try:
            report = server.serve(_requests(key=31 + len(out)))
        finally:
            t1 = time.monotonic()
            snap = FLIGHT.snapshot()
            FLIGHT.disarm()
            FLIGHT.clear()
        out[name] = (report, snap, t0, t1)
    return out


def test_what_a_module_leaves_on_is_named():
    """The check ``conftest.py`` makes at every module's end: an armed
    recorder, a ring or tables left in it after it was disarmed (what
    ``snapshot`` and ``/flight`` keep serving) and an open tracer each
    have a name; a module that cleans up has none."""
    assert instruments_left_on() == []
    FLIGHT.arm()
    try:
        assert instruments_left_on() == ["the flight recorder armed"]
        FLIGHT.describe_programs([{"program": {}, "ops": []}])
    finally:
        FLIGHT.disarm()
    try:
        assert instruments_left_on() == [
            "the flight recorder's ring or program tables uncleared"]
    finally:
        FLIGHT.clear()
    assert instruments_left_on() == []


def test_off_the_report_holds_no_table(runs):
    for name in ("cold", "off"):
        report, snap, _, _ = runs[name]
        assert report.programs == []
        assert "programs" not in report.as_dict()
        assert "programs" not in snap


def test_off_nothing_is_built_that_was_not_built_before(runs, watch):
    """The first run compiles the engine's programs; the second, still
    untraced, compiles, fetches and lowers nothing: remembering which
    programs exist added no build."""
    _, _, t0, t1 = runs["cold"]
    assert watch.between(t0, t1)
    _, _, t0, t1 = runs["off"]
    assert watch.between(t0, t1) == []


def test_on_a_table_for_each_program_the_run_ran(runs):
    report, _, _, _ = runs["on"]
    labels = [t["program"] for t in report.programs]
    assert labels == [
        {"fn": "_mixed", "kind": "decode", "tq": 1, "chunk_group": 0},
        {"fn": "_packed", "kind": "mixed", "tq": CHUNK, "chunk_group": 1},
    ]
    assert report.as_dict()["programs"] is report.programs
    for table in report.programs:
        assert len(table["ops"]) > 50
        assert all(len(row) == 3 and all(isinstance(x, str) for x in row)
                   for row in table["ops"])


def test_on_nothing_is_built_inside_the_run(runs, watch):
    """The tables are read off the executables the loop runs: no compile,
    no fetch from the compile cache and no lowering between the run's
    first tick and its last (nor before or after them)."""
    _, snap, t0, t1 = runs["on"]
    assert len([r for r in snap["records"] if "t_s" in r]) > 5
    assert watch.between(t0, t1) == []


def test_on_the_tables_name_the_parts(runs):
    report, _, _, _ = runs["on"]
    decode, packed = report.programs
    found = lambda t: {row[2].split("/")[0] for row in t["ops"]} - {""}
    dense = {scopes.EMBED, scopes.ATTN_IN, scopes.ATTN_CACHE,
             scopes.ATTN_DECODE, scopes.ATTN_OUT, scopes.FFN, scopes.HEAD}
    assert found(decode) == dense
    assert found(packed) == dense | {scopes.ATTN_CHUNK}
    assert found(decode) <= set(scopes.SCOPES)


def test_on_the_flight_dump_carries_them(runs):
    report, snap, _, _ = runs["on"]
    assert snap["programs"] == report.programs


def test_a_program_is_described_once(server, runs):
    """The table of a program already described is the same object: a run
    reads no program's text twice."""
    report, _, _, _ = runs["on"]
    again = server.program_tables()
    assert [id(t) for t in again] == [id(t) for t in report.programs]


def test_the_span_tracer_alone_turns_them_on(server, tmp_path):
    obs.TRACER.start(str(tmp_path / "trace.jsonl"))
    try:
        report = server.serve(_requests(key=77))
    finally:
        obs.TRACER.close()
    assert len(report.programs) == 2
    assert "programs" not in FLIGHT.snapshot()


def test_a_program_built_under_the_recorder_is_described_as_it_lands(watch):
    """An engine whose first run is traced: nothing is known at the top of
    the run, each program is described after the tick that built it."""
    params = init_params(jax.random.PRNGKey(1), CFG)
    fresh = SlotServer(params, CFG, prefill_chunk=CHUNK, slots=SLOTS,
                       cache_len=32, kv_block=CFG.attn_block_size)
    FLIGHT.clear()
    FLIGHT.arm()
    try:
        report = fresh.serve(_requests(key=5))
        snap = FLIGHT.snapshot()
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
    assert [t["program"]["fn"] for t in report.programs] \
        == ["_mixed", "_packed"]
    assert snap["programs"] == report.programs


def test_lower_programs_lowers_what_the_loop_dispatches(server, runs, watch):
    """``lower_programs`` describes a program by the operands the loop
    hands it, so compiling what it lowers is the loop's own executable."""
    t0 = time.monotonic()
    for tq in (1, CHUNK):
        text = server.lower_programs(tq)["mixed"].compile().as_text()
        assert "HloModule" in text
    assert watch.between(t0, time.monotonic()) == []
