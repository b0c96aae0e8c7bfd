"""A tick's device time by part (``benchmark/parts.py``), on hand-made events
and tables: the join of a trace's leaf events to the tick programs' tables,
by program first and by span only where two programs hold one name.
"""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import parts  # noqa: E402

DEC, MIX = parts.DEC, parts.MIX


def _table(kind, tq, ops):
    return {"program": {"fn": "_f", "kind": kind, "tq": tq,
                        "chunk_group": int(kind == "mixed")},
            "ops": [list(o) for o in ops]}


# Two programs. ``fusion.1 bf16[8,64]`` is in both under one scope,
# ``fusion.2`` in both under two scopes of one part; the kernels are of one
# program each.
TABLES = [
    _table("decode", 1, [
        ("fusion.1", "bf16[8,64]", "ffn/dot_general"),
        ("fusion.2", "bf16[8,64]", "attn_in/dot_general"),
        ("flash_decode_paged.8", "", "attn_decode/pallas_call"),
        ("fusion.7", "f32[8,1,512]", "head/dot_general"),
        ("copy.3", "s32[]", ""),
        ("fusion.5", "bf16[8,64]", "embed/<-fusion.9"),
        ("while.4", "", ""),
    ]),
    _table("mixed", 16, [
        ("fusion.1", "bf16[8,64]", "ffn/jit(silu)/mul"),
        ("fusion.2", "bf16[8,64]", "attn_out/dot_general"),
        ("flash_fwd.3", "", "attn_chunk/pallas_call"),
        ("fusion.30", "bf16[24,64]", "attn_cache/scatter"),
        ("fusion.31", "bf16[24,64]", "route/gather"),
        ("moe_grouped_matmul.2", "bf16[128,64]", "experts/pallas_call"),
        ("fusion.32", "bf16[24,64]", "conv/mul"),
    ]),
]
# start, end, chunk tokens, live slots: decode, mixed, decode, idle.
SPANS = [(0.0, 10.0, 0, 8), (10.0, 30.0, 16, 8), (30.0, 40.0, 0, 8),
         (40.0, 45.0, 0, 0)]


def test_keys_join_by_the_name_a_trace_gives():
    key = parts.keys(TABLES)
    assert key["flash_decode_paged.8"] == (DEC, "attn_decode")
    assert key["fusion.7 f32[8,1,512]"] == (DEC, "head")
    assert key["fusion.5 bf16[8,64]"] == (DEC, "head")      # embed -> head
    assert key["flash_fwd.3"] == (MIX, "attn_chunk")
    assert key["fusion.30 bf16[24,64]"] == (MIX, "attn_decode")  # the write
    assert key["fusion.31 bf16[24,64]"] == (MIX, "moe")
    assert key["moe_grouped_matmul.2 bf16[128,64]"] == (MIX, "moe")
    assert key["fusion.32 bf16[24,64]"] == (MIX, "conv")
    # In both programs with one part: the span decides the kind of tick.
    assert key["fusion.1 bf16[8,64]"] == (None, "ffn")
    # In both programs under two scopes of ONE part: still that part.
    assert parts.PART_OF["attn_in"] == parts.PART_OF["attn_out"] == "proj"
    assert key["fusion.2 bf16[8,64]"] == (None, "proj")
    # A row without a scope is a row all the same: its program is known.
    assert key["copy.3 s32[]"] == (DEC, parts.UNSCOPED)


def test_a_name_with_two_parts_is_unscoped():
    tables = [_table("decode", 1, [("fusion.9", "bf16[8]", "ffn/x")]),
              _table("mixed", 16, [("fusion.9", "bf16[8]", "head/y")])]
    assert parts.keys(tables)["fusion.9 bf16[8]"] == (None, parts.UNSCOPED)
    # ...and so is one a second program holds without a scope.
    tables[1]["ops"][0][2] = ""
    assert parts.keys(tables)["fusion.9 bf16[8]"] == (None, parts.UNSCOPED)


def test_a_verify_program_goes_by_the_span():
    key = parts.keys([_table("verify", 8, [("fusion.4", "bf16[8]", "ffn/x")])])
    assert key["fusion.4 bf16[8]"] == (None, "ffn")


EVENTS = [
    ("while.4", 0.5, 8.0),                       # encloses: not a leaf
    ("fusion.1 bf16[8,64]", 1.0, 2.0),           # shared: span 0, decode
    ("flash_decode_paged.8", 4.0, 1.0),
    ("copy.3 s32[]", 6.0, 0.5),                  # known program, no scope
    ("fusion.99 bf16[1]", 7.0, 0.25),            # no table: span 0
    # The mixed span, 10..30.
    ("fusion.7 f32[8,1,512]", 10.5, 1.5),        # the DECODE program's head,
    #   late: t_s trails the device. Still decode.
    ("fusion.1 bf16[8,64]", 12.0, 4.0),          # shared: span 1, mixed
    ("flash_fwd.3", 17.0, 3.0),
    ("fusion.30 bf16[24,64]", 21.0, 1.0),
    ("moe_grouped_matmul.2 bf16[128,64]", 23.0, 2.0),
    ("fusion.31 bf16[24,64]", 26.0, 0.5),
    ("fusion.98 bf16[2]", 27.0, 0.75),           # no table: span 1
    # Decode again, 30..40.
    ("fusion.32 bf16[24,64]", 30.5, 0.5),        # the MIXED program's, late
    ("flash_decode_paged.8", 33.0, 1.0),
    # An idle tick, 40..45: neither kind.
    ("fusion.97 bf16[3]", 41.0, 0.125),
    ("flash_decode_paged.8", 42.0, 1.0),         # its program says decode
    # Outside every span.
    ("fusion.1 bf16[8,64]", 46.0, 5.0),
]


def test_split_puts_an_operation_to_its_programs_kind_of_tick():
    got = parts.split(EVENTS, SPANS, parts.keys(TABLES))
    assert got[DEC] == {
        "ffn": 2.0, "attn_decode": 3.0, "head": 1.5,
        parts.UNSCOPED: 0.5 + 0.25}
    assert got[MIX] == {
        "ffn": 4.0, "attn_chunk": 3.0, "attn_decode": 1.0, "moe": 2.5,
        "conv": 0.5, parts.UNSCOPED: 0.75}
    assert got[None] == {parts.UNSCOPED: 0.125}


def test_parts_and_unscoped_add_up_to_the_leaves_total():
    """The closure every ``dec_*`` / ``mix_*`` metric rests on."""
    from benchmark import trace_reduce

    got = parts.split(EVENTS, SPANS, parts.keys(TABLES))
    inside = [e for e in trace_reduce.leaves(EVENTS)
              if SPANS[0][0] <= e[1] < SPANS[-1][1]]
    assert sum(s for p in got.values() for s in p.values()) \
        == pytest.approx(sum(d for _, _, d in inside))
    assert "while.4" not in {e[0] for e in inside}


def test_the_offset_moves_events_onto_the_spans_clock():
    moved = [(n, s + 1000.0, d) for n, s, d in EVENTS]
    key = parts.keys(TABLES)
    assert parts.split(moved, SPANS, key, 1000.0) \
        == parts.split(EVENTS, SPANS, key)
    assert parts.split(moved, SPANS, key) == {DEC: {}, MIX: {}, None: {}}


# -- through a run -----------------------------------------------------------


def _run(tables=TABLES, devices=1):
    """A traced run as the harness hands it to a reader: flight records
    whose stamps make SPANS (and one more record to close the last), the
    trace's window round them, the tables in the report."""
    flight = [{"t_s": a, "chunk_tokens": c, "occupancy": live}
              for a, _, c, live in SPANS] + [
                  {"t_s": 45.0, "chunk_tokens": 0, "occupancy": 8}]
    off = 500.0
    events = {f"/device:TPU:{i}": [(n, s + off, d) for n, s, d in EVENTS]
              for i in range(devices)}
    trace = {"offset_s": off, "t0": off - 1.0, "t1": off + 45.5,
             "devices": devices, "events": events}
    return types.SimpleNamespace(
        trace=trace, flight=flight,
        report={"programs": tables} if tables is not None else {})


@pytest.mark.parametrize("kind, part, ms", [
    (DEC, "ffn", 1e3 * 2.0 / 2), (DEC, "attn_decode", 1e3 * 3.0 / 2),
    (DEC, "head", 1e3 * 1.5 / 2), (DEC, "proj", 0.0), (DEC, "moe", 0.0),
    (MIX, "ffn", 1e3 * 4.0), (MIX, "attn_chunk", 1e3 * 3.0),
    (MIX, "attn_decode", 1e3 * 1.0), (MIX, "moe", 1e3 * 2.5),
    (MIX, "conv", 1e3 * 0.5), (MIX, "head", 0.0),
])
def test_milliseconds_a_tick_of_its_kind(kind, part, ms):
    """Two whole decode ticks with a live slot and one mixed tick; the idle
    tick counts for neither."""
    assert parts.ms_tick(_run(), kind, part) == pytest.approx(ms)


def test_two_devices_read_as_one():
    assert parts.ms_tick(_run(devices=2), MIX, "moe") \
        == pytest.approx(1e3 * 2.5)


def test_unscoped_share_of_the_ticks_leaf_seconds():
    unscoped = 0.5 + 0.25 + 0.75 + 0.125
    total = sum(d for n, s, d in EVENTS[1:-1])
    assert parts.unscoped_pct(_run()) == pytest.approx(
        100.0 * unscoped / total)


def test_a_tick_cut_by_the_windows_end_is_not_counted():
    run = _run()
    run.trace["t1"] = run.trace["offset_s"] + 39.0   # inside the third span
    got = parts.of_run(run)
    assert got["ticks"] == {DEC: 1, MIX: 1}
    assert got["seconds"][DEC]["attn_decode"] == pytest.approx(1.0)


@pytest.mark.parametrize("spoil", ["tables", "trace", "flight", "offset",
                                   "spans"])
def test_a_run_without_what_it_takes_reads_nothing(spoil):
    """A parent commit's report holds no tables; an untraced run no trace:
    every reader then gives None and raises nothing."""
    run = _run(tables=None) if spoil == "tables" else _run()
    if spoil == "trace":
        run.trace = None
    elif spoil == "flight":
        run.flight = None
    elif spoil == "offset":
        del run.trace["offset_s"]
    elif spoil == "spans":
        run.flight = run.flight[:1]
    assert parts.of_run(run) is None
    assert parts.ms_tick(run, DEC, "ffn") is None
    assert parts.unscoped_pct(run) is None


def test_every_reader_file_reads_through_the_shared_module():
    """The fourteen metrics ``BENCHMARK.json`` gained: each has its reader,
    and each reader gives a number for the hand-made run."""
    import json

    from benchmark.spec import Spec

    spec = Spec(os.path.join(ROOT, "BENCHMARK.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]
                 if m["name"].startswith(("dec_", "mix_"))
                 or m["name"] == "tick_unscoped_pct"]
    assert len(names) == 14
    for name in names:
        reader = spec.load_module("layer_metrics", name + ".py")
        assert isinstance(reader.read(_run()), float), name
        assert reader.read(_run(tables=None)) is None, name
