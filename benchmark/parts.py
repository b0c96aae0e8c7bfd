"""A tick's device time by the part of the model that spent it.

The program says which part of the model each operation of each tick
program belongs to: with tracing on, ``ServeReport.as_dict()["programs"]``
holds one table a program (``tree_attention_tpu/obs/scopes.py``), rows
``[op, result, scope]`` of the optimized module, where ``"<op> <result>"``
is the name ``trace_reduce.short_name`` gives the operation's events and
``scope`` starts with the name of the ``jax.named_scope`` the layer body
wrapped it in. Here the traced window's leaf events are joined to the tables
by that name, put to a kind of tick by the table's program (and by the span
the event starts in only where programs of both kinds hold the name), and
summed by part. A name that no table holds, that a table holds without a
scope, or that two tables put to different parts, is unscoped: the share of
those seconds is the guard on every part's reading.

A program without the tables (a parent commit) gives ``None`` everywhere.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Tuple

from benchmark import ticks, trace_reduce

try:
    from tree_attention_tpu.obs import scopes as _s
except ImportError:     # a program from before the tables: nothing to join
    _s = None

# The scopes the layer bodies use (the program's own vocabulary), and the
# part a metric reads each under.
PART_OF = {} if _s is None else {
    _s.EMBED: "head", _s.HEAD: "head",
    _s.ATTN_IN: "proj", _s.ATTN_OUT: "proj",
    _s.ATTN_CACHE: "attn_decode", _s.ATTN_DECODE: "attn_decode",
    _s.ATTN_CHUNK: "attn_chunk",
    _s.CONV: "conv", _s.FFN: "ffn",
    _s.ROUTE: "moe", _s.EXPERTS: "moe",
}
# A flight record's ``kind`` of the ticks a program runs: the kinds of tick
# the metrics tell apart. A program of any other kind goes by the span.
DEC, MIX = "dec", "mix"
_KIND_OF = {"decode": DEC, "mixed": MIX}
UNSCOPED = ""


def keys(tables: List[Dict[str, Any]]) -> Dict[str, Tuple[Optional[str], str]]:
    """Event name -> (kind of tick or None: by the span, part or
    ``UNSCOPED``) over every program's table."""
    seen: Dict[str, Tuple[set, set]] = {}
    for table in tables:
        kind = _KIND_OF.get(table["program"].get("kind"))
        for op, result, scope in table["ops"]:
            name = f"{op} {result}" if result else op
            part = PART_OF.get(scope.split("/", 1)[0], UNSCOPED)
            kinds, parts = seen.setdefault(name, (set(), set()))
            kinds.add(kind)
            parts.add(part)
    return {name: (next(iter(kinds)) if len(kinds) == 1 else None,
                   next(iter(parts)) if len(parts) == 1 else UNSCOPED)
            for name, (kinds, parts) in seen.items()}


def split(events: List[trace_reduce.Event], spans: List[ticks.Span],
          key: Dict[str, Tuple[Optional[str], str]],
          offset: float = 0.0) -> Dict[str, Any]:
    """Seconds of the leaf ``events`` (on the trace's clock, ``offset``
    ahead of the spans') that start inside the whole of ``spans``, by kind
    of tick and part: ``{DEC: {part: s}, MIX: {...}}``, with what lies in a
    tick of neither kind (no live slot) under ``None``. Every kind's parts,
    its ``UNSCOPED`` among them, add up to its events' seconds."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    out: Dict[Any, Dict[str, float]] = {DEC: {}, MIX: {}, None: {}}
    for name, s, d in trace_reduce.leaves(events):
        at = s - offset
        i = bisect.bisect_right(starts, at) - 1
        if i < 0 or at >= spans[-1][1]:
            continue
        kind, part = key.get(name, (None, UNSCOPED))
        if kind is None:
            _, _, chunk, live = spans[i]
            kind = MIX if chunk > 0 else DEC if live > 0 else None
        out[kind][part] = out[kind].get(part, 0.0) + d
    return out


def of_run(run) -> Optional[Dict[str, Any]]:
    """The traced window of ``run`` split: ``{"seconds": split's result
    summed over the devices, "ticks": {DEC: n, MIX: n}, "devices": n}``;
    None without a trace, flight records or the program's tables. Made
    once a run and kept on it."""
    if hasattr(run, "_parts"):
        return run._parts
    tr = run.trace
    tables = (run.report or {}).get("programs")
    out = None
    if tr and run.flight and "offset_s" in tr and tables:
        off = tr["offset_s"]
        w0, w1 = tr["t0"] - off, tr["t1"] - off      # on the host's clock
        spans = [s for s in ticks.spans(run.flight, w0, w1) if s[1] <= w1]
        if spans:
            key = keys(tables)
            seconds: Dict[Any, Dict[str, float]] = {DEC: {}, MIX: {}, None: {}}
            for events in tr["events"].values():
                for kind, parts in split(events, spans, key, off).items():
                    for part, s in parts.items():
                        seconds[kind][part] = seconds[kind].get(part, 0.0) + s
            out = {
                "seconds": seconds,
                "ticks": {DEC: sum(1 for s in spans if s[2] == 0 and s[3] > 0),
                          MIX: sum(1 for s in spans if s[2] > 0)},
                "devices": max(tr["devices"], 1),
            }
    run._parts = out
    return out


def ms_tick(run, kind: str, part: str) -> Optional[float]:
    """Milliseconds of ``part`` a tick of ``kind`` (``DEC`` / ``MIX``):
    the part's seconds over the whole ticks of that kind in the traced
    window. None where the run has no tables or no such tick."""
    got = of_run(run)
    if not got or not got["ticks"][kind]:
        return None
    return 1e3 * got["seconds"][kind].get(part, 0.0) \
        / (got["devices"] * got["ticks"][kind])


def unscoped_pct(run) -> Optional[float]:
    """100 x the unscoped seconds over all leaf seconds in the traced
    window's ticks."""
    got = of_run(run)
    if not got:
        return None
    total = sum(s for parts in got["seconds"].values()
                for s in parts.values())
    if not total:
        return None
    return 100.0 * sum(parts.get(UNSCOPED, 0.0)
                       for parts in got["seconds"].values()) / total
