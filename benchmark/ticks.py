"""Tick spans from the flight recorder's records, for the tick metrics."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

# start, end, chunk tokens, live slots
Span = Tuple[float, float, int, int]


def spans(flight: List[Dict[str, Any]], t0: float, t1: float) -> List[Span]:
    """A tick lasts from its record's stamp to the next record's; ticks
    that start inside ``[t0, t1)`` count."""
    recs = sorted((r for r in flight if "t_s" in r), key=lambda r: r["t_s"])
    out: List[Span] = []
    for a, b in zip(recs, recs[1:]):
        if t0 <= a["t_s"] < t1:
            out.append((a["t_s"], b["t_s"], int(a.get("chunk_tokens", 0)),
                        int(a.get("occupancy", 0))))
    return out


def durations_ms(run, mixed: bool) -> List[float]:
    if not run.flight:
        return []
    return [1e3 * (b - a) for a, b, chunk, _ in
            spans(run.flight, run.t_open, run.t_end) if (chunk > 0) == mixed]


def live_contexts(recs, t: float) -> List[int]:
    """Tokens each live request attends to in a tick that starts at ``t``:
    its prompt and what it has emitted so far."""
    out = []
    for r in recs:
        if not r.stamps or r.stamps[0] >= t:
            continue
        if r.finished is not None and r.finished < t:
            continue
        out.append(len(r.prompt) + sum(1 for s in r.stamps if s < t))
    return out
