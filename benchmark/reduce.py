"""From token stamps to the end-to-end metrics. Host clock only."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics; ``inf`` sorts last."""
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    pos = q * (len(v) - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or math.isinf(v[hi]):
        return v[hi] if pos > lo else v[lo]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def tokens_in(recs, t0: float, t1: float) -> int:
    return sum(1 for r in recs for s in r.stamps if t0 <= s < t1)


def token_gaps(recs, t0: float, t1: float) -> List[float]:
    """Gaps between consecutive tokens of one request, both inside the
    window, pooled over requests."""
    out: List[float] = []
    for r in recs:
        for a, b in zip(r.stamps, r.stamps[1:]):
            if a >= t0 and b < t1:
                out.append(b - a)
    return out


def ttfts(recs, t0: float, t1: float) -> List[float]:
    """First token's stamp minus the DUE time, over requests due inside the
    window. One still unserved when the window closes counts as infinite: it
    sorts last, and a median of mostly-unserved requests is then infinite
    rather than flattering."""
    out: List[float] = []
    for r in recs:
        if r.midlife or not (t0 <= r.due < t1):
            continue
        first = r.stamps[0] if r.stamps else None
        out.append(first - r.due if first is not None and first < t1
                   else math.inf)
    return out


TTFT_UNIT_TOKENS = 256


def ttfts_per_unit(recs, t0: float, t1: float) -> List[float]:
    """Time to first token over the prompt's started blocks of
    ``TTFT_UNIT_TOKENS`` tokens: a 3,584-token prompt and a 256-token one
    then read alike, and the median does not hang on which prompts the
    window happened to hold."""
    out: List[float] = []
    for r in recs:
        if r.midlife or not (t0 <= r.due < t1):
            continue
        first = r.stamps[0] if r.stamps else None
        units = max(-(-len(r.prompt) // TTFT_UNIT_TOKENS), 1)
        out.append((first - r.due) / units
                   if first is not None and first < t1 else math.inf)
    return out


def end_to_end(recs, t_open: float, t_end: float,
               setup_s: float) -> Dict[str, Dict[str, Any]]:
    """Every end-to-end metric this harness knows; the cell's list in the
    benchmark file picks the ones it reports."""
    seconds = t_end - t_open
    gaps = token_gaps(recs, t_open, t_end)
    first = ttfts(recs, t_open, t_end)
    per_unit = ttfts_per_unit(recs, t_open, t_end)
    tokens = tokens_in(recs, t_open, t_end)
    out: Dict[str, Dict[str, Any]] = {
        "setup_s": {"value": setup_s, "unit": "s"},
        "out_tok_s": {"value": tokens / seconds, "unit": "tokens/s",
                      "samples": tokens},
    }
    if gaps:
        out["tbt_p50_ms"] = {"value": 1e3 * percentile(gaps, 0.50),
                             "unit": "ms", "samples": len(gaps)}
        out["tbt_p99_ms"] = {"value": 1e3 * percentile(gaps, 0.99),
                             "unit": "ms", "samples": len(gaps)}
    if first:
        out["ttft_p50_ms"] = {"value": 1e3 * percentile(first, 0.50),
                              "unit": "ms", "samples": len(first)}
        out["ttft_per_256tok_p50_ms"] = {
            "value": 1e3 * percentile(per_unit, 0.50), "unit": "ms",
            "samples": len(per_unit)}
    return out


def halves(recs, t_open: float, t_end: float) -> Dict[str, float]:
    """Tokens per second of the window's first and second half: a printed
    aid for judging steadiness, not a metric."""
    mid = (t_open + t_end) / 2
    half = (t_end - t_open) / 2
    return {"first_half_tok_s": tokens_in(recs, t_open, mid) / half,
            "second_half_tok_s": tokens_in(recs, mid, t_end) / half}


def failures(recs, t_end: float) -> Dict[str, int]:
    """Attempted: requests that finished before the window's end, or failed.
    Failed: any of them with another outcome than its full budget, or with
    another number of tokens than asked for."""
    attempted = failed = 0
    for r in recs:
        if r.finished is None or r.finished >= t_end:
            continue
        attempted += 1
        if r.outcome != "budget" or len(r.tokens) != r.output:
            failed += 1
    return {"attempted": attempted, "failed": failed}


def stalls(polls, t_open: float, over_s: float = 0.5, keep: int = 5):
    """The longest intervals between two polls of the engine's loop (one
    tick, normally a tenth of a second or less): when it began, how long it
    took, and the CPU time the process used in it. A printed aid, not a
    metric: a tick of seconds with no CPU time is the process waiting (on
    the device or for a core), with CPU time it is the host's own work."""
    out = [[a[0] - t_open, b[0] - a[0], b[1] - a[1]]
           for a, b in zip(polls, polls[1:]) if b[0] - a[0] > over_s]
    return sorted(out, key=lambda x: -x[1])[:keep]
