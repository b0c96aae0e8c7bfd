"""The engine's own phase stamps, from the flight recorder's records.

Each executed tick's record holds ``phases``, ``[name, start]`` pairs in
absolute ``time.monotonic()`` seconds, and ``t_end``, the stamp that closes
the last one (``tree_attention_tpu/obs/flight.py`` ``TickPhases``). Two
readings are made of them: how long the host took for each part of a tick,
over the ticks of the window that ran with the profiler off, and which part
of a tick each idle interval of the device fell under, in the traced part.

A program without the stamps (a parent commit) has no ``phases`` in its
records; every function here then finds nothing and returns ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import reduce, trace_reduce

# The groups the device's idle time is split into, by the phase it fell
# under. What falls under no phase lies between two ticks: the loop was
# waiting for a request.
FETCH, DISPATCH, HOST, WAIT = "fetch", "dispatch", "host", "wait"
_GROUP = {"fetch": FETCH, "table_sync": DISPATCH, "dispatch": DISPATCH}

Interval = Tuple[float, float]


def tick_phases(rec: Dict[str, Any]) -> List[Tuple[str, float, float]]:
    """``(name, start, end)`` for each phase of one record; a phase lasts
    until the next one's start, the last until ``t_end``."""
    marks = rec["phases"]
    ends = [m[1] for m in marks[1:]] + [rec["t_end"]]
    return [(m[0], m[1], e) for m, e in zip(marks, ends)]


def profiler_start(run) -> float:
    """Where the untraced part of the window ends, on the host's clock:
    the trace's begin mark, or the window's close without a trace."""
    tr = run.trace
    if tr and "offset_s" in tr:
        return min(tr["t0"] - tr["offset_s"], run.t_end)
    return run.t_end


def fetched_ticks(run, t0: Optional[float] = None,
                  t1: Optional[float] = None) -> List[Dict[str, float]]:
    """Seconds per phase name, for each executed tick that fetched its
    tokens, started at or after ``t0`` and ended before ``t1``. The default
    is the window's part before the profiler started, so that the times are
    the host's own and not the python tracer's."""
    if not run.flight:
        return []
    t0 = run.t_open if t0 is None else t0
    t1 = profiler_start(run) if t1 is None else t1
    out = []
    for rec in run.flight:
        if not rec.get("phases") or rec["phases"][0][1] < t0 \
                or rec["t_end"] >= t1:
            continue
        spent: Dict[str, float] = {}
        for name, a, b in tick_phases(rec):
            spent[name] = spent.get(name, 0.0) + (b - a)
        if "fetch" in spent:
            out.append(spent)
    return out


def percentile_ms(run, names: Optional[Sequence[str]], q: float, *,
                  but: Sequence[str] = (), t0: Optional[float] = None,
                  t1: Optional[float] = None) -> Optional[float]:
    """The ``q`` quantile, in milliseconds, over :func:`fetched_ticks` of
    a tick's time in the phases ``names`` (``None``: every phase) less
    those in ``but``. ``None`` where no tick has the stamps."""
    sums = [1e3 * sum(v for k, v in spent.items()
                      if (names is None or k in names) and k not in but)
            for spent in fetched_ticks(run, t0, t1)]
    return reduce.percentile(sums, q) if sums else None


def rows(run) -> Optional[Tuple[int, int]]:
    """Rows the tick programs computed and rows that carried a token, over
    the window's ticks."""
    computed = useful = 0
    for rec in run.flight or ():
        if "rows_computed" in rec and run.t_open <= rec["t_s"] < run.t_end:
            computed += int(rec["rows_computed"])
            useful += int(rec["rows_useful"])
    return (computed, useful) if computed else None


# -- the device's idle time, by what the host was doing ----------------------


def idle_intervals(events: Sequence[trace_reduce.Event], t0: float,
                   t1: float) -> List[Interval]:
    """The parts of ``[t0, t1]`` that no event covers, in order."""
    busy = trace_reduce.union(
        [(s, s + d) for _, s, d in trace_reduce.clip(events, t0, t1)])
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def apportion(idle: Sequence[Interval],
              phases: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of ``idle`` under each group of phases, by overlap; what no
    phase covers goes to ``wait``. Both are in order and on one clock, and
    the phases do not overlap each other."""
    out = {FETCH: 0.0, DISPATCH: 0.0, HOST: 0.0, WAIT: 0.0}
    j = 0
    for a, b in idle:
        while j < len(phases) and phases[j][2] <= a:
            j += 1
        covered = 0.0
        k = j
        while k < len(phases) and phases[k][1] < b:
            name, s, e = phases[k]
            part = min(e, b) - max(s, a)
            if part > 0:
                out[_GROUP.get(name, HOST)] += part
                covered += part
            k += 1
        out[WAIT] += (b - a) - covered
    return out


def idle_shares(run) -> Optional[Dict[str, float]]:
    """Percent of the traced window in which the device ran nothing, split
    by the group of phases the host was in. The four add up to the idle
    share the trace's own reduction gives (``device_idle_pct``)."""
    tr = run.trace
    if not tr or "offset_s" not in tr or not tr.get("events") \
            or not tr["window_s"] or not run.flight:
        return None
    off = tr["offset_s"]
    phases = sorted(
        ((name, a + off, b + off)
         for rec in run.flight if rec.get("phases")
         for name, a, b in tick_phases(rec)),
        key=lambda p: p[1])
    if not phases:
        return None
    total = {FETCH: 0.0, DISPATCH: 0.0, HOST: 0.0, WAIT: 0.0}
    for events in tr["events"].values():
        part = apportion(idle_intervals(events, tr["t0"], tr["t1"]), phases)
        for k, v in part.items():
            total[k] += v
    scale = 100.0 / (len(tr["events"]) * tr["window_s"])
    return {k: v * scale for k, v in total.items()}
