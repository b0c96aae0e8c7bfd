"""From the profiler's xplane to device busy and idle time, time per named
operation, and idle gaps named by what the host was doing.

The reduction works on plain tuples, so it is tested without a profiler
(``tests/test_trace_reduce.py``, on a recorded fixture and on hand-made
events). Only :func:`load` touches the xplane file.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

BEGIN_MARK, END_MARK = "bench_trace_begin", "bench_trace_end"
DEVICE_PLANE, OPS_LINE = "/device:TPU:", "XLA Ops"
Event = Tuple[str, float, float]          # name, start_s, duration_s

class Recording:
    """One profiler trace of the end of a window: started from the engine's
    own thread, stopped after the engine has returned, then reduced."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.on = False
        self.begin_monotonic: Optional[float] = None

    def start(self) -> None:
        """Start the profiler and leave a mark on the trace's own clock."""
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        self.on = True
        self.begin_monotonic = time.monotonic()
        with jax.profiler.TraceAnnotation(BEGIN_MARK):
            pass

    def stop(self) -> Optional[str]:
        """Mark the end, stop the profiler; returns the xplane it wrote."""
        import jax

        with jax.profiler.TraceAnnotation(END_MARK):
            pass
        jax.profiler.stop_trace()
        self.on = False
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def reduce(self, xplane_path: str) -> Dict[str, Any]:
        """Load and reduce. ``offset_s`` is the trace's clock minus
        ``time.monotonic()``, read at the begin mark, so that a reader can
        lay host stamps over device events."""
        data = load(xplane_path)
        out = reduce_events(data)
        begin = find_mark(data["host"], BEGIN_MARK)
        if begin is not None and self.begin_monotonic is not None:
            out["offset_s"] = begin - self.begin_monotonic
        return out


def load(xplane_path: str) -> Dict[str, Any]:
    """The xplane as plain data: per device a list of operation events, and
    per host thread a list of events, in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    with open(xplane_path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return planes_to_events(data)


_RESULT = re.compile(r"[a-z0-9]+\[[0-9,]*\]")


def short_name(text: str) -> str:
    """A device event is named by its whole HLO instruction; keep the
    instruction's name and its result's type and shape:
    ``%fusion.209 = bf16[8,256,11008]{...} fusion(...)`` becomes
    ``fusion.209 bf16[8,256,11008]``. A tuple result keeps the name alone."""
    if " = " not in text:
        return text.lstrip("%")
    name, rest = text.split(" = ", 1)
    found = _RESULT.match(rest)
    name = name.lstrip("%")
    return f"{name} {found.group(0)}" if found else name


def leaves(events: List[Event]) -> List[Event]:
    """Drop the events that enclose others (a ``while`` around its body's
    operations): their time is their children's, counted once."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][1] >= e[1] + e[2]
            or ev[i + 1][1] + ev[i + 1][2] > e[1] + e[2]]


def planes_to_events(data) -> Dict[str, Any]:
    devices: Dict[str, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in line.events]
            if on_device:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (short_name(n), s, d) for n, s, d in events)
            elif plane.name.startswith("/host:"):
                host.setdefault(line.name, []).extend(events)
    return {"devices": devices, "host": host}


# -- pure reduction ---------------------------------------------------------


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint ones, in order."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def find_mark(host: Dict[str, List[Event]], name: str) -> Optional[float]:
    for events in host.values():
        for n, s, _ in events:
            if n == name:
                return s
    return None


class Frames:
    """The deepest host frame covering an instant, per thread. Events of one
    thread nest, so the covering event that started last is the deepest."""

    def __init__(self, host: Dict[str, List[Event]]):
        self._threads = []
        for events in host.values():
            ev = sorted((e for e in events if e[2] > 0), key=lambda e: e[1])
            if ev:
                self._threads.append(([e[1] for e in ev], ev))

    def at(self, t: float, reach: int = 4096) -> Optional[str]:
        best: Optional[Event] = None
        for starts, ev in self._threads:
            i = bisect.bisect_right(starts, t) - 1
            for j in range(i, max(i - reach, -1), -1):
                name, s, d = ev[j]
                if s + d > t:
                    if best is None or d < best[2]:
                        best = ev[j]
                    break
        return None if best is None else best[0]


def reduce_events(data: Dict[str, Any], t0: Optional[float] = None,
                  t1: Optional[float] = None) -> Dict[str, Any]:
    """Busy seconds averaged over the devices, the window, seconds per
    operation name, and idle gaps summed by the host frame at their middle.
    The window is ``[t0, t1]`` on the trace's clock (default: between the
    begin and end marks, else the whole trace)."""
    host, devices = data["host"], data["devices"]
    if t0 is None:
        t0 = find_mark(host, BEGIN_MARK)
    if t1 is None:
        t1 = find_mark(host, END_MARK)
    every = [e for ev in devices.values() for e in ev]
    if t0 is None:
        t0 = min((e[1] for e in every), default=0.0)
    if t1 is None:
        t1 = max((e[1] + e[2] for e in every), default=t0)
    window = max(t1 - t0, 0.0)
    n_dev = max(len(devices), 1)
    busy = 0.0
    per_op: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    frames = Frames(host)
    clipped: Dict[str, List[Event]] = {}
    for dev, events in devices.items():
        inside = clip(events, t0, t1)
        clipped[dev] = inside
        merged = union([(s, s + d) for _, s, d in inside])
        busy += sum(b - a for a, b in merged)
        for name, _, d in leaves(inside):
            per_op[name] = per_op.get(name, 0.0) + d / n_dev
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                who = frames.at((a + b) / 2) or "(no host frame)"
                gaps[who] = gaps.get(who, 0.0) + (b - a) / n_dev
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])
    return {
        "busy_s": busy / n_dev, "window_s": window, "t0": t0, "t1": t1,
        "devices": len(devices), "device_ops": rank(per_op),
        "idle_gaps": rank(gaps), "events": clipped,
    }


def describe(xplane_path: str, limit: int = 4) -> None:
    """Print what is in an xplane: planes, lines, a few events with their
    stats. For looking at a trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    with open(xplane_path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, "lines:", len(lines))
        for line in lines:
            events = list(line.events)
            print("  LINE", repr(line.name), "events:", len(events))
            for e in events[:limit]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:12]))


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])
    out = reduce_events(load(sys.argv[1]))
    print({k: (v[:12] if isinstance(v, list) else v)
           for k, v in out.items() if k != "events"})
