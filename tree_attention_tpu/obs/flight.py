"""Tick flight recorder: a bounded ring of per-tick serving records.

The serving engine's aggregate metrics say *how much*; the span trace says
*how long* — neither answers "what was the engine doing when it wedged?"
after the process is gone. This is the black box: every tick the engine
appends one small structured record (occupancy, slot states, chunk plan,
tokens emitted, whether the tick paid the host sync, queue depth, wall
time) to a fixed-capacity ring. Cost is O(1) per tick and bounded memory
forever; the ring holds the LAST ``capacity`` ticks — exactly the window a
post-mortem needs.

Dump triggers (any of):

- **on demand** — the ``/flight`` HTTP endpoint or :meth:`snapshot`;
- **on engine error** — ``SlotServer.serve`` dumps before re-raising;
- **on SIGTERM / SIGUSR1 / atexit** — :func:`obs.install_crash_handlers
  <tree_attention_tpu.obs.install_crash_handlers>` flushes the armed sink
  (``--flight-out`` / ``TA_FLIGHT_OUT``), so a killed or wedged run still
  leaves its last ticks on disk.

Liveness: :meth:`last_tick_age` is the seconds since the engine last
recorded a tick — the ``/healthz`` endpoint's truth (a serving process
whose ring stopped moving is wedged even if the HTTP thread still
answers).

Disabled (the default) is free: :meth:`record` is one attribute check and
an early return; call sites must build their record dict only under an
``if FLIGHT.enabled:`` guard — the same contract as span args.

The tick from inside: a :class:`TickPhases` (one per ``serve()`` run — a
process may run several engine threads) stamps the boundaries between the
phases of one engine tick and hands the one set of stamps to three sinks —
the tick's flight record, the ``jax.profiler`` trace, and the
``--trace-events`` JSONL.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from tree_attention_tpu.obs.tracing import TRACER

DEFAULT_CAPACITY = 256

#: The phases of one ``SlotServer.serve`` tick, in the order the loop runs
#: them; each lasts until the next mark (ARCHITECTURE.md "Observability").
TICK_PHASES = ("ingest", "sweep", "admit", "plan", "pack", "table_sync",
               "dispatch", "publish", "fetch", "emit", "account")
_ANNOTATION = {p: "tick:" + p for p in TICK_PHASES}


class FlightRecorder:
    """Fixed-capacity ring of per-tick records; disarmed until enabled."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        # Reentrant: the SIGTERM/SIGUSR1 flush runs on the main thread and
        # may interrupt a record() holding this lock; a plain Lock would
        # deadlock dump_if_armed instead of dumping.
        self._lock = threading.RLock()
        self._ring: deque = deque(maxlen=capacity)
        self._ticks_recorded = 0
        self._last_tick_t: Optional[float] = None
        self._idle = True
        self._dump_path: Optional[str] = None
        self._programs: List[Dict[str, Any]] = []
        self.enabled = False

    # -- lifecycle --------------------------------------------------------

    def arm(self, dump_path: Optional[str] = None,
            capacity: Optional[int] = None) -> None:
        """Enable recording; ``dump_path`` is where crash/error/signal
        dumps land (``None`` keeps the ring memory-only — ``/flight`` and
        :meth:`snapshot` still serve it)."""
        with self._lock:
            if capacity is not None and capacity != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=max(capacity, 1))
            self._dump_path = dump_path
        self.enabled = True

    def disarm(self) -> None:
        self.enabled = False
        with self._lock:
            self._dump_path = None

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._programs = []
            self._ticks_recorded = 0
            self._last_tick_t = None
            self._idle = True

    @property
    def capacity(self) -> int:
        return self._ring.maxlen or 0

    @property
    def ticks_recorded(self) -> int:
        return self._ticks_recorded

    # -- recording --------------------------------------------------------

    def record(self, rec: Optional[Dict[str, Any]]) -> None:
        """Append one per-tick record. The dict is the caller's — built
        only under ``if FLIGHT.enabled:`` so the disabled path allocates
        nothing (``record(None)`` when disabled is the no-op fast path)."""
        if not self.enabled or rec is None:
            return
        now = time.monotonic()
        with self._lock:
            self._ring.append(rec)
            self._ticks_recorded += 1
            self._last_tick_t = now
            self._idle = False

    def describe_programs(self, tables: List[Dict[str, Any]]) -> None:
        """Keep the engine's tables from its tick programs' operations to
        the parts of the model (``obs/scopes.py``; the engine's own list,
        which grows as it builds programs) for the dumps: a device trace
        taken beside the ring names operations ``fusion.453``, and the
        table says which part of the model each is."""
        with self._lock:
            self._programs = tables

    def mark_idle(self) -> None:
        """Declare the tick loop drained (a serve() run completed): the
        engine is between runs, not wedged — ``/healthz`` must not count
        a finished run's age as a stall. The ring and liveness timestamp
        survive for post-mortems; the next record() clears idleness."""
        with self._lock:
            self._idle = True

    @property
    def idle(self) -> bool:
        return self._idle

    def last_tick_age(self) -> Optional[float]:
        """Seconds since the last recorded tick; None before any tick."""
        t = self._last_tick_t
        return None if t is None else max(time.monotonic() - t, 0.0)

    # -- export -----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            records: List[Dict[str, Any]] = list(self._ring)
            ticks = self._ticks_recorded
            programs = list(self._programs)
        age = self.last_tick_age()
        return {
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "capacity": self.capacity,
            "ticks_recorded": ticks,
            "last_tick_age_s": None if age is None else round(age, 3),
            "records": records,
            **({"programs": programs} if programs else {}),
        }

    def dump(self, path: str, reason: str = "on_demand") -> None:
        """Write the ring as JSON (creates parent dirs)."""
        snap = self.snapshot()
        snap["reason"] = reason
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(snap, f, indent=2, default=str)
            f.write("\n")

    def dump_if_armed(self, reason: str) -> Optional[str]:
        """Dump to the armed sink path, if any — the crash/error hook.
        Never raises (the black box must not kill the workload it
        records); returns the path written or None."""
        path = self._dump_path
        if not self.enabled or not path:
            return None
        try:
            self.dump(path, reason=reason)
            return path
        except OSError:
            return None


#: The process-wide recorder the serving engine feeds.
FLIGHT = FlightRecorder()


class TickPhases:
    """Phase stamps inside one engine tick: one clock read a boundary,
    three sinks.

    The loop calls :meth:`begin` at the tick's top, :meth:`mark` at each
    boundary (it closes the phase that was open and opens the next) and
    :meth:`finish` as it builds the tick's flight record. While on, every
    phase is also a ``jax.profiler.TraceAnnotation("tick:<phase>")``,
    entered at its mark and left at the next: the profiler keeps it only
    while a trace session runs, and then the engine's phases lie over the
    device's operations on the profiler's own clock.

    On means ``FLIGHT.enabled or TRACER.active``, latched per tick by
    :meth:`begin` so that a tick is stamped whole or not at all. Off (the
    default), :meth:`mark` and :meth:`finish` are one attribute check and
    an early return: no clock read, no list, no dict — call them with
    positional arguments only.
    """

    __slots__ = ("on", "_marks", "_open", "_annotation")

    def __init__(self):
        self.on = False
        self._marks: Optional[List[List[Any]]] = None
        self._open: Any = None            # the entered TraceAnnotation
        self._annotation: Any = None      # jax.profiler.TraceAnnotation

    def begin(self, now: float, name: str = "ingest") -> None:
        """Tick top: latch on/off; ``now`` (the tick's own
        ``time.monotonic()`` stamp) opens ``ingest``. An iteration that
        closed a record halfway (it had to land the program before it
        ahead of its own planning) begins again at ``name``."""
        self.on = FLIGHT.enabled or TRACER.active
        if not self.on:
            return
        if self._annotation is None:
            # Not at import: the obs package must stay importable (and
            # cheap) without JAX.
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
        self._marks = []
        self._enter(name, now, None, None, None, None)

    def mark(self, name: str, tick: Optional[int] = None,
             kind: Optional[str] = None, tq: Optional[int] = None,
             ahead: Optional[bool] = None) -> None:
        """Close the open phase and open ``name``; a mark that names the
        phase already open changes nothing. ``tick``/``kind``/``tq``/
        ``ahead`` ride on the profiler annotation (the ``dispatch`` mark
        passes them: ``ahead`` says the program went out before the one
        before it was fetched)."""
        if not self.on:
            return
        if self._marks[-1][0] == name:
            return
        now = time.monotonic()
        self._open.__exit__(None, None, None)
        self._enter(name, now, tick, kind, tq, ahead)

    def _enter(self, name, now, tick, kind, tq, ahead) -> None:
        self._marks.append([name, now])
        if kind is None:
            self._open = self._annotation(_ANNOTATION[name])
        else:
            self._open = self._annotation(_ANNOTATION[name], tick=tick,
                                          kind=kind, tq=tq,
                                          ahead=bool(ahead))
        self._open.__enter__()

    def _leave(self) -> List[List[Any]]:
        """Switch off until the next :meth:`begin`, leave the open
        annotation, and hand back the tick's stamps."""
        self.on = False
        self._open.__exit__(None, None, None)
        marks, self._open, self._marks = self._marks, None, None
        return marks

    def abandon(self) -> None:
        """The iteration executed no tick (idle, fast-forward, an error):
        leave the open annotation and forget the stamps."""
        if self.on:
            self._leave()

    def finish(self, rec: Optional[Dict[str, Any]]) -> None:
        """Tick end: stamp ``t_end``, close the last phase, write
        ``phases`` (``[name, start]`` pairs, absolute ``time.monotonic()``
        seconds) and ``t_end`` into the flight record being built (``None``
        when the recorder is off) and, when the span tracer is active, one
        complete ``tick:<phase>`` event a phase."""
        if not self.on:
            return
        t_end = time.monotonic()
        marks = self._leave()
        if rec is not None:
            rec["phases"] = marks
            rec["t_end"] = t_end
        if TRACER.active:
            ends = [m[1] for m in marks[1:]] + [t_end]
            for (name, start), end in zip(marks, ends):
                # Whole nanoseconds first, then floored to microseconds
                # as the tracer's spans are: the events nest in the
                # tick's span to the digit.
                ts = round(start * 1e9) // 1000
                TRACER._emit_complete(_ANNOTATION[name], "serving", ts,
                                      round(end * 1e9) // 1000 - ts, None)
