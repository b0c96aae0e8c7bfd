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

Start-up from inside: a :class:`StartupRecord` (the recorder's
``startup``, always on, also reachable as :data:`STARTUP`) keeps what the
process did before the first tick and whenever it built a program since:
closed spans ``[name, t0, t1, fields]`` on ``time.monotonic()`` under the
fixed vocabulary :data:`STARTUP_SPANS`, from the process's own start. It
rides the recorder's dumps and ``/healthz`` whether or not the ring is
armed, and feeds the span tracer and the registry as its spans close.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from tree_attention_tpu.obs.metrics import REGISTRY, counter, gauge
from tree_attention_tpu.obs.tracing import TRACER

DEFAULT_CAPACITY = 256

#: The start-up record's vocabulary, in the order a process runs them
#: (ARCHITECTURE.md "Start-up from inside"); a span is named
#: ``startup:<phase>``.
STARTUP_SPANS = ("import", "backend", "params", "engine", "program",
                 "tables", "serve")
_STARTUP_NAME = {p: "startup:" + p for p in STARTUP_SPANS}

_STARTUP_SECONDS = gauge(
    "serving_startup_seconds",
    "seconds of the start-up record's closed spans so far, by phase "
    "(import, backend, params, engine, program, tables, serve): what a "
    "restart spent where",
    labels=("phase",),
)
_PROGRAMS_BUILT = counter(
    "serving_tick_programs_built_total",
    "tick programs an engine built, by program (the jit's name) and by "
    "source: compiled, or fetched from the persistent cache",
    labels=("program", "source"),
)

# The durations JAX reports while it builds one program, in the order it
# reports them (``jax._src.dispatch`` / ``compiler``). The retrieval event
# is there only on a hit of the persistent cache, and the backend's
# duration then holds it.
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"


def _process_start() -> Optional[float]:
    """When the kernel started this process, on ``time.monotonic()``'s
    clock: field 22 of ``/proc/self/stat`` (clock ticks since boot) against
    the boot clock now. None where ``/proc`` has none or says nonsense."""
    try:
        with open("/proc/self/stat") as f:
            # The command's name (field 2) may hold spaces: count from the
            # parenthesis that closes it.
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic() - age if age >= 0.0 else None


class _Build:
    """One tick program being built: the open ``startup:program`` span and
    how far JAX has come with it. The stages keep a program that some
    operation of the traced body compiles eagerly out of the sums: nothing
    counts until the body has returned (stage 1), then the outer trace's
    duration, the lowering's, the cache's and the backend's arrive in that
    order, and the last closes the span."""

    __slots__ = ("span", "stage", "done")

    def __init__(self, span: List[Any], done):
        self.span = span
        self.stage = 0
        self.done = done                # called with the closed span

    def take(self, record: "StartupRecord", event: str,
             seconds: float) -> None:
        fields = self.span[3]
        if self.stage == 1 and event == _EV_TRACE:
            fields["trace_s"] = seconds
            self.stage = 2
        elif self.stage == 2 and event == _EV_LOWER:
            fields["lower_s"] = seconds
            self.stage = 3
        elif self.stage == 3 and event == _EV_FETCH:
            fields["fetch_s"] = seconds
            fields["from_cache"] = True
        elif self.stage == 3 and event == _EV_COMPILE:
            fields["compile_s"] = max(seconds - fields["fetch_s"], 0.0)
            record._built(self)


class StartupRecord:
    """What the process did before it served, and every program it built
    since: closed spans ``[name, t0, t1, fields]``, bounded.

    Always on, and nothing of it runs in a tick that builds no program: a
    span costs its two clock reads and one list, where the caller has a
    stamp already it hands it over, and the spans are few (a handful a
    process, one a program built, one a ``serve()`` call). A span closes
    when the host's call returns: work the device finishes later (a
    placement, a queued transposition) shows in the span that first waits
    for it. ``t_process`` is the process's own start as the kernel has it
    (:func:`_process_start`), or the package's import stamp.

    Bounded: the first :attr:`HEAD` spans stay for good (the start-up
    proper), later ones turn over in a ring of :attr:`RING` (a server that
    lives long keeps its newest builds and ``serve()`` calls);
    ``dropped`` counts what the ring pushed out.

    Three sinks, fed as a span closes: the record itself
    (:meth:`snapshot`: ``ServeReport.startup``, the recorder's dumps,
    ``/healthz``), the span tracer when it is active (a complete event
    under the span's name; :meth:`publish` sends what closed before the
    tracer started) and the registry (``serving_startup_seconds{phase}``,
    ``serving_tick_programs_built_total{program, source}``).
    """

    HEAD = 256
    RING = 256

    def __init__(self, t_process: Optional[float] = None):
        if t_process is None:
            t_process = _process_start()
        if t_process is None:
            import tree_attention_tpu

            t_process = tree_attention_tpu._T_IMPORT
        self.t_process = t_process
        self._lock = threading.RLock()  # reentrant: see FlightRecorder
        self._head: List[List[Any]] = []
        self._ring: deque = deque(maxlen=self.RING)
        self._open: List[List[Any]] = []
        self._seconds = dict.fromkeys(STARTUP_SPANS, 0.0)
        self._closed = 0        # spans closed so far, kept or not
        self._traced = 0        # of them, sent to the tracer
        self._builds: Dict[int, _Build] = {}    # by the building thread
        self._listening = False

    # -- spans --------------------------------------------------------------

    def begin(self, phase: str, t0: Optional[float] = None,
              **fields: Any) -> List[Any]:
        """Open a span of ``phase`` at ``t0`` (now, without one); it shows
        in :meth:`snapshot` with ``t1`` None until :meth:`end`."""
        span = [_STARTUP_NAME[phase],
                time.monotonic() if t0 is None else t0, None, fields]
        with self._lock:
            self._open.append(span)
        return span

    def end(self, span: List[Any], t1: Optional[float] = None,
            **fields: Any) -> None:
        """Close ``span`` at ``t1`` (now, without one), with more fields.
        Ending a span twice, or one this record never began, does
        nothing."""
        t1 = time.monotonic() if t1 is None else t1
        with self._lock:
            if self._forget(span):
                span[3].update(fields)
                span[2] = t1
                self._keep(span)

    def add(self, phase: str, t0: float, t1: Optional[float] = None,
            **fields: Any) -> None:
        """A span that is over: from ``t0`` to ``t1`` (now, without
        one)."""
        self._keep([_STARTUP_NAME[phase], t0,
                    time.monotonic() if t1 is None else t1, fields])

    def _forget(self, span: List[Any]) -> bool:
        """Take ``span`` (that very list) off the open ones."""
        with self._lock:
            for i, held in enumerate(self._open):
                if held is span:
                    del self._open[i]
                    return True
        return False

    def _keep(self, span: List[Any]) -> None:
        name, t0, t1, fields = span
        phase = name[len("startup:"):]
        with self._lock:
            if len(self._head) < self.HEAD:
                self._head.append(span)
            else:
                self._ring.append(span)
            self._closed += 1
            self._seconds[phase] += t1 - t0
            seconds = self._seconds[phase]
        if REGISTRY.enabled:
            _STARTUP_SECONDS.labels(phase=phase).set(seconds)
            if phase == "program":
                _PROGRAMS_BUILT.labels(
                    program=fields["program"], source="cache"
                    if fields["from_cache"] else "compiled").inc()
        if TRACER.active:
            self._send_new()

    def _send_new(self) -> None:
        """Every closed span the tracer has not had, as complete events."""
        with self._lock:
            spans = self._head + list(self._ring)
            unsent = min(self._closed - self._traced, len(spans))
            self._traced = self._closed
        for name, t0, t1, fields in spans[len(spans) - unsent:]:
            ts = round(t0 * 1e9) // 1000
            TRACER._emit_complete(name, "startup", ts,
                                  round(t1 * 1e9) // 1000 - ts,
                                  fields or None)

    def publish(self) -> None:
        """Hand the sinks that were switched on late what closed before
        them (the import and the backend precede ``obs.configure``): the
        tracer the spans it has not had, the registry's gauge the phases'
        seconds. The counter counts from when the registry was enabled."""
        if TRACER.active:
            self._send_new()
        if REGISTRY.enabled:
            with self._lock:
                seconds = dict(self._seconds)
            for phase, s in seconds.items():
                if s:
                    _STARTUP_SECONDS.labels(phase=phase).set(s)

    # -- programs -----------------------------------------------------------

    def building(self, program: str, tq: int, tick: Optional[int],
                 done=None) -> _Build:
        """A tick program's function is being traced on this thread: open
        its ``startup:program`` span. The caller sets ``stage = 1`` on
        what this returns once the traced body is back (or calls
        :meth:`abandon`); the durations JAX then reports on this thread
        fill the span's fields, and the backend's closes it and calls
        ``done(span)``. ONE listener a process, registered here the first
        time: an event outside a build costs it one dict lookup."""
        build = _Build(self.begin(
            "program", program=program, tq=tq, tick=tick, trace_s=0.0,
            lower_s=0.0, compile_s=0.0, fetch_s=0.0, from_cache=False), done)
        me = threading.get_ident()
        with self._lock:
            listen, self._listening = not self._listening, True
            # A build this thread left half done (lowered and never
            # compiled) is forgotten with its span.
            stale, self._builds[me] = self._builds.get(me), build
        if stale is not None:
            self._forget(stale.span)
        if listen:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(self._on_event)
        return build

    def abandon(self, build: _Build) -> None:
        """The build came to nothing (the trace raised): drop its span."""
        self._forget(build.span)
        with self._lock:
            if self._builds.get(threading.get_ident()) is build:
                del self._builds[threading.get_ident()]

    def _on_event(self, event: str, seconds: float, **_: Any) -> None:
        build = self._builds.get(threading.get_ident())
        if build is not None:
            build.take(self, event, seconds)

    def _built(self, build: _Build) -> None:
        with self._lock:
            del self._builds[threading.get_ident()]
        self.end(build.span)
        if build.done is not None:
            build.done(build.span)

    # -- export -------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The record as JSON takes it: ``t_process``, ``spans`` in the
        order they opened (an open one with ``t1`` None and the fields it
        has so far), ``seconds`` of the closed ones by phase, and
        ``dropped``."""
        with self._lock:
            spans = self._head + list(self._ring) + [
                [name, t0, None, dict(fields)]
                for name, t0, _, fields in self._open]
            seconds = {p: round(s, 6) for p, s in self._seconds.items() if s}
            dropped = max(self._closed - self.HEAD - self.RING, 0)
        spans.sort(key=lambda s: s[1])
        return {"t_process": self.t_process, "spans": spans,
                "seconds": seconds, "dropped": dropped}

#: The phases of one ``SlotServer.serve`` tick, in the order the loop runs
#: them; each lasts until the next mark (ARCHITECTURE.md "Observability").
TICK_PHASES = ("ingest", "sweep", "admit", "plan", "pack", "table_sync",
               "dispatch", "publish", "fetch", "emit", "account")
_ANNOTATION = {p: "tick:" + p for p in TICK_PHASES}


class FlightRecorder:
    """Fixed-capacity ring of per-tick records; disarmed until enabled."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 startup: Optional["StartupRecord"] = None):
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
        # The start-up record rides this recorder's dumps and /healthz,
        # armed or not; clear() leaves it (it is the process's, not a
        # run's).
        self.startup = StartupRecord() if startup is None else startup

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
            "startup": self.startup.snapshot(),
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
#: The process-wide start-up record (the recorder's own).
STARTUP = FLIGHT.startup


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
             ahead: Optional[bool] = None,
             rows_cross: Optional[int] = None) -> None:
        """Close the open phase and open ``name``; a mark that names the
        phase already open changes nothing. ``tick``/``kind``/``tq``/
        ``ahead`` ride on the profiler annotation (the ``dispatch`` mark
        passes them: ``ahead`` says the program went out before the one
        before it was fetched), and ``rows_cross`` where the model cuts its
        rows at a seam (the rows the layers above it compute)."""
        if not self.on:
            return
        if self._marks[-1][0] == name:
            return
        now = time.monotonic()
        self._open.__exit__(None, None, None)
        self._enter(name, now, tick, kind, tq, ahead, rows_cross)

    def _enter(self, name, now, tick, kind, tq, ahead,
               rows_cross=None) -> None:
        self._marks.append([name, now])
        if kind is None:
            self._open = self._annotation(_ANNOTATION[name])
        else:
            more = {} if rows_cross is None else {"rows_cross": rows_cross}
            self._open = self._annotation(_ANNOTATION[name], tick=tick,
                                          kind=kind, tq=tq,
                                          ahead=bool(ahead), **more)
        self._open.__enter__()

    def built(self, built: List[Any]) -> None:
        """The open phase's call built a tick program (the ``dispatch``
        phase: the engine's ``_program_built``, inside the jit call): say
        so on its profiler annotation, ``[program, tq, seconds,
        from_cache]`` as text."""
        if self.on:
            self._open.set_metadata(built=str(built))

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
