"""Telemetry: the process-wide metrics registry and host-side span tracer.

Two complementary instruments, both off (and free) by default:

- :mod:`~tree_attention_tpu.obs.metrics` — thread-safe counters / gauges /
  fixed-bucket histograms with labels; exportable as JSON
  (``--metrics-out``) and Prometheus text format.
- :mod:`~tree_attention_tpu.obs.tracing` — span tracer emitting
  Chrome-trace-format JSONL (``--trace-events``), loadable in Perfetto
  alongside ``jax.profiler`` device traces; ``pid`` is the JAX process
  index so multi-host captures merge cleanly.

The serving observability plane builds on both: the tick flight recorder
(:mod:`~tree_attention_tpu.obs.flight`, ``--flight-out``) with the
start-up record beside it (``STARTUP``: always on, what the process did
before its first tick), the sliding-window SLO monitor
(:mod:`~tree_attention_tpu.obs.slo`), and the live HTTP exporter (:mod:`~tree_attention_tpu.obs.http`,
``--metrics-port`` — imported lazily; mounting ``/metrics`` must not tax
every library import). :func:`install_crash_handlers` makes all sinks
crash-safe (atexit + SIGTERM flush, SIGUSR1 live dump).

Lifecycle: the CLI (or any embedder) calls :func:`configure` once at
startup and :func:`shutdown` at exit; instrumentation sites declare their
metrics at import via :func:`counter` / :func:`gauge` / :func:`histogram`
and record unconditionally — the disabled path is a single flag check.

Environment fallbacks ``TA_METRICS_OUT`` / ``TA_TRACE_EVENTS`` let
subprocesses a run spawns (``--launch`` ranks) inherit telemetry without
plumbing flags; explicit arguments win, and spawners whose children have
no rank contract strip the vars instead (``bench.py``'s comparator
subprocesses — an unsuffixed child would clobber the parent's sinks).
Multi-process runs rank-suffix BOTH sink paths (each process owns its
files — the tracer truncates on open, so ranks must never share a path);
trace events additionally carry the rank as ``pid`` so the per-rank files
merge into one Perfetto timeline.
"""

from __future__ import annotations

import atexit
import os
from typing import Any, Dict, Optional

from tree_attention_tpu.obs.metrics import (  # noqa: F401
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    counter,
    gauge,
    histogram,
    percentile,
)
from tree_attention_tpu.obs.tracing import (  # noqa: F401
    SpanTracer,
    TRACEPARENT_HEADER,
    TRACER,
    flow,
    flow_id,
    instant,
    make_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    span,
)
from tree_attention_tpu.obs.flight import (  # noqa: F401
    FLIGHT,
    STARTUP,
    STARTUP_SPANS,
    FlightRecorder,
    StartupRecord,
)
from tree_attention_tpu.obs.reqlog import (  # noqa: F401
    REQLOG,
    ReqLog,
    RequestLedger,
    aggregate_ledgers,
)
from tree_attention_tpu.obs.slo import SLOMonitor  # noqa: F401

_STATE: Dict[str, Optional[str]] = {"metrics_out": None}


def enabled() -> bool:
    """True when the metrics registry records (the tracer has its own
    ``TRACER.active`` — either instrument can run alone)."""
    return REGISTRY.enabled


def enable() -> None:
    REGISTRY.enable()


def disable() -> None:
    REGISTRY.disable()


def _rank_suffixed(path: str) -> str:
    """Each process of a multi-process run owns its own metrics file —
    same convention as the CLI's rank-suffixed ``--log-file``. Detects
    both the local launcher's env contract (``TA_COORDINATOR``) and an
    already-initialized multi-host JAX runtime (metadata-server
    auto-detect), so N hosts on a shared filesystem never clobber one
    path; callers should configure *after* distributed init (the CLI
    does)."""
    from tree_attention_tpu.utils.logging import _process_count, _process_index

    if os.environ.get("TA_COORDINATOR") or _process_count() > 1:
        return f"{path}.p{_process_index()}"
    return path


def configure(
    metrics_out: Optional[str] = None,
    trace_events: Optional[str] = None,
    flight_out: Optional[str] = None,
) -> None:
    """Arm telemetry for this process.

    ``metrics_out``: path the exit snapshot (JSON) is written to by
    :func:`shutdown`; enables the registry. ``trace_events``: Chrome-trace
    JSONL sink path; starts the span tracer. ``flight_out``: arms the
    tick flight recorder with a crash-dump sink (written by
    :func:`shutdown`, on engine error, and by the signal handlers).
    ``None`` falls back to ``TA_METRICS_OUT`` / ``TA_TRACE_EVENTS`` /
    ``TA_FLIGHT_OUT`` so child processes inherit the parent's telemetry
    choice.
    """
    metrics_out = metrics_out or os.environ.get("TA_METRICS_OUT")
    trace_events = trace_events or os.environ.get("TA_TRACE_EVENTS")
    flight_out = flight_out or os.environ.get("TA_FLIGHT_OUT")
    if metrics_out:
        _STATE["metrics_out"] = _rank_suffixed(metrics_out)
        REGISTRY.enable()
    if trace_events:
        TRACER.start(_rank_suffixed(trace_events))
        # Spans without counters are half a story (and vice versa): one
        # flag arms both; --metrics-out alone still skips the JSON dump.
        REGISTRY.enable()
    if flight_out:
        FLIGHT.arm(_rank_suffixed(flight_out))
    if metrics_out or trace_events:
        # The request ledger rides whichever instrument is on: its
        # /requests view backs the metrics plane and its finish instant
        # lands in the trace; it has no sink file of its own.
        REQLOG.arm()
        # The start-up record began before any sink: hand them the spans
        # that closed before this call (the import, the backend).
        STARTUP.publish()


def shutdown() -> Dict[str, Any]:
    """Flush sinks: write the metrics snapshot (if configured), dump the
    flight recorder (if armed with a sink), close the tracer, and DISARM —
    a later run in the same process records nothing (and rewrites no
    earlier run's file) unless it calls :func:`configure` again. Metric
    values persist across configure cycles (process-lifetime totals); only
    the sinks and the enabled flag reset. Idempotent. Returns
    ``{"metrics_out": ..., "trace_events": ..., "flight_out": ...}`` — the
    sinks THIS run actually wrote — for the caller's exit log line."""
    out: Dict[str, Any] = {
        "metrics_out": None,
        "trace_events": TRACER.path if TRACER.active else None,
        "flight_out": None,
    }
    path = _STATE["metrics_out"]
    if path and REGISTRY.enabled:
        try:
            REGISTRY.write_json(path)
            out["metrics_out"] = path
        except OSError:
            pass  # never let observability fail the run at exit
    out["flight_out"] = FLIGHT.dump_if_armed("shutdown")
    _STATE["metrics_out"] = None
    REGISTRY.disable()
    TRACER.close()
    FLIGHT.disarm()
    REQLOG.disarm()
    return out


def flush() -> Dict[str, Any]:
    """Crash-time flush: write every armed sink WITHOUT disarming — the
    run may continue (SIGUSR1) or die an instant later (SIGTERM/atexit);
    either way the telemetry captured so far is on disk. Safe to call
    repeatedly; never raises."""
    out: Dict[str, Any] = {
        "metrics_out": None, "trace_events": None, "flight_out": None,
    }
    path = _STATE["metrics_out"]
    if path and REGISTRY.enabled:
        try:
            REGISTRY.write_json(path)
            out["metrics_out"] = path
        except OSError:
            pass
    if TRACER.active:
        TRACER.flush()
        out["trace_events"] = TRACER.path
    out["flight_out"] = FLIGHT.dump_if_armed("flush")
    return out


_HANDLERS: Dict[str, Any] = {"installed": False}


def install_crash_handlers() -> bool:
    """Make telemetry crash-safe: an interrupted run still flushes.

    Registers (idempotently, main thread only — signal handlers cannot be
    installed elsewhere; returns False in that case):

    - ``atexit`` — :func:`flush` as a backstop for exits that skip the
      caller's ``finally`` (``os._exit`` excepted; nothing catches that);
    - ``SIGTERM`` — flush every armed sink, restore the previous handler,
      and re-raise the signal so the process still dies with the standard
      143 (a supervisor's kill must stay a kill);
    - ``SIGUSR1`` — dump the flight recorder + flush and KEEP RUNNING: the
      live "what is this server doing" poke for a wedged-looking run.
    """
    import signal

    if _HANDLERS["installed"]:
        return True
    try:
        prev_term = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            flush()
            signal.signal(
                signal.SIGTERM,
                prev_term if prev_term is not None else signal.SIG_DFL,
            )
            os.kill(os.getpid(), signum)

        def _on_usr1(signum, frame):
            flush()

        signal.signal(signal.SIGTERM, _on_term)
        signal.signal(signal.SIGUSR1, _on_usr1)
    except ValueError:  # not the main thread
        return False
    atexit.register(flush)
    _HANDLERS["installed"] = True
    return True
