"""Live telemetry HTTP endpoint: /metrics, /healthz, /flight, /requests.

PR 1's registry was built so "a future serving layer can mount it on
``/metrics`` unchanged" — this is that layer. A stdlib
:class:`~http.server.ThreadingHTTPServer` on a daemon thread (zero new
dependencies, dies with the process) serving:

- ``/metrics`` — Prometheus text exposition format, byte-identical to
  :meth:`MetricsRegistry.to_prometheus` at scrape time;
- ``/metrics.json`` — the registry snapshot (the same shape
  ``--metrics-out`` writes at exit, but live);
- ``/healthz`` — tick liveness from the flight recorder: 200 while the
  engine is recording ticks (or idle before any tick), 503 once the last
  tick is older than ``stall_after`` — a wedged tick loop fails the check
  even though the HTTP thread still answers (that asymmetry is the point);
  its ``startup`` field is the start-up record (``obs/flight.py``), there
  whether or not the ring is armed;
- ``/flight`` — the flight recorder ring as JSON, the live post-mortem;
- ``/requests`` — the request ledger (ISSUE 16): live requests with
  their running wall segments plus the bounded ring of recently
  finished ones;
- ``/request/{uid}`` — one request's full ledger (live or recent), with
  its phase timeline — 404 for a uid the ring has already evicted;
- ``/slots`` — per-slot occupancy from the wired engine (state, uid,
  generated length, context length, paged block count); 404 when no
  engine was wired in.

Scrapes hold the registry lock only for the duration of one snapshot —
the same cost an exit dump pays; the engine's disabled-path contract is
untouched (the server only *reads*: ``/slots`` uses the engine's
GIL-atomic snapshot, never a lock the tick loop holds).

Server lifecycle (daemon thread, localhost bind, ``port=0`` OS-pick) is
the shared :class:`~tree_attention_tpu.utils.httpd.DaemonHTTPServer`
plumbing — the serving ingress rides the identical base.
"""

from __future__ import annotations

import json
from http.server import BaseHTTPRequestHandler
from typing import Any, Dict, Optional, Tuple

from tree_attention_tpu.obs.flight import FLIGHT, FlightRecorder
from tree_attention_tpu.obs.metrics import REGISTRY, MetricsRegistry
from tree_attention_tpu.obs.reqlog import REQLOG, ReqLog
from tree_attention_tpu.utils.httpd import DaemonHTTPServer

DEFAULT_STALL_AFTER = 60.0


def flight_health(flight: FlightRecorder,
                  stall_after: float = DEFAULT_STALL_AFTER,
                  ) -> Tuple[int, Dict[str, Any]]:
    """Tick-liveness verdict over one flight recorder: the shared core
    of this server's ``/healthz`` and the fleet router's federated
    health roll-up (a wedged replica must fail the FLEET check, not
    just its own process's — ISSUE 16 satellite)."""
    age = flight.last_tick_age()
    body: Dict[str, Any] = {
        "ticks_recorded": flight.ticks_recorded,
        "last_tick_age_s": None if age is None else round(age, 3),
        "stall_after_s": stall_after,
        # What the process did before its first tick and every program it
        # built since (the start-up record), armed or not: a replica that
        # came back slowly says here in which phase.
        "startup": flight.startup.snapshot(),
    }
    if age is None or flight.idle:
        # No tick yet, or the engine drained its run and said so
        # (mark_idle) — alive between runs, however long ago the last
        # tick was. Only a loop that stopped WITHOUT draining stalls.
        body["status"] = "idle"
        return 200, body
    if age <= stall_after:
        body["status"] = "ok"
        return 200, body
    body["status"] = "stalled"
    return 503, body


class MetricsHTTPServer(DaemonHTTPServer):
    """Daemon-thread HTTP exporter over one registry + flight recorder.

    ``engine`` (optional) is anything with a ``slots_snapshot()``
    method — a :class:`SlotServer` or :class:`DisaggServer` — backing
    ``/slots``; ``reqlog`` backs ``/requests`` and ``/request/{uid}``.
    """

    thread_name = "obs-http"

    def __init__(
        self,
        port: int = 0,
        host: str = "127.0.0.1",
        *,
        registry: MetricsRegistry = REGISTRY,
        flight: FlightRecorder = FLIGHT,
        reqlog: ReqLog = REQLOG,
        engine: Optional[Any] = None,
        stall_after: float = DEFAULT_STALL_AFTER,
    ):
        super().__init__(port, host)
        self._registry = registry
        self._flight = flight
        self._reqlog = reqlog
        self._engine = engine
        self._stall_after = stall_after

    def attach_engine(self, engine: Any) -> None:
        """Late-wire the engine backing ``/slots`` — the CLI starts this
        exporter before it builds the engine, so the wiring is a second
        step (one attribute store; handler threads read it GIL-atomically
        and a pre-attach scrape just 404s)."""
        self._engine = engine

    # -- endpoints --------------------------------------------------------

    def handle(self, method: str, req: BaseHTTPRequestHandler) -> None:
        if method != "GET":
            self.reply(req, 405, "metrics endpoint is read-only\n",
                       "text/plain")
            return
        path = req.path.split("?", 1)[0].rstrip("/") or "/"
        if path == "/metrics":
            self.reply(
                req, 200, self._registry.to_prometheus(),
                "text/plain; version=0.0.4; charset=utf-8",
            )
        elif path == "/metrics.json":
            self.reply(req, 200, self._registry.to_json(indent=2),
                       "application/json")
        elif path == "/healthz":
            code, body = flight_health(self._flight, self._stall_after)
            self.reply(req, code, json.dumps(body, indent=2),
                       "application/json")
        elif path == "/flight":
            self.reply(
                req, 200,
                json.dumps(self._flight.snapshot(), indent=2, default=str),
                "application/json",
            )
        elif path == "/requests":
            self.reply(
                req, 200,
                json.dumps(self._reqlog.snapshot(), indent=2),
                "application/json",
            )
        elif path.startswith("/request/"):
            self._request_detail(req, path[len("/request/"):])
        elif path == "/slots":
            if self._engine is None:
                self.reply(req, 404,
                           "no engine wired into this exporter\n",
                           "text/plain")
            else:
                self.reply(
                    req, 200,
                    json.dumps(self._engine.slots_snapshot(), indent=2),
                    "application/json",
                )
        elif path == "/":
            self.reply(
                req, 200,
                "tree_attention_tpu telemetry: /metrics /metrics.json "
                "/healthz /flight /requests /request/{uid} /slots\n",
                "text/plain",
            )
        else:
            self.reply(req, 404, f"no such endpoint: {path}\n",
                       "text/plain")

    def _request_detail(self, req: BaseHTTPRequestHandler,
                        tail: str) -> None:
        try:
            uid = int(tail)
        except ValueError:
            self.reply(req, 400, f"uid must be an integer, got {tail!r}\n",
                       "text/plain")
            return
        ledger = self._reqlog.get(uid)
        if ledger is None:
            self.reply(
                req, 404,
                f"no ledger for request {uid} (never seen, or evicted "
                f"from the recent ring)\n",
                "text/plain",
            )
            return
        self.reply(req, 200, json.dumps(ledger, indent=2),
                   "application/json")
