"""Fleet supervision: N replica engines behind one cache-aware router.

:mod:`~tree_attention_tpu.serving.router` is the routing brain; this
module is the *lifecycle* around it — the piece that turns one ingress
into a supervised fleet (ISSUE 11):

- :class:`LocalReplica` — one in-process :class:`SlotServer` +
  :class:`IngressServer` pair. Restart reuses the warmed engine (the
  serve loop is reusable by contract — a drained engine serves again
  without recompiling), so a rolling restart of an in-process fleet
  costs milliseconds, not a jit recompile. This is the CLI's
  ``--serve-fleet`` shape and the one the tier-1 integration test
  drives.
- :class:`ProcessReplica` — one replica as a child process running the
  CLI's ``--serve-http`` mode, supervised with the gang-lifecycle
  conventions :mod:`~tree_attention_tpu.host_runtime` established:
  SIGTERM-then-SIGKILL grace escalation on shutdown, exit statuses
  classified through the same ``ok/crash/deadline/stall`` vocabulary
  (:func:`~tree_attention_tpu.host_runtime._rank_exit_outcome`, the
  supervisor's 124/125/128+sig conventions), and a per-replica restart
  budget — the elastic-recovery idiom, per replica instead of
  whole-gang because replicas are independent (no collective to wedge).
- :class:`FleetSupervisor` — owns the replicas and the router: starts
  everything, health-polls replicas on a monitor thread (a dead replica
  is marked down in the router and restarted while budget lasts), and
  implements **rolling restart without drops**: drain one replica
  (router stops routing to it; its queued work sheds and the router
  requeues those requests on peers; in-flight streams finish), restart
  it, wait for readiness, rejoin it with a cleared affinity tree — then
  the next replica. At no point is an accepted request lost.

Threading contract: the supervisor's state is shared between its public
API (caller thread), the monitor thread, and nothing else — mutations
happen under ``self._lock`` (the invariant linter's lock-safety pass
scopes this file). Replicas' own state likewise. HTTP and process I/O
run outside the locks.
"""

from __future__ import annotations

import json
import subprocess
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from tree_attention_tpu.host_runtime import (
    _rank_exit_outcome,
    require_cpu_children,
)
from tree_attention_tpu.serving.ingress import IngressServer
from tree_attention_tpu.serving.router import FleetRouter
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("serving.fleet")


class LocalReplica:
    """In-process replica: one engine + one ingress on a loopback port.

    ``engine_factory`` is called once, lazily at first start; restarts
    wrap the SAME engine in a fresh :class:`IngressServer` (new port —
    the supervisor re-registers it with the router). The engine's radix
    cache therefore *survives* an in-process restart; the router still
    clears its affinity tree on rejoin, which is merely conservative
    (affinity re-learns in one request per prefix).
    """

    def __init__(self, name: str, engine_factory: Callable[[], Any], *,
                 max_queue: int = 64,
                 default_deadline_s: Optional[float] = None,
                 default_max_tokens: int = 16,
                 keepalive_s: float = 0.5):
        self.name = name
        self.metrics_url: Optional[str] = None  # in-process replicas
        # share the router's registry; there is nothing to federate
        self._factory = engine_factory
        self._ingress_kw = dict(
            max_queue=max_queue,
            default_deadline_s=default_deadline_s,
            default_max_tokens=default_max_tokens,
            keepalive_s=keepalive_s,
        )
        self._lock = threading.RLock()
        self._engine: Optional[Any] = None
        self._ingress: Optional[IngressServer] = None

    @property
    def engine(self):
        with self._lock:
            if self._engine is None:
                self._engine = self._factory()
            return self._engine

    @property
    def port(self) -> int:
        with self._lock:
            return 0 if self._ingress is None else self._ingress.port

    def start(self) -> int:
        engine = self.engine  # build outside the assignment lock hold
        with self._lock:
            if self._ingress is not None and self._ingress.running:
                return self._ingress.port
            ing = IngressServer(engine, port=0, **self._ingress_kw)
            self._ingress = ing
        return ing.start()

    def ready(self) -> bool:
        with self._lock:
            ing = self._ingress
        return (ing is not None and ing.running and not ing.draining
                and ing.engine_error is None and ing.report is None)

    def begin_drain(self) -> None:
        with self._lock:
            ing = self._ingress
        if ing is not None:
            ing.drain()

    def await_drained(self, timeout_s: float = 60.0) -> bool:
        """Block until the engine loop returns its report, then tear the
        HTTP listener down; True iff it drained inside the timeout.

        On a timeout the listener is deliberately KEPT: the engine
        thread still owns the serve loop, and tearing down the ingress
        would let :meth:`restart`'s undrained guard pass — two
        concurrent serve() loops on one engine corrupt slot/pool state.
        A timed-out drain leaves the replica down-but-intact for a
        later retry."""
        with self._lock:
            ing = self._ingress
        if ing is None:
            return True
        report = ing.join(timeout=timeout_s)
        if report is None:
            return False  # engine loop still running: keep the guard up
        ing.stop()
        return True

    def restart(self) -> int:
        """Fresh ingress around the same warmed engine; returns the new
        port. The caller drains first — restarting an undrained replica
        raises (its engine thread still owns the serve loop)."""
        with self._lock:
            if self._ingress is not None and self._ingress.running:
                raise RuntimeError(
                    f"replica {self.name}: restart before drain "
                    f"(the engine thread still owns the serve loop)"
                )
            ing = IngressServer(self.engine, port=0, **self._ingress_kw)
            self._ingress = ing
        return ing.start()

    def stop(self) -> None:
        self.begin_drain()
        self.await_drained()

    def leak_report(self) -> Dict[str, int]:
        return self.engine.leak_report()


class ProcessReplica:
    """Child-process replica: the CLI's ``--serve-http`` under gang-style
    supervision (SIGTERM drain -> grace -> SIGKILL; exit statuses read
    through :func:`host_runtime._rank_exit_outcome`'s vocabulary).

    ``argv`` must put the ingress on a FIXED ``port`` (the parent cannot
    learn an OS-picked port from a child it only holds a PID for); pass
    ``metrics_port`` when the child exports ``--metrics-port`` so the
    router can federate its scrape.
    """

    def __init__(self, name: str, argv: Sequence[str], *, port: int,
                 host: str = "127.0.0.1",
                 metrics_port: Optional[int] = None,
                 grace_s: float = 5.0,
                 start_timeout_s: float = 120.0):
        if port < 1:
            raise ValueError(
                f"replica {name!r} needs a fixed port (got {port}); the "
                f"parent cannot discover a child's OS-picked port"
            )
        self.name = name
        self.argv = list(argv)
        self.host = host
        self._port = port
        self.metrics_url = (
            f"http://{host}:{metrics_port}/metrics"
            if metrics_port is not None else None
        )
        self.grace_s = grace_s
        self.start_timeout_s = start_timeout_s
        self._lock = threading.RLock()
        self._proc: Optional[subprocess.Popen] = None
        self.last_outcome: Optional[str] = None

    @property
    def port(self) -> int:
        return self._port

    def start(self) -> int:
        import os

        with self._lock:
            if self._proc is not None and self._proc.poll() is None:
                return self._port
            env = dict(os.environ)
            env["TA_REPLICA"] = self.name  # ps/log attribution, the
            # JAX_PROCESS_INDEX idiom of launch_local
            # Replicas on a TPU host are in-process engines, one per
            # device; a child process must be held to the CPU.
            require_cpu_children(
                f"ProcessReplica {self.name!r}", self.argv, env
            )
            self._proc = subprocess.Popen(self.argv, env=env)
        deadline = time.monotonic() + self.start_timeout_s
        while time.monotonic() < deadline:
            if self.ready():
                return self._port
            with self._lock:
                rc = self._proc.poll()
            if rc is not None:
                raise RuntimeError(
                    f"replica {self.name} exited during startup "
                    f"({_rank_exit_outcome(rc)}, status {rc})"
                )
            time.sleep(0.2)
        raise RuntimeError(
            f"replica {self.name} not ready after {self.start_timeout_s}s"
        )

    def ready(self) -> bool:
        stats = self._stats()
        return bool(stats and stats.get("ready"))

    def _stats(self) -> Optional[Dict[str, Any]]:
        import urllib.request

        try:
            with urllib.request.urlopen(
                f"http://{self.host}:{self._port}/ingress/stats",
                timeout=2.0,
            ) as r:
                return json.loads(r.read())
        except (OSError, ValueError):
            return None

    def begin_drain(self) -> None:
        """The drain handshake: POST /admin/drain, falling back to
        SIGTERM (the CLI installs install_drain_signals, so both spell
        the same graceful drain)."""
        import urllib.request

        try:
            urllib.request.urlopen(urllib.request.Request(
                f"http://{self.host}:{self._port}/admin/drain",
                method="POST", data=b""), timeout=2.0).read()
            return
        except (OSError, ValueError):
            pass
        with self._lock:
            proc = self._proc
        if proc is not None and proc.poll() is None:
            proc.terminate()

    def await_drained(self, timeout_s: float = 60.0) -> bool:
        """Wait for the child to exit; escalate SIGTERM -> SIGKILL after
        the deadline + grace (the launcher's escalation shape). Always
        returns True — by then the process is GONE either way, so a
        restart is safe (the contract the supervisor checks); the exit
        classification lands in :attr:`last_outcome`
        (``ok/crash/deadline/stall``, the launcher vocabulary)."""
        with self._lock:
            proc = self._proc
        if proc is None:
            return True
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                rc = proc.wait(timeout=self.grace_s)
            except subprocess.TimeoutExpired:
                proc.kill()
                rc = proc.wait()
        if rc < 0:
            rc = 128 - rc  # Popen reports -SIGNUM, the launcher's rule
        with self._lock:
            self.last_outcome = _rank_exit_outcome(rc)
        if rc != 0:
            log.warning("fleet: replica %s exited %s (status %d)",
                        self.name, _rank_exit_outcome(rc), rc)
        return True

    def restart(self) -> int:
        return self.start()

    def stop(self) -> None:
        self.begin_drain()
        self.await_drained()

    def alive(self) -> bool:
        with self._lock:
            return self._proc is not None and self._proc.poll() is None


class FleetSupervisor:
    """Start, watch, and roll a fleet of replicas behind one router.

    Args:
      replicas: the handles (Local or Process; mixable).
      router: a pre-built :class:`FleetRouter` (its ``block`` must match
        the replicas' prefix block), or None to build a default.
      monitor_interval_s: health-poll period; 0 disables the monitor
        thread entirely (tests drive lifecycle explicitly).
      restarts: per-replica restart budget for UNPLANNED deaths (the
        elastic-recovery idiom); rolling restarts are planned and do not
        consume it.
    """

    def __init__(self, replicas: Sequence[Any], *,
                 router: Optional[FleetRouter] = None,
                 monitor_interval_s: float = 1.0,
                 restarts: int = 1):
        if not replicas:
            raise ValueError("a fleet needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"replica names must be unique: {names}")
        self.replicas: Dict[str, Any] = {r.name: r for r in replicas}
        self.router = router if router is not None else FleetRouter()
        self.monitor_interval_s = monitor_interval_s
        self.restarts = restarts
        self._lock = threading.RLock()
        # Serializes whole drain/restart SEQUENCES (monitor recovery vs
        # rolling restart) — self._lock only guards state snapshots, so
        # without this a monitor poll could observe a mid-roll replica
        # as unhealthy and race a second restart into it.
        self._op_lock = threading.Lock()
        self._maintenance: set = set()  # replicas mid-rolling-restart
        self._restarts_used: Dict[str, int] = {n: 0 for n in names}
        self._monitor: Optional[threading.Thread] = None
        self._stop_monitor = threading.Event()

    # -- lifecycle --------------------------------------------------------

    def start(self) -> int:
        """Start every replica, register them, start the router (and the
        monitor); returns the router's port."""
        for name, rep in sorted(self.replicas.items()):
            port = rep.start()
            self.router.add_replica(name, port,
                                    metrics_url=rep.metrics_url)
            log.info("fleet: replica %s up on port %d", name, port)
        port = self.router.start()
        if self.monitor_interval_s > 0:
            with self._lock:
                self._monitor = threading.Thread(
                    target=self._monitor_loop, name="fleet-monitor",
                    daemon=True,
                )
                self._monitor.start()
        log.info("fleet: router up on http://127.0.0.1:%d (%d replicas)",
                 port, len(self.replicas))
        return port

    def stop(self) -> None:
        """Graceful fleet shutdown: stop the monitor, drain every
        replica (concurrently), then the router."""
        self._stop_monitor.set()
        with self._op_lock:
            # Barrier: an in-flight _check_one recovery (drain up to
            # 30s + restart) must complete before the fleet drains, or
            # it would rejoin/restart a replica AFTER stop() returned —
            # a serve loop nothing will ever drain.
            pass
        with self._lock:
            mon = self._monitor
        if mon is not None:
            mon.join(timeout=60.0)
        for name in self.replicas:
            self.router.set_draining(name)
        for rep in self.replicas.values():
            rep.begin_drain()
        for rep in self.replicas.values():
            rep.await_drained()
        self.router.stop()

    # -- health monitor ---------------------------------------------------

    def _monitor_loop(self) -> None:
        while not self._stop_monitor.wait(self.monitor_interval_s):
            for name, rep in list(self.replicas.items()):
                with self._lock:
                    if name in self._maintenance:
                        continue
                self._check_one(name, rep)

    def _check_one(self, name: str, rep) -> None:
        if rep.ready():
            return
        with self._op_lock:
            # Re-check under the operation lock: a rolling restart may
            # have taken this replica into maintenance (or finished
            # healing it) between the monitor's poll and here — acting
            # on the stale observation would double-drain the replica
            # and burn the unplanned-restart budget on planned work.
            # A requested shutdown also beats recovery: stop()'s barrier
            # must not be followed by a resurrection.
            if self._stop_monitor.is_set():
                return
            with self._lock:
                if name in self._maintenance:
                    return
            # lint: allow[lock-order] _op_lock deliberately serializes whole recovery sequences (health probe included); state snapshots use self._lock, which never blocks
            if rep.ready():
                return
            self.router.mark_down(name)
            with self._lock:
                used = self._restarts_used.get(name, 0)
                if used >= self.restarts:
                    return
                self._restarts_used[name] = used + 1
            log.warning("fleet: replica %s unhealthy; restarting "
                        "(attempt %d/%d)", name, used + 1, self.restarts)
            try:
                # lint: allow[lock-order] the drain handshake must run under _op_lock — releasing it mid-recovery is exactly the double-drain race the lock exists to prevent
                rep.begin_drain()
                # lint: allow[lock-order] bounded in practice (30s drain + grace escalation to SIGKILL); _op_lock must be held or a rolling restart could double-serve the engine
                if not rep.await_drained(timeout_s=30.0):
                    # Wedged drain: the engine thread still owns its
                    # serve loop — restarting now would double-serve the
                    # engine. Leave it down; the next poll retries.
                    log.error("fleet: replica %s drain timed out; "
                              "leaving it down", name)
                    return
                # lint: allow[lock-order] restart-until-ready stays inside the serialized recovery section; start_timeout_s bounds it
                port = rep.restart()
            except (RuntimeError, OSError) as e:
                log.error("fleet: replica %s restart failed: %s", name, e)
                return
            self.router.rejoin(name, port=port, reset_tree=True)

    # -- rolling restart --------------------------------------------------

    def rolling_restart(self, *, drain_timeout_s: float = 60.0,
                        ready_timeout_s: float = 60.0) -> Dict[str, Any]:
        """Restart every replica, one at a time, with zero dropped
        accepted requests: the router stops routing to the victim, its
        queued work sheds replica-side and requeues router-side onto
        peers, its in-flight streams finish, then drain -> restart ->
        ready -> rejoin. Returns per-replica outcomes."""
        out: Dict[str, Any] = {}
        for name in sorted(self.replicas):
            rep = self.replicas[name]
            with self._op_lock:
                # Mutual exclusion with the monitor's recovery path: a
                # concurrent unplanned restart of the SAME replica would
                # double-drain it.
                with self._lock:
                    self._maintenance.add(name)
            try:
                self.router.set_draining(name)
                rep.begin_drain()
                drained = rep.await_drained(timeout_s=drain_timeout_s)
                if not drained:
                    # The engine loop is wedged past the timeout:
                    # restarting would double-serve the engine. Mark it
                    # down (it takes no routes), move on — the fleet
                    # keeps serving on its peers.
                    self.router.mark_down(name)
                    out[name] = {"drained": False, "skipped": True}
                    log.error("fleet: rolling restart of %s aborted — "
                              "drain timed out; replica left down", name)
                    continue
                leak = (rep.leak_report()
                        if hasattr(rep, "leak_report") else None)
                port = rep.restart()
                deadline = time.monotonic() + ready_timeout_s
                while not rep.ready():
                    if time.monotonic() >= deadline:
                        raise RuntimeError(
                            f"replica {name} not ready after restart"
                        )
                    time.sleep(0.05)
                self.router.rejoin(name, port=port, reset_tree=True)
                out[name] = {"drained": drained, "port": port,
                             **({"leak": leak} if leak else {})}
                log.info("fleet: rolled %s (drained=%s, new port %d)",
                         name, drained, port)
            finally:
                with self._lock:
                    self._maintenance.discard(name)
        return out

    # -- introspection ----------------------------------------------------

    def leak_reports(self) -> Dict[str, Dict[str, int]]:
        return {n: r.leak_report() for n, r in self.replicas.items()
                if hasattr(r, "leak_report")}

    @property
    def engines(self) -> List[Any]:
        """The in-process engines (LocalReplica fleets; bench/tests)."""
        return [r.engine for r in self.replicas.values()
                if isinstance(r, LocalReplica)]


def install_fleet_drain_signals(supervisor: FleetSupervisor
                                ) -> threading.Event:
    """SIGTERM/SIGINT -> set the returned event (main thread only).

    The ingress's :func:`install_drain_signals` drains one server from
    inside the handler because drain() is a quick flag flip; a fleet
    drain JOINS N engine loops, which must not run in a signal handler.
    So the handler only sets an event — the caller (the CLI's fleet
    loop) waits on it and runs :meth:`FleetSupervisor.stop` on the main
    thread. A second signal while draining escalates to the previous
    handler (an operator's double-SIGTERM must still kill a stuck
    drain), the same rule the ingress uses.
    """
    import signal

    evt = threading.Event()
    prev = {}

    def _begin_drain(signum, frame):
        if evt.is_set():
            handler = prev.get(signum)
            if callable(handler):
                handler(signum, frame)
            else:
                signal.signal(signum, signal.SIG_DFL)
                import os

                os.kill(os.getpid(), signum)
            return
        evt.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(sig, _begin_drain)
    return evt
