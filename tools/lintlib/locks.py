"""lock-safety: obs shared state and the crash-handler signal paths.

The obs instruments are mutated from the engine thread, the HTTP
exporter thread, async checkpoint threads, AND (the hard case) signal
handlers interrupting any of them mid-emission.  Three machine-checkable
rules keep that sound:

- **mutate under the lock** — in any ``obs/`` class owning a
  ``self._lock``, every write to underscore-prefixed shared state
  (``self._ring = …``, ``self._tids[k] = …``, ``self._ring.append``)
  outside ``__init__`` must sit inside a ``with self._lock:`` block.
  Non-underscore flags (``enabled``, ``active``) are the documented
  lock-free fast path — one attribute, atomic in CPython — and exempt.
- **crash-path locks are re-entrant** — a SIGTERM can interrupt a
  thread HOLDING an emission lock and then call the flush path, which
  takes the same lock: ``threading.Lock()`` deadlocks the
  flush-then-die contract, ``threading.RLock()`` flushes (the PR-4
  review fix, now enforced).  Applies to classes whose methods include
  a crash-path entry (``flush`` / ``close`` / ``dump`` /
  ``dump_if_armed`` / ``write_json``).
- **signal paths never emit** — everything reachable from
  ``obs.flush`` and the installed signal handlers may *write sinks*
  but must not call the emission APIs (``inc`` / ``observe`` /
  ``labels`` / ``instant`` / ``record``): an
  emission inside a handler allocates and re-enters emission locks at
  the exact moment they may be held.

Scope: ``tree_attention_tpu/obs/`` and — since ISSUE 10 —
``tree_attention_tpu/serving/ingress.py``: its HTTP handler threads
share state with the engine thread (queue depth, drain flag, the live
feeder's queue), and the same mutate-under-``self._lock`` contract
applies to every ingress class owning one. Since ISSUE 11 the fleet
tier joins too: ``serving/router.py`` (handler threads share the
replica registry, approximate trees, and in-flight counters) and
``serving/fleet.py`` (the supervisor's monitor thread shares replica
handles and restart budgets with the caller thread). The engine itself
stays out of scope by design: handler threads reach it only through the
three mailbox seams (``submit``/``cancel``/``request_drain``), so all
other ``SlotServer`` state remains single-threaded. Since ISSUE 12
``serving/disagg.py`` is in scope too: ``DisaggServer`` mirrors the
engine's mailbox contract (cancel/drain state under ``self._lock``, an
RLock — drain may flip from a SIGTERM handler), and the pass enforces
that everything else it owns — the handoff queue's run state — stays
either under the lock or deliberately OFF ``self`` (loop-locals that die
with the run).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.lintlib import Finding, Source, dotted, emit, lint_pass, parent

RULE = "lock-safety"

_CRASH_METHODS = {"flush", "close", "dump", "dump_if_armed", "write_json"}
_MUTATING_METHODS = {
    "append", "appendleft", "extend", "add", "clear", "pop", "popleft",
    "popitem", "remove", "discard", "update", "setdefault", "insert",
}
_EMISSION_APIS = {"inc", "dec", "observe", "labels", "instant"}
# Crash-path entries double as roots so per-file analysis still covers
# the cross-module hop (obs.flush -> REGISTRY.write_json lives in
# another file; rooting write_json itself closes the gap).
_SIGNAL_ROOTS = _CRASH_METHODS | {"_on_term", "_on_usr1"}


def _in_scope(path: str) -> bool:
    return (path.startswith("tree_attention_tpu/obs/")
            or path in (
                "tree_attention_tpu/serving/ingress.py",
                "tree_attention_tpu/serving/router.py",
                "tree_attention_tpu/serving/fleet.py",
                "tree_attention_tpu/serving/disagg.py",
                # The host KV tier (ISSUE 13): single-threaded by design
                # today (engine-loop only), so HostBlockPool owns no
                # lock — but the pass scopes it so the moment anyone
                # adds one (e.g. a background flusher thread), every
                # self._* mutation must move under it.
                "tree_attention_tpu/serving/host_pool.py",
            ))


def _under_lock(node: ast.AST) -> bool:
    p = parent(node)
    while p is not None:
        if isinstance(p, (ast.With, ast.AsyncWith)):
            for item in p.items:
                if (dotted(item.context_expr) or "") == "self._lock":
                    return True
        if isinstance(p, ast.FunctionDef):
            return False  # don't credit an outer function's lock
        p = parent(p)
    return False


def _self_underscore_target(expr: ast.AST) -> Optional[str]:
    """``self._name`` (through subscripts) when ``expr`` stores to one."""
    while isinstance(expr, ast.Subscript):
        expr = expr.value
    d = dotted(expr)
    if d and d.startswith("self._") and d.count(".") == 1:
        return d
    return None


def _check_locked_mutations(src: Source, findings: List[Finding]) -> None:
    for cls in ast.walk(src.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        init = next((m for m in cls.body
                     if isinstance(m, ast.FunctionDef)
                     and m.name == "__init__"), None)
        owns_lock = init is not None and any(
            isinstance(st, ast.Assign)
            and any(_self_underscore_target(t) == "self._lock"
                    for t in st.targets)
            for st in ast.walk(init)
        )
        if not owns_lock:
            continue
        for m in cls.body:
            if not isinstance(m, ast.FunctionDef) or m.name == "__init__":
                continue
            for node in ast.walk(m):
                tgt: Optional[str] = None
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (node.targets if isinstance(node, ast.Assign)
                               else [node.target])
                    for t in targets:
                        tgt = tgt or _self_underscore_target(t)
                elif (isinstance(node, ast.Call)
                      and isinstance(node.func, ast.Attribute)
                      and node.func.attr in _MUTATING_METHODS):
                    tgt = _self_underscore_target(node.func.value)
                if tgt is None or tgt == "self._lock":
                    continue
                if not _under_lock(node):
                    emit(findings, src, RULE, node,
                         f"{cls.name}.{m.name} mutates shared state "
                         f"{tgt} outside 'with self._lock:' (the obs "
                         f"threading contract)")


def _check_rlock(src: Source, findings: List[Finding]) -> None:
    for cls in ast.walk(src.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        crash_path = any(isinstance(m, ast.FunctionDef)
                         and m.name in _CRASH_METHODS for m in cls.body)
        if not crash_path:
            continue
        for node in ast.walk(cls):
            if (isinstance(node, ast.Assign)
                    and any(_self_underscore_target(t) == "self._lock"
                            for t in node.targets)
                    and isinstance(node.value, ast.Call)
                    and ((dotted(node.value.func) or "") == "Lock"
                         or (dotted(node.value.func) or "").endswith(
                             ".Lock"))):
                emit(findings, src, RULE, node,
                     f"{cls.name} is on the crash-flush path but uses a "
                     f"non-reentrant threading.Lock — a signal "
                     f"interrupting a lock-holding emit deadlocks the "
                     f"flush-then-die contract (use threading.RLock)")


def _signal_reachable(src: Source) -> List[Tuple[str, ast.FunctionDef]]:
    """Functions reachable (by last-component name, within this file)
    from the signal roots."""
    by_name: Dict[str, List[Tuple[str, ast.FunctionDef]]] = {}
    for node in ast.walk(src.tree):
        if isinstance(node, ast.FunctionDef):
            owner = parent(node)
            qual = (f"{owner.name}.{node.name}"
                    if isinstance(owner, ast.ClassDef) else node.name)
            by_name.setdefault(node.name, []).append((qual, node))
    reached: List[Tuple[str, ast.FunctionDef]] = []
    seen: Set[int] = set()
    work = [fn for root in _SIGNAL_ROOTS for fn in by_name.get(root, [])]
    while work:
        qual, fn = work.pop()
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        reached.append((qual, fn))
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d:
                    for cand in by_name.get(d.split(".")[-1], []):
                        work.append(cand)
    return reached


def _check_signal_paths(src: Source, findings: List[Finding]) -> None:
    for qual, fn in _signal_reachable(src):
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _EMISSION_APIS):
                emit(findings, src, RULE, node,
                     f"signal-path function '{qual}' calls emission API "
                     f".{node.func.attr}() — crash handlers must only "
                     f"flush sinks, never emit")
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "record"
                    and (dotted(node.func.value) or "").split(".")[-1]
                    in ("FLIGHT", "self")):
                emit(findings, src, RULE, node,
                     f"signal-path function '{qual}' records a flight "
                     f"tick — crash handlers must only flush sinks")


@lint_pass(RULE)
def check(src: Source) -> List[Finding]:
    if not _in_scope(src.path):
        return []
    findings: List[Finding] = []
    _check_locked_mutations(src, findings)
    _check_rlock(src, findings)
    _check_signal_paths(src, findings)
    return findings
