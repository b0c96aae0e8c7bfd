"""obs-guard: telemetry emissions must be dominated by their guard.

The obs instruments are internally safe when disabled (one attribute
check, early return) — but the *call sites* allocate before the call:
label dicts, span-args dicts, flight records.  The tested zero-alloc
contract (``tests/test_obs.py``'s disabled-path guard) therefore depends
on every emission site in hot-path modules building its payload only
under the matching guard:

- *allocating* metric mutations on module-level metric objects →
  ``obs.REGISTRY.enabled``.  A bare ``X.inc()`` / ``X.observe(v)`` with
  scalar args is the metrics module's documented unconditional-record
  design (the disabled path is one flag check, nothing built) and stays
  legal unguarded; a ``.labels(...)`` chain (dict/tuple/child lookup)
  or a display-literal argument allocates before the flag check and
  must be guarded;
- span/instant **args payloads** (``obs.instant(..., args={...})``,
  ``obs.span(..., args=...)``, ``some_span.set(...)``) →
  ``obs.TRACER.active`` (the ``args=None if not obs.TRACER.active else
  {...}`` conditional counts — the allocating branch is guarded);
- ``FLIGHT.record(rec)`` (and the ``rec`` build) → ``FLIGHT.enabled``;
- ``REQLOG.<seam>(...)`` ledger accumulation calls (ISSUE 16) →
  ``REQLOG.enabled``: every seam call builds at least a kwargs dict
  before the ledger's own early-return, so the zero-allocation
  disabled path the telemetry bench asserts depends on the call-site
  guard exactly like registry labels do.

Scope: every module under ``tree_attention_tpu/`` EXCEPT ``obs/`` itself
(the implementation is where the guards live; its internal early-returns
use ``self.enabled``, which this pass has no business re-deriving) —
with ONE exception since ISSUE 16: ``obs/reqlog.py`` is back IN scope,
because the ledger is itself an instrumentation *consumer* (it emits a
tracer instant at finish) and its emissions must honor the same guards
as any call site. ``serving/ingress.py`` (ISSUE 10) is in scope
automatically — its HTTP route/code counters and queue-depth gauge emit
from handler threads, where an unguarded label allocation would tax
every request even with telemetry off.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tools.lintlib import (
    Finding, GuardWalker, Source, dotted, emit, is_none, lint_pass,
)

RULE = "obs-guard"

#: Constructors whose module-level assignment makes a name a metric
#: object (``_TOKENS = obs.counter(...)``).
_METRIC_CTORS = {"counter", "gauge", "histogram"}

#: Metric mutation method names (Gauge.set included; span .set is routed
#: separately via the span-receiver check).
_METRIC_MUTS = {"inc", "dec", "observe", "set"}

#: Call targets whose ``args=`` payload is a tracer emission.
_TRACER_FNS = {"instant", "span"}

#: Request-ledger accumulation seams — each builds a payload (kwargs
#: dict, keyword defaults) before REQLOG's internal early-return, so the
#: call site owns the guard.
_REQLOG_SEAMS = {"open", "note", "blocks", "first_token", "park",
                 "resume", "finish"}


def _in_scope(path: str) -> bool:
    return (
        path == "tree_attention_tpu/obs/reqlog.py"
        or (path.startswith("tree_attention_tpu/")
            and not path.startswith("tree_attention_tpu/obs/"))
    )


def _module_metric_names(tree: ast.Module) -> Set[str]:
    names: Set[str] = set()
    for st in tree.body:
        if isinstance(st, ast.Assign) and isinstance(st.value, ast.Call):
            d = dotted(st.value.func)
            if d and d.split(".")[-1] in _METRIC_CTORS:
                for t in st.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
    return names


_ALLOC_ARGS = (ast.List, ast.Tuple, ast.Set, ast.Dict, ast.ListComp,
               ast.SetComp, ast.DictComp, ast.GeneratorExp)


def _metric_receiver(call: ast.Call,
                     metrics: Set[str]) -> Optional[str]:
    """Metric name when ``call`` is an ALLOCATING metric mutation —
    ``M.labels(...).inc(...)`` (child lookup + label tuple) or
    ``M.inc([...])``-style display args.  Bare scalar mutations are the
    documented free-when-disabled path and pass."""
    fn = call.func
    if not (isinstance(fn, ast.Attribute) and fn.attr in _METRIC_MUTS):
        return None
    recv = fn.value
    has_labels = False
    if (isinstance(recv, ast.Call) and isinstance(recv.func, ast.Attribute)
            and recv.func.attr == "labels"):
        has_labels = True
        recv = recv.func.value
    d = dotted(recv)
    if d is None or d.split(".")[-1] not in metrics:
        return None
    allocates = has_labels or any(
        isinstance(a, _ALLOC_ARGS) for a in call.args
    ) or any(isinstance(kw.value, _ALLOC_ARGS) for kw in call.keywords)
    return d if allocates else None


def _tracer_call_kind(call: ast.Call) -> Optional[str]:
    d = dotted(call.func)
    if not d:
        return None
    last = d.split(".")[-1]
    return last if last in _TRACER_FNS else None


def _args_payload(call: ast.Call) -> Optional[ast.expr]:
    """The ``args`` argument of a span/instant call (positional slot
    2)."""
    for kw in call.keywords:
        if kw.arg == "args":
            return kw.value
    if len(call.args) > 2:
        return call.args[2]
    return None


class _Walker(GuardWalker):
    def __init__(self, src: Source, findings: List[Finding]):
        super().__init__(src, findings)
        self.metrics = _module_metric_names(src.tree)
        self.span_names: Set[str] = set()

    # Track ``sp = obs.span(...)`` so later ``sp.set(...)`` maps to tracer.
    def visit_stmt(self, st: ast.stmt, guards: frozenset) -> None:
        if isinstance(st, ast.Assign) and isinstance(st.value, ast.Call):
            d = dotted(st.value.func)
            if d and d.split(".")[-1] == "span":
                for t in st.targets:
                    if isinstance(t, ast.Name):
                        self.span_names.add(t.id)

    def visit_expr_node(self, e: ast.expr, guards: frozenset) -> None:
        if not isinstance(e, ast.Call):
            return
        m = _metric_receiver(e, self.metrics)
        if m is not None:
            if "registry" not in guards:
                emit(self.findings, self.src, RULE, e,
                     f"metric emission {m}.{e.func.attr}() not under an "
                     f"obs.REGISTRY.enabled guard")
            return
        fname = _tracer_call_kind(e)
        if fname is not None:
            payload = _args_payload(e)
            self._check_payload(e, payload, guards, fname)
            return
        # some_span.set(...) — args attach to a live span object.
        fn = e.func
        if (isinstance(fn, ast.Attribute) and fn.attr == "set"):
            d = dotted(fn.value) or ""
            root = d.split(".")[0] if d else ""
            if root in self.span_names or "span" in d.lower():
                if "tracer" not in guards:
                    emit(self.findings, self.src, RULE, e,
                         f"span args ({d}.set(...)) built without an "
                         f"obs.TRACER.active guard")
            return
        # FLIGHT.record(rec)
        if (isinstance(fn, ast.Attribute) and fn.attr == "record"):
            d = dotted(fn.value) or ""
            if d.split(".")[-1] == "FLIGHT":
                if e.args and not is_none(e.args[0]) \
                        and "flight" not in guards:
                    emit(self.findings, self.src, RULE, e,
                         "FLIGHT.record(...) payload built without a "
                         "FLIGHT.enabled guard")
                return
        # REQLOG.<seam>(...) — ledger accumulation (ISSUE 16).
        if (isinstance(fn, ast.Attribute) and fn.attr in _REQLOG_SEAMS):
            d = dotted(fn.value) or ""
            if d.split(".")[-1] == "REQLOG" and "reqlog" not in guards:
                emit(self.findings, self.src, RULE, e,
                     f"REQLOG.{fn.attr}(...) ledger call not under an "
                     f"obs.REQLOG.enabled guard")

    def _check_payload(self, call: ast.Call, payload: Optional[ast.expr],
                       guards: frozenset, fname: str) -> None:
        """Flag an allocating args payload that can run unguarded.  The
        canonical guarded form ``None if not obs.TRACER.active else
        {...}`` is an IfExp whose allocating branch sits under the
        tracer guard — evaluated branch-by-branch here."""
        if payload is None or is_none(payload):
            return
        if isinstance(payload, ast.IfExp):
            from tools.lintlib import guard_kinds, guard_kinds_negated
            body_g = guards | guard_kinds(payload.test)
            else_g = guards | guard_kinds_negated(payload.test)
            for branch, g in ((payload.body, body_g),
                              (payload.orelse, else_g)):
                if not is_none(branch) and "tracer" not in g:
                    emit(self.findings, self.src, RULE, branch,
                         f"{fname}() args payload allocates outside an "
                         f"obs.TRACER.active guard")
            return
        if "tracer" not in guards:
            emit(self.findings, self.src, RULE, call,
                 f"{fname}() args payload allocates outside an "
                 f"obs.TRACER.active guard")


@lint_pass(RULE)
def check(src: Source) -> List[Finding]:
    if not _in_scope(src.path):
        return []
    findings: List[Finding] = []
    _Walker(src, findings).run()
    return findings
