"""donation-safety: donated buffers are dead until rebound or relayed.

Every hot-path dispatch donates its cache (``jax.jit(...,
donate_argnums=...)``): on TPU the XLA runtime ALIASES the output onto
the donated input's buffer, so the old binding is garbage the moment the
call is issued.  The CPU backend silently *copies* instead — which is
why the two bug classes this pass enforces are invisible in every CPU
test and fatal on the hardware:

- **read-after-donate** — a binding passed in a donated position must be
  rebound (assignment target, or a rebinder helper) before its next
  read.  ``self.tok, self.cache, self._key = self._mixed(...,
  self.cache, self._key)`` is the canonical safe shape: consumption and
  rebind in one statement.
- **missing relay** — the disaggregated pair shares ONE set of pool
  arrays between two engines; a dispatch through either worker donates
  the buffers the OTHER worker's cache still references.  The sharing is
  declared in-code with ``# lint: donated-alias[pf.cache, dc.cache]``
  (function-scoped): consuming any member consumes them all, and each
  member must be rebound — directly, or via a relay helper (a same-file
  method that assigns ``<param>.cache``, e.g. ``_relay_pool``) — before
  its next read.  Deleting one ``self._relay_pool(...)`` line in
  ``disagg.py`` is a lint failure, not a silent KV corruption on TPU.

Donation tables: same-file ``self._X = jax.jit(fn, donate_argnums=…)``
assignments are discovered; for cross-file dispatch (``disagg.py``
calling ``SlotServer`` programs through ``pf``/``dc``) the pass carries
:data:`SLOTSERVER_DONATIONS`, which is VERIFIED against ``engine.py``'s
discovered table on every run — editing a ``donate_argnums`` in
``engine.py`` without updating the table here is itself a finding, so
the two cannot drift.  A ``donate_argnums`` too dynamic to read (an
``IfExp``) falls back to treating every dotted-name argument of the
call as donated.

Known limit (documented, not enforced): a *conditionally* dispatching
helper — ``_admit``'s restore-scatter arc — is not modeled; its relay in
``disagg.py`` (after ``pf._tick_restored``) stays review-owned.

Scope: ``serving/engine.py``, ``serving/disagg.py``,
``serving/prefix_cache.py``.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.lintlib import Finding, Source, dotted, emit, lint_pass

RULE = "donation-safety"

_SCOPE = (
    "tree_attention_tpu/serving/engine.py",
    "tree_attention_tpu/serving/disagg.py",
    "tree_attention_tpu/serving/prefix_cache.py",
)

#: SlotServer's donated program families (attr -> donated positions of
#: the bound call), for cross-file receivers. Verified against
#: engine.py's discovered table — see _check_table_drift.
SLOTSERVER_DONATIONS: Dict[str, Tuple[int, ...]] = {
    "_mixed": (6,),
    "_packed": (9,),
    "_stage_chunk": (3,),
    "_stage_final": (3, 4, 5, 6),
    "_spec_lin": (8,),
    "_spec_tree": (10,),
    "_compact": (0,),
    "_dequant_hit": (0,),
    # Copy-on-write forking (ISSUE 15): the per-slot key seeding and
    # the fork's tail-block copy both donate their first operand.
    "_seed_key": (0,),
    "_fork_copy": (0,),
    # Sequence-sharded pools (ISSUE 18) add NO rows here by design: the
    # seq path reuses these same families — the donated pool operands
    # are now sharded arrays (NamedSharding over the seq axis), and XLA
    # buffer donation is per-shard-buffer, so the aliasing contract is
    # unchanged.  _check_table_drift pins this: a new donated family on
    # the sharded dispatch path must land in this table or fail lint.
}

#: SlotServer helpers that dispatch donating programs internally and
#: rebind the receiver's own cache before returning: a call through
#: receiver R consumes R.cache's ALIASES (the other worker's view) and
#: leaves R.cache itself fresh.
DISPATCHER_HELPERS = {"_run_staged_chunk", "_spec_commit_all",
                      "_apply_forks", "_fork_live", "_fork_child"}

_ALIAS_RE = re.compile(r"#\s*lint:\s*donated-alias\[([^\]]+)\]")


def _literal_positions(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


def _discover_donations(
    tree: ast.AST,
) -> Dict[str, Optional[Tuple[int, ...]]]:
    """``attr/local name -> donated positions`` for every
    ``X = jax.jit(fn, donate_argnums=...)`` in the file (None =
    positions unresolvable; call sites fall back to dotted-args)."""
    out: Dict[str, Optional[Tuple[int, ...]]] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and (dotted(node.value.func) or "") == "jax.jit"):
            continue
        donate = None
        for kw in node.value.keywords:
            if kw.arg == "donate_argnums":
                donate = kw.value
        if donate is None:
            continue
        for t in node.targets:
            d = dotted(t)
            if d is None:
                continue
            name = d.split(".")[-1]
            out[name] = _literal_positions(donate)
    return out


def _rebinder_summaries(tree: ast.AST) -> Dict[str, List[Tuple[int, str]]]:
    """Methods that assign ``<param>.<attr> = ...``: method name ->
    [(param position excluding self, attr)]. ``self._relay_pool(pf, dc)``
    thereby rebinds ``dc.cache``."""
    out: Dict[str, List[Tuple[int, str]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        params = [a.arg for a in node.args.args]
        if not params or params[0] != "self":
            continue
        rebinds: List[Tuple[int, str]] = []
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Assign):
                continue
            for t in sub.targets:
                d = dotted(t)
                if d and d.count(".") == 1 \
                        and d.split(".")[0] in params[1:]:
                    rebinds.append(
                        (params.index(d.split(".")[0]) - 1,
                         d.split(".")[1])
                    )
        if rebinds:
            out[node.name] = rebinds
    return out


def _function_aliases(src: Source, fn: ast.FunctionDef) -> List[Set[str]]:
    """donated-alias groups declared inside ``fn``'s line range."""
    end = getattr(fn, "end_lineno", fn.lineno)
    groups: List[Set[str]] = []
    for i in range(fn.lineno, end + 1):
        if 1 <= i <= len(src.lines):
            m = _ALIAS_RE.search(src.lines[i - 1])
            if m:
                groups.append(
                    {p.strip() for p in m.group(1).split(",") if p.strip()}
                )
    return groups


class _Flow:
    """Per-function consumed-binding dataflow (see module docstring)."""

    def __init__(self, src: Source, fn: ast.FunctionDef,
                 donations: Dict[str, Optional[Tuple[int, ...]]],
                 rebinders: Dict[str, List[Tuple[int, str]]],
                 findings: List[Finding]):
        self.src = src
        self.fn = fn
        self.donations = donations
        self.rebinders = rebinders
        self.findings = findings
        self.aliases = _function_aliases(src, fn)
        self.consumed: Set[str] = set()

    # -- helpers -----------------------------------------------------------

    def _alias_closure(self, name: str) -> Set[str]:
        out = {name}
        for g in self.aliases:
            if name in g:
                out |= g
        return out

    def _donating_call(self, call: ast.Call) -> Optional[List[str]]:
        """Dotted names this call donates, or None if not donating."""
        if not isinstance(call.func, ast.Attribute):
            return None
        name = call.func.attr
        recv = dotted(call.func.value)
        if recv is None:
            return None
        positions: Optional[Tuple[int, ...]]
        if name in self.donations:
            positions = self.donations[name]
        elif recv != "self" and name in SLOTSERVER_DONATIONS:
            positions = SLOTSERVER_DONATIONS[name]
        elif recv != "self" and name in DISPATCHER_HELPERS:
            # Internal dispatch + self-rebind: only the ALIASES of the
            # receiver's cache die here.
            own = f"{recv}.cache"
            return sorted(self._alias_closure(own) - {own})
        else:
            return None
        starred = any(isinstance(a, ast.Starred) for a in call.args)
        donated: List[str] = []
        if positions is None or starred:
            cand = [dotted(a) for a in call.args
                    if not isinstance(a, ast.Starred)]
            donated = [d for d in cand if d and "." in d]
        else:
            for p in positions:
                if p < len(call.args):
                    d = dotted(call.args[p])
                    if d:
                        donated.append(d)
        out: Set[str] = set()
        for d in donated:
            out |= self._alias_closure(d)
        return sorted(out)

    def _rebind(self, target: str) -> None:
        self.consumed = {
            c for c in self.consumed
            if not (c == target or c.startswith(target + "."))
        }

    def _reads(self, expr: ast.AST) -> List[Tuple[str, ast.AST]]:
        """Dotted-name Load reads inside ``expr``. Lambda bodies are
        PRUNED, not just skipped — a lambda's reads happen when it is
        later called, by which point the enclosing statement's rebind
        has landed (``ast.walk`` would descend into the subtree and
        false-positive them)."""
        out = []
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Lambda):
                continue
            d = dotted(node) if isinstance(node, (ast.Attribute,
                                                  ast.Name)) else None
            if d is not None and isinstance(getattr(node, "ctx", None),
                                            ast.Load):
                out.append((d, node))
            stack.extend(ast.iter_child_nodes(node))
        return out

    def _check_reads(self, expr: Optional[ast.AST],
                     exempt: Sequence[str] = ()) -> None:
        """``exempt``: bindings this statement rebinds — ``x.cache =
        dataclasses.replace(x.cache, ...)`` reads the stale container
        only to relay it, which is the fix, not the bug."""
        if expr is None:
            return
        for d, node in self._reads(expr):
            if d in exempt:
                continue
            for c in sorted(self.consumed):
                if d == c or d.startswith(c + "."):
                    emit(self.findings, self.src, RULE, node,
                         f"{self.fn.name} reads {d} after {c} was "
                         f"donated to a dispatch — rebind or relay it "
                         f"first (CPU hides this by copying; TPU "
                         f"aliases the buffer)")
                    self.consumed.discard(c)

    # -- statement walk ----------------------------------------------------

    def run(self) -> None:
        self.block(self.fn.body)

    def block(self, stmts: Sequence[ast.stmt]) -> None:
        for st in stmts:
            self.statement(st)

    def _handle_calls(self, expr: Optional[ast.AST]) -> None:
        """Consume donated bindings / apply rebinder summaries for every
        call inside ``expr`` (post-read, pre-target ordering)."""
        if expr is None:
            return
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            donated = self._donating_call(node)
            if donated:
                self.consumed |= set(donated)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in self.rebinders:
                args = [a for a in node.args
                        if not isinstance(a, ast.Starred)]
                for pos, attr in self.rebinders[node.func.attr]:
                    if pos < len(args):
                        d = dotted(args[pos])
                        if d:
                            self._rebind(f"{d}.{attr}")

    def statement(self, st: ast.stmt) -> None:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            return  # separate scope; analyzed on its own
        if isinstance(st, ast.Assign):
            targets = {
                dotted(el)
                for t in st.targets
                for el in (t.elts if isinstance(t, ast.Tuple) else [t])
            } - {None}
            # A binding both read and rebound here is the inline-relay
            # idiom — UNLESS the read is a donated argument of this very
            # statement's dispatch (donating an already-dead buffer is
            # exactly the missing-relay bug, rebind or not).
            redonated: Set[str] = set()
            for node in ast.walk(st.value):
                if isinstance(node, ast.Call):
                    redonated |= set(self._donating_call(node) or ())
            self._check_reads(st.value,
                              exempt=sorted(targets - redonated))
            self._handle_calls(st.value)
            for t in st.targets:
                for el in (t.elts if isinstance(t, ast.Tuple) else [t]):
                    d = dotted(el)
                    if d:
                        self._rebind(d)
                    elif isinstance(el, ast.Subscript):
                        self._check_reads(el.slice)
            return
        if isinstance(st, ast.AugAssign):
            self._check_reads(st.value)
            self._check_reads(st.target)
            self._handle_calls(st.value)
            return
        if isinstance(st, ast.Expr):
            self._check_reads(st.value)
            self._handle_calls(st.value)
            return
        if isinstance(st, (ast.Return,)):
            self._check_reads(st.value)
            self._handle_calls(st.value)
            return
        if isinstance(st, ast.If):
            self._check_reads(st.test)
            self._handle_calls(st.test)
            entry = set(self.consumed)
            self.block(st.body)
            after_body = self.consumed
            self.consumed = set(entry)
            self.block(st.orelse)
            self.consumed |= after_body  # conservative union join
            return
        if isinstance(st, (ast.For, ast.AsyncFor)):
            self._check_reads(st.iter)
            self._handle_calls(st.iter)
            # Twice: catches loop-carried consumption (a dispatch at the
            # bottom of the body feeding a read at the top).
            self.block(st.body)
            self.block(st.body)
            self.block(st.orelse)
            return
        if isinstance(st, ast.While):
            # Unlike a For iterable, the test re-evaluates every
            # iteration — a dispatch (or relay) in the condition feeds
            # the dataflow, and a dispatch at the bottom of the body
            # feeds a read in the NEXT evaluation of the test.
            self._check_reads(st.test)
            self._handle_calls(st.test)
            self.block(st.body)
            self._check_reads(st.test)
            self._handle_calls(st.test)
            self.block(st.body)
            self.block(st.orelse)
            return
        if isinstance(st, (ast.With, ast.AsyncWith)):
            for item in st.items:
                self._check_reads(item.context_expr)
                self._handle_calls(item.context_expr)
            self.block(st.body)
            return
        if isinstance(st, ast.Try):
            self.block(st.body)
            for h in st.handlers:
                self.block(h.body)
            self.block(st.orelse)
            self.block(st.finalbody)
            return
        for child in ast.iter_child_nodes(st):
            if isinstance(child, ast.expr):
                self._check_reads(child)
                self._handle_calls(child)


def _check_table_drift(src: Source,
                       discovered: Dict[str, Optional[Tuple[int, ...]]],
                       findings: List[Finding]) -> None:
    """engine.py only: every donating family the file builds that the
    cross-file table also claims must agree on positions.  (The other
    direction — a table name engine.py no longer builds — is pinned by
    ``tests/test_lint.py::TestDonationSafety::test_table_matches_engine``
    against the real tree, so fixture snippets stay usable here.)"""
    for name, pos in sorted(discovered.items()):
        if pos is None:
            continue  # dynamic donate_argnums: call sites use fallback
        claimed = SLOTSERVER_DONATIONS.get(name)
        if claimed is not None and tuple(claimed) != tuple(pos):
            emit(findings, src, RULE, src.tree,
                 f"donation table drift: engine.py builds {name} with "
                 f"donate_argnums={tuple(pos)} but tools/lintlib/"
                 f"donation.py claims {tuple(claimed)} — update "
                 f"SLOTSERVER_DONATIONS")


@lint_pass(RULE)
def check(src: Source) -> List[Finding]:
    if src.path not in _SCOPE:
        return []
    findings: List[Finding] = []
    donations = _discover_donations(src.tree)
    rebinders = _rebinder_summaries(src.tree)
    if src.path == "tree_attention_tpu/serving/engine.py":
        _check_table_drift(src, donations, findings)
    for node in ast.walk(src.tree):
        if isinstance(node, ast.FunctionDef):
            _Flow(src, node, donations, rebinders, findings).run()
    # Alias-closure consumption can flag one read once per group member.
    seen: Set[Tuple[int, int, str]] = set()
    out: List[Finding] = []
    for f in findings:
        k = (f.line, f.col, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out
