"""host-sync: the serving tick loop pays exactly ONE host sync per tick.

The stall-free tick (Sarathi-Serve, arXiv:2403.02310) is the serving
engine's product: every dispatch of the mixed-Tq program is async, and
the only device→host fetch is the per-tick batched token read.  A stray
``np.asarray(device_array)`` / ``.item()`` / ``jax.device_get`` /
``.block_until_ready()`` anywhere in the loop stalls the dispatch
pipeline — and is invisible in review because it looks like ordinary
numpy.  This pass flags every sync-forcing construct inside the scoped
functions; the ONE intended fetch carries the
``# lint: allow[host-sync] <reason>`` annotation.

Scope:

- ``SlotServer.serve`` in ``serving/engine.py`` — the tick loop proper
  (admission helpers run host-side numpy on *request* data, which is
  host memory; the loop body is where a device fetch stalls the tick);
- every top-level function of ``ops/decode.py`` and ``ops/__init__.py``
  — the dispatch layer must never materialise device values (it runs
  under jit for the serving families; a host sync there is a trace
  error at best and a per-call stall at worst);
- every top-level function of ``parallel/tree.py`` (ISSUE 18) — the
  sharded decode dispatch layer: ``paged_tree_decode`` and the ring/tree
  dispatchers run once per decode tick to build collective programs, so
  a sync here stalls every shard of every tick, and nothing in the file
  owns host-resident state that would need one;
- the ``*_seq`` pool-write dispatchers of ``models/decode.py``
  (ISSUE 18) — the seq-sharded scatter path runs under shard_map inside
  the engine's jitted families.  ``forward_step`` proper stays OUT of
  scope: it converts *request* metadata (host lists of starts/lengths)
  with ``np.asarray`` by design.

Rules:

- ``np.asarray(X)`` / ``np.array(X)`` where ``X`` is not a literal
  display (list/tuple/set/dict/comprehension/constant) — converting a
  built-on-host literal is allocation, converting anything else risks a
  device fetch;
- ``X.item()``, ``X.block_until_ready()``, ``jax.device_get(X)``,
  ``jax.block_until_ready(X)`` — always;
- ``float(X)`` / ``int(X)`` / ``bool(X)`` on *device-tainted* names:
  locals assigned from ``jnp.*`` calls or from the engine's jitted
  program families (``self._mixed``, ``self._spec_lin``, …), plus the
  device-resident attributes ``self.tok`` / ``self.cache`` /
  ``self._key`` — the implicit ``__float__`` sync.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tools.lintlib import Finding, Source, dotted, emit, lint_pass

RULE = "host-sync"

_SYNC_DOTTED = {"jax.device_get", "jax.block_until_ready"}
_ASARRAY = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_ZERO_ARG_SYNC_METHODS = {"item", "block_until_ready"}

#: Engine program families whose results live on device.
_DEVICE_FAMILIES = {
    "self._mixed", "self._stage_chunk", "self._stage_final",
    "self._spec_lin", "self._spec_tree", "self._compact",
}
_DEVICE_ATTRS = {"self.tok", "self.cache", "self._key"}

_LITERALS = (
    ast.List, ast.Tuple, ast.Set, ast.Dict, ast.ListComp, ast.SetComp,
    ast.DictComp, ast.GeneratorExp, ast.Constant,
)


def _scoped_functions(src: Source) -> List[ast.FunctionDef]:
    if src.path == "tree_attention_tpu/serving/engine.py":
        return [
            fn for cls in src.tree.body if isinstance(cls, ast.ClassDef)
            for fn in cls.body
            if isinstance(fn, ast.FunctionDef) and fn.name == "serve"
        ]
    if src.path == "tree_attention_tpu/serving/disagg.py":
        # The disaggregated tick loop (ISSUE 12): each worker pays its
        # one per-tick fetch inside DisaggServer.serve (and any helper
        # spelled *_tick); everything else — adoption, relays, admission
        # — is host bookkeeping that must not touch device buffers.
        return [
            fn for cls in src.tree.body if isinstance(cls, ast.ClassDef)
            for fn in cls.body
            if isinstance(fn, ast.FunctionDef)
            and (fn.name == "serve" or fn.name.endswith("_tick"))
        ]
    if src.path == "tree_attention_tpu/serving/host_pool.py":
        # The host KV tier (ISSUE 13) is the ONE place host sync is
        # intended — the staged D2H demotion batch lands in commit() —
        # so every method is in scope and each landing fetch must carry
        # its annotated reason; anything else touching device buffers
        # here (reads, alloc bookkeeping) is a staging-discipline bug.
        return [
            fn for cls in src.tree.body if isinstance(cls, ast.ClassDef)
            for fn in cls.body if isinstance(fn, ast.FunctionDef)
        ]
    if src.path in ("tree_attention_tpu/ops/decode.py",
                    "tree_attention_tpu/ops/__init__.py",
                    "tree_attention_tpu/parallel/tree.py"):
        # parallel/tree.py joins the dispatch scope with ISSUE 18: the
        # paged decode merge (paged_tree_decode) is built here every
        # tick, and a sync in any dispatcher stalls all shards at once.
        return [fn for fn in src.tree.body
                if isinstance(fn, ast.FunctionDef)]
    if src.path == "tree_attention_tpu/models/decode.py":
        # Only the seq-sharded pool-write dispatchers (ISSUE 18): the
        # *_seq scatter runs under shard_map inside jitted families.
        # forward_step itself converts request metadata (host lists)
        # with np.asarray by design and stays out of scope.
        return [fn for fn in src.tree.body
                if isinstance(fn, ast.FunctionDef)
                and fn.name.endswith("_seq")]
    return []


def _tainted_names(fn: ast.FunctionDef) -> Set[str]:
    """Local names bound (anywhere in the function) to device values.

    Function PARAMETERS are exempt even when later reassigned from a
    ``jnp.*`` call: the dispatch idiom ``if isinstance(x, Integral):
    int(x) …; else: x = jnp.asarray(x)`` converts the host case before
    the device rebind, and this pass is flow-insensitive."""
    params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                              + fn.args.kwonlyargs)}
    tainted: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        if not isinstance(node.value, ast.Call):
            continue
        d = dotted(node.value.func) or ""
        device = (
            d in _DEVICE_FAMILIES
            or d.startswith("jnp.")
            or d.startswith("jax.numpy.")
            or d.startswith("lax.")
        )
        if not device:
            continue
        for t in node.targets:
            targets = t.elts if isinstance(t, ast.Tuple) else [t]
            for el in targets:
                if isinstance(el, ast.Name) and el.id not in params:
                    tainted.add(el.id)
    return tainted


def _root_device(expr: ast.AST, tainted: Set[str]) -> Optional[str]:
    """Device-name when ``expr`` (through subscripts) roots at one."""
    while isinstance(expr, ast.Subscript):
        expr = expr.value
    d = dotted(expr)
    if d is None:
        return None
    if d in _DEVICE_ATTRS or d.split(".")[0] in tainted:
        return d
    return None


@lint_pass(RULE)
def check(src: Source) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _scoped_functions(src):
        tainted = _tainted_names(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func) or ""
            if d in _SYNC_DOTTED:
                emit(findings, src, RULE, node,
                     f"{d}(...) forces a host sync in {fn.name}()")
                continue
            if d in _ASARRAY:
                arg = node.args[0] if node.args else None
                if arg is not None and not isinstance(arg, _LITERALS):
                    emit(findings, src, RULE, node,
                         f"{d}(...) on a non-literal inside {fn.name}() "
                         f"fetches device buffers (annotate the one "
                         f"intended per-tick fetch)")
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _ZERO_ARG_SYNC_METHODS
                    and not node.args and not node.keywords):
                recv = dotted(node.func.value) or "<expr>"
                emit(findings, src, RULE, node,
                     f"{recv}.{node.func.attr}() forces a host sync in "
                     f"{fn.name}()")
                continue
            if (isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and len(node.args) == 1):
                dev = _root_device(node.args[0], tainted)
                if dev is not None:
                    emit(findings, src, RULE, node,
                         f"{node.func.id}({dev}...) implicitly syncs a "
                         f"device value in {fn.name}()")
    return findings
