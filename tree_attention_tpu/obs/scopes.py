"""The parts of a model, named where the work is, and carried to a device
trace by a table from a compiled program's operations to those names.

A device trace names an operation by its HLO instruction (``fusion.453``),
and a change to a program renumbers them all. The layer bodies under
``tree_attention_tpu/models/`` therefore wrap their seams in
``jax.named_scope`` with the names below, the same in every family. A scope
is compile-time metadata: the compiler keeps the path in each instruction's
``metadata={op_name="jit(f)/while/body/ffn/dot_general"}``, fusions too, and
it costs nothing when the program runs. :func:`table` reads the optimized
module's text back into rows ``[op, result, scope]``: ``"<op> <result>"`` is
the name the profiler gives the operation's events (the benchmark's
``trace_reduce.short_name``), ``scope`` the vocabulary's name found in
``op_name`` with the rest of the path kept (``"ffn/jit(silu)/mul"``), or
``""``. The serving engine exports one table a tick program while tracing is
on (``SlotServer.program_tables``: ``ServeReport.programs``, ``/flight``).

No JAX here: the obs package stays importable without it.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

#: One vocabulary (ARCHITECTURE.md "Observability", PERF.md section 3).
EMBED = "embed"              # the token rows' gather
ATTN_IN = "attn_in"          # pre-norm, q/k/v or the latent products, rotary
ATTN_CACHE = "attn_cache"    # a pool write, and a summary formed from
                             # written rows (an EVA layer's, one a chunk)
ATTN_DECODE = "attn_decode"  # the decode rows' attention, merge, unpacking
ATTN_CHUNK = "attn_chunk"    # a packed tick's chunk rows' attention
ATTN_OUT = "attn_out"        # the output projection and the residual add
CONV = "conv"                # a mixer with a fixed-size state: the gated
                             # short convolution, whole; a state-space mixer
                             # between its projections
FFN = "ffn"                  # a dense SwiGLU with its norm; shared experts
ROUTE = "route"              # router, choice, sort / gather / weigh / add
EXPERTS = "experts"          # the grouped expert products
HEAD = "head"                # final norm, logits, float32 convert, sampling
SCOPES = (EMBED, ATTN_IN, ATTN_CACHE, ATTN_DECODE, ATTN_CHUNK, ATTN_OUT,
          CONV, FFN, ROUTE, EXPERTS, HEAD)
#: Names INSIDE ``conv`` (never outermost; a part is read by the outermost
#: name alone and the table keeps the rest of the path, ``conv/ssm_update``):
#: a state-space mixer's taps with its step sizes and tail, a chunk group's
#: scan, the decode step, the gate with the grouped norm.
SSM_TAPS, SSM_SCAN, SSM_UPDATE, SSM_NORM = (
    "ssm_taps", "ssm_scan", "ssm_update", "ssm_norm")
#: ... and inside ``attn_cache``: an EVA layer's chunk summaries, read back
#: from the rows as the pool holds them, pooled and written.
EVA_SUMMARY = "eva_summary"
INNER = (SSM_TAPS, SSM_SCAN, SSM_UPDATE, SSM_NORM, EVA_SUMMARY)

#: Opcodes whose result names a buffer and moves no byte of it: they run as
#: no device operation, so a table leaves them out.
MOVES_NOTHING = frozenset(
    {"parameter", "get-tuple-element", "tuple", "bitcast", "constant"})
#: Opcodes that enclose other instructions' events (never a trace's leaves).
ENCLOSES = frozenset({"while", "call", "conditional"})

_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
# Attributes that name a computation whose instructions run as operations
# of their own (a fusion's ``calls=`` and a reduction's ``to_apply=`` do
# not: their instructions are part of one operation).
_RUNS = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")


class Instruction(NamedTuple):
    """One instruction of an optimized module outside every fusion."""

    op: str                  # the instruction's name, no ``%``
    result: str              # ``bf16[8,11008]``; empty for a tuple
    opcode: str
    scope: str               # ``ffn/jit(silu)/mul``; empty: none found
    nbytes: int              # of the result, every array of a tuple
    computation: str
    operands: Tuple[str, ...]


def scope_of(op_name: str) -> str:
    """The vocabulary's outermost name in a scope path and what follows
    it: ``jit(f)/while/body/ffn/dot_general`` gives ``ffn/dot_general``."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part in SCOPES:
            return "/".join(parts[i:])
    return ""


def _split_instruction(line: str) -> Optional[Tuple[str, str, str, str]]:
    """``(name, result type, opcode, the text after the opcode's "(")`` of
    one instruction line, or None for any other line."""
    body = line.strip()
    if body.startswith("ROOT "):
        body = body[5:]
    name, eq, rest = body.partition(" = ")
    if not eq or not name.startswith("%") or " " in name:
        return None
    if rest.startswith("("):             # a tuple: to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rtype, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        rtype, _, rest = rest.partition(" ")
    opcode, paren, after = rest.partition("(")
    if not paren or not re.fullmatch(r"[a-z][a-z0-9\-]*", opcode):
        return None
    return name[1:], rtype, opcode, after


def _operands(after: str) -> Tuple[str, ...]:
    """The names between the opcode's brackets."""
    depth = 1
    for i, ch in enumerate(after):
        depth += (ch in "([{") - (ch in ")]}")
        if depth == 0:
            return tuple(_NAME.findall(after[:i]))
    return tuple(_NAME.findall(after))


def _nbytes(rtype: str) -> int:
    total = 0
    for dtype, dims in _ARRAY.findall(rtype):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n * _ITEMSIZE.get(dtype, 0)
    return total


def _computations(text: str) -> Iterator[Tuple[str, bool, List[str]]]:
    """``(name, is the entry, instruction lines)`` of every computation."""
    name, entry, lines = None, False, []
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            words = line.split()
            entry = words[0] == "ENTRY"
            name, lines = words[1 if entry else 0].lstrip("%"), []
        elif name is not None and line.startswith("}"):
            yield name, entry, lines
            name = None
        elif name is not None:
            lines.append(line)


def instructions(text: str) -> List[Instruction]:
    """Every instruction of the optimized HLO ``text`` that can run as a
    device operation of its own: those of the entry computation and of the
    loop bodies, conditions and branches reached from it, not the inside of
    a fusion or of a reduction's combiner. ``scope`` is read from the
    instruction's own ``op_name``; :func:`resolve` fills in what it can of
    the rest."""
    parsed: Dict[str, List[Tuple[str, str, str, str, str]]] = {}
    entry = None
    for comp, is_entry, lines in _computations(text):
        rows = []
        for line in lines:
            split = _split_instruction(line)
            if split is not None:
                rows.append(split + (line,))
        parsed[comp] = rows
        if is_entry:
            entry = comp
    reached, todo = [], [entry] if entry is not None else list(parsed)[-1:]
    while todo:
        comp = todo.pop()
        if comp in reached or comp not in parsed:
            continue
        reached.append(comp)
        for _, _, opcode, after, _ in parsed[comp]:
            for one, many in _RUNS.findall(after):
                todo += [one] if one else _NAME.findall(many)
            if opcode == "call":
                todo += re.findall(r"\bto_apply=%([\w.\-]+)", after)
    out = []
    for comp in reached:
        for name, rtype, opcode, after, line in parsed[comp]:
            found = _ARRAY.match(rtype)
            meta = _OP_NAME.search(line)
            out.append(Instruction(
                op=name, result=found.group(0) if found else "",
                opcode=opcode, scope=scope_of(meta.group(1)) if meta else "",
                # An asynchronous start's tuple lists its operand too;
                # its done carries the result.
                nbytes=0 if opcode.endswith("-start") else _nbytes(rtype),
                computation=comp,
                operands=_operands(after)))
    return out


def resolve(instrs: List[Instruction], steps: int = 8) -> List[Instruction]:
    """What carries no scope of its own takes its neighbours': an
    instruction whose ``op_name`` holds no name of the vocabulary (the
    loop's own slice of a layer's weights, a layout copy with no
    ``op_name`` at all) takes the name its users in the same computation
    carry where they all carry one and agree, written ``ffn/<-fusion.33``
    after the first of them; a round at a time, so that the start of a
    prefetch behind its done behind a bitcast is reached, ``steps`` rounds
    at most. What is still without one (a fusion whose rows two groups
    of a packed step read, a copy on the way to the program's result) then
    takes the name its operands carry where those that carry one agree:
    ``attn_in/fusion.12->``."""
    def neighbours(of_users: bool) -> bool:
        near: Dict[Tuple[str, str], List[Instruction]] = {}
        by_op = {(ins.computation, ins.op): ins for ins in instrs}
        for ins in instrs:
            for operand in ins.operands:
                if of_users:
                    near.setdefault((ins.computation, operand), []).append(ins)
                elif (ins.computation, operand) in by_op:
                    near.setdefault((ins.computation, ins.op), []).append(
                        by_op[ins.computation, operand])
        changed = False
        for i, ins in enumerate(instrs):
            if ins.scope:
                continue
            of = near.get((ins.computation, ins.op), ())
            if not of_users:
                of = [o for o in of if o.scope]
            names = {o.scope.split("/", 1)[0] for o in of}
            if len(names) == 1 and "" not in names:
                instrs[i] = ins._replace(scope=(
                    f"{names.pop()}/<-{of[0].op}" if of_users
                    else f"{names.pop()}/{of[0].op}->"))
                changed = True
        return changed

    for _ in range(steps):
        if not neighbours(of_users=True):
            break
    neighbours(of_users=False)
    return instrs


def table(text: str) -> List[List[str]]:
    """Rows ``[op, result, scope]`` of an optimized module's text, one an
    instruction that runs as a device operation (:data:`MOVES_NOTHING`
    left out). ``"<op> <result>"``, or ``op`` alone where ``result`` is
    empty, is the operation's name in a device trace."""
    return [[ins.op, ins.result, ins.scope]
            for ins in resolve(instructions(text))
            if ins.opcode not in MOVES_NOTHING]
