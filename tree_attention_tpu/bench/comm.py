"""Communication accounting: collectives and payload bytes from compiled HLO.

The tree-vs-ring north star (BASELINE.json: ≥2× ring tokens/sec/chip at 1M
context) hinges on communication the emulated CPU mesh cannot *price* —
its collectives are memcpys, so wall-clock ratios understate the tree merge
(VERDICT r3 missing item 2). What the emulated mesh CAN do is **count**:
the compiled SPMD module lists every collective XLA will execute, with
exact payload shapes. This module parses that — turning the north-star
claim into measured collective counts and bytes-on-wire per step, which an
analytic ICI model (BASELINE.md) can then price for real hardware.

Counting from the *optimized* HLO, not the source program, means the
numbers include whatever XLA fused, deduplicated, or rewrote — e.g. the
tree merge's two psum operands riding one fused all-reduce.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Tuple

import jax

# Collective HLO opcodes and how their listed (per-participant) output size
# relates to bytes actually crossing the wire per device:
#
# - collective-permute: each device sends exactly its output bytes.
# - all-reduce: bandwidth-optimal lowering (reduce-scatter + all-gather)
#   moves 2·(N−1)/N × payload per device; latency-optimal tree lowerings
#   move payload × log N. We record the payload and let the pricing model
#   pick the lowering (the count and payload are the measurement).
# - all-gather: output is the gathered (N×) tensor; each device receives
#   (N−1)/N of it and sends its 1/N shard N−1 times (ring) or log N times.
# - reduce-scatter: dual of all-gather; output is the 1/N shard.
# - all-to-all: each device sends (N−1)/N of its input.
_COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# One typed array in an HLO shape string: `f32[1,16,1,128]` (layout braces
# and trailing annotations stripped before matching).
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _element_bytes(shape_str: str) -> List[Tuple[int, bool]]:
    """(bytes, has_dims) of each typed array in an HLO result type string
    (tuples like `(f32[8], f32[8,128])` yield one entry per element);
    ``has_dims`` distinguishes real arrays from dimensionless context
    scalars like the `u32[]` pair async-start ops append."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue  # token[] / opaque[] carry no payload
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((n * _DTYPE_BYTES[dtype], bool(dims)))
    return out


def _shape_bytes(shape_str: str, *, is_start: bool = False) -> int:
    """Payload bytes of one collective's result type.

    Sync form: a tuple result is a *fused* collective (e.g. the tree
    merge's two psum operands riding one all-reduce) — the payload is the
    sum. Async ``-start`` form: the tuple is
    ``((operands…), (results…), u32[] context…)`` — summing would
    double-count, and taking the max would overstate reduce-scatter, whose
    operand is the N×-larger tensor sitting beside the shard-sized result
    (ADVICE r4 item 1). So: drop the dimensionless context scalars and sum
    the second half of what remains — the results — which equals the sync
    form's payload for every collective opcode. An unexpected layout (odd
    element count) falls back to the max, which is exact for every opcode
    except reduce-scatter."""
    elems = _element_bytes(shape_str)
    if not elems:
        return 0
    if is_start and len(elems) > 1:
        arrays = [b for b, has_dims in elems if has_dims]
        if arrays and len(arrays) % 2 == 0:
            return sum(arrays[len(arrays) // 2:])
        return max(b for b, _ in elems)
    return sum(b for b, _ in elems)


# `%name = <result-type> <opcode>(`  — opcode may carry a -start suffix
# (async form; the matching -done is not a transfer and must not be
# double-counted).
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+("
    + "|".join(re.escape(op) for op in _COLLECTIVE_OPS)
    + r")(-start)?\("
)


def collective_stats(fn: Callable[..., Any], *args: Any) -> Dict[str, Any]:
    """Compile ``fn(*args)`` and count its collectives from the SPMD HLO.

    Returns ``{"ops": {opcode: {"count": n, "payload_bytes": b}, ...},
    "collective_count": total_ops, "payload_bytes_total": total_bytes,
    "has_loop": bool}`` where ``payload_bytes`` is the per-participant
    result size summed over ops of that opcode — the quantity the pricing
    model multiplies by the lowering's wire factor.

    ``has_loop=True`` flags a ``while`` op in the module: collectives
    inside a loop body execute per iteration but appear once in the text,
    so counts would be understated. The decode comparator's algorithms are
    loop-free by construction (the ring's hop chain is unrolled); callers
    measuring scan-based programs must multiply by trip count themselves.
    """
    return collectives_in_hlo(jax.jit(fn).lower(*args).compile().as_text())


def collectives_in_hlo(text: str) -> Dict[str, Any]:
    """:func:`collective_stats` over an already-compiled module's text
    (``compiled.as_text()``)."""
    ops: Dict[str, Dict[str, int]] = {}
    for line in text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        result_type, opcode = m.group(1), m.group(2)
        rec = ops.setdefault(opcode, {"count": 0, "payload_bytes": 0})
        rec["count"] += 1
        rec["payload_bytes"] += _shape_bytes(
            result_type, is_start=m.group(3) is not None
        )
    return {
        "ops": ops,
        "collective_count": sum(r["count"] for r in ops.values()),
        "payload_bytes_total": sum(r["payload_bytes"] for r in ops.values()),
        "has_loop": bool(re.search(r"\bwhile\(", text)),
    }


# A Pallas TPU kernel in optimized HLO: a custom call to Mosaic whose
# op_name metadata ends ".../<pallas_call name>/pallas_call" (the kernels in
# ops/ all pass ``name=``).
_PALLAS_CALL_RE = re.compile(
    r'custom_call_target="tpu_custom_call".*?op_name="[^"]*?([^/"]+)/pallas_call"'
)


def pallas_kernels(text: str) -> Dict[str, int]:
    """Pallas kernels compiled into a module, by name: ``{name: calls}``
    parsed from ``compiled.as_text()``. Empty means the program runs no
    Pallas kernel on the TPU (interpret mode and the reference paths leave
    no ``tpu_custom_call`` behind). A call inside a loop body (the layer
    scan) appears once."""
    found: Dict[str, int] = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = _PALLAS_CALL_RE.search(line)
        name = m.group(1) if m else "unnamed"
        found[name] = found.get(name, 0) + 1
    return found


def assert_loop_free(stats: Dict[str, Any], what: str) -> None:
    """Fail loudly when counts would be understated by a loop body."""
    if stats["has_loop"]:
        raise AssertionError(
            f"{what}: compiled module contains a while loop; collective "
            f"counts from HLO text would be understated — unroll the "
            f"communication loop or account for the trip count"
        )
