"""Operations and bytes the paged decode attention needs for one call (one
layer of one tick), from its shapes. What the algorithm needs, not what the
kernel moves: each slot's keys and values once, its queries in, its output
out. The grid's steps over empty table entries are the kernel's own cost."""

from __future__ import annotations

from typing import Dict, Sequence


def cost(*, contexts: Sequence[int], q_rows: int, heads: int, kv_heads: int,
         head: int, dtype_bytes: int) -> Dict[str, float]:
    """``contexts``: tokens each live slot attends to; ``q_rows``: query
    rows per slot (1 in a decode tick)."""
    kv = sum(2 * c * kv_heads * head * dtype_bytes for c in contexts)
    qo = len(contexts) * 2 * q_rows * heads * head * dtype_bytes
    flops = sum(4 * q_rows * heads * head * c for c in contexts)
    return {"bytes": float(kv + qo), "flops": float(flops)}
