"""Operations and bytes the paged latent (MLA) decode attention needs for
one call (one layer of one tick), from its shapes. What the algorithm needs,
not what the kernel moves: each slot's cached rows ``[c_kv | k_rope]`` once
(they are keys and, in their first ``rank`` lanes, values: read once for
both), its absorbed queries in, its latent output out. A row lies in the pool
on whole groups of the chip's 128 lanes (576 values on 640: the program pads
it with zeros, the configuration file's ``assumed.row_padding``); the pad is
streamed with the row and counted in the bytes, never in the operations. The
grid's steps over empty table entries are the kernel's own cost."""

from __future__ import annotations

from typing import Dict, Sequence


def lanes(row: int) -> int:
    """``row`` values as they lie in the pool: rounded up to 128 lanes."""
    return -(-row // 128) * 128


def cost(*, contexts: Sequence[int], q_rows: int, heads: int, rank: int,
         row: int, dtype_bytes: int) -> Dict[str, float]:
    """``contexts``: tokens each live slot attends to; ``q_rows``: query
    rows per slot (1 in a decode tick); ``row``: values a cached token
    takes (``rank`` latent + the rotary key)."""
    width = lanes(row)
    kv = sum(c * width * dtype_bytes for c in contexts)
    qo = len(contexts) * q_rows * heads * (width + rank) * dtype_bytes
    # q.row over the whole row, p.row over its first ``rank`` lanes.
    flops = sum(2 * q_rows * heads * (row + rank) * c for c in contexts)
    return {"bytes": float(kv + qo), "flops": float(flops)}
