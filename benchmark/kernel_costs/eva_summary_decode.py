"""Operations and bytes an EVA layer's summary-rows call needs for one call
(one layer of one tick), from its shapes. What the algorithm needs: of each
live slot the summary keys and values its row sees, one row a chunk of every
window closed before its own (``w0 / chunk`` rows, ``w0 = (t // window) *
window``), once; its queries in, its output and log-sum-exp out. A slot with
no closed window behind it costs nothing, its queries and outputs neither:
the program makes no such call for a group none of whose rows has one, and a
slot that rides along in another's call is the kernel's own cost."""

from __future__ import annotations

from typing import Dict, Sequence


def seen(context: int, window: int, chunk: int) -> int:
    """Summary rows the last row of a slot that holds ``context`` tokens
    after this tick sees."""
    return (context - 1) // window * (window // chunk)


def cost(*, contexts: Sequence[int], q_rows: int, heads: int, kv_heads: int,
         head: int, window: int, chunk: int,
         dtype_bytes: int) -> Dict[str, float]:
    """``contexts``: tokens each live slot attends from (its last row sits
    at ``context - 1``); ``q_rows``: query rows per slot (1 in a decode
    tick)."""
    rows = [seen(c, window, chunk) for c in contexts]
    kv = sum(2 * r * kv_heads * head * dtype_bytes for r in rows)
    qo = sum(1 for r in rows if r) * q_rows * heads * (
        2 * head * dtype_bytes + 4)
    flops = sum(4 * q_rows * heads * head * r for r in rows)
    return {"bytes": float(kv + qo), "flops": float(flops)}
