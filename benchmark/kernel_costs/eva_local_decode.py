"""Operations and bytes an EVA layer's exact-rows call needs for one call
(one layer of one tick), from its shapes. What the algorithm needs, not what
the kernel moves: of each live slot the keys and values its row sees exactly,
those of its own ALIGNED window up to itself (``t - w0 + 1`` rows, ``w0 = (t
// window) * window``: 1 to ``window`` rows, not the last ``window``), once;
its queries in, its output and log-sum-exp out. The kernel streams whole grid
steps (the step that holds the row is read to its end): that is its own
cost."""

from __future__ import annotations

from typing import Dict, Sequence


def seen(context: int, q_rows: int, window: int) -> int:
    """Exact rows the calls of a slot that holds ``context`` tokens after
    this tick make visible: from its first row's window start to its last
    row."""
    first = context - q_rows
    return context - first // window * window


def cost(*, contexts: Sequence[int], q_rows: int, heads: int, kv_heads: int,
         head: int, window: int, chunk: int,
         dtype_bytes: int) -> Dict[str, float]:
    """``contexts``: tokens each live slot attends from (its last row sits
    at ``context - 1``); ``q_rows``: query rows per slot (1 in a decode
    tick); ``window``: the aligned window; ``chunk`` is the summary call's
    and unused here."""
    del chunk
    rows = [seen(c, q_rows, window) for c in contexts]
    kv = sum(2 * r * kv_heads * head * dtype_bytes for r in rows)
    # Queries in and output out in the served type, one float32 a row and
    # head of log-sum-exp out.
    qo = len(contexts) * q_rows * heads * (2 * head * dtype_bytes + 4)
    flops = sum(4 * q_rows * heads * head * r for r in rows)
    return {"bytes": float(kv + qo), "flops": float(flops)}
