"""Operations and bytes a sliding-window layer's paged decode attention needs
for one call (one window layer of one tick), from its shapes. What the
algorithm needs, not what the kernel moves: of each live slot the keys and
values a row can see, ``min(context, window)`` tokens, once, its queries in,
its output out. The kernel streams whole grid steps (several blocks of which
the window covers a part): that is its own cost, and why its share of this
roofline reads low."""

from __future__ import annotations

from typing import Dict, Sequence


def cost(*, contexts: Sequence[int], q_rows: int, heads: int, kv_heads: int,
         head: int, window: int, dtype_bytes: int) -> Dict[str, float]:
    """``contexts``: tokens each live slot holds; ``q_rows``: query rows per
    slot (1 in a decode tick); ``window``: positions a row sees."""
    seen = [min(c, window + q_rows - 1) for c in contexts]
    kv = sum(2 * c * kv_heads * head * dtype_bytes for c in seen)
    qo = len(contexts) * 2 * q_rows * heads * head * dtype_bytes
    flops = sum(4 * q_rows * heads * head * min(c, window) for c in contexts)
    return {"bytes": float(kv + qo), "flops": float(flops)}
