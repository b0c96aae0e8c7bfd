"""Bytes and operations one decode tick needs for everything that is neither
the grouped expert product nor the paged decode attention: every weight
outside the experts once (the conv mixers' in-projection, taps and
out-projection; the attention layers' four projections; the leading dense
FFNs; the routers; the tied embedding as the head), each row's residual in and
out of every mixer and every feed-forward half, the conv layers' tails (two
rows read, one written, a row a layer), and the logits out in float32. What
the algorithm needs, not what sixty-odd small operations move between them."""

from __future__ import annotations

from typing import Dict, Sequence


def cost(*, contexts: Sequence[int], q_rows: int, hidden: int, heads: int,
         kv_heads: int, head: int, conv_layers: int, attention_layers: int,
         taps: int, dense_layers: int, dense_width: int, expert_layers: int,
         experts: int, vocab: int, dtype_bytes: int) -> Dict[str, float]:
    """``contexts``: one entry a live slot (a row each in a decode tick)."""
    rows = len(contexts) * q_rows
    conv = hidden * 3 * hidden + hidden * hidden + taps * hidden
    attn = 2 * hidden * heads * head + 2 * hidden * kv_heads * head
    dense = 3 * hidden * dense_width
    router = hidden * experts
    weights = (conv_layers * conv + attention_layers * attn
               + dense_layers * dense + expert_layers * router
               + vocab * hidden)
    layers = conv_layers + attention_layers
    residual = rows * hidden * 2 * 2 * layers       # in and out, two halves
    tails = rows * hidden * 3 * conv_layers
    moved = (weights + residual + tails) * dtype_bytes + rows * vocab * 4
    return {"bytes": float(moved), "flops": float(2 * rows * weights)}
