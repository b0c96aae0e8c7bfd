"""Operations and bytes the UNGATED grouped expert product needs for one
expert layer of one tick, experts in a latent: the TWO matrices (in, out; no
gate) of ``latent x width`` of every expert that got a row, once; each
row-expert pair's row in and out at the latent width and its hidden row out
and in between the two products; ``4 x latent x width`` operations a pair. A
tick's ``experts_touched`` and ``pairs`` are the flight record's (summed over
its expert layers: pass the sums and the result is the tick's).

``kernels.in_decode_ticks`` knows a tick's contexts, not its routing: called
with those alone this returns zeros, and the roofline's reader sums the cost
from the flight records itself (as ``moe_grouped_matmul``'s does, whose cost
counts a gated expert's three matrices of ``hidden x width``)."""

from __future__ import annotations

from typing import Dict, Optional, Sequence


def cost(*, latent: int, width: int, dtype_bytes: int,
         experts_held: int = 0,
         experts_touched: Optional[int] = None, pairs: Optional[int] = None,
         contexts: Optional[Sequence[int]] = None,
         q_rows: Optional[int] = None) -> Dict[str, float]:
    """``experts_held`` (a layer's, from the family's ``kernel_call``) is
    for the readers that want a share of them; the cost is of the touched."""
    del contexts, q_rows, experts_held
    if experts_touched is None or pairs is None:
        return {"bytes": 0.0, "flops": 0.0}
    weights = experts_touched * 2 * latent * width * dtype_bytes
    rows = pairs * 2 * (latent + width) * dtype_bytes
    return {"bytes": float(weights + rows),
            "flops": float(pairs * 4 * latent * width)}
