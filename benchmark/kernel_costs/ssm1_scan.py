"""Operations and bytes a Mamba-1 layer's decode step needs for one call (one
layer of one tick), from its shapes. What the algorithm needs: each live
slot's state of the layer read once and written once (``channels x state``
values of ``state_bytes``), its ``x`` and ``dt`` rows in and its ``y`` row out
(``channels`` each) and its ``B`` and ``C`` rows (``state`` each) in float32;
6 operations a state element (the decay's multiply by ``A``, its ``exp``
counted as one, the multiply into the state, the outer product's multiply and
the add; the multiply by ``C`` with its add counted as one more). A slot with
no row costs nothing: the kernel does not visit it. The layer's ``A``
(``channels x state``, fetched once a launch) is the layer's weights' and is
not counted."""

from __future__ import annotations

from typing import Dict, Sequence


def cost(*, contexts: Sequence[int], q_rows: int, channels: int, state: int,
         state_bytes: int) -> Dict[str, float]:
    """``contexts``: one entry a live slot (its length is not read: the
    state does not grow); ``q_rows``: rows a slot (1 in a decode tick)."""
    del q_rows
    elements = channels * state
    rows = (3 * channels + 2 * state) * 4
    live = len(contexts)
    return {"bytes": float(live * (2 * elements * state_bytes + rows)),
            "flops": float(live * 6 * elements)}
