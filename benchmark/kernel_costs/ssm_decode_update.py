"""Operations and bytes a state-space layer's decode step needs for one call
(one layer of one tick), from its shapes. What the algorithm needs: each live
slot's state of the layer read once and written once (``heads x head x state``
values of ``state_bytes``), its ``x`` and ``y`` rows (``heads x head``), its
``B`` and ``C`` rows (``groups x state``) and its step sizes (``heads``) in
float32; 6 operations a state element (the decay's multiply, the outer
product's multiply, the add; the multiply by ``C`` and its add; one more for
``dt x``, counted on the element for simplicity). A slot with no row costs
nothing: the kernel does not visit it."""

from __future__ import annotations

from typing import Dict, Sequence


def cost(*, contexts: Sequence[int], q_rows: int, heads: int, head: int,
         state: int, groups: int, state_bytes: int) -> Dict[str, float]:
    """``contexts``: one entry a live slot (its length is not read: the
    state does not grow); ``q_rows``: rows a slot (1 in a decode tick)."""
    del q_rows
    elements = heads * head * state
    rows = (2 * heads * head + 2 * groups * state + heads) * 4
    live = len(contexts)
    return {"bytes": float(live * (2 * elements * state_bytes + rows)),
            "flops": float(live * 6 * elements)}
