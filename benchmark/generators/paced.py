"""Open loop at a fixed rate: request i is due at the running sum of the
block-permuted gaps over the rate, whether or not earlier ones finished."""

from __future__ import annotations

from typing import Any, Dict, List, Optional


class Generator:
    def __init__(self, params: Dict[str, Any], slots: int):
        self.rate = float(params["rate_per_s"])
        if self.rate <= 0:
            raise ValueError("rate_per_s must be > 0")
        self._next: Optional[float] = None

    def due(self, t: float, outstanding: int, gap_of_next) -> List[float]:
        out: List[float] = []
        if self._next is None:
            self._next = gap_of_next() / self.rate
        while self._next <= t:
            out.append(self._next)
            self._next += gap_of_next() / self.rate
        return out

    def next_due(self) -> Optional[float]:
        return self._next
