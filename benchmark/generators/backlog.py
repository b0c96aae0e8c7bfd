"""Closed backlog: the queue never runs dry, so tokens per second is the
engine's capacity and not the arrival draw."""

from __future__ import annotations

from typing import Any, Dict, List, Optional


class Generator:
    def __init__(self, params: Dict[str, Any], slots: int):
        # In flight plus at least ``backlog_x_slots`` x slots waiting.
        self.target = slots + int(params["backlog_x_slots"]) * slots

    def due(self, t: float, outstanding: int,
            gap_of_next) -> List[float]:
        """Due times (seconds since the window opened) of the requests to
        release at ``t``; ``outstanding`` counts released, unfinished ones."""
        return [t] * max(self.target - outstanding, 0)

    def next_due(self) -> Optional[float]:
        return None
