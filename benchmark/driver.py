"""Feed the engine through its ``RequestSource`` seam and stamp every token.

One thread: the engine's tick loop calls :meth:`WindowSource.poll` once a
tick, and the source releases what is due, opens the window once the
requests caught mid-life have their first token, and closes it ``seconds``
later by a drain and a cancel of what is still in flight. Every token is
stamped in the request's ``on_token`` callback with ``time.monotonic()``.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

from benchmark import grid

from tree_attention_tpu.serving.engine import Request, RequestSource


@dataclasses.dataclass
class Rec:
    """What the benchmark knows of one request, all on the host's clock."""

    uid: int
    prompt: Any                      # np.ndarray of ids
    output: int                      # tokens asked for
    midlife: bool
    due: float                       # absolute, time.monotonic()
    released: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    outcome: Optional[str] = None
    queue_wait_s: Optional[float] = None


class WindowSource(RequestSource):
    def __init__(self, *, server, generator, traffic: Dict[str, Any],
                 seed: int, vocab: int, seconds: float, midlife: int,
                 midlife_multiple: int,
                 on_tick: Optional[Callable[[float], None]] = None,
                 clock: Callable[[], float] = time.monotonic):
        self._server = server
        self._gen = generator
        self._shapes = grid.shapes(traffic, seed)
        self._scheduled: collections.deque = collections.deque()
        self._seed, self._vocab = seed, vocab
        self._seconds = float(seconds)
        self._n_midlife, self._multiple = midlife, midlife_multiple
        self._on_tick = on_tick or (lambda now: None)
        self._clock = clock
        self.recs: List[Rec] = []
        self.phase = "new"           # new -> prefill -> window -> closed
        self.t_open: Optional[float] = None
        self.t_end: Optional[float] = None
        self.outstanding_min: Optional[int] = None  # after a release
        self.waiting_at_end: Optional[int] = None
        self._unfinished = 0
        # (wall, process CPU) at every poll of the window: a tick that takes
        # seconds shows here with what the process did meanwhile.
        self.polls: List[tuple] = []

    # -- requests ---------------------------------------------------------

    def _gap_of_next(self) -> float:
        s = next(self._shapes)
        self._scheduled.append(s)
        return s.gap

    def _take_shape(self) -> grid.Shape:
        if self._scheduled:
            return self._scheduled.popleft()
        return next(self._shapes)

    def _request(self, shape: grid.Shape, due: float, now: float,
                 midlife: bool) -> Request:
        ids = grid.token_ids(self._seed, shape.index, shape.prompt,
                             self._vocab)
        rec = Rec(uid=shape.index, prompt=ids, output=shape.output,
                  midlife=midlife, due=due, released=now)
        self.recs.append(rec)
        self._unfinished += 1
        clock = self._clock

        def on_token(tok: int, rec: Rec = rec) -> None:
            rec.stamps.append(clock())
            rec.tokens.append(int(tok))

        def on_finish(result, rec: Rec = rec) -> None:
            rec.finished = clock()
            rec.outcome = result.outcome
            rec.queue_wait_s = result.queue_wait_s
            self._unfinished -= 1

        return Request(
            uid=shape.index, prompt=ids.tolist(),
            max_new_tokens=shape.output, temperature=0.0,
            on_token=on_token, on_finish=on_finish, visible_at=due,
        )

    def _waiting(self) -> int:
        """Released requests with no token yet: queued or still prefilling."""
        return sum(1 for r in self.recs if r.finished is None and not r.stamps)

    # -- the seam ---------------------------------------------------------

    def poll(self, tick: int) -> List[Request]:
        now = self._clock()
        if self.phase == "new":
            self.phase = "prefill"
            out = []
            for k in range(self._n_midlife):
                shape = grid.midlife(next(self._shapes), k, self._n_midlife,
                                     self._multiple)
                out.append(self._request(shape, now, now, midlife=True))
            if out:
                return out
        if self.phase == "prefill":
            if any(not r.stamps for r in self.recs):
                return []
            self.phase = "window"
            self.t_open, self.t_end = now, now + self._seconds
        if self.phase != "window":
            return []
        if now >= self.t_end:
            self._close(now)
            return []
        self._on_tick(now)
        self.polls.append((now, time.process_time()))
        t = now - self.t_open
        dues = self._gen.due(t, self._unfinished, self._gap_of_next)
        out = [self._request(self._take_shape(), self.t_open + d, now,
                             midlife=False) for d in dues]
        # Released and unfinished, less the slots, is what waits at least:
        # a saturated cell shows here that its backlog never ran dry.
        if self.outstanding_min is None \
                or self._unfinished < self.outstanding_min:
            self.outstanding_min = self._unfinished
        return out

    def _close(self, now: float) -> None:
        self.phase = "closed"
        self.waiting_at_end = self._waiting()
        # What the window's end cuts is the benchmark's doing, not a
        # failure of the system: drain sheds the queue, cancel retires
        # what is in flight.
        self._server.request_drain()
        for r in self.recs:
            if r.finished is None:
                self._server.cancel(r.uid)

    def wait(self, timeout: float) -> bool:
        nxt = self._gen.next_due() if self.phase == "window" else None
        if nxt is not None:
            timeout = min(timeout,
                          max(self.t_open + nxt - self._clock(), 0.0))
        if timeout > 0:
            time.sleep(timeout)
        return True

    def close(self) -> None:
        self.phase = "closed"

    @property
    def exhausted(self) -> bool:
        return self.phase == "closed"
