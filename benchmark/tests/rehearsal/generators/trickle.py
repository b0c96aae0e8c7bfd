"""A generator kind that only the rehearsal adds: one request every
``every_s`` seconds times its gap, to show that a kind is a file."""


class Generator:
    def __init__(self, params, slots):
        self.every = float(params["every_s"])
        self._next = None

    def due(self, t, outstanding, gap_of_next):
        if self._next is None:
            self._next = gap_of_next() * self.every
        out = []
        while self._next <= t:
            out.append(self._next)
            self._next += gap_of_next() * self.every
        return out

    def next_due(self):
        return self._next
