"""Open loop: requests arrive on a fixed schedule, whatever the server does.

Parameters: `rate_rps`, the offered load in requests per second (an
absolute rate, never calibrated from the server), and the shape pool of
`_dists`. Arrival gaps are exponential (Poisson arrivals). Each request is
timed from its scheduled arrival, so a stall that delays the loop counts
against every request due meanwhile; `late_s` records by how much the
loop submitted each request after it was due.
"""
from __future__ import annotations

from bench.traffic._dists import Send, Tokens, shape_pool


class Generator:
    def __init__(self, params: dict, run_seed: int, vocab: int):
        self.shapes = shape_pool(params, run_seed)
        self.tokens = Tokens(run_seed, vocab)
        self.next_rid = 0
        self.t_next = None
        self.late_s: list[float] = []

    def start(self, now: float):
        self.t_next = now + self.shapes[0].gap_s

    def _shape(self, rid):
        return self.shapes[rid % len(self.shapes)]

    def due(self, now: float) -> list[Send]:
        out = []
        while self.t_next is not None and self.t_next <= now:
            rid = self.next_rid
            sh = self._shape(rid)
            out.append(Send(rid, self.tokens.prompt(rid, sh.prompt_len),
                            sh.max_new, sh.temperature, self.tokens.seed(rid),
                            self.t_next))
            self.late_s.append(now - self.t_next)
            self.next_rid += 1
            self.t_next += self._shape(self.next_rid).gap_s
        return out

    def next_due(self) -> float | None:
        return self.t_next

    def completed(self, rid: int, now: float):
        """Arrivals do not wait for completions."""

    def stop(self):
        self.t_next = None
