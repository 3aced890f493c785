"""Closed loop: `clients` callers, each sending its next request the moment
its previous one completes (no think time). All clients send at the start.
A request is timed from the moment its client sent it."""
from __future__ import annotations

from bench.traffic._dists import Send, Tokens, shape_pool


class Generator:
    def __init__(self, params: dict, run_seed: int, vocab: int):
        self.shapes = shape_pool(params, run_seed)
        self.tokens = Tokens(run_seed, vocab)
        self.clients = int(params["clients"])
        self.next_rid = 0
        self.ready: list[float] = []
        self.late_s: list[float] = []
        self.stopped = False

    def start(self, now: float):
        self.ready = [now] * self.clients

    def due(self, now: float) -> list[Send]:
        if self.stopped:
            return []
        out = []
        for t in self.ready:
            rid = self.next_rid
            sh = self.shapes[rid % len(self.shapes)]
            out.append(Send(rid, self.tokens.prompt(rid, sh.prompt_len),
                            sh.max_new, sh.temperature, self.tokens.seed(rid),
                            t))
            self.late_s.append(now - t)
            self.next_rid += 1
        self.ready = []
        return out

    def next_due(self) -> float | None:
        return min(self.ready) if self.ready else None

    def completed(self, rid: int, now: float):
        self.ready.append(now)

    def stop(self):
        self.stopped = True
