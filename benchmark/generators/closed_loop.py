"""Closed loop: ``clients`` callers that each wait for a reply and then
send their next request at once.  Prompts unique.  The n-th request sent
(over all clients) takes the length at quantile frac(offset + n * phi) of
the mix's distribution (phi the golden ratio, offset from the seed): any
stretch of a few dozen requests carries the same work whatever the seed."""

from __future__ import annotations

import random

from client import Request
from generators.common import quantile, unique_prompt

PHI = 0.6180339887498949


class Plan:
    def __init__(self, params: dict, seed: int, vocab: int, scale: float):
        self.params, self.seed = params, seed
        self.vocab, self.scale = vocab, scale
        self.clients = int(params["clients"])
        rng = random.Random(f"closed_loop/{seed}")
        self.offsets = (rng.random(), rng.random())
        self.sent = 0

    def _length(self, spec: dict, offset: float) -> int:
        u = (offset + self.sent * PHI) % 1.0
        return max(2, int(round(quantile(spec, u) * self.scale)))

    def _next(self, client: int, due: float) -> Request:
        rng = random.Random(f"closed_loop/{self.seed}/{self.sent}")
        n = self._length(self.params["prompt_tokens"], self.offsets[0])
        m = self._length(self.params["output_tokens"], self.offsets[1])
        req = Request(due, unique_prompt(rng, self.sent, n, self.vocab), m,
                      key=client)
        self.sent += 1
        return req

    def initial(self) -> list:
        gap = self.params.get("stagger_s", 0.05)
        return [self._next(c, c * gap) for c in range(self.clients)]

    def on_done(self, request, tokens, now_s) -> list:
        return [self._next(request.key, now_s)]


def make(params, seed, vocab, scale=1.0):
    return Plan(params, seed, vocab, scale)
