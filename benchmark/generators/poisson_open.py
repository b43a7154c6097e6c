"""Open loop: independent users.  Poisson arrivals at ``rate_per_s``, every
prompt unique from its first token.  The work is fixed by the mix and the
rate, not by the seed: the ramp and the window each get the expected count
of a Poisson process (arrival instants are then independent and uniform),
and the lengths are the distribution's own quantiles; the seed sets the
instants, the order of the lengths and the token ids."""

from __future__ import annotations

import random

from client import Request
from generators.common import (fixed_count_times, stratified_lengths,
                               unique_prompt)


class Plan:
    def __init__(self, params: dict, seed: int, vocab: int, scale: float):
        rng = random.Random(f"poisson_open/{seed}")
        ramp, end = float(params["ramp_s"]), float(params["horizon_s"])
        rate = params["rate_per_s"]
        self.requests = []
        for lo, hi in ((0.0, ramp), (ramp, end)):
            times = fixed_count_times(rng, rate, lo, hi)
            prompts = stratified_lengths(rng, params["prompt_tokens"],
                                         len(times), scale)
            outputs = stratified_lengths(rng, params["output_tokens"],
                                         len(times), scale)
            for t, n, m in zip(times, prompts, outputs):
                i = len(self.requests)
                self.requests.append(
                    Request(t, unique_prompt(rng, i, n, vocab), m, key=i))

    def initial(self) -> list:
        return list(self.requests)

    def on_done(self, request, tokens, now_s) -> list:
        return []


def make(params, seed, vocab, scale=1.0):
    return Plan(params, seed, vocab, scale)
