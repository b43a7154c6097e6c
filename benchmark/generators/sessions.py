"""Multi-turn sessions over a few shared system prompts.

Sessions start at ``rate_per_s`` TURNS a second (so sessions at
``rate_per_s / turns``), one in every interval of that length at a random
instant inside it (plain Poisson starts made the number of turns in a 50 s
window swing from 62 to 95 between seeds), take one of ``system_prompts``
in Zipf proportion, and run ``turns`` turns.  A turn's prompt is the system prompt, the history
(earlier user messages and the answers the server really gave) and a new
user message; the next turn is due ``think_s`` after the answer ended.
The arrival process is stationary from the first second: a session that
would have started before the run joins at the turn it would have reached
(``nominal_turn_s`` a turn), with made-up answers in its history.  Nothing
is put into the server's cache beforehand: reuse is earned by the traffic.
"""

from __future__ import annotations

import random

from client import Request
from generators.common import draw_length, draw_tokens, jittered_times


class Plan:
    def __init__(self, params: dict, seed: int, vocab: int, scale: float):
        p = self.params = params
        self.seed, self.vocab, self.scale = seed, vocab, scale
        rng = random.Random(f"sessions/{seed}")
        n_sys = int(p["system_prompts"])
        sys_len = max(2, int(round(p["system_prompt_tokens"] * scale)))
        # the system prompts belong to the deployment, not to the run
        sys_rng = random.Random(f"sessions/system/{p.get('system_seed', 0)}")
        self.system = [[1 + s % (vocab - 1)]
                       + draw_tokens(sys_rng, sys_len - 1, vocab)
                       for s in range(n_sys)]
        weights = [1.0 / (k + 1) ** p["zipf_s"] for k in range(n_sys)]
        turns, nominal = int(p["turns"]), float(p["nominal_turn_s"])
        starts = jittered_times(rng, p["rate_per_s"] / turns,
                                -turns * nominal, p["horizon_s"])
        # system prompts in Zipf proportion (largest remainders), dealt in
        # an order from the seed: the same mix of prompts every run
        share = [w / sum(weights) * len(starts) for w in weights]
        deal = [k for k, x in enumerate(share) for _ in range(int(x))]
        rest = sorted(range(n_sys), key=lambda k: share[k] - int(share[k]),
                      reverse=True)
        deal += rest[:len(starts) - len(deal)]
        rng.shuffle(deal)
        self.first = []
        self.state = {}
        for sid, a in enumerate(starts):
            srng = random.Random(f"sessions/{seed}/{sid}")
            which = deal[sid]
            done = 0 if a >= 0 else int(-a // nominal) + 1
            if done >= turns:
                continue
            history = list(self.system[which])
            for _ in range(done):           # turns "already had"
                history += draw_tokens(
                    srng, draw_length(srng, p["user_tokens"], scale), vocab)
                history += draw_tokens(
                    srng, draw_length(srng, p["answer_tokens"], scale), vocab)
            self.state[sid] = {"rng": srng, "history": history, "turn": done}
            self.first.append(self._turn(sid, max(0.0, a + done * nominal)))

    def _turn(self, sid: int, due: float) -> Request:
        st, p = self.state[sid], self.params
        st["history"] = st["history"] + draw_tokens(
            st["rng"], draw_length(st["rng"], p["user_tokens"], self.scale),
            self.vocab)
        m = draw_length(st["rng"], p["answer_tokens"], self.scale)
        return Request(due, list(st["history"]), m, key=sid)

    def initial(self) -> list:
        return list(self.first)

    def on_done(self, request, tokens, now_s) -> list:
        st = self.state[request.key]
        st["turn"] += 1
        if st["turn"] >= int(self.params["turns"]):
            return []
        st["history"] = st["history"] + list(tokens)
        lo, hi = self.params["think_s"]
        return [self._turn(request.key, now_s + st["rng"].uniform(lo, hi))]


def make(params, seed, vocab, scale=1.0):
    return Plan(params, seed, vocab, scale)
