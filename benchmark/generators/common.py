"""Draws shared by the generators: lengths and prompts from a seed."""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def draw_length(rng: random.Random, spec: dict, scale: float = 1.0) -> int:
    """One length drawn from ``spec``: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
    ``scale`` shrinks it for a rehearsal at a toy model's context."""
    if spec["dist"] == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = min(max(x, spec["min"]), spec["max"])
    return max(2, int(round(x * scale)))


def draw_tokens(rng: random.Random, n: int, vocab: int) -> list:
    """``n`` ids in [1, vocab)."""
    return [rng.randrange(1, vocab) for _ in range(n)]


def unique_prompt(rng: random.Random, index: int, n: int, vocab: int) -> list:
    """A prompt whose first id is set by ``index``: no two prompts of a
    run share even their first page, so the prefix cache cannot hit."""
    return [1 + index % (vocab - 1)] + draw_tokens(rng, n - 1, vocab)


def quantile(spec: dict, u: float) -> float:
    """The ``u``-quantile of the length distribution ``spec``."""
    if spec["dist"] == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return min(max(x, spec["min"]), spec["max"])


def stratified_lengths(rng: random.Random, spec: dict, n: int,
                       scale: float = 1.0) -> list:
    """``n`` lengths that are the distribution's own quantiles at
    (i + 0.5) / n, in an order drawn from ``rng``: every seed gives the
    same multiset of lengths, so the same work, and only their order and
    pairing differ.  A run's tails then move with the system and not with
    the luck of the draw."""
    out = [max(2, int(round(quantile(spec, (i + 0.5) / n) * scale)))
           for i in range(n)]
    rng.shuffle(out)
    return out


def fixed_count_times(rng: random.Random, rate: float, start: float,
                      end: float) -> list:
    """Arrival instants of a Poisson process on [start, end) given that
    it had its expected count: that many independent uniform instants."""
    n = int(round(rate * (end - start)))
    return sorted(rng.uniform(start, end) for _ in range(n))


def jittered_times(rng: random.Random, rate: float, start: float,
                   end: float) -> list:
    """One arrival in every interval of length 1 / rate, at a uniform
    instant inside it: locally random, but every stretch of the run sees
    the same number of arrivals whatever the seed."""
    gap = 1.0 / rate
    n = int(round((end - start) / gap))
    return [start + (i + rng.random()) * gap for i in range(n)]
