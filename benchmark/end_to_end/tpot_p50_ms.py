"""Median over requests of time per output token.  All rows of the batch
advance in lock-step, so this is the clean reading of the step time as a
client sees it; it needs no minimum count and is defined in a closed loop
too."""
from arith import median, tpot_ms


def read(ctx):
    xs = [x for x in map(tpot_ms, ctx["ok"]) if x is not None]
    return median(xs) if xs else None
