"""Benchmark client: median time from the due instant to the first token
in the traced run.  Recorded for every open-loop cell, also where the
spread between runs is too wide for it to be a bounded end-to-end metric."""
from arith import median, ttft_ms


def read(ctx):
    if ctx["mix"]["loop"] != "open" or not ctx["ok"]:
        return None
    return median([ttft_ms(r) for r in ctx["ok"]])
