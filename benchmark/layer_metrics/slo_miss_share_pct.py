"""Benchmark client: share of the window's requests that missed a limit of
the mix (TTFT or TPOT; a failed request misses).  Near capacity this swings
with the smallest change, which is why it is recorded here and not bounded
as an end-to-end metric."""
from arith import tpot_ms, ttft_ms


def read(ctx):
    lim = ctx["mix"].get("limits")
    if not lim or not ctx["sample"]:
        return None
    met = sum(1 for r in ctx["ok"]
              if ttft_ms(r) <= lim["ttft_ms"]
              and (tpot_ms(r) or 0.0) <= lim["tpot_ms"])
    return 100.0 * (1.0 - met / len(ctx["sample"]))
