"""Model step of a looped model: median device milliseconds of ONE pass of
the layer stack in a decode step.  Over the executions that carried no
prefill segment (``segments == 0`` in the dispatch record each was joined
to, ``dispatch_join.py``): device time / (``steps`` x ``ut_steps``), the
passes from the program's ``/stats.loop``.  It holds a pass's share of
the head, the sampling and the loop's own overhead, which is what a token
pays.  ``None`` for a one-pass model (no ``loop`` section) and under 5
such executions."""
from arith import median
from dispatch_join import join


def read(ctx):
    passes = (ctx["stats_close"].get("loop") or {}).get("ut_steps")
    if not passes:
        return None
    per_pass = [d / (r["steps"] * passes) for _, d, r in join(ctx)["pairs"]
                if r["segments"] == 0 and r["steps"] > 0]
    return median(per_pass) / 1e6 if len(per_pass) >= 5 else None
