"""90th percentile over requests of time per output token."""
from arith import percentile, tpot_ms


def read(ctx):
    if ctx["mix"]["loop"] != "open":
        return None
    xs = [tpot_ms(r) for r in ctx["ok"]]
    return percentile([x for x in xs if x is not None], 90)
