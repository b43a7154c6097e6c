"""Device: share of the traced window in which no operation ran, on the
chip that was busy least."""


def read(ctx):
    return ctx["trace"].get("idle_pct_worst") if ctx["trace"] else None
