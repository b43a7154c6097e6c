"""Benchmark client: output tokens that arrived inside the window, of
well-formed requests, over the window's length, in the traced run.  Below
saturation it follows the offered rate; in the saturated cell it is the
capacity, recorded here because its spread between runs does not fit a
bound yet (PERF.md section 6)."""
from arith import tokens_in_window


def read(ctx):
    t_open, t_close = ctx["window"]
    return tokens_in_window(ctx["well_formed"], t_open, t_close) \
        / (t_close - t_open)
