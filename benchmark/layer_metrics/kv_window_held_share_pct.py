"""KV manager: what the window kind's pool held at its fullest, over what
the same requests would have held there with no window (a page for every
block of every token they hold), as the program's
``/stats.kvcache.kinds.window`` says (``pages_held_peak`` over
``pages_unwindowed_peak``; both peaks since the engine started).  Under
100 only if pages behind the window went back to the pool while their
requests ran; ``pages_returned`` counts them.  ``None`` from a program
without a pool a kind of block."""


def read(ctx):
    kind = (ctx["stats_close"].get("kvcache", {}).get("kinds", {})
            .get("window"))
    if not kind or not kind.get("pages_unwindowed_peak"):
        return None
    return 100.0 * kind["pages_held_peak"] / kind["pages_unwindowed_peak"]
