"""KV manager: the rows of the pool that the running requests held at
its fullest over the tokens those requests held then, as the program's
``/stats.kvcache.eva`` says (``rows_held_peak`` over
``tokens_held_peak``: a request of ``n`` tokens holds a summary a chunk of
every closed window and the open window's exact rows).  100 would be a
row a token; the cache of this family reads a quarter to a third at
6-10k contexts.  ``None`` from a program without a summarised cache."""


def read(ctx):
    eva = ctx["stats_close"].get("kvcache", {}).get("eva")
    if not eva or not eva.get("tokens_held_peak"):
        return None
    return 100.0 * eva["rows_held_peak"] / eva["tokens_held_peak"]
