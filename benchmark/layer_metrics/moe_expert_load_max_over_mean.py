"""Expert layer: how uneven the routing was over the window.  The rows
each expert got (``/stats.moe.expert_rows``, summed over layers and
executions), fullest expert over the mean: 1.0 is uniform; the grouped
matmul's longest group, and under expert parallelism the slowest rank,
grow with it.  ``None`` for a model without experts."""


def read(ctx):
    try:
        a = ctx["stats_open"]["moe"]["expert_rows"]
        b = ctx["stats_close"]["moe"]["expert_rows"]
    except (KeyError, TypeError):
        return None
    rows = [y - x for x, y in zip(a, b)]
    if not rows or sum(rows) <= 0:
        return None
    return max(rows) / (sum(rows) / len(rows))
