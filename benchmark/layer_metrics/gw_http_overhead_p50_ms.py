"""Gateway + HTTP: the client's median TTFT minus the replica's own
(``/stats.latency.ttft_p50_ms``, which starts at ``submit``, after the
gateway and the HTTP handler; its reservoir is the last 512 requests)."""
from arith import median, ttft_ms


def read(ctx):
    inner = ctx["stats_close"].get("latency", {}).get("ttft_p50_ms")
    if inner is None or not ctx["ok"]:
        return None
    return median([ttft_ms(r) for r in ctx["ok"]]) - inner
