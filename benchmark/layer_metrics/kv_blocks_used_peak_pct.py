"""KV manager: the fullest the page pool was, from ``/stats`` polled once
a second during the traced part of the window."""


def read(ctx):
    polls = [p["kvcache"] for p in ctx["marks"].get("polls", [])
             if "kvcache" in p]
    if not polls:
        return None
    return 100.0 * max(p["blocks_used"] / p["blocks_total"] for p in polls)
