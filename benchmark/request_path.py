"""The request's path outside the engine, by the program's own word.

``/stats.request_path`` (``telemetry.tracing.RequestPath``, owned by the
replica's HTTP server) is the record beside ``dispatch_trace`` and on its
clock, the replica's ``time.monotonic()``, which ``dispatch_join.py``
places on the trace's:

* **ingress**, a request: ``gateway_s`` (the seconds the gateway says it
  held the request before it forwarded it), ``read_parse_s`` (the
  handler's entry to the body read and decoded), ``submit_s`` (to the
  engine's own submit stamp), ``ingress_count``, and a ring of the last
  256 requests as rows ``[t_gateway, t_accept, t_parsed, t_submit,
  prompt_tokens, streamed]``;
* **egress**, a hand-off of the scheduler's to one stream: ``egress_s``
  (the hand-off's stamp to the return of the write of its last line),
  ``egress_max_s``, ``handoffs``, ``tokens``, ``lines``, ``writes``,
  ``bytes``, and ``handler_cpu_s``, the handler threads' own CPU seconds.

The counters advance at every hand-off, so their growth between the
window's two snapshots is exact to one hand-off a stream.  A program
without the record (the parent of the PR that brought it) gives ``None``
everywhere here, and the readers return ``None``; on a cell alone, with
no run behind it, asking for the run's ``/stats`` raises.
"""

from __future__ import annotations

from dispatch_join import snapshots
from layer_metrics import delta


def per(ctx, keys, count, scale: float = 1.0, section: str = "request_path"):
    """``scale`` x the growth of the sum of ``request_path[key]`` over the
    growth of ``count`` (a counter of ``section``), the window's edges;
    ``None`` where the program has no record or nothing was counted."""
    parts = [delta(ctx, "request_path", key) for key in keys]
    n = delta(ctx, section, count)
    return scale * sum(parts) / n if None not in parts and n else None


def rows(ctx):
    """The union of ``request_path.recent`` over the run's snapshots as
    dicts, oldest first; ``None`` where no snapshot has the record."""
    ctx["stats_close"]              # a cell alone has no run to ask
    seen, found = {}, False
    for snap in snapshots(ctx):
        rp = snap.get("request_path")
        if rp and rp.get("fields"):
            found = True
            for row in rp["recent"]:
                seen[tuple(row[:4])] = dict(zip(rp["fields"], row))
    return [seen[k] for k in sorted(seen, key=lambda k: k[1])] if found else None


def merged(intervals) -> list:
    """The union of ``(a, b)`` intervals as disjoint ones, in order."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def seconds_in_all(first, second, w0: float, w1: float) -> float:
    """Seconds that lie in an interval of ``first`` AND in one of
    ``second`` AND in ``[w0, w1]``."""
    total = 0.0
    second = merged(second)
    for a, b in merged(first):
        for c, d in second:
            total += max(0.0, min(b, d, w1) - max(a, c, w0))
    return total
