"""The ``breakdown`` of a traced run: the device operations that took
most time, under the names the trace prints, and the longest idle gaps,
each labelled from the client's own timeline."""

from __future__ import annotations


def in_flight_at(records, t: float) -> int:
    return sum(1 for r in records if r.sent and r.sent <= t < r.end)


def build(ctx) -> dict:
    tr, marks = ctx["trace"], ctx["marks"]
    ops = [[name, secs] for name, secs in tr["op_self_s"][:10]]
    # the trace counts nanoseconds from the profiler's start; the replica
    # stamped time.monotonic() (the client's clock too) around that call
    base = marks["trace_started"]["running"]["monotonic"]
    gaps = []
    for start_ns, secs in tr["longest_gaps"][:5]:
        mid = base + start_ns / 1e9 + secs / 2
        label = ("requests_in_flight" if in_flight_at(ctx["records"], mid)
                 else "no_request_in_flight")
        gaps.append([label, secs])
    return {"device_ops": ops, "idle_gaps": gaps}
