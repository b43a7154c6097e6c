"""The program's own account of the device's idle time.

``idle_host_attributed_pct`` books the scheduler's host phases other than
``wait`` against the idle seconds of the traced window.  Three more
things the program says of itself (``/stats.dispatch_trace``) close that
account, each an interval on the replica's ``time.monotonic()``, which
``dispatch_join.py`` places on the trace's clock:

* a **late read**: a record's ``late`` is 1 where the device had
  finished before the host came to read, so the device stood idle from
  the execution's end (the trace's) to the record's ``t_done``, inside
  the span the record calls ``wait``;
* an **empty engine**: ``idles`` holds ``[t0, t1]`` of every wait of the
  scheduler with nothing to do, a ring of 64; the run polls ``/stats``
  once a second while it traces, and the union of the rings by ``t0``
  holds them all.  The device is then idle because no request had
  reached the ENGINE, whatever the client counted in flight;
* a **stall**: ``stalls`` holds a row for every span of host work of 50
  ms or more, with the CPU time of the thread and of the process, the
  seconds garbage collections took, the involuntary context switches,
  and the ``cause`` the program's rule read from them.

A program without these keys (the parent of the PR that brought them)
gives ``None`` everywhere here, and the readers return ``None``.
"""

from __future__ import annotations

import breakdown
from dispatch_join import join, phase_intervals, snapshots


def ring(ctx, key: str):
    """The union of ``dispatch_trace[key]`` over the run's snapshots, by
    the rows' ``t0``, oldest first; ``None`` where no snapshot has it."""
    rows, found = {}, False
    for snap in snapshots(ctx):
        dt = snap.get("dispatch_trace") or {}
        if key in dt:
            found = True
            for row in dt[key]:
                rows[row["t0"] if isinstance(row, dict) else row[0]] = row
    return [rows[t] for t in sorted(rows)] if found else None


def traced_window(ctx):
    """``(w0, w1, idle seconds)``: the trace's window on the monotonic
    clock and the worst chip's idle seconds there; ``None`` where the
    join found no pairs."""
    j, tr = join(ctx), ctx["trace"]
    if not j["pairs"]:
        return None
    off = j["offset"]
    w0, w1 = (off + ns / 1e9 for ns in tr["window_ns"])
    return w0, w1, tr["window_s"] * tr["idle_pct_worst"] / 100.0


def reads(ctx, late: bool):
    """``[(execution's end, t_done, record)]`` of the matched pairs whose
    read came late (or, ``late`` false, in time: the interval is then the
    host's wake-up after the device finished), on the monotonic clock;
    ``None`` where the records have no such column."""
    j = join(ctx)
    if not j["pairs"] or not all("late" in r for _, _, r in j["pairs"]):
        return None
    off = j["offset"]
    return [(off + (start + dur) / 1e9, rec["t_done"], rec)
            for start, dur, rec in j["pairs"] if bool(rec["late"]) == late]


def seconds_inside(intervals, w0: float, w1: float) -> float:
    return sum(max(0.0, min(b, w1) - max(a, w0)) for a, b in intervals)


def label(ctx, g0: float, g1: float) -> str:
    """What the program says of the gap ``[g0, g1)``: ``stall:<cause>``
    where a stall row covers at least half of it, ``engine_empty`` or
    ``late_read`` where such an interval holds its midpoint, else the
    host phase that does (``no_phase`` if none).  In ``wait`` with the
    read in time the device either had not begun the execution the call
    had enqueued (``wait:not_started``: the runtime's side of the call,
    not the scheduler's) or had ended it and the host was waking up
    (``wait:read``)."""
    mid = (g0 + g1) / 2
    for row in ring(ctx, "stalls") or []:
        a, b = row["t0"], row["t0"] + row["wall"]
        if min(b, g1) - max(a, g0) >= (g1 - g0) / 2:
            return f"stall:{row['cause']}"
    if any(a <= mid < b for a, b in ring(ctx, "idles") or []):
        return "engine_empty"
    if any(a <= mid < b for a, b, _ in reads(ctx, late=True) or []):
        return "late_read"
    j = join(ctx)
    starts = {rec["seq"]: j["offset"] + start / 1e9
              for start, _, rec in j["pairs"]}
    for rec in j["records"]:
        for phase, a, b in phase_intervals(rec):
            if a <= mid < b:
                if phase != "wait" or rec["seq"] not in starts:
                    return phase
                return ("wait:not_started" if mid < starts[rec["seq"]]
                        else "wait:read")
    return "no_phase"


def gap_labels(ctx) -> list:
    """``[[the program's label, the client's, ms, seconds into the
    trace]]`` of the trace's longest gaps (``breakdown.py`` gives the
    client's side: a request sent and not ended at the gap's midpoint,
    by the client's stamps)."""
    off = join(ctx)["offset"]
    theirs = breakdown.build(ctx)["idle_gaps"]
    return [[label(ctx, off + start_ns / 1e9, off + start_ns / 1e9 + secs),
             client, round(secs * 1e3, 3), round(start_ns / 1e9, 3)]
            for (start_ns, secs), (client, _) in zip(
                ctx["trace"]["longest_gaps"], theirs)]
