"""Scheduler: milliseconds a second of the window that the scheduler
thread spent in spans of host work of 50 ms or more
(``/stats.dispatch_trace``: ``stall_s``, the window's edges, so the whole
window and not the traced part).  An ordinary span is under 5 ms and the
shortest execution 30 ms: a span that long has held the device up, and
every client's next token.  0 in a run without a stall.

Earlier line ``[stalls]``: every row of the program's ring that lies in
the window or reaches into it (the counter grows when a span ends): its
span and dispatch, wall and CPU seconds, the CPU seconds of the
process's other threads, the seconds in garbage collections, the
thread's involuntary context switches and the ``cause`` the program read
from them (``gc``, ``own_cpu``, ``other_threads``: the program's;
``off_cpu``: blocked in a call or taken off the core); and what the
collector did over the window."""
from idle_account import ring
from layer_metrics import delta


def read(ctx):
    stall_s = delta(ctx, "dispatch_trace", "stall_s")
    if stall_s is None:
        return None
    t_open, t_close = ctx["window"]
    rows = [dict(r, t0=round(r["t0"] - t_open, 3),
                 other_cpu=round(r["proc_cpu"] - r["cpu"], 5))
            for r in ring(ctx, "stalls") or []
            if r["t0"] < t_close and r["t0"] + r["wall"] >= t_open]
    a, b = (ctx[k]["dispatch_trace"]["gc"]
            for k in ("stats_open", "stats_close"))
    print(f"[stalls] {delta(ctx, 'dispatch_trace', 'stall_count')} stalls, "
          f"{stall_s:.4f} s, in the window (t0 from its opening): {rows}; "
          f"gc over the window: pause {b['pause_s'] - a['pause_s']:.4f} s "
          f"(longest since the start {b['max_pause_s']:.4f}), collections "
          f"{[y - x for x, y in zip(a['collections'], b['collections'])]}",
          flush=True)
    return 1e3 * stall_s / ctx["seconds"]
