"""Gateway + HTTP replica: the CPU milliseconds the replica's handler
threads take beside one dispatch (``/stats.request_path.handler_cpu_s``,
each thread's own ``time.thread_time()``, differences summed, over
``dispatch_trace.seq``; the window's edges).  Python runs one thread at a
time, so this is at most what the handlers hold of the GIL a dispatch,
which the scheduler thread's plan of the next dispatch waits for.

Earlier line ``[handlers]``: the same beside the scheduler's
``spans.ahead_plan`` wall less CPU a dispatch (the seconds its plan stood
still without running) and ``spans.deliver``'s."""
from layer_metrics import delta
from request_path import per


def read(ctx):
    cpu = per(ctx, ("handler_cpu_s",), "seq", 1e3, section="dispatch_trace")
    if cpu is None:
        return None
    n = delta(ctx, "dispatch_trace", "seq")
    a, b = (ctx[k]["dispatch_trace"].get("spans", {})
            for k in ("stats_open", "stats_close"))
    waited = {name: 1e3 * ((b[name]["wall_s"] - b[name]["cpu_s"])
                           - (a[name]["wall_s"] - a[name]["cpu_s"])) / n
              for name in ("ahead_plan", "deliver") if name in a and name in b}
    print(f"[handlers] over {n} dispatches the handler threads took "
          f"{cpu:.3f} ms of CPU a dispatch; the scheduler's wall less CPU a "
          f"dispatch: "
          + ", ".join(f"{k} {v:.3f} ms" for k, v in waited.items()),
          flush=True)
    return cpu
