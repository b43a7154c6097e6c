"""Device: how much of the worst chip's idle time lies between the end
of an execution and a read of it that came late.  Over the matched pairs
of ``dispatch_join.py`` whose record has ``late`` = 1: the seconds from
the execution's end on the trace's clock to the record's ``t_done``, cut
to the traced window, over the idle seconds there (``window_s`` x
``idle_pct_worst``, as ``idle_host_attributed_pct``, which leaves these
seconds out by construction: they lie in ``wait``).  Earlier line
``[late]``: how many, their sum, and the five longest with the record's
``seq`` and ``await``."""
from dispatch_join import join
from idle_account import reads, seconds_inside, traced_window


def read(ctx):
    window = traced_window(ctx) if ctx["trace"] else None
    late = reads(ctx, late=True)
    if window is None or late is None:
        return None
    w0, w1, idle_s = window
    late_s = seconds_inside([(a, b) for a, b, _ in late], w0, w1)
    longest = [[r["seq"], round((b - a) * 1e3, 3), round(r["await"] * 1e3, 3)]
               for a, b, r in sorted(late, key=lambda x: x[0] - x[1])[:5]]
    print(f"[late] {len(late)} late reads of {len(join(ctx)['pairs'])} "
          f"matched executions, {late_s:.4f} s of {idle_s:.4f} s idle in "
          f"the traced window; longest (seq, ms from the execution's end to "
          f"t_done, await ms): {longest}", flush=True)
    return 100.0 * late_s / idle_s if idle_s > 0 else None
