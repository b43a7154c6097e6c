"""Device: how much of the worst chip's idle time the engine spent with
nothing to do.  Seconds of the program's ``idles`` rows (every wait of
the scheduler for a request, from a millisecond on; the union of the
rings over the run's ``/stats`` polls) inside the traced window, over the
idle seconds there (``window_s`` x ``idle_pct_worst``).  Idle because no
request had reached the ENGINE, whatever the client counted in flight: a
gap that ``breakdown`` labels ``requests_in_flight`` and this reader
``engine_empty`` lies between the client's ``sent`` and the engine's
queue (the load generator, the gateway, the HTTP handler).

Earlier line ``[idle]``: for each of the trace's longest gaps the
program's label (``engine_empty``, ``late_read``, ``stall:<cause>`` or a
host phase) beside the client's, its length and where in the trace it
began; and the account: the host's phases, the late reads and the empty
engine as shares of the idle seconds, and what the three leave out by
construction: the host's wake-up after an execution that was read in
time (its end on the trace's clock to ``t_done``)."""
from dispatch_join import join, phase_intervals
from idle_account import (gap_labels, reads, ring, seconds_inside,
                          traced_window)


def read(ctx):
    window = traced_window(ctx) if ctx["trace"] else None
    idles = ring(ctx, "idles")
    if window is None or idles is None:
        return None
    w0, w1, idle_s = window
    if idle_s <= 0:
        return None
    empty_s = seconds_inside(idles, w0, w1)
    late_s, woken_s = (
        seconds_inside([(a, b) for a, b, _ in reads(ctx, late) or []],
                       w0, w1) for late in (True, False))
    host_s = seconds_inside(
        [(a, b) for rec in join(ctx)["records"]
         for phase, a, b in phase_intervals(rec) if phase != "wait"], w0, w1)
    host, late, empty, all3, woken = (
        round(100.0 * s / idle_s, 1)
        for s in (host_s, late_s, empty_s, host_s + late_s + empty_s,
                  woken_s))
    waits = sum(1 for a, b in idles if b > w0 and a < w1)
    print(f"[idle] longest gaps (the program's label, the client's, ms, s "
          f"into the trace): {gap_labels(ctx)}; of {idle_s:.4f} s idle in "
          f"the traced window: host phases {host} %, late reads {late} %, "
          f"engine empty {empty} % ({empty_s:.4f} s in {waits} waits), "
          f"together {all3} %; beside them the host's wake-up after a read "
          f"in time {woken} %", flush=True)
    return 100.0 * empty_s / idle_s
