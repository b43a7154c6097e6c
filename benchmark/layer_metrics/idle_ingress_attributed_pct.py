"""Device: how much of the worst chip's idle time the engine stood empty
WHILE a request was already inside the gateway or the replica's handler.
Seconds that lie in a row of the program's ``idles`` (the scheduler's
waits for a request) and in at least one ingress row ``[t_gateway,
t_submit]`` of ``/stats.request_path`` (the gateway took the request ..
the engine had it) and in the traced window, over the idle seconds there.
A part of ``idle_engine_empty_attributed_pct``: what is left of that
share is then the client's side of the gateway's socket, by the program's
own word.  ``None`` untraced, and where the program has no such record.

Earlier line ``[ingress]``: the engine-empty seconds of the traced
window, how many of them had a request inside, and the requests."""
from idle_account import ring, seconds_inside, traced_window
from request_path import rows, seconds_in_all


def read(ctx):
    window = traced_window(ctx) if ctx["trace"] else None
    idles, reqs = ring(ctx, "idles"), rows(ctx)
    if window is None or idles is None or reqs is None:
        return None
    w0, w1, idle_s = window
    if idle_s <= 0:
        return None
    inside = [(r["t_gateway"], r["t_submit"]) for r in reqs]
    held_s = seconds_in_all(idles, inside, w0, w1)
    empty_s = seconds_inside(idles, w0, w1)
    n = sum(1 for a, b in inside if b > w0 and a < w1)
    print(f"[ingress] of {empty_s:.4f} s with the engine empty in the "
          f"traced window, {held_s:.4f} s had a request inside the gateway "
          f"or the handler ({n} requests' ingress there) and "
          f"{empty_s - held_s:.4f} s had none: "
          f"{100.0 * held_s / idle_s:.1f} % and "
          f"{100.0 * (empty_s - held_s) / idle_s:.1f} % of {idle_s:.4f} s "
          f"idle", flush=True)
    return 100.0 * held_s / idle_s
