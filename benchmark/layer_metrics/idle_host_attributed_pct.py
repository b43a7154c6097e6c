"""Device: how much of the worst chip's idle time the scheduler's host
phases account for.  Seconds of the phases other than ``wait`` (the host
is then blocked on the device) that fall inside the traced window, from
the dispatch records placed on the trace's clock (``dispatch_join.py``),
over the idle seconds there (``window_s`` x ``idle_pct_worst``).  Near
100: the device idles because the host is between two dispatches, and
the earlier line ``[gaps]`` names, for each of the trace's longest gaps,
the phase that holds its midpoint.  It can pass 100: ``launch`` runs on
after the device has started."""
from dispatch_join import join, phase_intervals


def phase_at(recs: list, t: float) -> str:
    for rec in recs:
        for phase, a, b in phase_intervals(rec):
            if a <= t < b:
                return phase
    return "no_phase"


def read(ctx):
    j, tr = join(ctx), ctx["trace"]
    if not j["pairs"]:
        return None
    off = j["offset"]
    w0, w1 = (off + ns / 1e9 for ns in tr["window_ns"])
    host_s = sum(max(0.0, min(b, w1) - max(a, w0))
                 for rec in j["records"]
                 for phase, a, b in phase_intervals(rec) if phase != "wait")
    idle_s = tr["window_s"] * tr["idle_pct_worst"] / 100.0
    gaps = [[phase_at(j["records"], off + start_ns / 1e9 + secs / 2),
             round(secs * 1e3, 3)] for start_ns, secs in tr["longest_gaps"]]
    print(f"[gaps] longest gaps (phase at the midpoint, ms): {gaps}; host "
          f"phases but wait {host_s:.4f} s of {idle_s:.4f} s idle in the "
          f"traced window", flush=True)
    return 100.0 * host_s / idle_s if idle_s > 0 else None
