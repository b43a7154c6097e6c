"""Scheduler: host milliseconds of one scheduler iteration that are not
spent waiting for the device (``bookkeeping`` + ``intake`` + ``pack`` +
``launch`` + ``drain`` of ``/stats.dispatch_trace.phase_s``), per
dispatch, between the ``/stats`` reads at the trace's edges.  Earlier
line ``[host]``: the phases one by one."""


def read(ctx):
    marks = ctx["marks"]
    try:
        a = marks["stats_trace_start"]["dispatch_trace"]
        b = marks["stats_trace_stop"]["dispatch_trace"]
    except (KeyError, TypeError):
        return None
    n = b["seq"] - a["seq"]
    if n <= 0:
        return None
    per = {p: (b["phase_s"][p] - a["phase_s"][p]) / n * 1e3
           for p in b["phase_s"]}
    phases = ", ".join(f"{p} {v:.3f}" for p, v in per.items())
    print(f"[host] ms per dispatch over {n} dispatches: {phases}",
          flush=True)
    return sum(v for p, v in per.items() if p != "wait")
