"""The synthetic run of ``test_dispatch_join`` with two long gaps in it,
for the readers of the program's idle account.

The read of iteration 10 comes 0.4 s after its execution's end: the host
stood still in the plan of the next dispatch (one row of ``stalls``), so
record 111 is ``late``.  Before iteration 20 the engine waited 0.6 s for
a request (one row of ``idles``), while the client counted one in flight.
``keys=False`` gives what the parent of the PR that brought the keys
shows: the same clock, and none of them."""
from types import SimpleNamespace

from test_dispatch_join import (BOOK, DRAIN, FIELDS, LAG, LAUNCH, MODEL,
                                RUNNING, SKEW, STARTUP, timeline)

LATE_AT, LATE_S, EMPTY_AT, EMPTY_S = 10, 0.4, 20, 0.6
GC = {"pause_s": 0.25, "max_pause_s": 0.02, "collections": [40, 3, 0]}


def laid_out():
    """``(rows, executions, idles, stalls)`` on the monotonic clock."""
    rows, execs, idles, stalls, shift = [], [], [], [], 0.0
    for i, (row, dev_start, dur) in enumerate(timeline()):
        row = list(row)
        if i == EMPTY_AT:
            t0 = rows[-1][2] + DRAIN + BOOK
            idles.append([round(t0, 5), round(t0 + EMPTY_S, 5)])
            shift += EMPTY_S
        row[1] = round(row[1] + shift, 5)
        held = LATE_S if i == LATE_AT else 0.0
        row[2] = round(row[2] + shift + held, 5)
        row[7] = round(row[7] + held, 5)
        if held:
            t0 = row[1] + LAUNCH + 0.001
            stalls.append({"seq": row[0] + 1, "span": "ahead_plan",
                           "t0": round(t0, 5),
                           "wall": round(row[2] - 0.0004 - t0, 5),
                           "cpu": 0.003, "proc_cpu": 0.011, "gc": 0.0,
                           "nivcsw": 2, "cause": "off_cpu"})
        rows.append(row + [0.0, int(bool(held)), 0.0004 if held else 0.27])
        execs.append((dev_start + shift, dur))
        shift += held
    return rows, execs, idles, stalls


def snap(rows, seq, idles, stalls, keys):
    dt = {"seq": seq, "fields": FIELDS, "recent": [r[:len(FIELDS)]
                                                   for r in rows]}
    if keys:
        late = sum(1 for r in laid_out()[0] if r[0] <= seq and r[-2])
        dt.update(fields=FIELDS + ["ahead", "late", "await"], recent=rows,
                  late_reads=late, idles=idles, stalls=stalls,
                  stall_count=len(stalls), gc=GC,
                  stall_s=round(sum(s["wall"] for s in stalls), 6))
    return {"dispatch_trace": dt}


def make_ctx(keys=True, with_stalls=True, traced=(3, 28)):
    rows, execs, idles, stalls = laid_out()
    if not with_stalls:
        stalls = []
    offset = RUNNING + SKEW
    ex = [[(s - offset) * 1e9, d * 1e9] for s, d in execs[slice(*traced)]]
    w0, w1 = ex[0][0], ex[-1][0] + ex[-1][1]
    busy = sum(d for _, d in ex) / 1e9
    gaps = sorted(([a[0] + a[1], (b[0] - a[0] - a[1]) / 1e9]
                   for a, b in zip(ex, ex[1:])), key=lambda g: -g[1])
    # the rings as three overlapping polls saw them
    snaps = [snap(rows[a:b], 100 + b,
                  [r for r in idles if r[1] <= rows[b - 1][2]],
                  [s for s in stalls if s["t0"] <= rows[b - 1][2]], keys)
             for a, b in ((0, 15), (10, 25), (18, 30))]
    # one request the client counts in flight over both gaps
    request = SimpleNamespace(sent=rows[5][1], end=rows[25][2])
    return {
        "config": {"model_config": MODEL, "serve_flags": ["--greedy"]},
        "cell": {"chips": 1}, "records": [request],
        "window": (rows[0][1] - 1.0, rows[0][1] + 49.0), "seconds": 50.0,
        "stats_open": snap([], 100, [], [], keys) if not keys else {
            "dispatch_trace": dict(
                snap([], 100, [], [], keys)["dispatch_trace"],
                gc={"pause_s": 0.05, "max_pause_s": 0.02,
                    "collections": [10, 1, 0]})},
        "stats_close": snaps[-1], "stats_end": snaps[-1],
        "marks": {
            "trace_started": {
                "start": {"monotonic": RUNNING - STARTUP},
                "running": {"monotonic": RUNNING}},
            "stats_trace_start": snaps[0], "polls": snaps[1:],
            "stats_trace_stop": snaps[-1]},
        "trace": {
            "modules": {"jit_mixed_step": ex},
            "window_ns": [w0, w1], "window_s": (w1 - w0) / 1e9,
            "idle_pct_worst": 100 * (1 - busy / ((w1 - w0) / 1e9)),
            "longest_gaps": gaps[:5], "op_self_s": [["fusion.1", 1.0]]},
    }


def idle_s(ctx) -> float:
    tr = ctx["trace"]
    return tr["window_s"] * tr["idle_pct_worst"] / 100.0


LATE_GAP_S = LATE_S + LAG + 0.00002 * (LATE_AT % 3)
