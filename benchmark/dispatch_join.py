"""Join the program's dispatch records to the device trace.

The program keeps one record per scheduler dispatch that reached the
device (``/stats.dispatch_trace``: ``fields`` names the columns of the
rows in ``recent``, a ring of the last 128; instants are the replica's
``time.monotonic()``).  A traced run snapshots ``/stats`` once a second,
so the union of the rings over ``marks.polls`` and the snapshots at the
edges holds every dispatch of the traced part of the window.

The device trace counts nanoseconds from the profiler's start, and the
replica stamped ``time.monotonic()`` just before ``start_trace`` and just
after it returned (``marks.trace_started.start`` / ``.running``).  The
trace's zero lies between the two stamps (at the first, as far as the v5e
has shown), and ``running``, which ``breakdown.py`` uses to label a gap,
is off by the profiler's start-up: tens of milliseconds, several times a
gap between two executions.  ``join`` therefore estimates the constant.
It pairs the executions, in order, with the records, in order, once for
every record the first execution could belong to without leaving the two
stamps (a quarter second of slack; anywhere, if none lands there), and
keeps the pairing under which the time from an execution's end on the
device to the host's ``t_done`` is steadiest: under the right pairing
that lag is the host's wake-up, steady to a fraction of a millisecond;
under a neighbouring one it swings with every difference between two
executions.  Every pair then bounds the offset from both sides (an
execution lies between its record's ``t_launch`` and ``t_done``); the
offset is the middle of what the pairs leave, and the printed ``skew`` is
how far it lies from ``running``.

Then each ``jit_mixed_step`` event gets the record whose ``[t_launch,
t_done)`` holds the event's start.  The join checks itself: matched events
must keep the records' order, the share matched is printed on a ``[join]``
line, and under 90 % matched ``join`` gives no pairs, so every metric that
needs them returns ``None``.  A program without ``dispatch_trace`` (the
parent of the PR that added it) gives no records and no pairs either.
"""

from __future__ import annotations

from bisect import bisect_right

from arith import median, percentile

MIN_SHARE = 0.9
SLACK_S = 0.25


def snapshots(ctx) -> list:
    """Every ``/stats`` snapshot the run kept, oldest first."""
    marks = ctx.get("marks", {})
    named = [marks.get("stats_trace_start"), *marks.get("polls", []),
             marks.get("stats_trace_stop"), ctx.get("stats_close"),
             ctx.get("stats_end")]
    return [s for s in named if s]


def records(ctx) -> list:
    """The union of the rings by ``seq``, as dicts, in order."""
    by_seq = {}
    for snap in snapshots(ctx):
        dt = snap.get("dispatch_trace")
        if not dt or not dt.get("fields"):
            continue
        for row in dt["recent"]:
            by_seq[row[0]] = dict(zip(dt["fields"], row))
    return [by_seq[s] for s in sorted(by_seq)]


def executions(ctx) -> list:
    """``[start_ns, dur_ns]`` of the serving program's executions on the
    first chip, in order."""
    mods = (ctx.get("trace") or {}).get("modules", {})
    name = ctx["config"].get("step_module", "jit_mixed_step")
    return sorted(mods.get(name, []))


def _spread(values) -> float:
    return (percentile(values, 75, min_beyond=0)
            - percentile(values, 25, min_beyond=0))


def estimate_offset(execs, recs, lo: float, hi: float):
    """``(offset, half width, lag spread)``: the trace's zero on the
    monotonic clock, searched between ``lo`` and ``hi``; ``None`` where
    no pairing of the first execution with a record lands there.

    Under a pairing every pair bounds the offset from both sides, since
    an execution lies between its record's two instants: ``t_launch -
    start <= offset <= t_done - end``.  The offset is the middle of what
    all pairs leave (``half width`` to either side: the host's latency
    before the device starts and after it ends, a few milliseconds); if
    they leave nothing, the median lag."""
    best = None
    end0 = (execs[0][0] + execs[0][1]) / 1e9
    by_seq = {r["seq"]: r for r in recs}
    for rec in recs:
        if not lo <= rec["t_done"] - end0 <= hi:
            continue
        # by number, not by position: the rings may have missed a record
        pairs = [(e, by_seq[rec["seq"] + i]) for i, e in enumerate(execs)
                 if rec["seq"] + i in by_seq]
        if len(pairs) < MIN_SHARE * len(execs):
            continue         # the records end before the executions do
        lags = [r["t_done"] - (s + d) / 1e9 for (s, d), r in pairs]
        cand = (_spread(lags), rec["seq"])
        if best is None or cand < best:
            best = cand
            least = max(r["t_launch"] - s / 1e9 for (s, _), r in pairs)
            most = min(lags)
            found = (((least + most) / 2, (most - least) / 2)
                     if least <= most else (median(lags), 0.0))
    return (*found, best[0]) if best else None


def phase_intervals(rec: dict) -> list:
    """``[(phase, start, end)]`` of one record on the monotonic clock.
    ``launch`` starts and ``pack`` ends at ``t_launch``; ``wait`` ends and
    ``drain`` starts at ``t_done``; ``intake`` and ``bookkeeping`` lie
    before ``pack``, back to back (an idle engine's blocking wait, which
    is no phase, would sit between them: then the device is idle for want
    of requests, not of the host)."""
    tl, td = rec["t_launch"], rec["t_done"]
    t_pack = tl - rec["pack"]
    t_intake = t_pack - rec["intake"]
    return [("bookkeeping", t_intake - rec["bookkeeping"], t_intake),
            ("intake", t_intake, t_pack), ("pack", t_pack, tl),
            ("launch", tl, tl + rec["launch"]),
            ("wait", tl + rec["launch"], td),
            ("drain", td, td + rec["drain"])]


def join(ctx) -> dict:
    """``{"records", "executions", "pairs": [(start_ns, dur_ns, record)],
    "offset", "skew_s", "lag_spread_s", "share"}``, worked out once a run
    and kept in ``ctx``.  ``pairs`` is empty where the join failed its
    own checks; ``offset`` is ``None`` where there was nothing to join."""
    if "_dispatch_join" in ctx:
        return ctx["_dispatch_join"]
    recs, execs = records(ctx), executions(ctx)
    out = {"records": recs, "executions": execs, "pairs": [],
           "offset": None, "skew_s": None, "lag_spread_s": None,
           "share": 0.0}
    ctx["_dispatch_join"] = out
    started = ctx.get("marks", {}).get("trace_started") or {}
    if not recs or not execs or "running" not in started:
        if execs:
            print(f"[join] {len(execs)} executions in the trace and "
                  f"{len(recs)} dispatch records: nothing to join",
                  flush=True)
        return out
    running = started["running"]["monotonic"]
    before = started.get("start", started["running"])["monotonic"]
    est = (estimate_offset(execs, recs, before - SLACK_S, running + SLACK_S)
           or estimate_offset(execs, recs, float("-inf"), float("inf")))
    offset, half, out["lag_spread_s"] = est if est else (running, 0.0, None)
    launches = [r["t_launch"] for r in recs]
    pairs = []
    for start, dur in execs:
        at = offset + start / 1e9
        i = bisect_right(launches, at) - 1
        if i >= 0 and at < recs[i]["t_done"]:
            pairs.append((start, dur, recs[i]))
    seqs = [r["seq"] for _, _, r in pairs]
    in_order = all(a < b for a, b in zip(seqs, seqs[1:]))
    out.update(offset=offset, skew_s=offset - running,
               share=len(pairs) / len(execs))
    ok = in_order and out["share"] >= MIN_SHARE
    if ok:
        out["pairs"] = pairs
    spread = out["lag_spread_s"]
    print(f"[join] matched {len(pairs)} of {len(execs)} executions"
          f"{'' if in_order else ' OUT OF ORDER'}"
          f"{'' if ok else ' (under 90 %: no metric reads the join)'}, "
          f"skew {out['skew_s']:+.4f} s from the stamp after start_trace "
          f"({running - before:.3f} s after the one before) +- "
          f"{half * 1e3:.2f} ms, lag spread "
          f"{'n/a' if spread is None else f'{spread * 1e3:.3f} ms'}, "
          f"{len(recs)} records seq {recs[0]['seq']}..{recs[-1]['seq']}"
          + (f", first pair seq {seqs[0]} at {pairs[0][0] / 1e9:.4f} s of "
             f"the trace" if pairs else ""), flush=True)
    return out


def step_ms_p50(ctx, prefill: bool):
    """Median device milliseconds of the matched executions that carried
    prefill segments (``prefill``) or none; ``None`` under 5 of them."""
    durs = [d for _, d, r in join(ctx)["pairs"]
            if (r["segments"] > 0) == prefill]
    return median(durs) / 1e6 if len(durs) >= 5 else None
