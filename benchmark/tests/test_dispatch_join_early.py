"""dispatch_join on records of a two-deep device queue.

Where a dispatch was enqueued behind its predecessor (the record's
``early``), ``t_launch(n+1) < t_done(n)``: the records' intervals overlap
and the device goes from one execution to the next with no host in
between.  The join's premise is "an execution lies between its record's
two instants", which still holds, and an execution's start still falls
after its own record's launch and before the next one's (dispatch n+2 is
launched only after n has returned and been drained).  Forty iterations
laid out by hand: executions of 36-38 ms, the next dispatch launched 12 ms
into the running one and started by the device 50 us after that one's
end; every sixth is a miss, packed in a gap of 6 ms after the host saw
its predecessor done.  The host sees an execution done 1.2 ms after its
end."""
import pytest

import dispatch_join as dj
from layer_metrics import sched_ahead_early_share_pct

FIELDS = ["seq", "t_launch", "t_done", "bookkeeping", "intake", "pack",
          "launch", "wait", "drain", "with_finals", "segments", "finals",
          "prefill_tokens", "active_rows", "steps", "kv_tokens", "ahead",
          "late", "await", "early"]
LAG, TO_DEVICE, HANDOVER, GAP, LAUNCH = 0.0012, 0.0009, 0.00005, 0.006, 0.001
RUNNING, SKEW, STARTUP = 7000.0, -0.9, 1.2


def timeline(miss_every=6):
    """``[(record row, device start, device seconds)]``."""
    out, end = [], None
    for i in range(40):
        dur = 0.036 + 0.002 * ((i * 7919) % 13) / 13     # no period
        early = i > 0 and i % miss_every != 0
        if early:
            prev_start = out[-1][1]
            t_launch = prev_start + 0.012
            start = end + HANDOVER
        else:
            t_launch = 7010.0 if end is None else end + LAG + GAP
            start = t_launch + TO_DEVICE
        end = start + dur
        t_done = end + LAG + 0.00003 * (i % 3)
        row = [201 + i, round(t_launch, 5), round(t_done, 5), 0.0, 0.0,
               0.0 if early else GAP - 0.003, LAUNCH, 0.02, 0.0, 0, 2, 0, 512,
               40, 4, 90000, 0.004 if early else 0.0, 0, 0.02, int(early)]
        out.append((row, start, dur))
    return out


def make_ctx(traced=(4, 36), miss_every=6, committed=40, in_flight=0):
    """``committed``: the records the last poll saw; ``in_flight``: the
    early dispatches after them that it showed as launched and not yet
    returned (the program's row for such a one: its number, its
    ``t_launch``, ``early``, zeros)."""
    tl = timeline(miss_every)
    rows = [r for r, _, _ in tl][:committed]
    rows += [[r[0], r[1], 0.0] + [0] * (len(FIELDS) - 4) + [1]
             for r, _, _ in tl[committed:committed + in_flight]]
    offset = RUNNING + SKEW
    execs = [[(s - offset) * 1e9, d * 1e9] for _, s, d in tl[slice(*traced)]]

    def stats(rows, seq, early):
        return {"dispatch_trace": {"seq": seq, "fields": FIELDS,
                                   "recent": rows, "ahead_early": early}}

    n_early = sum(r[-1] for r in rows)
    return {
        "config": {}, "cell": {"chips": 1},
        "stats_open": stats([], 200, 0),
        "stats_close": stats(rows[-8:], 240, n_early),
        "marks": {"trace_started": {
                      "start": {"monotonic": RUNNING - STARTUP},
                      "running": {"monotonic": RUNNING}},
                  "stats_trace_start": stats(rows[:24], 224, 0),
                  "polls": [stats(rows[16:], 240, n_early)]},
        "trace": {"modules": {"jit_mixed_step": execs}},
    }


def test_overlapping_records_still_get_their_own_executions(capsys):
    ctx = make_ctx()
    j = dj.join(ctx)
    assert [r["seq"] for _, _, r in j["pairs"]] == list(range(205, 237))
    assert j["share"] == 1.0
    assert sum(r["early"] for _, _, r in j["pairs"]) == 27
    # the misses bound the offset from below (the device starts 0.9 ms
    # after their launch), every pair from above (the host's 1.2 ms)
    assert j["skew_s"] == pytest.approx(SKEW + (LAG - TO_DEVICE) / 2,
                                        abs=3e-5)
    assert j["lag_spread_s"] < 1e-4
    assert "matched 32 of 32 executions, skew" in capsys.readouterr().out
    # every early one was launched before its predecessor was seen done
    recs = j["records"]
    for a, b in zip(recs, recs[1:]):
        assert (b["t_launch"] < a["t_done"]) == bool(b["early"])


def test_a_window_of_early_dispatches_alone_is_joined_too():
    """No miss among the traced executions: the launches bound the offset
    only loosely from below (each came some 25 ms before its execution's
    start), so the middle of what the pairs leave lies 12 ms early; every
    execution's start still falls after its own record's launch and
    before the next one's, and gets its own record."""
    ctx = make_ctx(miss_every=1000)
    j = dj.join(ctx)
    assert [r["seq"] for _, _, r in j["pairs"]] == list(range(205, 237))
    assert all(r["early"] for _, _, r in j["pairs"])
    assert -0.014 < j["skew_s"] - SKEW < -0.010


def test_the_traces_tail_is_left_unmatched_not_given_to_a_predecessor(capsys):
    """The last poll of a traced run comes before the profiler stops, so
    the trace ends with an execution or two whose records were not
    committed yet.  Behind an early launch such an execution begins
    before its predecessor's ``t_done``: with the predecessor's record the
    last one known, the join would hand it that record a second time and
    refuse itself as out of order.  The program shows an early dispatch
    from its launch on, so the execution finds its own row, which has not
    returned, and stays unmatched as a tail always did."""
    ctx = make_ctx(traced=(4, 36), committed=34, in_flight=2)
    j = dj.join(ctx)
    assert [r["seq"] for _, _, r in j["pairs"]] == list(range(205, 235))
    assert "matched 30 of 32 executions, skew" in capsys.readouterr().out
    # (a row that has not returned bounds no offset: the join falls back
    # on the median lag, the host's wake-up later than the middle)
    assert j["skew_s"] == pytest.approx(SKEW + LAG, abs=5e-5)
    # without those rows: the failure this guards against
    j = dj.join(make_ctx(traced=(4, 36), committed=34))
    assert j["pairs"] == [] and "OUT OF ORDER" in capsys.readouterr().out


def test_the_early_share_reads_the_counter():
    assert sched_ahead_early_share_pct.read(make_ctx()) == pytest.approx(
        100 * 33 / 40)
    assert sched_ahead_early_share_pct.read(
        make_ctx(miss_every=1000)) == pytest.approx(100 * 39 / 40)
