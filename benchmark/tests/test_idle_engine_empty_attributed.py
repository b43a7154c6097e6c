"""``idle_engine_empty_attributed_pct`` and the ``[idle]`` line on the
synthetic run of ``idle_runs``: a 0.6 s wait of the engine for a request
that the client counted in flight, a 0.4 s stall, and the host's phases
between two executions."""
import pytest

import idle_account
from idle_runs import EMPTY_S, idle_s, make_ctx
from layer_metrics import (idle_engine_empty_attributed_pct as empty,
                           idle_host_attributed_pct,
                           idle_late_read_attributed_pct)


def test_the_empty_engines_seconds_over_the_idle_seconds(capsys):
    ctx = make_ctx()
    assert empty.read(ctx) == pytest.approx(100 * EMPTY_S / idle_s(ctx),
                                            rel=1e-4)
    line = capsys.readouterr().out
    assert "engine empty" in line and "(0.6000 s in 1 waits)" in line
    # the three readers close the account of the idle seconds
    total = (empty.read(ctx) + idle_late_read_attributed_pct.read(ctx)
             + idle_host_attributed_pct.read(ctx))
    assert 95 < total < 105
    assert f"together {total:.1f} %" in line
    # 24 reads in time, 0.52-0.56 ms each (and the join's 1.25 ms)
    assert "wake-up after a read in time 3.1 %" in line


def test_each_long_gap_gets_a_label_from_the_programs_side(capsys):
    """The client counts a request in flight over both long gaps; the
    program says one was its own stall and the other an empty engine."""
    labels = idle_account.gap_labels(make_ctx())
    assert [(l[0], l[1], round(l[2])) for l in labels[:2]] == [
        ("engine_empty", "requests_in_flight", 614),
        ("stall:off_cpu", "requests_in_flight", 414)]
    # the ordinary turn-around: the midpoint of 13.5 ms lies in `intake`
    assert all(l[0] in ("intake", "pack") and l[2] < 15 for l in labels[2:])
    # ... and where in the trace each began
    assert [l[3] for l in labels[:2]] == [17.665, 14.577]
    # without the stall ring the late read is what is left to say
    assert idle_account.gap_labels(make_ctx(with_stalls=False))[1][0] == (
        "late_read")
    empty.read(make_ctx())
    assert "['stall:off_cpu', 'requests_in_flight', 41" in (
        capsys.readouterr().out)


def test_a_gap_inside_wait_says_which_side_of_the_execution_it_lies_on():
    """With the read in time, `wait` holds two idle stretches that are
    not the scheduler's: the call has returned and the execution has not
    begun, and the execution has ended and the host is waking up."""
    ctx = make_ctx()
    for snap in [ctx["marks"]["stats_trace_start"], *ctx["marks"]["polls"]]:
        for row in snap["dispatch_trace"]["recent"]:
            if row[0] == 106:       # its call returned after 1 ms, not 5
                row[6], row[7] = 0.001, round(row[7] + 0.004, 5)
    rec = next(r for r in idle_account.join(ctx)["records"]
               if r["seq"] == 106)
    assert idle_account.label(ctx, rec["t_launch"] + 0.0011,
                              rec["t_launch"] + 0.0015) == "wait:not_started"
    assert idle_account.label(ctx, rec["t_done"] - 0.0004,
                              rec["t_done"]) == "wait:read"


def test_the_union_of_the_rings_keeps_a_row_once():
    ctx = make_ctx()
    assert len(idle_account.ring(ctx, "idles")) == 1
    assert [r["span"] for r in idle_account.ring(ctx, "stalls")] == [
        "ahead_plan"]
    assert idle_account.ring(make_ctx(keys=False), "idles") is None


def test_nothing_to_read_is_none_and_never_raises():
    assert empty.read(make_ctx(keys=False)) is None    # the parent
    ctx = make_ctx()
    ctx["trace"] = {}
    assert empty.read(ctx) is None
