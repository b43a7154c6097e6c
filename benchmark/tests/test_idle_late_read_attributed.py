"""``idle_late_read_attributed_pct`` on the synthetic run of
``idle_runs``: one read that came 0.4 s late."""
import pytest

from idle_runs import LATE_GAP_S, idle_s, make_ctx
from layer_metrics import (idle_host_attributed_pct,
                           idle_late_read_attributed_pct as late)


def test_the_late_reads_seconds_over_the_idle_seconds(capsys):
    ctx = make_ctx()
    # the join places the trace 1.25 ms early (test_dispatch_join)
    assert late.read(ctx) == pytest.approx(
        100 * (LATE_GAP_S + 0.00125) / idle_s(ctx), rel=1e-4)
    line = capsys.readouterr().out
    assert "[late] 1 late reads of 25 matched executions" in line
    assert "[[111, 401.77, 0.4]]" in line              # seq, ms, await ms
    # the sibling leaves these seconds out by construction: to it the
    # gap lies in `wait`
    idle_host_attributed_pct.read(ctx)
    assert "['wait', 413.52]" in capsys.readouterr().out


def test_a_late_read_outside_the_traced_window_counts_nothing():
    assert late.read(make_ctx(traced=(12, 28))) == 0.0


def test_nothing_to_read_is_none_and_never_raises():
    assert late.read(make_ctx(keys=False)) is None     # the parent's rows
    ctx = make_ctx()
    ctx["trace"] = {}                                  # no device trace
    assert late.read(ctx) is None
    ctx = make_ctx()
    ctx["marks"]["trace_started"] = {}                 # nothing to join on
    assert late.read(ctx) is None
