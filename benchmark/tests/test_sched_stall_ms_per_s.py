"""``sched_stall_ms_per_s`` reads the window's edges and prints the
ring's rows of the window."""
import pytest

from idle_runs import make_ctx
from layer_metrics import sched_stall_ms_per_s as stall


def test_stalled_milliseconds_a_second_of_the_window(capsys):
    ctx = make_ctx()
    row = ctx["stats_close"]["dispatch_trace"]["stalls"][0]
    assert stall.read(ctx) == pytest.approx(1e3 * row["wall"] / 50.0)
    line = capsys.readouterr().out
    assert "[stalls] 1 stalls, 0.68" in line
    assert "'span': 'ahead_plan'" in line and "'cause': 'off_cpu'" in line
    assert "'other_cpu': 0.008" in line and "'nivcsw': 2" in line
    assert "pause 0.2000 s" in line and "collections [30, 2, 0]" in line


def test_a_window_without_a_stall_reads_zero():
    assert stall.read(make_ctx(with_stalls=False)) == 0.0


def test_nothing_to_read_is_none_and_never_raises():
    assert stall.read(make_ctx(keys=False)) is None
    assert stall.read({"stats_open": {}, "stats_close": {},
                       "marks": {}}) is None
