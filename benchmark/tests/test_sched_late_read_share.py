"""``sched_late_read_share_pct`` reads the window's edges, and nothing
where the program keeps no such counter (the parent of the PR that
brought it)."""
from layer_metrics import sched_late_read_share_pct as share


def _ctx(a, b):
    return {"stats_open": a, "stats_close": b, "marks": {}}


def test_share_of_the_windows_dispatches_that_were_read_late():
    ctx = _ctx({"dispatch_trace": {"seq": 40, "late_reads": 2}},
               {"dispatch_trace": {"seq": 840, "late_reads": 22}})
    assert share.read(ctx) == 2.5
    same = {"dispatch_trace": {"seq": 40, "late_reads": 0}}
    assert share.read(_ctx(same, {"dispatch_trace": {
        "seq": 90, "late_reads": 0}})) == 0.0        # a reading, not None


def test_nothing_to_read_is_none_and_never_raises():
    same = {"dispatch_trace": {"seq": 7, "late_reads": 1}}
    assert share.read(_ctx(same, same)) is None      # no dispatch at all
    assert share.read(_ctx({}, {})) is None          # no such section
    # the parent's section: dispatches, and no such counter
    assert share.read(_ctx({"dispatch_trace": {"seq": 1, "ahead_hits": 1}},
                           {"dispatch_trace": {"seq": 9, "ahead_hits": 8}})
                      ) is None
