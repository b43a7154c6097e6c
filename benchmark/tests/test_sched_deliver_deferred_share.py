"""``sched_deliver_deferred_share_pct`` reads the window's edges, and
nothing where the program keeps no such counter (the parent of the PR
that brought it)."""
from layer_metrics import sched_deliver_deferred_share_pct as share


def _ctx(a, b):
    return {"stats_open": a, "stats_close": b, "marks": {}}


def test_share_of_the_windows_dispatches_handed_over_behind_a_launch():
    ctx = _ctx({"dispatch_trace": {"seq": 40, "ahead_hits": 22,
                                   "delivered_after_launch": 12}},
               {"dispatch_trace": {"seq": 840, "ahead_hits": 622,
                                   "delivered_after_launch": 212}})
    assert share.read(ctx) == 25.0
    # a cell whose every dispatch is launched as prepared reads 0, not
    # nothing
    ctx["stats_close"]["dispatch_trace"]["delivered_after_launch"] = 12
    assert share.read(ctx) == 0.0


def test_nothing_to_read_is_none_and_never_raises():
    same = {"dispatch_trace": {"seq": 7, "delivered_after_launch": 5}}
    assert share.read(_ctx(same, same)) is None      # no dispatch at all
    assert share.read(_ctx({}, {})) is None          # no such section
    # the parent's section: dispatches and hits, and no such counter
    assert share.read(_ctx({"dispatch_trace": {"seq": 1, "ahead_hits": 1}},
                           {"dispatch_trace": {"seq": 9, "ahead_hits": 8}})
                      ) is None
