"""``sched_decode_only_share_pct`` reads the window's edges, and nothing
where the program keeps no dispatch record."""
from layer_metrics import sched_decode_only_share_pct as share


def _ctx(a, b):
    return {"stats_open": a, "stats_close": b, "marks": {}}


def test_share_of_the_windows_dispatches_that_packed_nothing():
    ctx = _ctx({"dispatch_trace": {"seq": 40, "decode_only": 30,
                                   "prefill": 10}},
               {"dispatch_trace": {"seq": 440, "decode_only": 330,
                                   "prefill": 110}})
    assert share.read(ctx) == 75.0


def test_nothing_to_read_is_none_and_never_raises():
    same = {"dispatch_trace": {"seq": 7, "decode_only": 5}}
    assert share.read(_ctx(same, same)) is None      # no dispatch at all
    assert share.read(_ctx({}, {})) is None          # no such section
    assert share.read(_ctx({"dispatch_trace": {"seq": 1}},
                           {"dispatch_trace": {"seq": 9}})) is None
