"""``sched_slab_fill_pct`` reads the window's edges, and nothing where
the program keeps no such counter (the parent of the PR that brought
it)."""
from layer_metrics import sched_slab_fill_pct as fill


def _ctx(a, b):
    return {"stats_open": a, "stats_close": b, "marks": {}}


def test_share_of_the_slabs_rows_that_held_a_prompt_token():
    ctx = _ctx({"dispatch_trace": {"seq": 40, "prefill_tokens": 1000,
                                   "slab_rows": 2048}},
               {"dispatch_trace": {"seq": 840, "prefill_tokens": 20200,
                                   "slab_rows": 27648}})
    assert fill.read(ctx) == 75.0


def test_nothing_to_read_is_none_and_never_raises():
    same = {"dispatch_trace": {"seq": 7, "prefill_tokens": 300,
                               "slab_rows": 512}}
    assert fill.read(_ctx(same, same)) is None       # no slab at all
    assert fill.read(_ctx({}, {})) is None           # no such section
    # the parent's section: dispatches, and no such counters
    assert fill.read(_ctx({"dispatch_trace": {"seq": 1, "decode_only": 1}},
                          {"dispatch_trace": {"seq": 9, "decode_only": 8}})
                     ) is None
