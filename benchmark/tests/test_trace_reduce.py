"""trace_reduce on the small recorded trace (see make_small_xplane.py for
its events; every number below is worked by hand there)."""
from pathlib import Path

import pytest

import trace_reduce

PB = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(str(PB)))


def test_only_device_planes_count(reduced):
    assert reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(450e-6)


def test_busy_is_the_union_and_idle_the_worst_chip(reduced):
    assert reduced["busy_s_per_chip"] == pytest.approx([230e-6, 450e-6])
    assert reduced["busy_s"] == pytest.approx(340e-6)
    assert reduced["idle_pct_worst"] == pytest.approx(100 * (1 - 230 / 450))


def test_longest_gaps_first(reduced):
    gaps = reduced["longest_gaps"]
    assert [round(d * 1e6) for _, d in gaps] == [170, 50]
    assert gaps[0][0] == pytest.approx(1_000_000 + 230_000)   # ns, trace clock
    assert reduced["gap_count"] == 2


def test_op_ranking_uses_own_time_averaged_over_chips(reduced):
    own = dict(reduced["op_self_s"])
    # chip 0: while.1 100 - 30 - 40 = 30; fusion.1 30 + 30; chip 1: fusion.1 450
    assert own["fusion.1"] == pytest.approx((60e-6 + 450e-6) / 2)
    assert own["while.1"] == pytest.approx(30e-6 / 2)
    assert own["_paged_call.9"] == pytest.approx(40e-6 / 2)
    assert reduced["op_self_s"][0][0] == "fusion.1"
    assert reduced["op_self_total_s"] == pytest.approx(340e-6)


def test_kernel_and_collective_shares_read_the_ops_by_name(reduced):
    from layer_metrics import attn_kernel_busy_share_pct as attn
    from layer_metrics import collective_busy_share_pct as coll
    ctx = {"trace": reduced}
    assert attn.read(ctx) == pytest.approx(100 * 20 / 340)
    assert coll.read(ctx) == pytest.approx(100 * 25 / 340)
    assert attn.read({"trace": {}}) is None


def test_op_names_are_cut_at_the_equals_sign():
    line = "%_paged_call.9 = bf16[32,4,8,128]{3,2,1,0} custom-call(s32[32,256] %x)"
    assert trace_reduce.short_name(line) == "_paged_call.9"
    assert trace_reduce.short_name("jit_mixed_step(1)") == "jit_mixed_step(1)"


def test_module_executions_keep_their_name_without_fingerprint(reduced):
    durs = [d for _, d in reduced["modules"]["jit_mixed_step"]]
    assert durs == pytest.approx([230_000, 50_000])


def test_union_and_self_times_directly():
    assert trace_reduce.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    own = trace_reduce.self_times([("a", 0, 10), ("b", 1, 3), ("c", 2, 1),
                                   ("b", 6, 2)])
    assert own == {"a": 5, "b": 4, "c": 1}
