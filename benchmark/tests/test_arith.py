"""Percentile and per-request arithmetic on hand-made records."""
import pytest

import arith
from client import Record


def rec(due, times, sent=None):
    r = Record(due=due, prompt_len=4, max_new=len(times))
    r.sent = due if sent is None else sent
    r.token_times = list(times)
    r.tokens = [1] * len(times)
    r.status = 200
    return r


def test_percentile_interpolates_between_ranks():
    xs = list(range(1, 201))                  # 1..200
    assert arith.percentile(xs, 50, min_beyond=0) == pytest.approx(100.5)
    assert arith.percentile(xs, 95) == pytest.approx(190.05)


@pytest.mark.parametrize("n,q,refused", [
    (199, 95, True),      # 9.95 samples beyond the 95th: a maximum in disguise
    (200, 95, False),     # exactly ten beyond
    (20, 50, False), (19, 50, True), (999, 99, True), (1000, 99, False)])
def test_percentile_needs_ten_samples_beyond(n, q, refused):
    got = arith.percentile(list(range(n)), q)
    assert (got is None) == refused


def test_ttft_counts_from_the_due_instant_not_the_send():
    r = rec(due=10.0, times=[10.5, 10.6], sent=10.3)
    assert arith.ttft_ms(r) == pytest.approx(500.0)
    assert arith.late_ms(r) == pytest.approx(300.0)


def test_tpot_is_per_request_over_fused_blocks():
    # decode block 4: tokens arrive four at a time, 100 ms apart
    r = rec(0.0, [1.0] * 1 + [1.1] * 4 + [1.2] * 4)
    assert arith.tpot_ms(r) == pytest.approx(200.0 / 8)
    assert arith.tpot_ms(rec(0.0, [1.0])) is None


def test_tokens_in_window_counts_arrivals_only_inside():
    a = rec(0.0, [0.5, 1.0, 1.5, 2.0])        # due before the window
    b = rec(1.2, [1.9, 2.5])
    assert arith.tokens_in_window([a, b], 1.0, 2.0) == 3   # 1.0, 1.5, 1.9


def test_spread_is_interquartile_range_over_median():
    assert arith.spread([10, 10, 10, 10]) == 0
    assert arith.spread([1, 2, 3, 4, 5]) == pytest.approx((4 - 2) / 3)
