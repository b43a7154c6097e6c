"""The five readers of ``/stats.request_path`` on the synthetic run of
``idle_runs`` (a 0.6 s wait of the engine for a request inside the traced
window), with the record the replica's HTTP server keeps laid beside its
dispatch record: counters at the window's two edges, and three requests'
ingress rows on the same clock.  Two of them reach the engine at the end
of the empty wait and overlap each other; the third came while the device
was busy."""
import pytest

from idle_runs import idle_s, laid_out, make_ctx
from layer_metrics import (http_egress_mean_ms, http_handler_cpu_ms_per_dispatch,
                           http_ingress_mean_ms, http_writes_per_token,
                           idle_engine_empty_attributed_pct,
                           idle_ingress_attributed_pct)
import request_path

FIELDS = ["t_gateway", "t_accept", "t_parsed", "t_submit", "prompt_tokens",
          "streamed"]
READERS = (http_ingress_mean_ms, http_egress_mean_ms,
           http_handler_cpu_ms_per_dispatch, http_writes_per_token,
           idle_ingress_attributed_pct)
OPEN = dict(ingress_count=10, gateway_s=0.01, read_parse_s=0.02,
            submit_s=0.005, handoffs=100, egress_s=0.5, egress_max_s=0.03,
            tokens=400, lines=400, writes=800, bytes=16000,
            handler_cpu_s=1.0)
CLOSE = dict(ingress_count=30, gateway_s=0.05, read_parse_s=0.08,
             submit_s=0.025, handoffs=350, egress_s=1.5, egress_max_s=0.07,
             tokens=1400, lines=1400, writes=2800, bytes=56000,
             handler_cpu_s=1.6)


def ingress_rows():
    """Two requests that reach the engine as its empty wait ends, 40 ms
    of gateway and handler between them (30 + 30, 20 of them shared), and
    one that overlaps no wait."""
    rows, _, idles, _ = laid_out()
    end = idles[0][1]
    busy = rows[8][1] + 0.002
    return [[busy, busy + 0.001, busy + 0.002, busy + 0.003, 300, 1],
            [end - 0.040, end - 0.030, end - 0.020, end - 0.010, 200, 1],
            [end - 0.030, end - 0.020, end - 0.015, end, 100, 1]]


def with_path(ctx, rows=None):
    """``ctx`` with the record in every snapshot: the counters at the
    window's edges, the ring split over the polls as they would see it."""
    rows = ingress_rows() if rows is None else rows
    ctx["stats_open"] = dict(ctx["stats_open"], request_path=dict(
        OPEN, fields=FIELDS, recent=[]))
    polls = ctx["marks"]["polls"]
    polls[0] = dict(polls[0], request_path=dict(CLOSE, fields=FIELDS,
                                                recent=rows[:2]))
    last = dict(ctx["stats_close"], request_path=dict(
        CLOSE, fields=FIELDS, recent=rows[1:]))
    ctx["stats_close"] = ctx["stats_end"] = last
    ctx["marks"]["stats_trace_stop"] = polls[-1] = last
    return ctx


def test_the_four_counter_metrics_are_the_hand_values(capsys):
    ctx = with_path(make_ctx())
    # (0.04 + 0.06 + 0.02) s over 20 requests
    assert http_ingress_mean_ms.read(ctx) == pytest.approx(6.0)
    line = capsys.readouterr().out
    assert ("20 requests in the window, ms each: gateway 2.000, read + "
            "parse 3.000, submit 1.000") in line
    assert "250 hand-offs, egress mean 4.000 ms (longest since the " \
           "start 70.000)" in line
    assert ("4.000 lines a hand-off, 2.000 writes and 40.000 bytes a "
            "token; handler CPU 20.000 ms a dispatch") in line
    assert http_egress_mean_ms.read(ctx) == pytest.approx(4.0)
    assert http_writes_per_token.read(ctx) == pytest.approx(2.0)
    # 0.6 s of the handlers' CPU over the window's 30 dispatches
    assert http_handler_cpu_ms_per_dispatch.read(ctx) == pytest.approx(20.0)


def test_the_handlers_cpu_is_printed_beside_the_plans_wall_less_cpu(capsys):
    ctx = with_path(make_ctx())
    for key, wall, cpu in (("stats_open", 1.0, 0.9), ("stats_close", 1.9, 1.2)):
        ctx[key]["dispatch_trace"] = dict(
            ctx[key]["dispatch_trace"],
            spans={"ahead_plan": {"n": 1, "wall_s": wall, "cpu_s": cpu,
                                  "max_s": 0.1},
                   "deliver": {"n": 1, "wall_s": 0.3, "cpu_s": 0.3,
                               "max_s": 0.1}})
    assert http_handler_cpu_ms_per_dispatch.read(ctx) == pytest.approx(20.0)
    # (1.9 - 1.2) - (1.0 - 0.9) = 0.6 s over 30 dispatches
    assert ("took 20.000 ms of CPU a dispatch; the scheduler's wall less "
            "CPU a dispatch: ahead_plan 20.000 ms, deliver 0.000 ms"
            ) in capsys.readouterr().out


def test_idle_seconds_with_a_request_inside_are_a_union_not_a_sum(capsys):
    ctx = with_path(make_ctx())
    # [end - 40 ms, end] of the 0.6 s wait; the busy one counts nothing
    assert idle_ingress_attributed_pct.read(ctx) == pytest.approx(
        100 * 0.040 / idle_s(ctx), rel=1e-3)
    line = capsys.readouterr().out
    assert "of 0.6000 s with the engine empty" in line
    assert "0.0400 s had a request inside the gateway or the handler " \
           "(3 requests' ingress there) and 0.5600 s had none" in line
    # a part of the engine-empty share, never more
    assert idle_ingress_attributed_pct.read(ctx) <= (
        idle_engine_empty_attributed_pct.read(ctx))


def test_an_ingress_row_that_overlaps_no_wait_counts_nothing():
    ctx = with_path(make_ctx(), rows=ingress_rows()[:1])
    assert idle_ingress_attributed_pct.read(ctx) == 0.0
    # ... and one that lies outside the traced window neither
    rows, _, idles, _ = laid_out()
    late = rows[-1][2] + 5.0
    ctx = with_path(make_ctx(), rows=[[late, late, late, late + 1, 1, 1]])
    assert idle_ingress_attributed_pct.read(ctx) == 0.0


def test_the_union_of_the_rings_keeps_a_row_once():
    got = request_path.rows(with_path(make_ctx()))
    assert [r["prompt_tokens"] for r in got] == [300, 200, 100]
    assert request_path.merged([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [
        [0, 2], [3, 4]]
    assert request_path.seconds_in_all([(0, 10)], [(1, 2), (1.5, 3), (8, 12)],
                                       0, 9) == pytest.approx(3.0)


@pytest.mark.parametrize("reader", READERS,
                         ids=lambda r: r.__name__.rsplit(".", 1)[-1])
def test_nothing_to_read_is_none_and_never_raises(reader):
    # the parent's program: a dispatch record and no request-path record
    assert reader.read(make_ctx()) is None
    assert reader.read(make_ctx(keys=False)) is None


def test_the_idle_share_needs_a_trace_and_the_counters_a_window():
    ctx = with_path(make_ctx())
    ctx["trace"] = {}
    assert idle_ingress_attributed_pct.read(ctx) is None
    assert http_ingress_mean_ms.read(ctx) == pytest.approx(6.0)
    # a window in which nothing was counted divides by nothing
    ctx = with_path(make_ctx())
    ctx["stats_open"]["request_path"] = dict(CLOSE, fields=FIELDS, recent=[])
    for reader in READERS[:2] + READERS[3:4]:
        assert reader.read(ctx) is None
