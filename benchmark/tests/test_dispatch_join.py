"""dispatch_join and the six readers on a synthetic run.

Thirty scheduler iterations laid out by hand on a monotonic clock, every
host phase of a fixed length: pack 4 ms, launch 5 ms (the device starts
3 ms into it), the execution, 0.5 ms (and a few hundredths) until the host
sees it done, drain 3 ms, bookkeeping 1 ms, intake 2 ms.  Every fourth
iteration carries two prefill segments and runs ~290 ms, the others
~280 ms.  The records reach the benchmark over three ``/stats`` snapshots
that overlap; the trace holds executions 3..27 and started 1.3 s before
the stamp the replica took after ``start_trace`` returned."""
import pytest

import dispatch_join as dj
from layer_metrics import (decode_kernel_hbm_pct, idle_host_attributed_pct,
                           sched_host_ms_per_dispatch,
                           sched_queue_wait_mean_ms, step_decode_ms_p50,
                           step_prefill_ms_p50)

FIELDS = ["seq", "t_launch", "t_done", "bookkeeping", "intake", "pack",
          "launch", "wait", "drain", "with_finals", "segments", "finals",
          "prefill_tokens", "active_rows", "steps", "kv_tokens"]
PACK, LAUNCH, TO_DEVICE, LAG, DRAIN, BOOK, INTAKE = (
    0.004, 0.005, 0.003, 0.0005, 0.003, 0.001, 0.002)
RUNNING, SKEW, STARTUP = 4000.0, -1.3, 1.5
MODEL = {"hidden_size": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim_override": 16, "intermediate_size": 128,
         "num_layers": 2, "family": "qwen2"}
KV_BYTES = 2 * 2 * 2 * 16 * 2          # layers x (K, V) x heads x dim x bf16


def timeline():
    """``[(record row, device start, device seconds)]`` of 30 iterations."""
    out, t_launch = [], 4010.0
    for i in range(30):
        prefill = i % 4 == 0
        dur = (0.290 + 0.001 * (i % 5)) if prefill else (
            0.280 + 0.001 * (i % 7))
        dev_start = t_launch + TO_DEVICE
        t_done = dev_start + dur + LAG + 0.00002 * (i % 3)
        row = [101 + i, round(t_launch, 5), round(t_done, 5), BOOK, INTAKE,
               PACK, LAUNCH, round(t_done - t_launch - LAUNCH, 5), DRAIN,
               int(prefill), 2 if prefill else 0, int(prefill),
               300 if prefill else 0, 8, 4, 1000 + 10 * i]
        out.append((row, dev_start, dur))
        t_launch = t_done + DRAIN + BOOK + INTAKE + PACK
    return out


def stats(rows, seq):
    return {"dispatch_trace": {
        "seq": seq, "fields": FIELDS, "recent": rows,
        "phase_s": {"bookkeeping": BOOK * seq,
                    "intake": INTAKE * seq, "pack": PACK * seq,
                    "launch": LAUNCH * seq, "wait": 0.28 * seq,
                    "drain": DRAIN * seq},
        "queue_wait_ms_sum": 150.0 * seq, "queue_wait_count": seq // 2}}


def make_ctx(snapshots=((0, 15), (10, 25), (18, 30)), traced=(3, 28)):
    tl = timeline()
    rows = [r for r, _, _ in tl]
    offset = RUNNING + SKEW
    execs = [[(s - offset) * 1e9, d * 1e9] for _, s, d in tl[slice(*traced)]]
    w0, w1 = execs[0][0], execs[-1][0] + execs[-1][1]
    busy = sum(d for _, d in execs) / 1e9
    gaps = sorted(([a[0] + a[1], (b[0] - a[0] - a[1]) / 1e9]
                   for a, b in zip(execs, execs[1:])), key=lambda g: -g[1])
    snaps = [stats(rows[a:b], 100 + b) for a, b in snapshots]
    return {
        "config": {"model_config": MODEL, "serve_flags": ["--greedy"]},
        "cell": {"chips": 1}, "health": {"device_kind": "TPU v5 lite"},
        "stats_open": stats([], 100), "stats_close": stats(rows[-5:], 130),
        "stats_end": stats(rows[-5:], 130),
        "marks": {
            "trace_started": {
                "start": {"monotonic": RUNNING - STARTUP},
                "running": {"monotonic": RUNNING}},
            "stats_trace_start": snaps[0], "polls": snaps[1:],
            "stats_trace_stop": snaps[-1]},
        "trace": {
            "modules": {"jit_mixed_step": execs, "jit_other": [[0.0, 5.0]]},
            "window_ns": [w0, w1], "window_s": (w1 - w0) / 1e9,
            "idle_pct_worst": 100 * (1 - busy / ((w1 - w0) / 1e9)),
            "longest_gaps": gaps[:5],
            "op_self_s": [["fusion.1", 1.0], ["_paged_call.1", 0.4],
                          ["_paged_call.7", 0.1],
                          ["_paged_prefill_call.2", 0.2]]},
    }


def test_records_are_the_union_of_the_rings_by_seq():
    recs = dj.records(make_ctx())
    assert [r["seq"] for r in recs] == list(range(101, 131))
    assert recs[12]["kv_tokens"] == 1120 and recs[12]["segments"] == 2


def test_every_execution_gets_its_own_record_despite_the_skew(capsys):
    ctx = make_ctx()
    j = dj.join(ctx)
    assert [r["seq"] for _, _, r in j["pairs"]] == list(range(104, 129))
    assert j["share"] == 1.0
    # every pair bounds the offset: the device starts 3 ms after t_launch
    # and ends 0.5 ms before t_done, so the middle lies 1.25 ms early
    assert j["skew_s"] == pytest.approx(SKEW + (LAG - TO_DEVICE) / 2,
                                        abs=2e-5)
    assert j["lag_spread_s"] < 1e-4
    assert dj.join(ctx) is j                      # worked out once
    line = capsys.readouterr().out
    assert line.count("[join]") == 1
    assert "matched 25 of 25 executions, skew -1.3013 s" in line


def test_a_record_the_rings_missed_costs_one_match_and_no_more(capsys):
    ctx = make_ctx(snapshots=((0, 12), (14, 30)))   # 113 and 114 never seen
    j = dj.join(ctx)
    assert [r["seq"] for _, _, r in j["pairs"]] == (
        list(range(104, 113)) + list(range(115, 129)))
    assert j["skew_s"] == pytest.approx(SKEW + (LAG - TO_DEVICE) / 2,
                                        abs=2e-5)
    assert "matched 23 of 25 executions" in capsys.readouterr().out


def test_medians_by_what_the_execution_carried():
    ctx = make_ctx()
    tl = timeline()[3:28]
    srt = lambda prefill: sorted(  # noqa: E731
        d for r, _, d in tl if (r[10] > 0) == prefill)
    decode, prefill = srt(False), srt(True)
    assert len(decode) == 19 and len(prefill) == 6
    assert step_decode_ms_p50.read(ctx) == pytest.approx(decode[9] * 1e3)
    assert step_prefill_ms_p50.read(ctx) == pytest.approx(
        (prefill[2] + prefill[3]) / 2 * 1e3)
    assert step_prefill_ms_p50.read(ctx) > step_decode_ms_p50.read(ctx)
    # under five executions of a kind there is no median to give
    assert step_prefill_ms_p50.read(make_ctx(traced=(3, 19))) is None


def test_decode_kernel_hbm_pct_is_the_hand_value():
    ctx = make_ctx()
    tokens_read = sum((1000 + 10 * i) * 4 for i in range(3, 28))
    want = 100 * tokens_read * KV_BYTES / ((0.4 + 0.1) * 819e9)
    assert decode_kernel_hbm_pct.read(ctx) == pytest.approx(want)
    assert decode_kernel_hbm_pct.kv_element_bytes(
        ["--kv-dtype", "int8"]) == 1
    four = make_ctx()
    four["cell"] = {"chips": 4}           # a chip holds a quarter
    assert decode_kernel_hbm_pct.read(four) == pytest.approx(want / 4)


def test_under_90_percent_matched_nothing_reads_the_join(capsys):
    ctx = make_ctx(snapshots=((0, 15),))  # records end at execution 14
    assert dj.join(ctx)["pairs"] == []
    assert "no metric reads the join" in capsys.readouterr().out
    for reader in (step_decode_ms_p50, step_prefill_ms_p50,
                   decode_kernel_hbm_pct, idle_host_attributed_pct):
        assert reader.read(ctx) is None


def test_a_program_without_the_records_gives_none_and_does_not_raise():
    ctx = make_ctx()
    bare = {"mixed": {"dispatches": 3}}
    ctx.update(stats_open=bare, stats_close=bare, stats_end=bare)
    ctx["marks"].update(stats_trace_start=bare, polls=[bare, bare],
                        stats_trace_stop=bare)
    for reader in (sched_queue_wait_mean_ms, sched_host_ms_per_dispatch,
                   step_decode_ms_p50, step_prefill_ms_p50,
                   decode_kernel_hbm_pct, idle_host_attributed_pct):
        assert reader.read(ctx) is None
    untraced = dict(make_ctx(), trace={}, marks={"polls": []})
    for reader in (sched_host_ms_per_dispatch, step_decode_ms_p50,
                   decode_kernel_hbm_pct, idle_host_attributed_pct):
        assert reader.read(untraced) is None


def test_counters_between_two_snapshots(capsys):
    ctx = make_ctx()
    # window's edges: seq 100 -> 130, 150 ms a dispatch, a wait every other
    assert sched_queue_wait_mean_ms.read(ctx) == pytest.approx(300.0)
    # trace's edges: seq 115 -> 130; every phase but wait
    assert sched_host_ms_per_dispatch.read(ctx) == pytest.approx(
        (BOOK + INTAKE + PACK + LAUNCH + DRAIN) * 1e3)
    assert "[host] ms per dispatch over 15 dispatches" in (
        capsys.readouterr().out)


def test_idle_time_is_attributed_to_the_host_phases(capsys):
    ctx = make_ctx()
    # 24 whole turn-arounds of 15 ms host time (2 ms of launch overlap the
    # next execution) and the end of launch 104 that lies in the window:
    # 2 ms, and the 1.25 ms by which the join places the trace early
    host_s = 24 * (DRAIN + BOOK + INTAKE + PACK + LAUNCH) + (
        LAUNCH - TO_DEVICE - (LAG - TO_DEVICE) / 2)
    tr = ctx["trace"]
    idle_s = tr["window_s"] * tr["idle_pct_worst"] / 100
    assert idle_s == pytest.approx(24 * 0.0135 + 0.00002 * 24, abs=1e-4)
    assert idle_host_attributed_pct.read(ctx) == pytest.approx(
        100 * host_s / idle_s, rel=1e-3)
    # a gap's midpoint lies 6.75 ms after the device stopped, placed 1.25
    # ms early: past the lag and drain (3.5 ms), bookkeeping (4.5), inside
    # intake (6.5)
    out = capsys.readouterr().out
    assert out.count("'intake'") == 5 and "requests_in_flight" not in out
    rec = dj.records(ctx)[5]
    assert idle_host_attributed_pct.phase_at([rec], rec["t_done"] + 0.001) \
        == "drain"
    assert idle_host_attributed_pct.phase_at([rec], rec["t_launch"] - 0.005) \
        == "intake"
    assert idle_host_attributed_pct.phase_at([rec], rec["t_launch"] - 9) \
        == "no_phase"
