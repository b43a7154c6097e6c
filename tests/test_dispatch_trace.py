"""The scheduler's dispatch record (docs/DESIGN.md §20).

One record per mixed dispatch that reached the device, kept by
``telemetry.tracing.DispatchTrace`` and shown under ``/stats`` as
``dispatch_trace``.  Pinned here, at toy size on the CPU (counts and
order only; a time from this file is a time of XLA's CPU backend):

- the records add up to the counters that were there before
  (``mixed.dispatches``, ``mixed.prefill_tokens``, ``device_loop_steps``);
- ``kv_token_steps`` is the hand count of what two scripted requests
  made the decode kernel read;
- the host phases tile the iteration: they never exceed the wall time
  between a record and its neighbour, and an idle engine's blocking wait
  is in none of them;
- the ring is bounded and ``/stats`` stays small JSON;
- a request's queue wait ends at the launch of its first dispatch, so
  the SLO ledger books a prefill time in the mixed path;
- ``GET /trace`` ties a request to its dispatches by id;
- a ``jax.profiler`` capture holds the ``sched.*`` rows and changes no
  generated id;
- every dispatch is counted once, as launched as prepared under its
  predecessor's execution (a hit), as packed in the gap for a named
  reason (a miss), or as the first after an idle wait; a hit's ``ahead``
  seconds lie under the previous execution, and a dispatch launched
  before its predecessor is committed keeps its own seconds;
- what the host was doing while the device sat idle: a record says
  whether its read came late (``late``, ``await``), an idle engine's
  wait is an interval on the clock (``idles``), every kind of host work
  has wall and CPU seconds (``spans``), garbage collections are timed
  (``gc``), and a span of ``STALL_S`` leaves a row that names where its
  seconds went (``stalls``).
"""

import gc
import json
import sys
import threading
import time
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.loader import load_or_init
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.telemetry.slo import get_slo_ledger
from distributed_inference_demo_tpu.telemetry import tracing
from distributed_inference_demo_tpu.telemetry.flightrecorder import (
    get_flight_recorder)
from distributed_inference_demo_tpu.telemetry.tracing import (
    AHEAD_MISS_REASONS, DISPATCH_FIELDS, DISPATCH_LAST_FIELDS, DISPATCH_PHASES,
    DISPATCH_SPANS,
    AWAIT_STALL_S, LOOP_DISPATCH_FIELDS, MOE_DISPATCH_FIELDS, STALL_CAUSES,
    STALL_FIELDS, STALL_S, DispatchTrace)

# bf16 weights and pages, and the int8-weight family the chip cells serve
MODELS = ("llama-test", "qwen2-test-int8")
LONG, SHORT = list(range(2, 24)), [3, 14, 15]      # 22 and 3 tokens
ROUNDING = 2e-5 + 6e-5      # two instants and six phases at 1e-5


def engine(model="llama-test", **kw):
    cfg = get_model_config(model)
    kw.setdefault("max_seq", 96)
    kw.setdefault("max_batch", 4)
    kw.setdefault("sampling", SamplingParams(greedy=True))
    kw.setdefault("prompt_buckets", (16, 48))
    kw.setdefault("kv_block_tokens", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_block", 4)
    kw.setdefault("mixed_token_budget", 24)
    return ContinuousBatchingEngine(cfg, load_or_init(model, cfg, seed=0),
                                    **kw)


def ticking(start: float, tick: float) -> types.SimpleNamespace:
    """A ``time`` whose monotonic clock advances one ``tick`` a read and
    whose process clock stands still."""
    ticks = iter(range(10 ** 6))
    return types.SimpleNamespace(
        monotonic=lambda: start + tick * next(ticks),
        process_time=lambda: 0.0)


def settled_stats(eng) -> dict:
    """``stats()`` once the scheduler has committed its last dispatch: a
    request's ``wait`` returns while that dispatch is still draining."""
    deadline = time.monotonic() + 10
    while True:
        st = eng.stats()
        if (st["dispatch_trace"]["seq"] == st["mixed"]["dispatches"]
                or time.monotonic() > deadline):
            return st
        time.sleep(0.01)


def rows(stats) -> list:
    """``dispatch_trace.recent`` as dicts keyed by ``fields``."""
    dt = stats["dispatch_trace"]
    return [dict(zip(dt["fields"], r)) for r in dt["recent"]]


@pytest.fixture(scope="module")
def scripted():
    """One engine, two requests one after the other (so the packing does
    not depend on thread timing), a pause with nothing to do between
    them; ``(stats, requests, exported trace)``."""
    with engine() as eng:
        first = eng.submit(LONG, 10, trace_id=11)
        first.wait(timeout=300)
        time.sleep(0.3)
        second = eng.submit(SHORT, 6, trace_id=12)
        second.wait(timeout=300)
        yield settled_stats(eng), (first, second), eng.export_trace()


@pytest.mark.quick
@pytest.mark.parametrize("model", MODELS)
def test_records_add_up_to_the_counters_that_were_there(model):
    prompts = [SHORT, LONG, [9, 2, 6, 5, 3, 5], list(range(40, 75))]
    with engine(model) as eng:
        for r in [eng.submit(p, n) for p, n in zip(prompts, (10, 12, 8, 9))]:
            r.wait(timeout=300)
        st = settled_stats(eng)
    dt, recs = st["dispatch_trace"], rows(st)
    assert tuple(dt["fields"]) == DISPATCH_FIELDS + DISPATCH_LAST_FIELDS
    assert dt["seq"] == st["mixed"]["dispatches"] == len(recs)
    assert [r["seq"] for r in recs] == list(range(1, dt["seq"] + 1))
    assert (sum(r["prefill_tokens"] for r in recs)
            == st["mixed"]["prefill_tokens"] == sum(map(len, prompts)))
    assert (sum(r["steps"] for r in recs)
            == st["device_loop"]["device_loop_steps"])
    assert dt["decode_only"] + dt["prefill"] == dt["seq"]
    assert dt["prefill"] == sum(1 for r in recs if r["segments"])
    assert sum(r["finals"] for r in recs) == len(prompts)
    assert all(r["with_finals"] == (r["finals"] > 0) for r in recs)
    assert dt["kv_token_steps"] == sum(r["kv_tokens"] * r["steps"]
                                       for r in recs)
    assert dt["queue_wait_count"] == len(prompts)
    # the slab a dispatch ran is the segments it packed (chunk 8), and
    # the prompt tokens are what of it held a token
    assert dt["slab_rows"] == 8 * sum(r["segments"] for r in recs)
    assert dt["prefill_tokens"] == st["mixed"]["prefill_tokens"]
    assert 0 < dt["prefill_tokens"] < dt["slab_rows"]


def test_kv_token_steps_is_the_hand_count(scripted):
    """22-token prompt, 10 new: one dispatch packs 8 + 8 + 6 (the final
    installs the row: 22 tokens of KV) and decodes 4 steps; the next
    finds 22 + 5 tokens and decodes 4; the last finds 22 + 9 and has one
    token of budget left.  3-token prompt, 6 new: 3 tokens x 4 steps,
    then 3 + 5 tokens x 1 step."""
    stats, _, _ = scripted
    recs = rows(stats)
    assert [(r["segments"], r["finals"], r["prefill_tokens"],
             r["active_rows"], r["steps"], r["kv_tokens"])
            for r in recs] == [(3, 1, 22, 0, 4, 22), (0, 0, 0, 1, 4, 27),
                               (0, 0, 0, 1, 1, 31), (1, 1, 3, 0, 4, 3),
                               (0, 0, 0, 1, 1, 8)]
    assert (stats["dispatch_trace"]["kv_token_steps"]
            == 22 * 4 + 27 * 4 + 31 * 1 + 3 * 4 + 8 * 1)
    assert stats["dispatch_trace"]["decode_only"] == 3
    assert stats["dispatch_trace"]["prefill"] == 2
    # a slab of three segments for 22 tokens, of one for 3
    assert stats["dispatch_trace"]["slab_rows"] == (3 + 1) * 8
    assert stats["dispatch_trace"]["prefill_tokens"] == 22 + 3


def test_prefill_pages_walked_is_the_hand_count(scripted):
    """Chunk 8 over pages of 8 tokens, a table of 96 / 8 = 12 pages: the
    22-token prompt's segments start at 0, 8 and 16 and their tiles end
    on pages 1, 2 and 3; the 3-token prompt's one segment walks one page.
    A dispatch without a slab walks none.  Beside it the steps a grid of
    one page of the table a step would have had: 4 tiles x 12."""
    stats, _, _ = scripted
    assert [r["prefill_pages_walked"] for r in rows(stats)] == [
        1 + 2 + 3, 0, 0, 1, 0]
    assert stats["dispatch_trace"]["prefill_pages_walked"] == 7
    assert stats["dispatch_trace"]["prefill_pages_grid"] == 4 * 12


def test_head_rows_is_the_hand_count(scripted):
    """PR 48: the head runs on one position a segment of the slab, not
    on the chunk's eight, and on every slot (four here) at each decode
    step: 3 segments + 4 steps x 4 slots, then 4 x 4, 1 x 4, 1 + 4 x 4,
    1 x 4.  With the head over every position the two slabs would have
    read 24 and 8 where they read 3 and 1."""
    stats, _, _ = scripted
    recs = rows(stats)
    assert [r["head_rows"] for r in recs] == [
        r["segments"] + r["steps"] * 4 for r in recs] == [19, 16, 4, 17, 4]
    assert stats["dispatch_trace"]["head_rows"] == 60


def test_phases_fit_between_a_record_and_its_neighbour(scripted):
    recs = rows(scripted[0])
    for r in recs:
        assert all(r[p] >= 0 for p in DISPATCH_PHASES)
        assert r["t_launch"] <= r["t_done"]
        # launch and wait are exactly what lies between the two instants
        assert (abs(r["launch"] + r["wait"] - (r["t_done"] - r["t_launch"]))
                <= ROUNDING)
    for a, b in zip(recs, recs[1:]):
        between = (a["drain"] + b["bookkeeping"] + b["intake"] + b["pack"])
        assert between <= b["t_launch"] - a["t_done"] + ROUNDING


def test_an_idle_engines_wait_is_no_phase(scripted):
    stats, (first, second), _ = scripted
    dt = stats["dispatch_trace"]
    assert dt["idle_wait_s"] >= 0.25            # the scripted pause
    fourth = rows(stats)[3]                      # the first dispatch after
    assert fourth["bookkeeping"] + fourth["intake"] < 0.25
    assert second.t_sched - second.t_submit < 0.25


def test_ring_is_bounded_and_stats_stay_small_json(monkeypatch):
    # values as wide as a long-lived chip replica's: two weeks of uptime,
    # every phase with all its digits, a full four-chip batch
    monkeypatch.setattr(tracing, "time",
                        ticking(1234567.123456, 0.00123457))
    tr = DispatchTrace()
    tr.seq = 99_000
    for _ in range(300):
        for phase in DISPATCH_PHASES[:-1]:
            tr.enter(phase)
        tr.commit(t_launch=tr.enter("drain") - 0.3, t_done=tr._at[0],
                  with_finals=True, segments=3, finals=3,
                  prefill_tokens=768, active_rows=64, steps=4,
                  kv_tokens=262144)
    monkeypatch.undo()
    snap = tr.snapshot()
    assert snap["seq"] == 99_300 and len(snap["recent"]) == 128
    assert [r[0] for r in snap["recent"]] == list(range(99_173, 99_301))
    assert all(r[3:9] == [0.00123] * 6 for r in snap["recent"])
    # (a row is ~147 bytes since PR 61's column, `slab_carried_step`)
    assert 16 * 1024 < len(json.dumps(snap["recent"])) < 19 * 1024
    assert snap["kv_token_steps"] == 300 * 262144 * 4
    with engine() as eng:
        eng.submit(SHORT, 3).wait(timeout=300)
        st = settled_stats(eng)
        assert json.loads(json.dumps(st))["dispatch_trace"]["seq"] == st[
            "mixed"]["dispatches"]
        eng.reset_stats()
        st = eng.stats()
        assert st["dispatch_trace"]["seq"] == st["mixed"]["dispatches"] == 0
        assert st["dispatch_trace"]["recent"] == []


def test_queue_wait_ends_at_the_first_dispatch_with_one_slot():
    """One slot, two requests: the second's only segment is a final,
    which parks until the slot frees, so it waits out the first's whole
    decode; and from its launch to its first token is prefill, which
    the SLO ledger booked as 0 s in the mixed path before."""
    with engine(max_batch=1) as eng:
        first = eng.submit(LONG, 12)
        second = eng.submit(SHORT, 4)
        first.wait(timeout=300)
        second.wait(timeout=300)
        st = settled_stats(eng)
    for r in (first, second):
        assert r.t_submit < r.t_sched < r.t_first
        assert 1 <= r.first_seq <= r.final_seq
    assert second.first_seq > first.final_seq
    assert (second.t_sched - second.t_submit
            >= first.t_done - max(first.t_first, second.t_submit))
    lat = st["latency"]
    assert 0 < lat["queue_wait_p50_ms"] <= lat["queue_wait_p95_ms"]
    assert lat["queue_wait_p95_ms"] <= lat["ttft_p95_ms"]
    dt = st["dispatch_trace"]
    assert dt["queue_wait_count"] == 2
    assert dt["queue_wait_ms_sum"] == pytest.approx(
        sum(r.t_sched - r.t_submit for r in (first, second)) * 1e3,
        abs=0.01)
    closed = {t["rid"]: t for t in get_slo_ledger().recent(256)}
    for r in (first, second):
        assert closed[r.rid]["prefill_s"] > 0
        assert closed[r.rid]["queue_wait_s"] == pytest.approx(
            r.t_sched - r.t_submit)


def test_prefill_spans_carry_the_ids_of_their_dispatches(scripted):
    stats, (first, second), trace = scripted
    spans = {e["args"]["rid"]: e["args"] for e in trace["traceEvents"]
             if e.get("name") == "engine.prefill"}
    assert spans[first.rid]["first_seq"] == spans[first.rid]["final_seq"] == 1
    assert spans[second.rid]["first_seq"] == 4
    with engine() as eng:            # 40 tokens: five segments, budget 3
        req = eng.submit(list(range(40, 80)), 2, trace_id=13)
        req.wait(timeout=300)
        args = [e["args"] for e in eng.export_trace()["traceEvents"]
                if e.get("name") == "engine.prefill"][0]
    assert args["first_seq"] == req.first_seq == 1
    assert args["final_seq"] == req.final_seq == 2


def test_every_dispatch_is_a_hit_a_miss_or_a_first(scripted):
    """The scripted run: a first dispatch (it carries the prompt), two
    prepared under their predecessors, a pause, a first again, one
    prepared.  A record's ``ahead`` is positive exactly on a hit."""
    dt = scripted[0]["dispatch_trace"]
    recs = rows(scripted[0])
    assert tuple(dt["ahead_misses"]) == AHEAD_MISS_REASONS
    assert dt["ahead_first"] == 2 and dt["ahead_hits"] == 3
    assert sum(dt["ahead_misses"].values()) == 0
    assert [r["ahead"] > 0 for r in recs] == [False, True, True, False,
                                              True]


def test_ahead_seconds_lie_under_the_previous_execution(scripted):
    """What was prepared for record n+1 was prepared while n executed:
    inside n's ``wait``, which still runs from the call's return to
    ``t_done``; and the seventh key of ``phase_s`` holds at least the
    seconds the records name (it also holds the drains that ran under
    an execution, and plans that were refused)."""
    dt = scripted[0]["dispatch_trace"]
    recs = rows(scripted[0])
    for a, b in zip(recs, recs[1:]):
        assert 0 <= b["ahead"] <= a["wait"] + ROUNDING
        if b["ahead"] > 0:
            assert a["t_launch"] < a["t_done"] <= b["t_launch"]
            assert a["drain"] == 0     # it ran under b's execution
            assert b["bookkeeping"] == b["intake"] == 0
    assert set(dt["phase_s"]) == set(DISPATCH_PHASES) | {"ahead"}
    assert dt["phase_s"]["ahead"] >= sum(r["ahead"] for r in recs) - ROUNDING
    assert dt["phase_s"]["ahead"] <= dt["phase_s"]["wait"]


def _during_call(eng, n, act):
    """Run ``act`` on the scheduler's thread right after its ``n``-th
    call of ``mixed_step`` was enqueued: during that execution."""
    inner, calls = eng._mixed_step, [0]

    def hooked(*a):
        out = inner(*a)
        calls[0] += 1
        if calls[0] == n:
            act()
        return out

    eng._mixed_step = hooked


@pytest.mark.parametrize("reason", ["arrival", "cancel", "export",
                                    "finish"])
def test_a_miss_names_what_reached_the_scheduler(reason):
    """Two rows decode; during the third execution something reaches the
    scheduler (a request, a cancel, an export), or a row ends by ``eos``
    unannounced: the fourth dispatch is packed in the gap, in the old
    order, and counted under that reason."""
    probe = {}
    if reason == "finish":
        with engine() as eng:
            alone = eng.submit(SHORT, 12).wait(timeout=300).tolist()
        probe = {"eos": alone[6], "ends": alone.index(alone[6]) + 1}
    with engine(eos_id=probe.get("eos")) as eng:
        box = {}

        def act():
            if reason == "arrival":
                box["late"] = eng.submit([4, 4, 2], 3)
            elif reason == "cancel":
                box["short"].cancel()
            elif reason == "export":
                t = threading.Thread(
                    target=lambda: box.update(
                        ckpt=eng.export_request(box["long"])),
                    daemon=True)
                t.start()
                deadline = time.monotonic() + 10
                while not eng._export_q and time.monotonic() < deadline:
                    time.sleep(0.001)

        _during_call(eng, 3, act)
        box["long"] = eng.submit(LONG, 30)
        box["short"] = eng.submit(SHORT, 12)
        for r in list(box.values()):
            r.wait(timeout=300)
        if "late" in box:
            box["late"].wait(timeout=300)
        st = settled_stats(eng)
    dt = st["dispatch_trace"]
    assert dt["ahead_misses"][reason] >= 1, dt["ahead_misses"]
    assert (dt["ahead_hits"] + sum(dt["ahead_misses"].values())
            + dt["ahead_first"] == dt["seq"])
    assert dt["ahead_hits"] >= 2
    if reason == "finish":
        assert box["short"].tokens[-1] == probe["eos"]
        assert len(box["short"].tokens) == probe["ends"] < 12
    if reason == "export":
        assert box["ckpt"]["tokens"]


def test_a_dispatch_launched_before_its_predecessor_commits(monkeypatch):
    """The cursor's cut: ``launch`` opens the next dispatch's seconds,
    and a dispatch committed after its successor was launched (it was
    drained under the successor's execution) still gets its own; work
    under an execution is booked to ``ahead`` and is no tile."""
    monkeypatch.setattr(tracing, "time", ticking(100.0, 0.001))
    tr = DispatchTrace()
    fields = dict(with_finals=False, segments=0, finals=0,
                  prefill_tokens=0, active_rows=1, steps=4, kv_tokens=8)
    for phase in ("bookkeeping", "intake", "pack"):
        tr.enter(phase)
    t1 = tr.enter("launch")
    first = tr.launched_phases
    tr.enter("wait")
    with tr.ahead() as spent:          # the next one, prepared
        pass
    d1 = tr.enter("pack")              # its validation
    t2 = tr.enter("launch")            # launched as prepared
    tr.enter("wait")
    with tr.ahead():                   # the first one, drained
        pass
    assert tr.launched == 2 and tr.seq == 0
    tr.commit(t_launch=t1, t_done=d1, phases=first, how="first", **fields)
    d2 = tr.enter("drain")
    tr.commit(t_launch=t2, t_done=d2, ahead=spent[0], how="hit", **fields)
    monkeypatch.undo()
    snap = tr.snapshot()
    a, b = (dict(zip(snap["fields"], r)) for r in snap["recent"])
    assert (a["seq"], b["seq"]) == (1, 2)
    assert [a[p] for p in DISPATCH_PHASES] == [.001, .001, .001, .001,
                                               .003, 0]
    assert [b[p] for p in DISPATCH_PHASES] == [0, 0, .001, .001, .003,
                                               .001]
    assert a["ahead"] == 0 and b["ahead"] == .001
    assert a["launch"] + a["wait"] == pytest.approx(d1 - t1)
    assert b["t_launch"] - a["t_done"] == pytest.approx(b["pack"])
    assert snap["phase_s"]["ahead"] == pytest.approx(.002)
    assert snap["phase_s"]["wait"] == pytest.approx(.006)
    assert (snap["ahead_hits"], snap["ahead_first"]) == (1, 1)
    # a launch that failed its requests hands its seconds on
    tr.enter("pack")
    tr.enter("launch")
    tr.abandon()
    assert tr.launched == tr.seq == 2
    tr.enter("pack")
    tr.enter("launch")
    tr.enter("wait")
    # counters reset with a dispatch in flight: it commits as number 1
    tr.reset()
    tr.commit(t_launch=1.0, t_done=tr.enter("drain"), how="other",
              **fields)
    assert tr.seq == tr.launched == 1
    assert tr.snapshot()["ahead_misses"]["other"] == 1


def test_a_dispatch_launched_before_its_predecessor_returned(monkeypatch):
    """An early launch: the cut falls inside the predecessor's execution,
    so its ``wait`` ends there and the successor's holds the read of the
    predecessor, whose ``await``, ``late`` and ``t_done``
    (``returned``) are still its own; the successor's record says
    ``early`` in its last column and ``ahead_early`` counts it, and from
    its launch to its commit the snapshot shows it as a row of its
    number and ``t_launch`` alone (``t_done`` 0).  An early
    launch that fails its requests puts the cursor back under the
    dispatch that is still running."""
    monkeypatch.setattr(tracing, "time", ticking(100.0, 0.001))
    tr = DispatchTrace()
    fields = dict(with_finals=False, segments=2, finals=0,
                  prefill_tokens=16, active_rows=1, steps=4, kv_tokens=8)
    tr.enter("pack")
    t1 = tr.enter("launch")
    first = tr.launched_phases
    tr.enter("wait")
    with tr.ahead() as spent:          # the next one, prepared: closed
        pass
    with tr.ahead("ahead_launch"):     # enqueued behind the first: work
        t2 = tr.enter("wait", cut=True)     # under it, no phase of a gap
        second = tr.launched_phases
        tr.opened(t2)
    # in flight, it shows after the ring: its number, its launch, `early`
    assert tr.snapshot()["recent"] == [
        [2, round(t2, 5), 0.0] + [0] * (len(DISPATCH_FIELDS) - 3) + [1]]
    tr.awaiting(True, first)           # the read of the FIRST
    d1 = tr.returned()
    assert tr.launched == 2 and tr.seq == 0 and t2 < d1
    with tr.ahead("ahead_drain"):      # the first one, drained
        pass
    tr.commit(t_launch=t1, t_done=d1, phases=first, how="first", **fields)
    assert [r[0] for r in tr.snapshot()["recent"]] == [1, 2]
    with tr.ahead():                   # the third, prepared: not closed
        pass
    tr.awaiting(False, second)
    d2 = tr.enter("drain")
    tr.commit(t_launch=t2, t_done=d2, ahead=spent[0], how="hit", early=True,
              **fields)
    monkeypatch.undo()
    snap = tr.snapshot()
    assert snap["fields"][-1] == "early"
    # the committed record took the place of the row it had in flight
    a, b = (dict(zip(snap["fields"], r)) for r in snap["recent"])
    assert [a[p] for p in DISPATCH_PHASES] == [0, 0, .001, .001, .004, 0]
    # no pack and no launch before an early one: its seconds are `wait`
    # from the cut on, its call among them
    assert [b[p] for p in DISPATCH_PHASES] == [0, 0, 0, 0, .009, .001]
    assert (a["await"], a["late"], a["early"]) == (.001, 1, 0)
    assert (b["await"], b["late"], b["early"]) == (.001, 0, 1)
    # the first's read lies in the second's wait, past its own
    assert a["t_launch"] + a["launch"] + a["wait"] == pytest.approx(
        b["t_launch"])
    assert b["t_launch"] < a["t_done"] < b["t_done"]
    assert b["launch"] + b["wait"] == pytest.approx(d2 - t2)
    assert snap["phase_s"]["wait"] == pytest.approx(.013)
    assert snap["phase_s"]["launch"] == pytest.approx(.001)
    assert snap["phase_s"]["ahead"] == pytest.approx(.005)
    assert snap["spans"]["ahead_launch"]["n"] == 1
    assert snap["spans"]["await"]["n"] == 2 and snap["late_reads"] == 1
    assert (snap["ahead_hits"], snap["ahead_hits_slab"],
            snap["ahead_early"], snap["ahead_first"]) == (1, 1, 1, 1)
    # an early launch that fails: the running one is the last launched
    tr.enter("pack")
    tr.enter("launch")
    running = tr.launched_phases
    tr.enter("wait")
    tr.enter("wait", cut=True)         # early, and it raises
    tr.abandon()                       # under the running one again
    assert tr.launched == 3 and tr.launched_phases is running
    assert tr._into is running and tr._seq == 3
    tr.awaiting(False, running)
    tr.commit(t_launch=1.0, t_done=tr.enter("drain"), how="hit", ahead=.001,
              **fields)
    assert tr.seq == tr.launched == 3
    # a hit that was not early is no early one
    assert tr.snapshot()["recent"][-1][-1] == 0
    assert (tr.ahead_hits, tr.ahead_early) == (2, 1)


def test_a_prepared_dispatch_that_fails_hands_the_cursor_back(monkeypatch):
    """A dispatch launched as prepared, under a predecessor that is
    still to be drained, fails its requests (``abandon``): the
    predecessor is the last one launched again, so its drain in the gap
    and its commit book to its own record, and the failed launch's
    seconds go on to the dispatch packed next.  Hits are also counted
    by whether they carried a segment."""
    monkeypatch.setattr(tracing, "time", ticking(100.0, 0.001))
    tr = DispatchTrace()
    fields = dict(with_finals=False, finals=0, prefill_tokens=0,
                  active_rows=1, steps=4, kv_tokens=8)
    tr.enter("pack")
    t1 = tr.enter("launch")
    tr.enter("wait")
    with tr.ahead():
        pass
    d1 = tr.enter("pack")              # the prepared one's validation
    tr.enter("launch")                 # ... and its launch, which raises
    tr.abandon()
    assert tr.launched == 1 and tr.seq == 0
    tr.enter("drain")                  # the first one, as after a miss
    tr.commit(t_launch=t1, t_done=d1, how="first", segments=2, **fields)
    tr.enter("pack")
    t2 = tr.enter("launch")
    tr.enter("wait")
    tr.commit(t_launch=t2, t_done=tr.enter("drain"), how="hit",
              ahead=0.001, segments=2, **fields)
    tr.commit(t_launch=t2, t_done=t2, how="hit", ahead=0.001, segments=0,
              phases=dict.fromkeys(tracing._OWN, 0.0), **fields)
    monkeypatch.undo()
    snap = tr.snapshot()
    a, b, _ = (dict(zip(snap["fields"], r)) for r in snap["recent"])
    assert [a[p] for p in DISPATCH_PHASES] == [0, 0, .001, .001, .003, .001]
    # the validation and the failed launch, then its own pack and launch
    assert [b[p] for p in DISPATCH_PHASES] == [0, 0, .002, .002, .001, .001]
    assert (snap["ahead_hits"], snap["ahead_hits_slab"]) == (2, 1)
    assert snap["ahead_first"] == 1 and snap["seq"] == 3


def test_the_blocking_read_is_the_end_of_wait(scripted):
    """``await`` runs from the end of the plan to ``t_done``, inside
    ``wait``; ``late`` is 0 or 1 and ``late_reads`` their sum; the four
    parts of the work under an execution (the hand-off behind a launch
    made in the gap, ``deliver``, is the fourth) add up to the seventh
    key of ``phase_s``, which keeps its seven keys, and the phases still
    tile (``launch`` + ``wait`` = ``t_done - t_launch``)."""
    dt = scripted[0]["dispatch_trace"]
    recs = rows(scripted[0])
    assert DISPATCH_FIELDS[-4:] == ("ahead", "late", "await",
                                    "slab_carried_step")
    for r in recs:
        assert 0 < r["await"] <= r["wait"] + ROUNDING
        assert r["late"] in (0, 1)
    assert dt["late_reads"] == sum(r["late"] for r in recs)
    assert list(dt["phase_s"]) == list(DISPATCH_PHASES) + ["ahead"]
    spans = dt["spans"]
    assert tuple(spans) == DISPATCH_SPANS and "wait" not in spans
    assert (spans["ahead_plan"]["wall_s"] + spans["ahead_drain"]["wall_s"]
            + spans["ahead_launch"]["wall_s"] + spans["deliver"]["wall_s"]
            == pytest.approx(dt["phase_s"]["ahead"], abs=4e-6))
    for r in recs:
        assert (abs(r["launch"] + r["wait"] - (r["t_done"] - r["t_launch"]))
                <= ROUNDING)
    for name, sp in spans.items():
        assert set(sp) == {"n", "wall_s", "cpu_s", "max_s"}
        assert 0 <= sp["max_s"] <= sp["wall_s"]
        if name != "await":     # a phase's seconds are its spans'
            assert sp["wall_s"] == pytest.approx(
                dt["phase_s"].get(name, sp["wall_s"]), abs=2e-6)
    # every dispatch was read once and planned under once; the three
    # hits were drained under their successors
    assert spans["await"]["n"] == spans["ahead_plan"]["n"] == dt["seq"]
    assert spans["ahead_drain"]["n"] == dt["ahead_hits"]
    # a hand-off behind every launch made in the gap, and behind it the
    # tokens of every dispatch that was drained in a gap but the last of
    # a request, which no launch followed
    assert spans["deliver"]["n"] == dt["seq"] - dt["ahead_hits"]
    assert (dt["delivered_after_launch"]
            == sum(dt["ahead_misses"].values()))
    first, second = scripted[1]
    assert dt["delivered_tokens"] == len(first.tokens) + len(second.tokens)
    assert 2 <= dt["delivered_streams"] <= dt["seq"]
    assert spans["await"]["wall_s"] == pytest.approx(
        sum(r["await"] for r in recs), abs=len(recs) * 1e-5)
    assert dt["stall_count"] == len(dt["stalls"])


def _plan_with(eng, n, act):
    """Run ``act`` once, inside the first pack of dispatch ``n``: for
    every dispatch of one request but the first, inside the plan made
    under its predecessor's execution (``_plan_ahead``).  By the
    dispatch's number and not by a count of packs: a plan that is turned
    away is packed again in the gap."""
    inner, done = eng._pack_mixed, []

    def hooked(*a):
        if eng.dispatch_trace.launched + 1 == n and not done:
            done.append(n)
            act()
        return inner(*a)

    eng._pack_mixed = hooked


def _spin(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("how, cause", [
    (lambda: time.sleep(0.2), "off_cpu"), (lambda: _spin(0.2), "own_cpu")],
    ids=["sleep", "busy_loop"])
def test_a_span_that_stands_still_leaves_one_stall_row(how, cause):
    """0.2 s inside the plan of the third dispatch: one row in the ring,
    the span and where its seconds went, the same row in the flight
    recorder; and the dispatch that executed meanwhile was read late."""
    get_flight_recorder().clear()
    with engine() as eng:
        _plan_with(eng, 3, how)
        eng.submit(LONG, 14).wait(timeout=300)
        st = settled_stats(eng)
    dt = st["dispatch_trace"]
    stalls = [r for r in dt["stalls"] if r["span"] == "ahead_plan"]
    assert len(stalls) == 1, dt["stalls"]
    row = stalls[0]
    assert tuple(row) == STALL_FIELDS
    assert row["seq"] == 3 and 0.2 <= row["wall"] < 2.0
    # the spin ends after 0.2 s of the thread's CPU time (read on a
    # clock of 10 ms ticks), the sleep uses none
    assert (row["cpu"] < 0.05) if cause == "off_cpu" else (row["cpu"] >= 0.18)
    # ... and the cause is the rule's over the row's own seconds: on a
    # machine that lets the thread run that is `cause`; with the other
    # workers of a test run on its cores, 0.2 s of CPU take the spin
    # more than 0.4 s, and then the row must say that the thread stood
    # off the CPU for most of them, as it did
    half = row["wall"] / 2
    assert row["cause"] == (
        "gc" if row["gc"] >= half else "own_cpu" if row["cpu"] >= half
        else "other_threads" if row["proc_cpu"] - row["cpu"] >= half
        else "off_cpu")
    if row["wall"] < 0.3:
        assert row["cause"] == cause
    assert row["proc_cpu"] >= row["cpu"] - 1e-4 and row["nivcsw"] >= 0
    assert dt["stall_count"] == len(dt["stalls"])
    assert dt["stall_s"] == pytest.approx(
        sum(r["wall"] for r in dt["stalls"]), abs=1e-4)
    assert dt["spans"]["ahead_plan"]["max_s"] == pytest.approx(row["wall"],
                                                               abs=1e-4)
    # the plan was the third dispatch's, made under the second's
    # execution: a toy execution is long over after 0.2 s
    recs = rows(st)
    assert recs[1]["late"] == 1 and recs[1]["await"] < 0.1
    assert dt["late_reads"] == sum(r["late"] for r in recs) >= 1
    events = [e for e in get_flight_recorder().snapshot()
              if e["kind"] == "sched_stall" and e["span"] == "ahead_plan"]
    assert [{k: e[k] for k in STALL_FIELDS} for e in events] == [row]


@pytest.mark.parametrize("cause", STALL_CAUSES)
def test_a_stall_names_where_its_seconds_went(monkeypatch, cause):
    """The rule, in its order, on a clock held by hand: 0.1 s in
    ``pack``, of which the collector, the thread, another thread or
    nobody had 60 ms."""
    clock = dict(monotonic=10.0, thread_time=1.0, process_time=5.0)
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        monotonic=lambda: clock["monotonic"],
        process_time=lambda: clock["process_time"]))
    # the thread's CPU seconds come with its context switches
    monkeypatch.setattr(tracing, "resource", types.SimpleNamespace(
        RUSAGE_THREAD=None, getrusage=lambda who: types.SimpleNamespace(
            ru_utime=clock["thread_time"], ru_stime=0.0, ru_nivcsw=0)))
    tr = DispatchTrace()
    tr.enter("pack")
    clock["monotonic"] += 0.1
    if cause == "gc":
        tr.gc_pause_s += 0.06
        clock["thread_time"] += 0.06      # the collector ran on it
        clock["process_time"] += 0.06
    elif cause == "own_cpu":
        clock["thread_time"] += 0.06
        clock["process_time"] += 0.07
    elif cause == "other_threads":
        clock["thread_time"] += 0.01
        clock["process_time"] += 0.07
    tr.enter("launch")
    clock["monotonic"] += STALL_S - 0.001  # a long span, and no stall
    tr.enter("wait")
    clock["monotonic"] += 1.0              # nor is the device's time
    tr.awaiting(False)
    clock["monotonic"] += 0.5              # nor a read under a second
    tr.leave()
    snap = tr.snapshot()
    assert [(r["span"], r["seq"], r["t0"], r["wall"], r["cause"])
            for r in snap["stalls"]] == [("pack", 1, 10.0, 0.1, cause)]
    assert (snap["stall_count"], snap["stall_s"]) == (1, 0.1)
    assert snap["await_stall_count"] == 0
    assert snap["spans"]["await"] == {"n": 1, "wall_s": 0.5, "cpu_s": 0.0,
                                      "max_s": 0.5}


def test_a_read_that_stands_still_for_a_second_leaves_a_row_of_its_own(
        monkeypatch):
    """The blocking read is the device's time when all is well, so it is
    no stall of the host's work and ``stall_s`` / ``stall_count`` read as
    they did; from ``AWAIT_STALL_S`` on it leaves a row all the same
    (``span: await``), with what the thread and the process did
    meanwhile, counted apart."""
    clock = dict(monotonic=10.0, thread_time=1.0, process_time=5.0)
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        monotonic=lambda: clock["monotonic"],
        process_time=lambda: clock["process_time"]))
    monkeypatch.setattr(tracing, "resource", types.SimpleNamespace(
        RUSAGE_THREAD=None, getrusage=lambda who: types.SimpleNamespace(
            ru_utime=clock["thread_time"], ru_stime=0.0, ru_nivcsw=0)))
    tr = DispatchTrace()
    tr.enter("launch")
    tr.enter("wait")
    for seconds in (AWAIT_STALL_S - 0.01, AWAIT_STALL_S + 0.5):
        clock["monotonic"] += 0.02
        tr.awaiting(False)
        clock["monotonic"] += seconds
        clock["process_time"] += 0.004     # next to nothing ran meanwhile
        tr.returned()
    snap = tr.snapshot()
    [row] = snap["stalls"]
    assert set(row) == set(STALL_FIELDS)
    assert (row["span"], row["seq"], row["wall"], row["cpu"],
            row["proc_cpu"], row["gc"], row["cause"]) == (
        "await", 1, 1.5, 0.0, 0.004, 0.0, "off_cpu")
    assert row["t0"] == pytest.approx(10.04 + AWAIT_STALL_S - 0.01)
    assert (snap["await_stall_count"], snap["stall_count"],
            snap["stall_s"]) == (1, 0, 0.0)
    assert snap["spans"]["await"]["n"] == 2
    tr.reset()
    assert tr.snapshot()["await_stall_count"] == 0


def test_garbage_collections_are_timed_until_the_engine_closes():
    """One hook in ``gc.callbacks`` from the engine's start to its
    close; a full collection over a large graph of cycles shows in the
    oldest generation's count and in the pause."""
    before = list(gc.callbacks)
    with engine() as eng:
        hooks = [h for h in gc.callbacks if h not in before]
        assert hooks == [eng.dispatch_trace._on_gc]
        a = eng.stats()["dispatch_trace"]["gc"]
        junk = []
        for _ in range(100_000):
            x, y = [], []
            x.append(y), y.append(x)
            junk.append(x)
        del junk, x, y
        gc.collect()
        b = eng.stats()["dispatch_trace"]["gc"]
    assert list(gc.callbacks) == before
    assert set(b) == {"pause_s", "max_pause_s", "collections"}
    assert b["collections"][2] > a["collections"][2]
    assert b["pause_s"] > a["pause_s"] and b["max_pause_s"] > 0
    assert b["max_pause_s"] <= b["pause_s"]


def test_an_idle_engines_wait_is_an_interval_on_the_clock():
    """The engine blocks in ``queue.get`` from its start to the first
    submit: one row of ``idles``, which brackets the submit."""
    with engine() as eng:
        time.sleep(0.05)
        t_submit = time.monotonic()
        eng.submit(SHORT, 3).wait(timeout=300)
        dt = settled_stats(eng)["dispatch_trace"]
    assert len(dt["idles"]) == 1
    t0, t1 = dt["idles"][0]
    assert t0 <= t_submit - 0.04 and t_submit <= t1 + 1e-5
    assert dt["idle_wait_s"] == pytest.approx(t1 - t0, abs=2e-5)
    # the first dispatch was launched after the wait ended
    assert rows({"dispatch_trace": dt})[0]["t_launch"] >= t1 - 1e-5


@pytest.mark.parametrize("model, extra", [
    ("llama-test", ()), ("ouro-test", LOOP_DISPATCH_FIELDS),
    ("olmoe-test-int8", MOE_DISPATCH_FIELDS)])
def test_a_models_own_columns_follow_the_base_columns(model, extra):
    with engine(model) as eng:
        eng.submit(LONG, 6).wait(timeout=300)
        st = settled_stats(eng)
    dt = st["dispatch_trace"]
    assert tuple(dt["fields"]) == (DISPATCH_FIELDS + extra
                                   + DISPATCH_LAST_FIELDS)
    assert all(len(r) == len(dt["fields"]) for r in dt["recent"])
    for r in rows(st):
        assert r["late"] in (0, 1) and 0 < r["await"] <= r["wait"] + ROUNDING
        assert all(isinstance(r[f], int) and r[f] > 0 for f in extra)


def _capture(logdir, out):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with engine() as eng:
        out["plain"] = eng.submit(LONG, 10).wait(timeout=120).tolist()
        jax.profiler.start_trace(str(logdir), profiler_options=opts)
        try:
            out["traced"] = eng.submit(LONG, 10).wait(timeout=120).tolist()
            # one more dispatch before the capture ends: a request's
            # `wait` returns while its last dispatch is still draining,
            # and since the scheduler prepares a dispatch under its
            # predecessor a request's only `sched.drain` and the
            # `sched.bookkeeping` after it are that last dispatch's; the
            # scheduler has closed both rows before it picks this up
            eng.submit(SHORT, 1).wait(timeout=120)
        finally:
            jax.profiler.stop_trace()
        out["seq"] = settled_stats(eng)["dispatch_trace"]["seq"]


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """A ``jax.profiler`` capture around one request, under a time limit
    of its own; ``(ids before, ids while tracing, host events)``."""
    logdir, out = tmp_path_factory.mktemp("capture"), {}
    worker = threading.Thread(target=_capture, args=(logdir, out),
                              daemon=True)
    worker.start()
    worker.join(timeout=240)
    assert not worker.is_alive(), "the capture did not end in 240 s"
    from jax.profiler import ProfileData
    pb = sorted(logdir.rglob("*.xplane.pb"))[-1]
    events = [(plane.name, ev.name, dict(ev.stats))
              for plane in ProfileData.from_file(str(pb)).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("sched.") or ev.name == "mixed_step"]
    return out, events


def test_ids_are_identical_with_and_without_a_capture(capture):
    out, _ = capture
    assert out["plain"] == out["traced"] and len(out["traced"]) == 10


def test_a_capture_holds_the_sched_rows_on_a_host_plane(capture):
    out, events = capture
    assert {p for p, _, _ in events} == {"/host:CPU"}
    names = {n for _, n, _ in events}
    assert {f"sched.{p}" for p in DISPATCH_PHASES} <= names
    assert "mixed_step" in names
    # the work under an execution in its parts (the hand-off behind the
    # first dispatch's launch among them), and the blocking read
    assert {"sched.ahead_plan", "sched.ahead_drain", "sched.deliver",
            "sched.await"} <= names
    assert "sched.ahead" not in names
    # the request's three dispatches and the one after, each under its
    # number
    packs = sorted(s["seq"] for _, n, s in events if n == "sched.pack")
    assert packs == list(range(out["seq"] - 3, out["seq"] + 1))
    steps = sorted(s["step_num"] for _, n, s in events if n == "mixed_step")
    assert steps == packs
