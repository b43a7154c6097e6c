"""The early launch (docs/DESIGN.md §19, PR 54): a prepared dispatch that
is closed (no ``eos`` on the engine, no news, every segment its budget
allows packed) is enqueued behind its predecessor before that has
returned.  ``tests/test_mixed_batching.py`` holds the contract on scripted
traffic with every plan refused (case ``full_slab`` and the counters of
every case); here: what lands BEHIND an early launch, the plans that stay
on the old order of calls, ``close()`` with two dispatches enqueued, and a
window model's and a summarised cache's pages under early launches.  (A
file of its own so that the driver's workers, which take a file each,
share the engines these build.)"""

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import test_evabyte_engine as eva  # noqa: E402
import test_laguna_engine as laguna  # noqa: E402
from test_mixed_batching import (  # noqa: E402
    GREEDY, KEEPER, LONG72, SCRIPTS, SamplingParams, assert_no_leak,
    mixed_engine, scripted_run, settle)
from test_mixed_batching import params  # noqa: E402,F401  (the fixture)


# what lands AFTER an early launch, and the same traffic for the old
# order.  The hook fires an event right after the call it is keyed by
# was enqueued: call 6 is enqueued early, while execution 5 runs, so in
# the as-it-is run the event lands during execution 5, after dispatch 6
# went to the device; the every-plan-refused run has it during execution
# 5 as well, after call 5, where its gap's intake serves it before
# dispatch 6 is packed
BEHIND_EARLY = {
    "arrival": ("submit", "late", [8, 8, 1], 5),
    "cancel": ("cancel", "row"),
}
for _name, _event in BEHIND_EARLY.items():
    for _old in (False, True):
        SCRIPTS[f"{_name}_behind_early{'.old' if _old else ''}"] = {
            1: [("submit", "row", [9, 2, 6], 40)],
            3: [("submit", "long", LONG72, 6)],
            5 if _old else 6: [_event],
        }

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("what", list(BEHIND_EARLY))
def test_what_lands_behind_an_early_launch_waits_one_dispatch_more(
        params, sampled, what):
    """An arrival or a cancel that reaches the scheduler after dispatch
    n+1 was enqueued behind n, while n still runs, is served by the
    intake after n+1 and not by the one before it.  An arrival loses
    nothing by it: n+1 had packed every segment its budget allows from
    the admissions before it, so it gets the ``first_seq`` the old order
    gives it, and every stream, record, counter and page is the old
    order's.  A cancelled row rides n+1 and gets none of its tokens:
    its stream ends where the old order ends it, the other streams, the
    rng, the tables and the pages are the old order's, no page leaks
    (``scripted_run`` checks ``used == tree.block_count``), and only
    dispatch n+1's record shows the row that rode it."""
    sampling = (SamplingParams(greedy=False, temperature=0.9, top_k=40)
                if sampled else GREEDY)
    ahead = scripted_run(params, sampling, None, False,
                         f"{what}_behind_early")
    old = scripted_run(params, sampling, None, True,
                       f"{what}_behind_early.old")
    assert ahead["script_done"] and old["script_done"]
    # the event landed behind an early launch: dispatch 6 was one
    recs = {r["seq"]: r for r in ahead["recs"]}
    assert recs[5]["early"] and recs[6]["early"]
    assert old["trace"]["ahead_early"] == 0
    same, olds = dict(ahead["same"]), dict(old["same"])
    if what == "arrival":
        late = same["streams"]["late"]
        assert len(late[0]) == 5 and late[4] == late[5] > 7
        assert ahead["trace"]["ahead_misses"]["arrival"] == old["trace"][
            "ahead_misses"]["arrival"]
    else:
        toks, _, cancelled, error, _, _ = same["streams"]["row"]
        # token #1 and three more in its final's dispatch (2: the
        # slab's pass carried the keeper's first step, and the row
        # joined the loop behind it), then three dispatches of four
        assert cancelled and error == "None" and len(toks) == 1 + 3 + 3 * 4
        assert ahead["trace"]["ahead_misses"]["cancel"] == 1
        # it rode dispatch 6, which the old order packed without it
        rode, packed = recs[6], old["recs"][5]
        assert (rode["active_rows"], packed["active_rows"]) == (2, 1)
        assert rode["segments"] == packed["segments"] == 2
        assert (same["chunk_stats"]["mixed_packed_tokens"]
                == olds["chunk_stats"]["mixed_packed_tokens"] + 4)
        for run in (same, olds):
            run["records"] = run["records"][:5] + run["records"][6:]
            run["chunk_stats"] = dict(run["chunk_stats"],
                                      mixed_packed_tokens=None)
    for key in same:
        assert same[key] == olds[key], key


def _calls_in_order(eng):
    """Log the scheduler's launches and blocking reads as they are
    made: ``[("launch" | "await", the dispatch's number)]``."""
    log, launch, wait = [], eng._launch_mixed, eng._await_mixed

    def launched(plan, **kw):
        flight = launch(plan, **kw)
        if flight is not None:
            flight.number = 1 + sum(kind == "launch" for kind, _ in log)
            log.append(("launch", flight.number))
        return flight

    def awaited(flight):
        log.append(("await", flight.number))
        return wait(flight)

    eng._launch_mixed, eng._await_mixed = launched, awaited
    return log


# what an engine is or carries that keeps every plan open, and the
# traffic that would otherwise close some: (eos, the prompt sent while
# the keeper decodes)
OPEN_PLANS = {
    # the validation needs the returned tokens
    "eos": (255, LONG72),
    # room for two segments and a prompt of two chunks and a final: the
    # chunks arrive and are packed in the gap, the final is prepared
    # alone, and an arrival during it would ride beside it
    "spare_segment": (None, LONG72[:20]),
    "decode_only": (None, None),
    # the control: the same engine, the long prompt, no eos
    "closed": (None, LONG72),
}


@pytest.mark.parametrize("case", list(OPEN_PLANS))
def test_a_plan_that_is_not_closed_keeps_the_old_order_of_calls(params,
                                                                case):
    """With an ``eos``, with a segment to spare, or with nothing packed a
    prepared dispatch is launched only when its predecessor has returned:
    every launch but the first follows the blocking read of the dispatch
    before it, as it always did, and ``ahead_early`` stays 0.  The same
    engine without an ``eos`` enqueues the long prompt's full slabs
    before that read."""
    eos, prompt = OPEN_PLANS[case]
    eng = mixed_engine(params, max_batch=3, eos_id=eos)
    log = _calls_in_order(eng)
    started = []
    inner = eng._mixed_step

    def hooked(*a):
        out = inner(*a)
        if not started and prompt is not None:     # during execution 1
            started.append(eng.submit(prompt, 5))
        return out

    eng._mixed_step = hooked
    with eng:
        keeper = eng.submit(KEEPER, 30)
        keeper.wait(timeout=300)
        for r in started:
            r.wait(timeout=300)
        settle(eng)
        dt = eng.stats()["dispatch_trace"]
        assert_no_leak(eng)
    recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
    assert len(recs) == dt["seq"] == len(log) // 2
    old_order = [(kind, n) for n in range(1, dt["seq"] + 1)
                 for kind in ("launch", "await")]
    if case == "closed":
        assert dt["ahead_early"] == 3 and log != old_order
        for n in [r["seq"] for r in recs if r["early"]]:
            assert log.index(("launch", n)) < log.index(("await", n - 1))
        return
    assert dt["ahead_early"] == 0 and not any(r["early"] for r in recs)
    assert log == old_order
    # ... and plans of the kind in question were prepared and launched
    hits = [r for r in recs if r["ahead"] > 0]
    assert hits and dt["ahead_hits"] == len(hits)
    if case == "decode_only":
        assert not any(r["segments"] for r in hits)
    elif case == "spare_segment":
        assert [r["segments"] for r in hits if r["segments"]] == [1]
    else:
        assert sum(r["segments"] == 2 for r in hits) >= 3


def test_close_with_two_dispatches_enqueued_awaits_both(params):
    """``close()`` called while one dispatch runs and the next waits
    behind it in the device's queue returns, both are awaited, drained
    and committed, every request ends once, and no page leaks."""
    eng = mixed_engine(params, max_batch=3)
    log = _calls_in_order(eng)
    inner, fail = eng._mixed_step, eng._fail_request
    reqs, failed, closer = {}, [], []

    def failing(req, err):
        failed.append(id(req))
        return fail(req, err)

    def hooked(*a):
        out = inner(*a)
        if not log:                                # during execution 1
            reqs["long"] = eng.submit(LONG72, 6)
        # this call, logged when its launch returns, was enqueued behind
        # one that has not been read yet
        if not closer and log and log[-1][0] == "launch":
            thread = threading.Thread(target=eng.close)
            thread.start()
            closer.append(thread)
            while eng._running:                    # close() has begun
                time.sleep(0.001)
        return out

    eng._mixed_step, eng._fail_request = hooked, failing
    reqs["keeper"] = eng.submit(KEEPER, 60)
    deadline = time.monotonic() + 300
    while not closer:
        assert time.monotonic() < deadline, log
        time.sleep(0.005)
    closer[0].join(timeout=60)
    assert not closer[0].is_alive() and not eng._thread.is_alive()
    # the second of the two was the last launched, and both were read
    assert log[-3:] == [("launch", len(log) // 2),
                        ("await", len(log) // 2 - 1),
                        ("await", len(log) // 2)]
    assert sorted(log) == sorted((kind, n) for n in range(1, len(log) // 2 + 1)
                                 for kind in ("launch", "await"))
    dt = eng.dispatch_trace
    assert dt.seq == dt.launched == len(log) // 2
    assert dt.snapshot()["recent"][-1][-1] == 1     # the last was early
    for r in reqs.values():
        assert r.done.is_set()
        assert "engine closed" in repr(r.error)
    assert sorted(failed) == sorted(id(r) for r in reqs.values())
    assert_no_leak(eng)



# ---------------------------------------------------------------------------
# pages freed and windows written over while the predecessor still reads
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def laguna_params():
    return laguna.init_full_params(laguna.jax.random.PRNGKey(0), laguna.CFG)


@pytest.fixture(scope="module")
def eva_params():
    return eva.init_full_params(eva.jax.random.PRNGKey(0), eva.CFG)


_SEEN = {}      # the dense path's tokens, and the first order's


def _dense_once(params, prompt, out):
    key = (tuple(prompt), tuple(out))
    if key not in _SEEN:
        _SEEN[key] = laguna._dense_greedy(params, list(prompt), out)
    return _SEEN[key]


@pytest.mark.parametrize("refuse", [False, True], ids=["early", "old_order"])
def test_full_slabs_enqueued_behind_their_predecessors_free_pages_safely(
        laguna_params, refuse):
    """A row decodes while long prompts stream two chunks a dispatch:
    every such slab is packed full under its predecessor and enqueued
    behind it (docs/DESIGN.md §19), so a launch returns pages behind the
    window to their pool while the dispatch before still reads them, and
    later plans take those pages up.  Tokens are the dense path's all the
    same, every page comes back, and with every plan refused (the old
    order) nothing is enqueued early and nothing differs."""
    rng = np.random.default_rng(3)
    keeper = rng.integers(0, 256, size=5)
    prompts = [rng.integers(0, 256, size=n) for n in (70, 37)]
    with laguna._engine(laguna_params, **laguna.MIXED) as eng:
        if refuse:
            eng._ahead_refusal = lambda flight: "other"
        first = eng.submit(keeper, 40)
        while not first.tokens:                  # it decodes
            time.sleep(0.002)
        reqs = [eng.submit(p, 4) for p in prompts]
        outs = [list(r.wait(300)) for r in reqs]
        kept = list(first.wait(300))
        settle(eng)
        st = eng.stats()
        assert eng._wmgr.used_blocks == 0 and eng._window_reserved == 0
        assert eng.kv_cache.used_blocks == 0
    # (the dense path runs eagerly: a few tokens each, once for both
    # orders)
    assert len(kept) == 40 and kept[:8] == _dense_once(
        laguna_params, keeper, kept[:8])
    assert _SEEN.setdefault("kept", kept) == kept
    for p, out in zip(prompts, outs):
        assert len(out) == 4 and out == _dense_once(laguna_params, p, out)
    dt = st["dispatch_trace"]
    assert st["kvcache"]["kinds"]["window"]["pages_returned"] > 0
    assert dt["ahead_hits"] + sum(dt["ahead_misses"].values()) + dt[
        "ahead_first"] == dt["seq"]
    if refuse:
        assert dt["ahead_early"] == dt["ahead_hits"] == 0
    else:
        # 70 tokens are eight chunks: the gap's two, then three pairs early
        assert dt["ahead_early"] >= 3
        recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
        assert all(r["segments"] == 2 for r in recs if r["early"])


@pytest.mark.parametrize("refuse", [False, True], ids=["early", "old_order"])
def test_full_slabs_enqueued_early_write_over_a_window_in_order(eva_params,
                                                                refuse):
    """A row decodes while long prompts stream two chunks a dispatch, each
    such slab enqueued behind its predecessor (docs/DESIGN.md §19): a
    window's pages are written over by the next window's chunks while the
    dispatch before may still read them, which the device's order makes
    safe.  Tokens are the dense forward's through every close, with the
    early launches and with every plan refused."""
    prompts = [eva._prompt(n, 30 + i) for i, n in enumerate((61, 53, 44))]
    keeper = eva._prompt(5, 29)
    with eva._engine(eva_params) as eng:
        if refuse:
            eng._ahead_refusal = lambda flight: "other"
        first = eng.submit(keeper, 50)
        while not first.tokens:                  # it decodes
            time.sleep(0.002)
        reqs = [eng.submit(p, 9) for p in prompts]
        outs = [r.wait(120).tolist() for r in reqs]
        kept = first.wait(120).tolist()
        settle(eng)
        st = eng.stats()
    assert kept == eva._dense_greedy(eva_params, keeper, 50)
    for p, out in zip(prompts, outs):
        assert out == eva._dense_greedy(eva_params, p, 9), len(p)
    assert st["kvcache"]["blocks_used"] == 0
    cached = [len(p) + 8 for p in prompts] + [len(keeper) + 49]
    assert st["kvcache"]["eva"]["windows_closed"] == sum(
        n // eva.W for n in cached)
    dt = st["dispatch_trace"]
    if refuse:
        assert dt["ahead_early"] == dt["ahead_hits"] == 0
    else:
        assert dt["ahead_early"] >= 3
        recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
        # windows closed inside slabs that were enqueued early
        assert any(r["early"] and r["windows_closed"] for r in recs)
