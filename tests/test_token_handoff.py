"""The hand-off of a dispatch's tokens to their streams (docs/DESIGN.md
§19, PR 57): what the scheduler records for a stream waits in an outbox
and goes out as one hand-off a stream (``TokenStream.put_many``), after a
miss directly behind the launch of the drained dispatch's successor, and
the outbox is empty whenever the scheduler thread may block or leave.

- (a) an engine that goes idle has handed over everything;
- (b) where in an iteration the streams get a dispatch's tokens, on a
  miss and on a hit, by an event log through patched methods;
- (c) per stream: tokens in order, then the sentinel, then ``done``,
  however the request ends;
- (d) ``put_many`` itself, and its counterpart ``get_all`` (PR 59), and
  what ``generate_stream`` makes of it: the steps that are ready, as a
  list (``all_ready``) or one by one;
- (e) the serialized loop, a speculative drain and a migration relay
  deliver what they did;
- (f) the counters, and greedy outputs against the plain engine's."""

import queue
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_mixed_batching import (  # noqa: E402
    CFG, GREEDY, KEEPER, LONG40, InferenceEngine, assert_no_leak, expected,
    mixed_engine, settle, spec_kw)
from test_mixed_batching import (  # noqa: E402,F401  (the fixtures)
    draft_params, oracle, params)
from test_request_path import Held, hand_off  # noqa: E402

from distributed_inference_demo_tpu.comm.transport import (  # noqa: E402
    LoopbackNetwork, LoopbackTransport)
from distributed_inference_demo_tpu.runtime.batching import (  # noqa: E402
    TokenStream)
from distributed_inference_demo_tpu.runtime.migration import (  # noqa: E402
    MigrationWorker)

SHORT = [5, 4, 3, 2]


def read_stream(req, timeout=120.0) -> list:
    """What a consumer reads off ``req``'s stream, the sentinel left
    out; fails if anything follows the sentinel or ``done`` is not set
    by the time the sentinel can be read."""
    got = []
    while True:
        item = req.stream.get(timeout=timeout)
        if item is None:
            break
        got.append(item)
    assert req.done.wait(timeout=5)
    assert req.stream.empty()
    return got


# ---------------------------------------------------------------------------
# (a) nothing stays behind when the engine goes idle


def _watch_blocking_gets(eng) -> list:
    """Note, at every ``queue.get`` of the scheduler that may block for
    good (no timeout), what the outbox holds."""
    seen, get = [], eng._queue.get

    def watched(*a, **kw):
        if not a and kw.get("timeout") is None:
            seen.append([(len(r.outbox), r.done.is_set())
                         for r in eng._outbox])
        return get(*a, **kw)

    eng._queue.get = watched
    return seen


@pytest.mark.quick
@pytest.mark.parametrize("budget", [24, 0], ids=["mixed", "serialized"])
def test_an_engine_that_goes_idle_has_handed_over_everything(
        params, oracle, budget):
    """A lone request's ``generate`` and ``generate_stream`` return (the
    last tokens of an answer sat in no outbox while the engine slept),
    and the outbox is empty at every blocking ``queue.get``."""
    want = expected(oracle, KEEPER, 9)
    with mixed_engine(params, mixed_token_budget=budget) as eng:
        seen = _watch_blocking_gets(eng)
        out = eng.generate(np.asarray(KEEPER), 9, timeout=120)
        np.testing.assert_array_equal(out.tokens[0], want)
        streamed = [int(t[0]) for t in eng.generate_stream(
            np.asarray(KEEPER), 9, timeout=120)]
        assert streamed == list(want)
        # a request cancelled while it waits ends in the intake, and the
        # engine goes back to sleep with nothing to launch
        req = eng.submit(SHORT, 5)
        req.cancel()
        assert read_stream(req) == list(req.tokens)
        deadline = time.monotonic() + 10
        while len(seen) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
    assert len(seen) >= 3 and not any(seen), seen


# ---------------------------------------------------------------------------
# (b) where in an iteration the hand-off is made


def _event_log(eng) -> list:
    """Log, on the scheduler's thread: ``("launch>", n)`` / ``("launch<",
    n)`` around the call of dispatch n, ``("drain", n)`` when n's drain
    has filled the outbox, ``("plan", n)`` when the plan under n is
    begun, ``("put", n)`` for a hand-off that holds tokens of dispatch n
    (the latest drained)."""
    log, drained = [], [0]
    launch, drain, plan = (eng._launch_mixed, eng._drain_mixed,
                           eng._plan_ahead)
    deliver = eng._deliver

    def launched(p, **kw):
        n = eng.dispatch_trace.launched + 1
        log.append(("launch>", n))
        flight = launch(p, **kw)
        if flight is not None:
            flight.number = n
            log.append(("launch<", n))
        return flight

    def drained_(flight):
        record = drain(flight)
        drained[0] = flight.number
        log.append(("drain", flight.number))
        return record

    def planned(flight):
        log.append(("plan", flight.number))
        return plan(flight)

    def delivered():
        if eng._outbox:
            log.append(("put", drained[0]))
        return deliver()

    eng._launch_mixed, eng._drain_mixed = launched, drained_
    eng._plan_ahead, eng._deliver = planned, delivered
    return log


@pytest.mark.quick
@pytest.mark.parametrize("refuse", [True, False], ids=["miss", "hit"])
def test_the_hand_off_follows_the_next_launch(params, refuse):
    """On a miss the streams receive dispatch n's tokens after
    ``_launch_mixed(n+1)`` has returned and before ``_plan_ahead``; on a
    hit where they did before, right behind n's drain, which already
    lies behind n+1's launch.  The last dispatch's tokens, which no
    launch follows, go out before the engine sleeps."""
    with mixed_engine(params) as eng:
        log = _event_log(eng)
        if refuse:
            eng._ahead_refusal = lambda flight: "other"
        req = eng.submit(KEEPER, 21)
        assert read_stream(req) == list(req.tokens)
        settle(eng)
        dt = eng.stats()["dispatch_trace"]
    last = dt["seq"]
    assert last >= 5 and len(req.tokens) == 21
    hits = dt["ahead_hits"]
    assert hits == (0 if refuse else last - 1)
    at = {ev: i for i, ev in enumerate(log)}
    for n in range(1, last):
        # every dispatch that decoded handed tokens over exactly once
        assert log.count(("put", n)) == 1, (n, log)
        assert (at[("launch<", n + 1)] < at[("put", n)]
                < at[("plan", n + 1)]), (n, log)
        if refuse:
            assert (at[("drain", n)] < at[("launch>", n + 1)]
                    and at[("put", n)] == at[("launch<", n + 1)] + 1)
        else:
            assert at[("put", n)] == at[("drain", n)] + 1
    # the last one: drained in the gap, and nothing left to launch
    assert at[("put", last)] == at[("drain", last)] + 1 == len(log) - 1
    assert dt["delivered_after_launch"] == (last - 1 if refuse else 0)


# ---------------------------------------------------------------------------
# (c) per stream: tokens, then the sentinel, then `done`


def _end_by(eng, how, req):
    """Bring ``req`` (decoding on ``eng``) to its end by ``how``."""
    if how == "cancel":
        req.cancel()
    elif how == "fail":
        # on the scheduler's thread, between two dispatches, as the
        # engine fails a request of its own (`_sweep_cancelled`'s place)
        sweep = eng._sweep_cancelled
        # (the sweep runs at the top of an iteration: no hits)
        eng._ahead_refusal = lambda flight: "other"

        def failing():
            for i, r in enumerate(eng._slots):
                if r is req:
                    eng._slots[i] = None
                    eng._sentinel_slot(i)
                    eng._fail_request(req, ValueError("scripted"))
            return sweep()

        eng._sweep_cancelled = failing
    elif how == "close":
        eng.close()
    elif how == "crash":
        def crashing(plan, **kw):
            raise MemoryError("scripted device loss")

        eng._launch_mixed = crashing


ENDINGS = {
    # how: (eos id, the error a consumer finds)
    "length": (None, None),
    "eos": ("probe", None),
    "cancel": (None, None),
    "fail": (None, ValueError),
    "close": (None, RuntimeError),
    "crash": (None, MemoryError),
}


@pytest.mark.parametrize("how", list(ENDINGS))
def test_a_stream_ends_after_its_last_token_however_it_ends(
        params, oracle, how):
    """Tokens in order, the sentinel after the last of them, ``done``
    with the sentinel, on completion, ``eos``, cancel, ``_fail_request``,
    ``close()`` with the request in flight, and the crash path; a
    second request beside it is served the same."""
    eos, error = ENDINGS[how]
    want = [int(t) for t in expected(oracle, KEEPER, 40)]
    if eos == "probe":
        eos = want[9]                  # ends the row inside a block
    eng = mixed_engine(params, eos_id=eos)
    seen, seen_by = [], []

    def consume(req, into):
        into.append(read_stream(req))

    try:
        req = eng.submit(KEEPER, 40)
        other = eng.submit(SHORT, 40)
        readers = [threading.Thread(target=consume, args=a, daemon=True)
                   for a in ((req, seen), (other, seen_by))]
        for t in readers:
            t.start()
        if how not in ("length", "eos"):
            deadline = time.monotonic() + 120
            while len(req.tokens) < 6 and time.monotonic() < deadline:
                time.sleep(0.002)
            _end_by(eng, how, req)
        for t in readers:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        eng.close()
    assert seen and seen_by, "a reader found tokens behind the sentinel"
    # everything recorded was handed over, in order, before the end
    assert seen[0] == list(req.tokens) == want[:len(seen[0])]
    assert seen_by[0] == list(other.tokens)
    assert req.outbox is None and other.outbox is None
    assert not eng._outbox
    if how == "length":
        assert len(seen[0]) == 40
    elif how == "eos":
        assert seen[0][-1] == eos and len(seen[0]) == want.index(eos) + 1
    else:
        assert 6 <= len(seen[0]) < 40
    if error is None:
        assert req.error is None and req.cancelled == (how == "cancel")
    else:
        assert isinstance(req.error, error)
    if how in ("length", "eos"):
        # the stamps are the hand-off's: not before the bookkeeping
        assert req.t_submit < req.t_first <= req.t_done


# ---------------------------------------------------------------------------
# (d) put_many


@pytest.mark.quick
def test_put_many_wakes_a_blocked_get_once_and_gets_return_singly():
    stream = TokenStream()
    notifies, notify = [], stream.not_empty.notify
    stream.not_empty.notify = lambda n=1: (notifies.append(n), notify(n))[1]
    got = []
    reader = threading.Thread(target=lambda: got.append(stream.get()),
                              daemon=True)
    reader.start()
    deadline = time.monotonic() + 5
    while not stream.not_empty._waiters and time.monotonic() < deadline:
        time.sleep(0.001)              # blocked in get()
    stream.put_many([7, 8, 9, None])
    reader.join(timeout=5)
    assert got == [7] and len(notifies) == 1
    assert stream.qsize() == 3 and not stream.empty()
    assert [stream.get_nowait() for _ in range(3)] == [8, 9, None]
    with pytest.raises(queue.Empty):
        stream.get_nowait()
    # `task_done` / `join` count every item of a hand-off
    assert stream.unfinished_tasks == 4
    for _ in range(4):
        stream.task_done()
    stream.join()
    with pytest.raises(ValueError):
        stream.task_done()
    # a plain `put` beside it (the migration relay's) keeps its place
    stream.put(1)
    stream.put_many([2, 3])
    stream.put(None)
    assert [stream.get() for _ in range(4)] == [1, 2, 3, None]


@pytest.mark.quick
def test_get_all_takes_what_is_there_in_order_and_waits_only_when_empty():
    stream = TokenStream()
    stream.put(1)
    stream.put_many([2, 3])
    assert stream.get_all() == [1, 2, 3] and stream.empty()
    # like `get`, it leaves `unfinished_tasks` to `task_done`
    assert stream.unfinished_tasks == 3
    # one item queued is one item returned: it waits for no more
    stream.put(4)
    assert stream.get_all(timeout=30) == [4]
    # the deadline: `queue.Empty` once it has passed with nothing to take,
    # at once for a deadline already reached
    t0 = time.monotonic()
    with pytest.raises(queue.Empty):
        stream.get_all(timeout=0.05)
    assert 0.04 <= time.monotonic() - t0 < 5
    with pytest.raises(queue.Empty):
        stream.get_all(timeout=0.0)
    # a consumer blocked in it wakes once a hand-off, and has all of it,
    # the sentinel in its place
    got = []
    reader = threading.Thread(target=lambda: got.append(stream.get_all()),
                              daemon=True)
    reader.start()
    deadline = time.monotonic() + 5
    while not stream.not_empty._waiters and time.monotonic() < deadline:
        time.sleep(0.001)              # blocked in get_all()
    stream.put_many([7, 8, 9, None])
    reader.join(timeout=5)
    assert got == [[7, 8, 9, None]] and stream.empty()
    # `get` beside it still hands out one
    stream.put_many([5, 6])
    assert stream.get() == 5 and stream.get_all() == [6]


def test_get_all_loses_and_repeats_nothing_under_threads():
    """More threads than cores and a switch every few bytecodes: 16
    streams, each fed by two producers (``put_many`` and ``put``) and
    read by one ``get_all`` consumer; every producer's items arrive
    once and in its order."""
    n, streams = 2000, [TokenStream() for _ in range(16)]
    got = [[] for _ in streams]

    def produce(stream, tag):
        for i in range(0, n, 4):
            if tag:
                stream.put_many([(tag, j) for j in range(i, i + 4)])
            else:
                for j in range(i, i + 4):
                    stream.put((tag, j))

    def consume(stream, out):
        while len(out) < 2 * n:
            out.extend(stream.get_all(timeout=60))

    threads = [threading.Thread(target=produce, args=(s, tag), daemon=True)
               for s in streams for tag in (0, 1)]
    threads += [threading.Thread(target=consume, args=(s, out), daemon=True)
                for s, out in zip(streams, got)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for stream, out in zip(streams, got):
        assert stream.empty() and stream.unfinished_tasks == 2 * n
        for tag in (0, 1):
            assert [j for t, j in out if t == tag] == list(range(n))


def _stream_of(backend, rows, max_new, **kw):
    """``backend``'s stream over ``rows`` prompts, started (its requests
    are made at the first ``next``, which waits for a token: so on a
    thread), and the requests."""
    gen = backend.generate_stream(np.arange(3 * rows).reshape(rows, 3),
                                  max_new, **kw)
    first = []
    starter = threading.Thread(target=lambda: first.append(next(gen)),
                               daemon=True)
    starter.start()
    reqs = backend.made.get(timeout=30)
    return gen, reqs, starter, first


@pytest.mark.quick
@pytest.mark.parametrize("all_ready", [True, False],
                         ids=["lists", "per_step"])
def test_generate_stream_yields_the_steps_that_are_ready(all_ready):
    """With ``all_ready`` a list of every step that is there, never an
    empty one; without it the same steps one by one."""
    gen, [req], starter, first = _stream_of(Held(), 1, 16,
                                            all_ready=all_ready)
    hand_off(req, [3])
    starter.join(timeout=30)
    hand_off(req, [4, 5, 6, 7])
    hand_off(req, [8, None])
    got = first + list(gen)
    if all_ready:
        assert [[int(s[0]) for s in steps] for steps in got] == [
            [3], [4, 5, 6, 7, 8]]
    else:
        assert [int(s[0]) for s in got] == [3, 4, 5, 6, 7, 8]
    assert not req.cancelled


@pytest.mark.quick
@pytest.mark.parametrize("all_ready", [True, False],
                         ids=["lists", "per_step"])
def test_generate_stream_keeps_its_deadline_error_and_cancel(all_ready):
    backend = Held()
    # the deadline: TimeoutError, and the row is cancelled
    gen, [req], starter, first = _stream_of(backend, 1, 16, timeout=0.3,
                                            all_ready=all_ready)
    hand_off(req, [3, 4])
    starter.join(timeout=30)
    with pytest.raises(TimeoutError, match="deadline"):
        list(gen)
    assert req.cancelled
    # a row's error reaches the consumer behind the tokens it had, and its
    # sibling is cancelled
    gen, [a, b], starter, first = _stream_of(backend, 2, 16,
                                             all_ready=all_ready)
    hand_off(a, [10, 11, 12])
    hand_off(b, [20, 21, None], error=RuntimeError("device lost"))
    starter.join(timeout=30)
    steps = list(first[0]) if all_ready else first
    with pytest.raises(RuntimeError, match="device lost"):
        for item in gen:
            steps.extend(item if all_ready else [item])
    assert [s.tolist() for s in steps] == [[10, 20], [11, 21]]
    assert a.cancelled and not b.cancelled      # b was done, a was not
    # a stream abandoned with steps still in hand cancels its row
    gen, [req], starter, first = _stream_of(backend, 1, 16,
                                            all_ready=all_ready)
    hand_off(req, [3, 4, 5])
    starter.join(timeout=30)
    gen.close()
    assert req.cancelled
    # ... and one that has read its sentinel does not
    gen, [req], starter, first = _stream_of(backend, 1, 16,
                                            all_ready=all_ready)
    hand_off(req, [3, None])
    starter.join(timeout=30)
    assert list(gen) == []
    assert not req.cancelled


# ---------------------------------------------------------------------------
# (e) the other paths deliver what they did


def _relay_pair(params):
    """Two mixed engines with live-migration workers on one loopback
    fabric."""
    net = LoopbackNetwork()
    engines = [mixed_engine(params, max_seq=160, max_batch=2,
                            kv_cache_blocks=48) for _ in range(2)]
    workers = [MigrationWorker(e, LoopbackTransport(name, net),
                               ack_timeout=10.0)
               for e, name in zip(engines, ("src", "dst"))]
    threads = [threading.Thread(target=w.serve_forever, daemon=True)
               for w in workers]
    for t in threads:
        t.start()
    return SimpleNamespace(engines=engines, workers=workers,
                           threads=threads)


PATHS = ["serialized", "serialized_chunked", "spec_pld", "spec_draft",
         "mixed_pld", "migration_relay"]


@pytest.mark.parametrize("path", PATHS)
def test_every_path_delivers_what_it_did(params, oracle, path, request):
    """The serialized loop (one-shot and chunked admission), the
    speculative drains (serialized and mixed) and a migration relay hand
    each stream its request's greedy tokens, in order, then the
    sentinel."""
    prompts = [(KEEPER, 17), (LONG40, 9), (SHORT, 12)]
    want = [[int(t) for t in expected(oracle, p, n)] for p, n in prompts]
    if path == "migration_relay":
        pair = _relay_pair(params)
        src, dst = pair.engines
        try:
            long_want = [int(t) for t in InferenceEngine(
                CFG, params, max_seq=160, sampling=GREEDY).generate(
                    np.asarray(KEEPER)[None, :], 120).tokens[0]]
            req = src.submit(KEEPER, 120, request_id="h1")
            got = []
            reader = threading.Thread(
                target=lambda: got.append(read_stream(req)), daemon=True)
            reader.start()
            deadline = time.monotonic() + 120
            while len(req.tokens) < 3 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert pair.workers[0].migrate_out("h1", "dst") is True
            reader.join(timeout=120)
            assert got == [long_want] and req.error is None
            assert pair.workers[1].stats["migrated_in"] == 1
            # the relay fed the tail: the source's scheduler had handed
            # over every token it recorded before the stream changed hands
            assert 3 <= src.stats()["dispatch_trace"]["delivered_tokens"] < 120
        finally:
            for w in pair.workers:
                w.stop()
            for t in pair.threads:
                t.join(timeout=2)
            for e in pair.engines:
                e.close()
        return
    kw = {
        "serialized": dict(mixed_token_budget=0, prefill_chunk=None),
        "serialized_chunked": dict(mixed_token_budget=0),
        "spec_pld": dict(mixed_token_budget=0, **spec_kw("pld")),
        "spec_draft": dict(mixed_token_budget=0, **spec_kw(
            "draft", request.getfixturevalue("draft_params"))),
        "mixed_pld": spec_kw("pld"),
    }[path]
    with mixed_engine(params, **kw) as eng:
        reqs = [eng.submit(p, n) for p, n in prompts]
        got = [read_stream(r) for r in reqs]
        settle(eng)
        # (`/stats` shows the section on the mixed path only)
        dt = eng.dispatch_trace.snapshot()
        assert_no_leak(eng)
    assert got == want
    assert not eng._outbox
    assert dt["delivered_tokens"] == sum(map(len, want))
    # one hand-off a stream and step, never one a token
    assert 3 <= dt["delivered_streams"] <= dt["delivered_tokens"]


# ---------------------------------------------------------------------------
# (f) the counters


@pytest.mark.quick
def test_the_counters_count_hand_offs_tokens_and_deferred_dispatches(
        params, oracle):
    """``delivered_tokens`` is the sum of what the streams got,
    ``delivered_streams`` the wake-ups (one a stream a hand-off: a block
    of four tokens is one), ``delivered_after_launch`` the misses (each
    had a successor), and greedy outputs are the plain engine's."""
    prompts = [(KEEPER, 30), (LONG40, 10), (SHORT, 21), ([9, 2, 6], 1)]
    with mixed_engine(params) as eng:
        puts = []
        put_many = TokenStream.put_many
        reqs = []
        for p, n in prompts:
            # one after the other, under a decoding row: an arrival is a
            # miss, and the dispatches between two arrivals are hits
            reqs.append(eng.submit(p, n))
            reqs[-1].stream.put_many = (
                lambda items, r=reqs[-1]: (puts.append(list(items)),
                                           put_many(r.stream, items))[1])
            deadline = time.monotonic() + 120
            while (len(reqs[0].tokens) < 5 * len(reqs)
                   and time.monotonic() < deadline):
                time.sleep(0.002)
        got = [read_stream(r) for r in reqs]
        settle(eng)
        dt = eng.stats()["dispatch_trace"]
    assert got == [[int(t) for t in expected(oracle, p, n)]
                   for p, n in prompts]
    assert dt["delivered_tokens"] == sum(n for _, n in prompts)
    misses = sum(dt["ahead_misses"].values())
    assert dt["delivered_after_launch"] == misses > 0
    assert dt["ahead_hits"] > 0
    assert dt["ahead_hits"] + misses + dt["ahead_first"] == dt["seq"]
    # what the patched streams saw: a decode block is one hand-off of
    # four tokens, and an end rides with its request's last tokens
    assert len(puts) == dt["delivered_streams"]
    assert max(len(p) for p in puts) >= 4
    assert sum(p[-1] is None for p in puts) == 4
    assert all(None not in p[:-1] for p in puts)
    assert dt["spans"]["deliver"]["n"] == misses + dt["ahead_first"]


def test_serialized_engine_counts_hand_offs_too(params):
    """The serialized loop has no dispatch records, and the same
    counters: a fused block of four is one hand-off."""
    with mixed_engine(params, mixed_token_budget=0) as eng:
        req = eng.submit(SHORT, 13)
        assert len(read_stream(req)) == 13
        dt = eng.dispatch_trace
        deadline = time.monotonic() + 5
        while dt.delivered_tokens < 13 and time.monotonic() < deadline:
            time.sleep(0.002)
    assert dt.delivered_tokens == 13
    assert dt.delivered_streams < 13 and dt.delivered_after_launch == 0
    assert CFG.vocab_size > max(req.tokens)
