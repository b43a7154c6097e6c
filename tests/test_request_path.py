"""The request's path outside the engine (docs/DESIGN.md §16, §19).

``telemetry.tracing.RequestPath``, owned by ``InferenceHTTPServer`` and
shown as ``/stats.request_path`` beside the backend's ``dispatch_trace``.
Pinned here at toy size on the CPU, over real sockets (counts and order
only; a time from this file is a time of XLA's CPU backend):

- a row's instants are ordered on the dispatch record's clock:
  ``t_accept <= t_parsed <= t_submit <= t_launch`` of the request's first
  dispatch, and a hand-off's stamp lies before its write;
- the cumulative counters are the sums over the rows and over a hand
  count of what the clients received, exactly, under 16 concurrent
  streams too;
- a reply that is not streamed counts in ingress only;
- a snapshot taken while a request streams holds its finished hand-offs;
- the engine's ``perf_counter`` stamps and the record's ``monotonic`` are
  one clock;
- a backend that does not stream has no ``request_path`` and its
  ``/stats`` is as it was;
- what a handler writes (PR 59), read off a raw socket chunk by chunk:
  a hand-off of ``k`` tokens is ONE chunk of ``k`` lines, the lines
  byte for byte what a chunk a line carried; one token is written at
  once; tokens, then a failure's line, then the terminating chunk; a
  backend that cannot say what is ready has a line a chunk.
"""

import http.client
import json
import queue
import socket
import sys
import threading
import time
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_inference_demo_tpu.models import get_model_config  # noqa: E402
from distributed_inference_demo_tpu.models.loader import load_or_init  # noqa: E402
from distributed_inference_demo_tpu.ops.sampling import SamplingParams  # noqa: E402
from distributed_inference_demo_tpu.runtime.batching import (  # noqa: E402
    ContinuousBatchingEngine, Request)
from distributed_inference_demo_tpu.runtime.http_server import (  # noqa: E402
    InferenceHTTPServer)
from distributed_inference_demo_tpu.telemetry.tracing import (  # noqa: E402
    GATEWAY_HELD_HEADER, REQUEST_PATH_FIELDS, RequestPath)

MODEL = "llama-test"
ROUNDING = 2e-5             # two instants at 1e-5


@pytest.fixture(scope="module")
def served():
    cfg = get_model_config(MODEL)
    eng = ContinuousBatchingEngine(
        cfg, load_or_init(MODEL, cfg, seed=0), max_seq=96, max_batch=8,
        sampling=SamplingParams(greedy=True), prompt_buckets=(16, 48),
        kv_block_tokens=8, prefill_chunk=8, decode_block=4,
        mixed_token_budget=40)
    server = InferenceHTTPServer(eng, port=0, model_name=MODEL)
    server.start()
    yield server
    server.shutdown()
    eng.close()


def call(server, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=120)
    try:
        conn.request(method, path,
                     body=None if body is None else json.dumps(body),
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def stats(server):
    return json.loads(call(server, "GET", "/stats")[1])


def stream(server, prompt, max_new, headers=None):
    """The lines of one streamed request, as the client received them."""
    status, body = call(server, "POST", "/generate",
                        {"prompt_ids": prompt, "max_new_tokens": max_new,
                         "stream": True}, headers)
    assert status == 200
    return [json.loads(l) for l in body.splitlines()]


def rows_of(snap):
    return [dict(zip(snap["fields"], r)) for r in snap["recent"]]


def grown(before, after):
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float)) and k != "egress_max_s"}


def test_the_two_clocks_are_one():
    """The engine stamps ``perf_counter``, the records ``monotonic``: on
    Linux both read CLOCK_MONOTONIC, and nothing converts between them."""
    gaps = [abs(time.perf_counter() - time.monotonic()) for _ in range(100)]
    assert min(gaps) < 1e-3


def test_a_rows_instants_are_ordered_on_the_dispatch_records_clock(served):
    before = stats(served)
    tid = "00000000000a11ce"
    lines = stream(served, list(range(2, 20)), 9, {"X-DWT-Trace-Id": tid})
    assert sum(len(l["tokens"]) for l in lines) == 9
    after = stats(served)
    rp = after["request_path"]
    assert rp["fields"] == list(REQUEST_PATH_FIELDS)
    assert rp["ingress_count"] == before["request_path"]["ingress_count"] + 1
    row = rows_of(rp)[-1]
    assert row["t_gateway"] == row["t_accept"]      # no gateway, no header
    assert row["t_accept"] <= row["t_parsed"] <= row["t_submit"]
    assert (row["prompt_tokens"], row["streamed"]) == (18, 1)
    # ... and before the launch of the dispatch that first carried it
    # (the handler records its two spans at its request's end, behind the
    # last byte its client reads, and an export takes what it gives)
    spans, deadline = {}, time.monotonic() + 30
    while "http.egress" not in spans and time.monotonic() < deadline:
        spans.update({e["name"]: e for e in json.loads(
            call(served, "GET", "/trace")[1])["traceEvents"]
            if e.get("args", {}).get("trace_id") == tid})
    assert {"http.ingress", "engine.prefill", "engine.decode",
            "http.egress"} <= set(spans)
    dt = after["dispatch_trace"]
    launch = {r[0]: r[1] for r in dt["recent"]}
    first = spans["engine.prefill"]["args"]["first_seq"]
    assert row["t_submit"] <= launch[first] + ROUNDING
    # the handler's span ends where the engine's wait in the queue begins
    ing, pre = spans["http.ingress"], spans["engine.prefill"]
    assert abs(ing["ts"] + ing["dur"] + 1e3 * pre["args"]["queue_wait_ms"]
               - pre["ts"]) <= 3
    assert ing["args"]["gateway_ms"] == 0 and ing["args"]["streamed"]
    # a hand-off's stamp lies before its write: none is negative, and the
    # first lies behind the submit
    eg = spans["http.egress"]
    assert eg["ts"] >= ing["ts"] + ing["dur"] and eg["dur"] >= 0
    assert eg["args"]["max_ms"] >= 0 and eg["args"]["cpu_ms"] >= 0
    d = grown(before["request_path"], rp)
    assert eg["args"]["handoffs"] == d["handoffs"] >= 2    # 5 + 4
    # a write is a chunk, and a chunk holds whole hand-offs (here 5 + 4
    # tokens: two writes, or one if the handler came late to the first)
    assert eg["args"]["lines"] == 9
    assert 1 <= eg["args"]["writes"] <= eg["args"]["handoffs"]
    assert d["writes"] == eg["args"]["writes"]
    assert 0 <= d["egress_s"] <= rp["egress_max_s"] * d["handoffs"] + 1e-6


def test_the_counters_are_the_sums_over_the_rows_and_a_hand_count(served):
    """16 streams at once through 8 slots: every counter is exact."""
    call(served, "POST", "/stats/reset")
    assert stats(served)["request_path"]["ingress_count"] == 0
    got, errors = [None] * 16, []

    def one(i):
        try:
            got[i] = stream(served, list(range(2, 6 + i)), 5 + i % 7)
        except Exception as e:      # noqa: BLE001 (reported below)
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    received = sum(len(l["tokens"]) for lines in got for l in lines)
    assert received == sum(5 + i % 7 for i in range(16))
    snap = stats(served)
    rp, dt = snap["request_path"], snap["dispatch_trace"]
    assert rp["tokens"] == received == dt["delivered_tokens"]
    assert rp["handoffs"] == dt["delivered_streams"]
    # a line a token as ever; a write a chunk, a chunk one stream's whole
    # hand-offs: at least one a stream, at most one a hand-off
    assert rp["lines"] == received and 16 <= rp["writes"] <= rp["handoffs"]
    assert rp["writes"] < received
    assert rp["bytes"] == sum(len(json.dumps(l)) + 1
                              for lines in got for l in lines)
    assert 0 <= rp["egress_max_s"] <= rp["egress_s"]
    assert rp["handler_cpu_s"] >= 0
    # the ingress sums are the sums over the rows
    rows = rows_of(rp)
    assert rp["ingress_count"] == len(rows) == 16
    assert sorted(r["prompt_tokens"] for r in rows) == list(range(4, 20))
    assert rp["gateway_s"] == 0
    for key, a, b in (("read_parse_s", "t_accept", "t_parsed"),
                      ("submit_s", "t_parsed", "t_submit")):
        assert rp[key] == pytest.approx(sum(r[b] - r[a] for r in rows),
                                        abs=16 * ROUNDING)


def test_a_reply_that_is_not_streamed_counts_in_ingress_only(served):
    before = stats(served)["request_path"]
    status, body = call(served, "POST", "/generate",
                        {"prompt_ids": [3, 14, 15], "max_new_tokens": 6},
                        {GATEWAY_HELD_HEADER: "0.25"})
    assert status == 200 and len(json.loads(body)["tokens"][0]) == 6
    after = stats(served)["request_path"]
    d = grown(before, after)
    assert d["ingress_count"] == 1
    assert d["gateway_s"] == pytest.approx(0.25)
    assert not any(d[k] for k in ("handoffs", "tokens", "lines", "writes",
                                  "bytes", "egress_s"))
    row = rows_of(after)[-1]
    assert row["streamed"] == 0 and row["prompt_tokens"] == 3
    assert row["t_accept"] - row["t_gateway"] == pytest.approx(0.25,
                                                               abs=ROUNDING)
    # a header that is no number books nothing, and refuses nothing
    status, _ = call(served, "POST", "/generate",
                     {"prompt_ids": [3, 14, 15], "max_new_tokens": 2},
                     {GATEWAY_HELD_HEADER: "soon"})
    assert status == 200
    assert grown(after, stats(served)["request_path"])["gateway_s"] == 0


class _Scripted:
    """A backend whose stream the test holds: two hand-offs of two
    tokens, the second only once ``go`` is set.  ``resumed`` is set when
    the handler asks for the third token, which it does once the second
    line is on the socket and the first hand-off is booked.  It takes no
    ``all_ready``, so the handler has its items one at a time: a line a
    chunk, a write a line."""

    def __init__(self):
        self.go, self.resumed = threading.Event(), threading.Event()

    def stats(self):
        return {"stages": []}

    def generate(self, prompt_ids, max_new_tokens, seed=0):
        raise NotImplementedError

    def generate_stream(self, prompt_ids, max_new_tokens, seed=0,
                        on_submit=None):
        req = SimpleNamespace(t_submit=time.perf_counter(),
                              t_submit_wall=time.time(), handoffs=deque())
        on_submit([req])
        for last in (False, True):
            req.handoffs.append((time.perf_counter(), 2))
            yield np.asarray([7], np.int32)
            yield np.asarray([8], np.int32)
            if not last:
                self.resumed.set()
                assert self.go.wait(timeout=60)


def test_a_snapshot_taken_mid_request_holds_its_finished_hand_offs():
    backend = _Scripted()
    server = InferenceHTTPServer(backend, port=0)
    server.start()
    try:
        lines = []
        client = threading.Thread(target=lambda: lines.extend(
            stream(server, [1, 2, 3], 4)))
        client.start()
        assert backend.resumed.wait(timeout=60)
        mid = stats(server)["request_path"]
        assert (mid["ingress_count"], mid["handoffs"], mid["tokens"],
                mid["lines"], mid["writes"]) == (1, 1, 2, 2, 2)
        backend.go.set()
        client.join(timeout=60)
        assert [l["tokens"] for l in lines] == [[7], [8], [7], [8]]
        end = stats(server)["request_path"]
        assert (end["handoffs"], end["tokens"], end["lines"],
                end["writes"]) == (2, 4, 4, 4)
        assert end["egress_s"] >= mid["egress_s"] >= 0
        assert end["handler_cpu_s"] >= mid["handler_cpu_s"] >= 0
    finally:
        backend.go.set()
        server.shutdown()


# ---------------------------------------------------------------------------
# what a handler writes: one chunk, one write, for all its streams hold


class Held:
    """The engine's own ``generate_stream`` over requests that no
    scheduler serves: the test is the scheduler, and puts what it likes
    on their streams (``made`` hands it each request's rows)."""

    eos_id = None
    generate_stream = ContinuousBatchingEngine.generate_stream

    def __init__(self):
        self.made = queue.Queue()

    def stats(self):
        return {"stages": []}

    def generate(self, prompt_ids, max_new_tokens, seed=0):
        raise NotImplementedError

    def _submit_rows(self, ids, max_new_tokens, tenant=None, trace_id=0):
        reqs = [Request(prompt=np.asarray(row), max_new=max_new_tokens,
                        t_submit=time.perf_counter()) for row in ids]
        self.made.put(reqs)
        return reqs


def hand_off(req, items, error=None) -> None:
    """What ``ContinuousBatchingEngine._deliver`` does for one stream."""
    tokens = [t for t in items if t is not None]
    if tokens:
        req.handoffs.append((time.perf_counter(), len(tokens)))
    if items[-1] is None:
        req.error = error
        req.done.set()
    req.stream.put_many(items)


class RawClient:
    """A client that sees the chunks: one streamed ``/generate`` over a
    bare socket, the reply read chunk by chunk as it was framed."""

    def __init__(self, server, prompt, max_new):
        body = json.dumps({"prompt_ids": prompt, "max_new_tokens": max_new,
                           "stream": True}).encode()
        self.sock = socket.create_connection((server.host, server.port),
                                             timeout=30)
        self.sock.sendall(
            b"POST /generate HTTP/1.1\r\nHost: test\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: %d\r\n\r\n%b" % (len(body), body))
        self.file = self.sock.makefile("rb")
        self.headers = None

    def chunk(self) -> bytes:
        """The next chunk's data (``b""`` is the terminating chunk)."""
        if self.headers is None:
            assert self.file.readline().split()[1] == b"200"
            self.headers = []
            while (line := self.file.readline()) not in (b"\r\n", b""):
                self.headers.append(line)
        size = int(self.file.readline().strip(), 16)
        data = self.file.read(size)
        assert self.file.read(2) == b"\r\n"
        return data

    def close(self):
        self.file.close()
        self.sock.close()


def booked(server, n, key="handoffs") -> dict:
    """The record once it holds ``n`` hand-offs: a hand-off is booked
    when its write has returned, which is after the client can have
    read it (and a request with no hand-off to count by at its end)."""
    deadline = time.monotonic() + 30
    while True:
        rp = stats(server)["request_path"]
        if rp[key] >= n or time.monotonic() > deadline:
            return rp
        time.sleep(0.005)


def line_of(step, token) -> bytes:
    """A step's line as a chunk a line carried it (the parent's bytes)."""
    return (json.dumps({"step": step, "tokens": [token]}) + "\n").encode()


@pytest.fixture
def held():
    backend = Held()
    server = InferenceHTTPServer(backend, port=0)
    server.start()
    yield SimpleNamespace(backend=backend, server=server)
    server.shutdown()


@pytest.mark.quick
@pytest.mark.parametrize("k", [1, 4, 7])
def test_a_hand_off_of_k_tokens_is_one_chunk_of_k_lines(held, k):
    """... and dechunked it is byte for byte what a chunk a line was; the
    record counts ``k`` lines, one write."""
    client = RawClient(held.server, [1, 2, 3], k + 2)
    try:
        [req] = held.backend.made.get(timeout=30)
        tokens = list(range(40, 40 + k))
        hand_off(req, tokens)
        assert client.chunk() == b"".join(
            line_of(i, t) for i, t in enumerate(tokens))
        mid = booked(held.server, 1)
        assert (mid["handoffs"], mid["tokens"], mid["lines"],
                mid["writes"]) == (1, k, k, 1)
        # the last hand-off holds the sentinel: its lines, then the end
        hand_off(req, [98, 99, None])
        assert client.chunk() == line_of(k, 98) + line_of(k + 1, 99)
        assert client.chunk() == b""
        end = booked(held.server, 2)
        assert (end["handoffs"], end["tokens"], end["lines"],
                end["writes"]) == (2, k + 2, k + 2, 2)
        assert end["bytes"] == sum(
            len(line_of(i, t)) for i, t in enumerate(tokens + [98, 99]))
    finally:
        client.close()


@pytest.mark.quick
def test_a_handler_that_finds_one_token_writes_one_at_once(held):
    """No waiting for more: the first token is on the socket before the
    second exists, and the second, put 0.2 s later, is a second chunk."""
    client = RawClient(held.server, [1, 2, 3], 8)
    try:
        [req] = held.backend.made.get(timeout=30)
        hand_off(req, [7])
        client.sock.settimeout(5)       # a handler that waited would not
        assert client.chunk() == line_of(0, 7)      # ... have written it
        time.sleep(0.2)
        hand_off(req, [8])
        assert client.chunk() == line_of(1, 8)
        # two hand-offs that pile up while the handler is away (it cannot
        # be held, so: put under one lock) go out as one chunk
        with req.stream.mutex:
            req.handoffs.extend([(time.perf_counter(), 2)] * 2)
            req.stream.queue.extend([1, 2, 3, 4])
            req.stream.not_empty.notify()
        assert client.chunk() == b"".join(
            line_of(2 + i, t) for i, t in enumerate([1, 2, 3, 4]))
        hand_off(req, [None])
        assert client.chunk() == b""
        rp = booked(held.server, 4)
        assert (rp["handoffs"], rp["tokens"], rp["lines"],
                rp["writes"]) == (4, 6, 6, 3)
    finally:
        client.close()


@pytest.mark.quick
def test_tokens_then_a_failure_tokens_lines_error_line_end(held):
    """A row that fails with tokens ready: their lines are written, then
    the error's line, then the terminating chunk."""
    client = RawClient(held.server, [1, 2, 3], 8)
    try:
        [req] = held.backend.made.get(timeout=30)
        hand_off(req, [5])
        assert client.chunk() == line_of(0, 5)
        hand_off(req, [6, 7, None], error=RuntimeError("device lost"))
        assert client.chunk() == line_of(1, 6) + line_of(2, 7)
        assert json.loads(client.chunk()) == {"error": "device lost"}
        assert client.chunk() == b""
    finally:
        client.close()


@pytest.mark.quick
def test_two_rows_stream_the_steps_both_have(held):
    """A multi-row prompt: a chunk holds the steps every unfinished row
    has; a row that ended is padded; the longest row's tail ends it."""
    client = RawClient(held.server, [[1, 2, 3], [4, 5, 6]], 8)
    try:
        a, b = held.backend.made.get(timeout=30)
        hand_off(a, [10, 11, 12])
        hand_off(b, [20])
        assert [json.loads(l)["tokens"]
                for l in client.chunk().splitlines()] == [[10, 20]]
        hand_off(b, [21, None])             # b ends a step before a's third
        assert [json.loads(l) for l in client.chunk().splitlines()] == [
            {"step": 1, "tokens": [11, 21]}, {"step": 2, "tokens": [12, 0]}]
        hand_off(a, [13, None])
        assert [json.loads(l)["tokens"]
                for l in client.chunk().splitlines()] == [[13, 0]]
        assert client.chunk() == b""
    finally:
        client.close()


@pytest.mark.quick
def test_a_backend_without_the_batch_form_has_a_line_a_chunk():
    """``generate_stream`` takes no ``all_ready``: the handler has its
    items one at a time, and every line is a chunk and a write."""
    class PerStep:
        def stats(self):
            return {"stages": []}

        def generate(self, prompt_ids, max_new_tokens, seed=0):
            raise NotImplementedError

        def generate_stream(self, prompt_ids, max_new_tokens, seed=0):
            for t in range(max_new_tokens):
                yield np.asarray([30 + t], np.int32)

    server = InferenceHTTPServer(PerStep(), port=0)
    server.start()
    client = RawClient(server, [1, 2, 3], 5)
    try:
        assert [client.chunk() for _ in range(6)] == [
            line_of(t, 30 + t) for t in range(5)] + [b""]
        rp = booked(server, 5, "tokens")
        assert (rp["tokens"], rp["lines"], rp["writes"]) == (5, 5, 5)
    finally:
        client.close()
        server.shutdown()


def test_a_backend_that_does_not_stream_has_no_request_path():
    class Plain:
        def stats(self):
            return {"stages": [], "mine": 1}

        def generate(self, prompt_ids, max_new_tokens, seed=0):
            ids = np.asarray(prompt_ids)
            return SimpleNamespace(tokens=np.zeros(
                (ids.shape[0], max_new_tokens), np.int32))

    server = InferenceHTTPServer(Plain(), port=0)
    server.start()
    try:
        assert server.request_path is None
        status, body = call(server, "POST", "/generate",
                            {"prompt_ids": [1, 2], "max_new_tokens": 3})
        assert status == 200 and json.loads(body)["tokens"] == [[0, 0, 0]]
        assert stats(server) == {"stages": [], "mine": 1}
        assert call(server, "POST", "/stats/reset")[0] == 501
    finally:
        server.shutdown()


def test_the_record_alone_adds_up_under_threads():
    """``+=`` on a shared attribute loses counts under threads; the
    record's one lock a call does not."""
    rec = RequestPath()

    def work():
        for _ in range(1000):
            rec.egress([0.0], 1.0, 4, 4, 1, 100, 0.001)

    # more threads than cores, and a switch every few bytecodes
    threads = [threading.Thread(target=work) for _ in range(64)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    snap = rec.snapshot()
    assert (snap["handoffs"], snap["tokens"], snap["lines"], snap["writes"],
            snap["bytes"]) == (64000, 256000, 256000, 64000, 6400000)
    assert snap["egress_s"] == pytest.approx(64000.0)
    assert snap["egress_max_s"] == 1.0
    assert snap["handler_cpu_s"] == pytest.approx(64.0)
    rec.reset()
    assert rec.snapshot()["handoffs"] == 0 and not rec.snapshot()["recent"]
