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
  ``/stats`` is as it was.
"""

import http.client
import json
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
    ContinuousBatchingEngine)
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
    spans = {e["name"]: e for e in json.loads(
        call(served, "GET", "/trace")[1])["traceEvents"]
        if e.get("args", {}).get("trace_id") == tid}
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
    assert (eg["args"]["lines"], eg["args"]["writes"]) == (9, 18)
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
    assert rp["lines"] == received and rp["writes"] == 2 * received
    assert rp["handoffs"] == dt["delivered_streams"]
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
    line is on the socket and the first hand-off is booked."""

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
                mid["lines"], mid["writes"]) == (1, 1, 2, 2, 4)
        backend.go.set()
        client.join(timeout=60)
        assert [l["tokens"] for l in lines] == [[7], [8], [7], [8]]
        end = stats(server)["request_path"]
        assert (end["handoffs"], end["tokens"], end["lines"],
                end["writes"]) == (2, 4, 4, 8)
        assert end["egress_s"] >= mid["egress_s"] >= 0
        assert end["handler_cpu_s"] >= mid["handler_cpu_s"] >= 0
    finally:
        backend.go.set()
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
            rec.egress([0.0], 1.0, 4, 4, 8, 100, 0.001)

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
            snap["bytes"]) == (64000, 256000, 256000, 512000, 6400000)
    assert snap["egress_s"] == pytest.approx(64000.0)
    assert snap["egress_max_s"] == 1.0
    assert snap["handler_cpu_s"] == pytest.approx(64.0)
    rec.reset()
    assert rec.snapshot()["handoffs"] == 0 and not rec.snapshot()["recent"]
