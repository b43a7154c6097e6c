"""Open-loop timing against a fake server that stalls: one request at a
time, each holding the line for a fixed time before it streams."""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

import arith
from client import Request, run_plan

HOLD_S = 0.15


class Slow(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if body["prompt_ids"][0][0] == 666:
            out = json.dumps({"error": "refused"}).encode()
            self.send_response(503)
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)
            return
        time.sleep(HOLD_S)
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        for i in range(body["max_new_tokens"]):
            data = (json.dumps({"step": i, "tokens": [7]}) + "\n").encode()
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.write(b"0\r\n\r\n")
        self.close_connection = True


class Plan:
    def __init__(self, reqs):
        self.reqs, self.done = reqs, []

    def initial(self):
        return list(self.reqs)

    def on_done(self, request, tokens, now_s):
        self.done.append((request.key, len(tokens)))
        return []


@pytest.fixture()
def server():
    srv = HTTPServer(("127.0.0.1", 0), Slow)       # one request at a time
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)
    assert not t.is_alive()


def test_a_stall_is_charged_to_the_requests_behind_it(server):
    plan = Plan([Request(0.00, [1, 2], 3, key=0),
                 Request(0.02, [1, 2], 3, key=1),
                 Request(0.04, [1, 2], 3, key=2)])
    t0, recs = run_plan(server, plan, 10.0, 10.0)
    assert [r.problem(100) for r in recs] == ["", "", ""]
    assert sorted(plan.done) == [(0, 3), (1, 3), (2, 3)]
    ttft = [arith.ttft_ms(r) for r in recs]
    # the server serves them in turn: each waits for those before it, and
    # that wait counts, because time runs from the due instant
    assert ttft[0] == pytest.approx(HOLD_S * 1e3, abs=60)
    assert ttft[1] == pytest.approx((2 * HOLD_S - 0.02) * 1e3, abs=80)
    assert ttft[2] == pytest.approx((3 * HOLD_S - 0.04) * 1e3, abs=100)
    # ...while the generator itself sent every request on time
    assert max(arith.late_ms(r) for r in recs) < 20


def test_failures_are_records_not_exceptions(server):
    plan = Plan([Request(0.0, [666], 2, key="refused"),
                 Request(0.0, [1], 2, key="cut"),
                 Request(5.0, [1], 2, key="never sent")])
    _, recs = run_plan(server, plan, 1.0, 0.05)    # give up before the hold
    by = {r.key: r for r in recs}
    assert "never sent" not in by
    assert by["cut"].problem(100) == "not finished by the end of the drain"
    assert by["refused"].problem(100) != ""
    assert plan.done == []
