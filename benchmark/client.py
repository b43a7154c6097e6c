"""The load generator: one thread, non-blocking sockets, streamed replies.

Every measured request is ``POST /generate`` with ``prompt_ids``,
``max_new_tokens`` and ``"stream": true``.  One ``selectors`` loop sends
each request when it is due and stamps every token line when its bytes
arrive, so the meter needs no thread per request and does not fight the
server for cores.  A request is timed from the instant it was DUE, not
from when it was sent (open loop); how late the loop sent it is kept as
``sent - due``.
"""

from __future__ import annotations

import heapq
import json
import selectors
import socket
import time
from dataclasses import dataclass, field

from stack import http_json


@dataclass
class Request:
    """One request of a plan.  ``due`` is seconds from the plan's start;
    ``key`` is the generator's own handle (a client, a session turn)."""
    due: float
    prompt: list
    max_new: int
    key: object = None


@dataclass
class Record:
    """What the client saw of one request (times: ``time.monotonic()``)."""
    due: float
    prompt_len: int
    max_new: int
    key: object = None
    sent: float = 0.0
    status: int = 0
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    error: str = ""
    end: float = 0.0

    def problem(self, vocab: int) -> str:
        """Why this request failed, or '' if it is well formed."""
        if self.error:
            return self.error
        if self.status != 200:
            return f"HTTP {self.status}"
        if len(self.tokens) != self.max_new:
            return f"{len(self.tokens)} tokens, asked for {self.max_new}"
        if not all(isinstance(t, int) and 0 <= t < vocab
                   for t in self.tokens):
            return "token outside the vocabulary"
        return ""


class _Conn:
    """One in-flight request: socket, unsent bytes, response parser."""

    def __init__(self, rec: Record, req: Request, sock, out: bytes):
        self.rec, self.req, self.sock, self.out = rec, req, sock, out
        self.buf = b""
        self.headers_done = False
        self.chunked = False
        self.length = None
        self.body = b""          # decoded body bytes not yet split in lines
        self.need = None         # bytes left of the current chunk
        self.finished = False

    def feed(self, data: bytes, now: float) -> None:
        self.buf += data
        if not self.headers_done:
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = self.buf[:end].decode("latin-1").split("\r\n")
            self.buf = self.buf[end + 4:]
            self.rec.status = int(head[0].split()[1])
            for h in head[1:]:
                k, _, v = h.partition(":")
                k, v = k.strip().lower(), v.strip().lower()
                if k == "transfer-encoding" and "chunked" in v:
                    self.chunked = True
                elif k == "content-length":
                    self.length = int(v)
            self.headers_done = True
        if self.chunked:
            self._dechunk()
        else:
            self.body += self.buf
            self.buf = b""
            if self.length is not None and len(self.body) >= self.length:
                self.finished = True
        self._lines(now)

    def _dechunk(self) -> None:
        while True:
            if self.need is None:
                eol = self.buf.find(b"\r\n")
                if eol < 0:
                    return
                size = int(self.buf[:eol].split(b";")[0] or b"0", 16)
                self.buf = self.buf[eol + 2:]
                if size == 0:
                    self.finished = True
                    return
                self.need = size
            if len(self.buf) < self.need + 2:
                return
            self.body += self.buf[:self.need]
            self.buf = self.buf[self.need + 2:]
            self.need = None

    def _lines(self, now: float) -> None:
        rec = self.rec
        if rec.status != 200:
            if self.finished:
                rec.error = (f"HTTP {rec.status}: "
                             + self.body[:200].decode("utf-8", "replace"))
            return
        while True:
            eol = self.body.find(b"\n")
            if eol < 0:
                return
            raw, self.body = self.body[:eol].strip(), self.body[eol + 1:]
            if not raw:
                continue
            try:
                item = json.loads(raw)
            except ValueError:
                rec.error = f"unparseable line {raw[:80]!r}"
                return
            if "error" in item:
                rec.error = f"error line: {item['error']}"
            elif item.get("tokens"):
                rec.tokens.append(item["tokens"][0])
                rec.token_times.append(now)


def _http_request(host: str, port: int, req: Request) -> bytes:
    body = json.dumps({"prompt_ids": [req.prompt],
                       "max_new_tokens": req.max_new,
                       "stream": True}).encode()
    return (f"POST /generate HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\nConnection: close\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


def run_plan(port: int, plan, stop_sending_at: float, give_up_at: float,
             t0: float | None = None, host: str = "127.0.0.1",
             clock=time.monotonic) -> tuple:
    """Drive ``plan`` against ``host:port``.  ``plan.initial()`` gives the
    first requests, ``plan.on_done(request, tokens, seconds_from_start)``
    the ones a finished request sets off (a closed-loop client's next, a
    session's next turn).  Times are seconds from ``t0``.  A request due at
    or after ``stop_sending_at`` is not sent; at ``give_up_at`` whatever is
    unfinished is closed with the error ``"not finished by the end of the
    drain"``.  Returns ``(t0, [Record, ...])`` in order of sending."""
    t0 = clock() if t0 is None else t0
    sel = selectors.DefaultSelector()
    heap, seq, records, live = [], 0, [], 0
    for r in plan.initial():
        heapq.heappush(heap, (r.due, seq, r))
        seq += 1

    def close(conn: _Conn, now: float) -> None:
        nonlocal live, seq
        try:
            sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        conn.sock.close()
        conn.rec.end = now
        live -= 1
        if not conn.finished and not conn.rec.error:
            conn.rec.error = "connection closed before the reply ended"
        if not conn.rec.error:
            for nxt in plan.on_done(conn.req, conn.rec.tokens, now - t0):
                heapq.heappush(heap, (nxt.due, seq, nxt))
                seq += 1

    try:
        while True:
            now = clock()
            if now - t0 >= give_up_at:
                break
            while heap and heap[0][0] <= now - t0:
                _, _, req = heapq.heappop(heap)
                if req.due >= stop_sending_at:
                    continue
                rec = Record(due=t0 + req.due, prompt_len=len(req.prompt),
                             max_new=req.max_new, key=req.key)
                records.append(rec)
                sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sock.setblocking(False)
                sock.connect_ex((host, port))
                conn = _Conn(rec, req, sock, _http_request(host, port, req))
                sel.register(sock, selectors.EVENT_WRITE, conn)
                live += 1
            if heap and heap[0][0] >= stop_sending_at:
                heap.clear()
            if not heap and live == 0:
                # a closed loop or a session may set off more only from
                # close(); nothing in flight and nothing due = done
                break
            wait = give_up_at - (now - t0)
            if heap:
                wait = min(wait, heap[0][0] - (now - t0))
            for key, mask in sel.select(max(0.0, min(wait, 0.5))):
                conn = key.data
                now = clock()
                try:
                    if mask & selectors.EVENT_WRITE:
                        err = conn.sock.getsockopt(socket.SOL_SOCKET,
                                                   socket.SO_ERROR)
                        if err:
                            raise OSError(err, "connect failed")
                        if not conn.rec.sent:
                            conn.rec.sent = now
                        n = conn.sock.send(conn.out)
                        conn.out = conn.out[n:]
                        if not conn.out:
                            sel.modify(conn.sock, selectors.EVENT_READ, conn)
                    else:
                        data = conn.sock.recv(65536)
                        if data:
                            conn.feed(data, now)
                        if not data or conn.finished:
                            close(conn, now)
                except (BlockingIOError, InterruptedError):
                    continue
                except (OSError, ValueError, IndexError) as e:
                    conn.rec.error = (conn.rec.error
                                      or f"{type(e).__name__}: {e}")
                    close(conn, now)
    finally:
        now = clock()
        for key in list(sel.get_map().values()):
            conn = key.data
            conn.rec.error = (conn.rec.error
                              or "not finished by the end of the drain")
            sel.unregister(conn.sock)
            conn.sock.close()
            conn.rec.end = now
        sel.close()
    return t0, records


def ask(port: int, prompt: list, max_new: int, logprobs: bool = False,
        timeout: float = 600.0) -> dict:
    """One blocking, unstreamed request (canaries, warm-up, the reference
    check): ``{"status", "tokens", "logprobs", "generation", "error"}``.
    ``generation`` is what the reply says of how the one sequence was
    generated (its entry of the reply's ``generation`` list, JSON, not
    looked into here: the family's replay reads it), ``None`` where the
    reply says nothing."""
    body = {"prompt_ids": [prompt], "max_new_tokens": max_new}
    if logprobs:
        body["logprobs"] = True
    status, out = http_json(port, "POST", "/generate", body, timeout)
    return {"status": status, "tokens": list((out.get("tokens") or [[]])[0]),
            "logprobs": list((out.get("logprobs") or [[]])[0]),
            "generation": (out.get("generation") or [None])[0],
            "error": out.get("error", "")}
