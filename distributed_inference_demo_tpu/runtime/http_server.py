"""HTTP inference endpoint: the working replacement for the reference's stub.

The reference parses HTTP by hand off a raw socket and answers every
inference request with ``"Inference not implemented yet"``
(``server.py:539-678``).  Here: a stdlib ``ThreadingHTTPServer`` exposing

- ``GET  /health``    — model, device, capacity
- ``GET  /stats``     — hot-loop metrics (per-stage comm/compute split,
  byte counts, ring-RTT percentiles — the reference's
  ``commutimeArraySum``/``infertimeArraySum`` dump as an API,
  ``Communication.java:650-661``); for a backend that streams, also
  ``request_path``: what a request costs between the socket and the
  engine, both ways (``telemetry.tracing.RequestPath``, docs/DESIGN.md
  §16)
- ``GET  /metrics``   — Prometheus text exposition (telemetry/catalog):
  the same stage counters as /stats plus batching/speculative and
  monitor series, scrapeable by a stock Prometheus
- ``GET  /trace``     — Chrome trace-event JSON of the spans recorded
  since the last call (pipeline + batching backends; load in Perfetto)
- ``GET  /timeline``  — recent per-request timeline records + the
  per-tenant SLO/goodput summary (telemetry/slo; ``?n=`` bounds the
  tail)
- ``POST /generate``  — ``{"prompt_ids": [[...]], "max_new_tokens": N,
  "stream": false}`` → ``{"tokens": [[...]]}``; with ``"prompt": "text"``
  when a tokenizer is attached; ``"stream": true`` switches to chunked
  JSONL, one ``{"step": i, "tokens": [...]}`` line per decoded step (the
  reference streams partial decodes to its UI via DataRepository,
  ``Communication.java:629-638`` — this is that capability as an API).

The backend is anything with the engine surface (``generate`` /
``generate_stream``): the single-chip ``InferenceEngine``, or an
``ElasticHeader`` via :class:`HeaderBackend`.
"""

from __future__ import annotations

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from ..telemetry import catalog as _metrics
from ..telemetry.tracing import GATEWAY_HELD_HEADER, RequestPath
from .overload import SchedulerOverloaded


def _round_lps(row) -> list:
    """JSON-friendly logprob row (6 decimals ≈ float32 noise floor)."""
    return [round(float(x), 6) for x in row]


class StopMatcher:
    """Incremental stop-sequence matching over streamed text — one owner
    for the blocking and streaming ``stop`` paths.

    ``feed(piece) -> (emittable, matched)``: ``emittable`` is the text
    that can be released to the client NOW — everything before the
    longest trailing run that is still a prefix of some stop string
    (streaming must never emit characters it would have to retract when
    the stop completes a step later).  When a stop completes,
    ``matched`` is True, ``pos`` is the cut position (start of the
    earliest match across all stop strings), and ``emittable`` carries
    exactly the remaining pre-stop text.

    The cut is CHUNKING-INDEPENDENT: feeding per-token pieces and
    feeding the whole text yield the same ``pos`` (the position the
    whole-string ``min(text.find(s))`` reference produces).  The subtle
    case is a short stop completing while an EARLIER-starting longer
    stop is still a live prefix of the buffer tail (stop=["abc", "b"],
    fed "a" then "b": "b" completes at 1, but "ab" may still become
    "abc" cutting at 0) — the verdict is DEFERRED, bounded by the
    longest stop length, until the earlier candidate completes (it wins)
    or dies (the completed match stands).  ``finish()`` resolves a
    still-pending verdict at stream end: no more text can arrive, so the
    completed match stands."""

    def __init__(self, stop):
        self.stop = list(stop)
        # empty stop set = valid pass-through matcher (never matches)
        self._maxlen = max((len(s) for s in self.stop), default=0)
        # only the UNEMITTED tail is buffered: emitted text was released
        # precisely because the holdback proved no future stop can start
        # inside it, so matching stays O(piece + longest_stop) per feed
        # and memory stays bounded regardless of generation length.
        self._buf = ""
        self._base = 0                  # absolute offset of _buf[0]
        self.pos: Optional[int] = None  # absolute cut position

    def feed(self, piece: str):
        if self.pos is not None:
            return "", True
        self._buf += piece
        return self._scan(final=False)

    def _live_start_before(self, comp: int) -> Optional[int]:
        """Earliest start j < comp of a LONGER stop still a live prefix
        running through the buffer end — the position a later-completing
        match could still cut at, making the verdict at ``comp``
        undecidable this feed.  Only starts within ``maxlen`` of the
        buffer end can qualify (a live prefix must outrun the buffer)."""
        buf = self._buf
        for j in range(max(0, len(buf) - self._maxlen + 1), comp):
            tail = buf[j:]
            if any(len(s) > len(tail) and s.startswith(tail)
                   for s in self.stop):
                return j
        return None

    def _scan(self, final: bool):
        buf = self._buf
        hits = [buf.find(s) for s in self.stop if s in buf]
        if hits:
            comp = min(hits)
            live = None if final else self._live_start_before(comp)
            if live is None:
                self.pos = self._base + comp
                out = buf[:comp]
                self._base += comp
                self._buf = ""
                return out, True
            # verdict deferred: emit only up to the live earlier
            # candidate's start; the pending completed match stays in
            # the buffer and is re-found (or beaten) next feed
            out = buf[:live]
            self._base += live
            self._buf = buf[live:]
            return out, False
        hold = max((k for s in self.stop for k in range(1, len(s))
                    if buf.endswith(s[:k])), default=0)
        safe_end = len(buf) - hold
        if safe_end > 0:
            out = buf[:safe_end]
            self._base += safe_end
            self._buf = buf[safe_end:]
            return out, False
        return "", False

    def finish(self):
        """End of stream: resolve any deferred verdict (a pending
        completed match now stands — no more text can complete the
        earlier candidate) and release held-back text otherwise.
        Returns ``(emittable, matched)`` like ``feed``."""
        if self.pos is not None:
            return "", True
        out, matched = self._scan(final=True)
        if not matched:
            out += self._buf
            self._base += len(self._buf)
            self._buf = ""
        return out, matched

    def flush(self) -> str:
        """Back-compat wrapper: ``finish()``'s text alone.  Callers that
        can still act on a late match should use ``finish`` and check
        ``matched`` (a deferred verdict may resolve to a cut here)."""
        return self.finish()[0]


class _StopSession:
    """The per-row decode/match/cut core shared by the BLOCKING and
    STREAMING stop paths (one owner — the eos-flush and token-truncation
    rules must not fork).  ``consume(item)`` processes one step's [b]
    tokens and returns per-row emittable text; ``finish()`` flushes rows
    that ran to length.  Results: ``toks`` (truncated, ragged),
    ``texts``, ``reason`` ("stop" | "eos" | "length"), ``done``.

    With ``logprobs=True``, ``consume`` takes the backend's
    ``(tokens, logprobs)`` pairs and ``lps`` carries per-row logprob
    rows truncated EXACTLY where ``toks`` truncates — one cut position,
    two parallel lists, so a stop can never leave a logprob for a token
    the client never saw (or vice versa)."""

    def __init__(self, tokenizer, stop, b: int, eos,
                 logprobs: bool = False):
        from ..tokenizer import StreamDetokenizer
        self.eos = eos
        self.detoks = [StreamDetokenizer(tokenizer) for _ in range(b)]
        self.matchers = [StopMatcher(stop) for _ in range(b)]
        self.texts = [""] * b
        self.toks = [[] for _ in range(b)]
        self.lens = [[] for _ in range(b)]   # cum text len per token
        self.lps = [[] for _ in range(b)] if logprobs else None
        self.done = [False] * b
        self.reason = ["length"] * b
        self.b = b

    def _cut(self, r: int) -> None:
        """Apply a completed match: truncate text at the cut and keep
        every token needed to produce it (up to the first whose
        cumulative visible text reaches the cut) — logprob rows cut at
        the same token index."""
        import bisect
        m = self.matchers[r].pos
        keep = bisect.bisect_left(self.lens[r], m) + 1
        self.toks[r] = self.toks[r][:min(keep, len(self.toks[r]))]
        if self.lps is not None:
            self.lps[r] = self.lps[r][:len(self.toks[r])]
        self.texts[r] = self.texts[r][:m]
        self.done[r], self.reason[r] = True, "stop"

    def _push(self, r: int, raw: str) -> None:
        self.texts[r] += raw
        if self.lens[r]:
            self.lens[r][-1] = len(self.texts[r])

    def consume(self, item) -> list:
        if self.lps is not None:
            toks_item, lps_item = item
            lp_arr = np.asarray(lps_item).reshape(-1).tolist()
        else:
            toks_item, lp_arr = item, None
        arr = np.asarray(toks_item).reshape(-1).tolist()
        pieces = [""] * self.b
        for r in range(self.b):
            if self.done[r]:
                continue
            self.toks[r].append(int(arr[r]))
            if lp_arr is not None:
                self.lps[r].append(float(lp_arr[r]))
            raw = self.detoks[r].push(arr[r])
            self.texts[r] += raw
            self.lens[r].append(len(self.texts[r]))
            pieces[r], matched = self.matchers[r].feed(raw)
            if matched:
                self._cut(r)
            elif self.eos is not None and int(arr[r]) == self.eos:
                # natural termination beats budget (a row past its eos
                # only pads — engine _mask_eos); the detokenizer may
                # still hold back chars from EARLIER tokens: flush them
                # through the matcher so they are neither lost nor
                # allowed to complete a stop unnoticed
                tail = self.detoks[r].flush()
                self._push(r, tail)
                extra, matched = self.matchers[r].feed(tail)
                pieces[r] += extra
                if not matched:
                    # the row is over: resolve any deferred verdict (a
                    # pending completed stop now stands) before calling
                    # it an eos finish
                    extra2, matched = self.matchers[r].finish()
                    pieces[r] += extra2
                if matched:
                    self._cut(r)
                else:
                    self.done[r], self.reason[r] = True, "eos"
        return pieces

    def finish(self) -> list:
        """Flush detok + matcher holdback for rows that ran to length
        (a stop may still complete inside the flushed tail)."""
        pieces = [""] * self.b
        for r in range(self.b):
            if self.done[r]:
                continue
            tail = self.detoks[r].flush()
            self._push(r, tail)
            piece, matched = self.matchers[r].feed(tail)
            if not matched:
                extra, matched = self.matchers[r].finish()
                piece += extra
            pieces[r] = piece
            if matched:
                self._cut(r)
        return pieces


def _accepts_kwarg(fn, name: str) -> bool:
    """Duck-typed capability check: does ``fn`` accept ``name=``?  True
    for an explicit parameter OR a **kwargs catch-all (wrapper backends
    that forward to an engine)."""
    import inspect
    params = inspect.signature(fn).parameters
    return (name in params
            or any(p.kind is inspect.Parameter.VAR_KEYWORD
                   for p in params.values()))


# a handler reads its thread's CPU clock at the first hand-off of every
# quarter second and at its request's end, not at every hand-off: the
# read is a system call (5.8 us on the benchmark's machine, where the
# clock also ticks in steps of 10 ms, seven hand-offs' worth: PERF.md
# section 6, PR 58), and the differences add up to the same sum
_CPU_READ_S = 0.25


class _Passage:
    """One ``/generate`` request's way through its handler thread: the
    stamps of the way in, and what the thread wrote and used on the way
    out since it last committed to the server's
    :class:`~..telemetry.tracing.RequestPath` (``rec``; ``None`` for a
    backend that does not stream: the stamps still feed the two
    ``dwt_http_*`` series).  Every instant is ``time.monotonic()``.  The
    handler's own: nothing here is shared with another thread but the
    requests' ``handoffs``, which the scheduler appends to and this
    takes off."""

    def __init__(self, rec, headers):
        self.rec = rec
        self.t_accept = self.t_cpu = time.monotonic()
        self.cpu = time.thread_time()
        try:
            held = float(headers.get(GATEWAY_HELD_HEADER) or 0.0)
        except ValueError:
            held = 0.0
        self.gateway_s = min(max(held, 0.0), 3600.0)
        tid = headers.get("X-DWT-Trace-Id")
        try:
            self.trace_id = int(tid[:64], 16) if tid else 0
        except ValueError:
            self.trace_id = 0
        # epoch seconds at monotonic 0, for a traced request's two spans
        self.wall0 = time.time() - self.t_accept if self.trace_id else 0.0
        self.t_parsed = self.t_submit = 0.0
        self.rows = self.prompt_tokens = 0
        self.streamed = False
        self.reqs: list = []
        self.bases: list = []
        self.consumed = 0           # items taken from the backend
        self.edge = float("inf")    # `consumed` at the next hand-off's end
        self.lines = self.writes = self.bytes = 0    # since the last commit
        # the request's own totals: its egress span and the two series
        self.tokens = self.handoffs = self.all_lines = self.all_writes = 0
        self.max_s = self.cpu_s = 0.0
        self.t_first_handoff = self.t_last_write = 0.0

    def parsed(self, ids, streamed: bool) -> None:
        self.t_parsed = time.monotonic()
        self.rows, self.prompt_tokens = len(ids), int(ids.size)
        self.streamed = streamed

    def submitted(self, reqs=()) -> None:
        """The backend has the request: the engine calls this with the
        rows' ``Request`` objects (``on_submit``) and the stamp is its
        own ``t_submit``; for a backend that takes no ``on_submit`` the
        handler calls it just before the backend."""
        if self.t_submit:
            return
        self.reqs, self.bases = list(reqs), [0] * len(reqs)
        if self.reqs:
            last = self.reqs[-1]
            self.t_submit, self.edge = last.t_submit, 1
            if self.trace_id and last.t_submit_wall:
                self.wall0 = last.t_submit_wall - last.t_submit
        else:
            self.t_submit = time.monotonic()
        if self.rec is not None:
            self.rec.ingress(self.gateway_s, self.t_accept, self.t_parsed,
                             self.t_submit, self.prompt_tokens,
                             self.streamed)

    def counted(self, batches, flush):
        """The backend's items, one by one, out of the ``batches`` it
        gives them in; ``flush`` is called where a batch is used up,
        before the backend is asked for the next and may block."""
        for batch in batches:
            for item in batch:
                self.consumed += 1
                yield item
            flush()

    def commit(self, final: bool = False) -> float:
        """A write has returned, every line of every item taken so far
        was in it or in one before it, and ``consumed`` has reached
        ``edge``: take every hand-off whose tokens are all written off
        the requests' ``handoffs`` and book it (a write that carried
        two hand-offs' lines books both, with one instant), with what
        was written since the last commit (``lines``; ``writes``, the
        calls of ``wfile.write`` that carried them, one a chunk;
        ``bytes``, the lines' own) and the thread's CPU seconds since
        it last read them (``_CPU_READ_S``)."""
        now = time.monotonic()
        stamps, tokens, edge = [], 0, float("inf")
        # (a reply that is not streamed writes no hand-off out)
        for i, r in enumerate(self.reqs if self.streamed else ()):
            q, base = r.handoffs, self.bases[i]
            while q and base + q[0][1] <= self.consumed:
                t, n = q.popleft()
                base += n
                tokens += n
                stamps.append(t)
            self.bases[i] = base
            if q:
                edge = min(edge, base + q[0][1])
        if self.reqs and edge == float("inf"):
            edge = self.consumed + 1    # the next hand-off is not there yet
        self.edge = edge
        if not (stamps or final):
            return now
        if final and not self.reqs and self.streamed:
            tokens = self.consumed * self.rows      # no hand-off to count by
        cpu_s = 0.0
        if final or now - self.t_cpu >= _CPU_READ_S:
            cpu = time.thread_time()
            cpu_s, self.cpu, self.t_cpu = cpu - self.cpu, cpu, now
        if self.rec is not None and self.t_submit:
            self.rec.egress(stamps, now, tokens, self.lines, self.writes,
                            self.bytes, cpu_s)
        if stamps:
            first = min(stamps)
            self.t_first_handoff = self.t_first_handoff or first
            self.max_s = max(self.max_s, now - first)
            self.t_last_write = now
        self.handoffs += len(stamps)
        self.tokens += tokens
        self.all_lines += self.lines
        self.all_writes += self.writes
        self.cpu_s += cpu_s
        self.lines = self.writes = self.bytes = 0
        return now

    def finish(self, tracer) -> None:
        """The request's end, whatever it was: the thread's last commit,
        the layer's two series from the same stamps, and on a request
        with a trace id its two spans into the replica's recorder."""
        end = self.commit(final=True)
        if not self.t_submit:
            return                  # refused before any backend saw it
        _metrics.HTTP_REQUEST_SECONDS.observe(end - self.t_accept,
                                              route="/generate")
        _metrics.HTTP_GENERATED_TOKENS.inc(self.tokens)
        if not (self.trace_id and hasattr(tracer, "record")):
            return
        tracer.record(
            "http.ingress", self.trace_id, ts=self.wall0 + self.t_accept,
            dur=self.t_submit - self.t_accept,
            gateway_ms=round(1e3 * self.gateway_s, 3),
            read_parse_ms=round(1e3 * (self.t_parsed - self.t_accept), 3),
            submit_ms=round(1e3 * (self.t_submit - self.t_parsed), 3),
            prompt_tokens=self.prompt_tokens, streamed=self.streamed)
        if self.handoffs:
            tracer.record(
                "http.egress", self.trace_id,
                ts=self.wall0 + self.t_first_handoff,
                dur=self.t_last_write - self.t_first_handoff,
                handoffs=self.handoffs, lines=self.all_lines,
                writes=self.all_writes,
                max_ms=round(1e3 * self.max_s, 3),
                cpu_ms=round(1e3 * self.cpu_s, 3))


class HeaderBackend:
    """Adapts a PipelineHeader/ElasticHeader to the engine surface used by
    the HTTP handler (generate + generate_stream)."""

    def __init__(self, header, max_seq: int, num_stages: int = 2):
        from ..telemetry.anomaly import AnomalyMonitor
        self.header = header
        self.max_seq = max_seq
        self.num_stages = num_stages
        self._lock = threading.Lock()   # one pipeline run at a time
        # straggler watch over the polled stage snapshots: every /stats
        # or /metrics collection feeds the detector, so a scheduled
        # Prometheus scrape is what drives straggler-hop detection in
        # production (no extra polling thread)
        self.anomaly = AnomalyMonitor(config={
            "backend": type(self).__name__, "num_stages": num_stages,
            "max_seq": max_seq})

    def stats(self) -> dict:
        """Header snapshot + polled downstream stage snapshots."""
        with self._lock:
            stages = self.header.collect_stats(self.num_stages)
        self.anomaly.observe({"stages": stages})
        return {"stages": stages}

    def export_trace(self) -> dict:
        """Chrome trace JSON of all spans recorded since the last export
        (header + every downstream stage, via the statsreq path)."""
        with self._lock:
            return self.header.collect_trace(self.num_stages)

    def scrape_stats(self) -> dict:
        """Like :meth:`stats` but BOUNDED end to end — a Prometheus
        scrape runs on a schedule and must not stall behind an in-flight
        generation (the request lock is held for a whole run) or a dead
        stage (the stats poll waits ~10s per missing reply).  When the
        pipeline is busy, return no stages: the scrape renders the
        last-bridged series instead of going DOWN exactly while the
        system is under the load telemetry exists to observe."""
        if not self._lock.acquire(timeout=2.0):
            return {"stages": []}
        try:
            stages = self.header.collect_stats(self.num_stages,
                                               timeout=2.0)
        finally:
            self._lock.release()
        self.anomaly.observe({"stages": stages})
        return {"stages": stages}

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 seed: int = 0):
        import time

        from .engine import GenerationResult
        ids = np.asarray(prompt_ids)
        t0 = time.perf_counter()
        with self._lock:
            toks = self.header.generate(ids, max_new_tokens)
        return GenerationResult(tokens=toks, prompt_len=ids.shape[1],
                                num_new=toks.shape[1],
                                seconds=time.perf_counter() - t0)

    def generate_stream(self, prompt_ids: np.ndarray, max_new_tokens: int,
                        seed: int = 0):
        """TRUE streaming over the pipeline: the header's run loop fires
        ``on_token`` per ring step on a worker thread; tokens are yielded
        the moment each one returns from the tail (the reference streams
        partial decodes to its UI the same way, DataRepository)."""
        import queue as queue_mod

        q: "queue_mod.Queue" = queue_mod.Queue()
        SENTINEL = object()

        def run():
            try:
                with self._lock:
                    self.header.generate_many(
                        [np.asarray(prompt_ids)], max_new_tokens,
                        on_token=lambda i, step, toks: q.put(toks))
            except BaseException as e:     # surface in the consumer
                q.put(e)
            finally:
                q.put(SENTINEL)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is SENTINEL:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
        t.join(timeout=10)

    def classify(self, prompt_ids: np.ndarray, label_token_ids):
        with self._lock:
            [pred] = self.header.classify_many(
                [np.asarray(prompt_ids)], label_token_ids)
        return pred

    def reset_stats(self):
        with self._lock:
            self.header.reset_stats()

    def debug_state(self) -> dict:
        """Backend fragment of ``GET /debugz``: the ring steps still
        awaiting their reply (racy read of header-owned state — a
        diagnostic peek, not an invariant) + straggler-detector state."""
        sent = getattr(self.header, "_sent_at", {})
        return {"num_stages": self.num_stages,
                "in_flight": [[r, s] for r, s in sorted(sent.keys())],
                "anomaly": self.anomaly.state()}


class InferenceHTTPServer:
    """Threaded HTTP server over an engine-like backend."""

    def __init__(self, backend, host: str = "127.0.0.1", port: int = 0,
                 tokenizer=None, model_name: str = "",
                 default_max_new: int = 128,
                 request_timeout: Optional[float] = None):
        """``request_timeout``: per-request deadline for blocking
        ``/generate`` — passed as ``timeout=`` to backends that accept
        it (the continuous-batching engine cancels the request through
        ``Request.cancel()``, freeing its slot) and mapped to a 504
        instead of a hang.  None/0 = no deadline."""
        self.backend = backend
        self.tokenizer = tokenizer
        self.model_name = model_name
        self.default_max_new = default_max_new
        self.request_timeout = request_timeout or None
        # the request's path outside the engine, beside the backend's
        # dispatch record in /stats (a backend that does not stream has
        # none, and its /stats is as it was)
        self.request_path = (RequestPath()
                             if hasattr(backend, "generate_stream")
                             else None)
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):   # quiet by default
                pass

            # known routes only: the route label must stay bounded — a
            # client probing arbitrary paths must not mint one counter
            # child (and one /metrics line) per junk URL forever
            _ROUTES = frozenset((
                "/health", "/stats", "/stats/reset", "/metrics", "/trace",
                "/timeline", "/debugz", "/sketch", "/generate",
                "/classify"))

            def _json(self, code: int, obj: dict,
                      headers: Optional[dict] = None) -> None:
                # counted BEFORE the body goes out: a client that reacts
                # to this response with a /metrics scrape must see its
                # own request (the scrape itself bypasses _json)
                route = self.path.split("?")[0]
                if route not in self._ROUTES:
                    route = "other"
                _metrics.HTTP_REQUESTS.inc(route=route, code=str(code))
                body = json.dumps(obj).encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                tid = getattr(self, "_trace_id", None)
                if tid:
                    self.send_header("X-DWT-Trace-Id", tid)
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _obs_kwargs(self, fn) -> dict:
                """tenant/trace_id kwargs for backends that take them
                (the continuous-batching engine) — duck-typed like
                image/timeout, so pipeline backends stay untouched.
                Called just before the backend is: an engine that takes
                ``on_submit`` stamps the request's passage itself, for
                any other backend the handler does, here."""
                out = {}
                if _accepts_kwarg(fn, "on_submit"):
                    out["on_submit"] = self._passage.submitted
                else:
                    self._passage.submitted()
                tenant = getattr(self, "_tenant", None)
                if tenant and _accepts_kwarg(fn, "tenant"):
                    out["tenant"] = str(tenant)
                tid = getattr(self, "_trace_id", None)
                if tid and _accepts_kwarg(fn, "trace_id"):
                    try:
                        out["trace_id"] = int(str(tid), 16)
                    except ValueError:
                        pass
                return out

            def _shed(self, e: SchedulerOverloaded) -> None:
                """503/429 + Retry-After: the admission queue is past
                its configured depth — honest fast rejection, not an
                unbounded queue (clients with backoff recover; clients
                without get a clear signal instead of a timeout).  The
                exception carries the code: 503 = service saturated
                (batching scheduler), 429 = back off, the sp queue is
                full behind a long-context request."""
                self._json(getattr(e, "http_code", 503), {"error": str(e)},
                           headers={"Retry-After":
                                    str(max(1, int(e.retry_after_s)))})

            def _metrics_scrape(self) -> None:
                """Prometheus text exposition over the shared registry +
                this backend's bridged series (telemetry/catalog)."""
                try:
                    text = _metrics.scrape(outer.backend)
                    code = 200
                except Exception as e:   # the scrape must never crash
                    text = f"# scrape error: {e}\n"
                    code = 500
                body = text.encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; "
                                 "charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path.split("?")[0] == "/metrics":
                    self._metrics_scrape()
                elif self.path == "/trace":
                    # spans recorded since the last /trace call, as
                    # Chrome trace JSON (Perfetto-loadable)
                    if hasattr(outer.backend, "export_trace"):
                        try:
                            self._json(200, outer.backend.export_trace())
                        except Exception as e:
                            self._json(500, {"error": str(e)})
                    else:
                        self._json(501, {"error": "backend has no trace "
                                                  "export"})
                elif self.path == "/health":
                    import jax
                    devs = jax.devices()
                    # status follows the backend: a batching engine
                    # whose scheduler thread died answers 503 with the
                    # error that killed it, not "ok"
                    health = (outer.backend.health()
                              if hasattr(outer.backend, "health")
                              else {"status": "ok"})
                    # every device JAX reports, in JAX's order (the
                    # order local_tp_mesh takes them in), with the
                    # allocator's view where the backend has one (the
                    # CPU's memory_stats() is None): weights and pool
                    # resident on every chip of a tp mesh
                    devices = []
                    for d in devs:
                        ms = d.memory_stats() or {}
                        devices.append({
                            "id": d.id, "device": str(d),
                            **{k: ms[k] for k in (
                                "bytes_in_use", "peak_bytes_in_use",
                                "bytes_limit") if k in ms}})
                    self._json(200 if health["status"] == "ok" else 503, {
                        **health,
                        "model": outer.model_name,
                        "backend": type(outer.backend).__name__,
                        "device": str(devs[0]),
                        "platform": devs[0].platform,
                        "device_kind": devs[0].device_kind,
                        "device_count": len(devs),
                        "devices": devices,
                        # the cores this process may run on: whatever
                        # else serves beside it (a gateway, a client,
                        # a poller) shares them
                        "host_cpus": len(os.sched_getaffinity(0)),
                        "max_seq": getattr(outer.backend, "max_seq", None),
                    })
                elif self.path == "/stats":
                    stats = (outer.backend.stats()
                             if hasattr(outer.backend, "stats")
                             else {"stages": []})
                    if outer.request_path is not None:
                        stats = {**stats, "request_path":
                                 outer.request_path.snapshot()}
                    self._json(200, stats)
                elif self.path.split("?")[0] == "/timeline":
                    # recent closed request timelines + per-tenant SLO
                    # summary (telemetry/slo) — the fleet plane's
                    # where-did-the-milliseconds-go surface
                    from urllib.parse import parse_qs, urlparse
                    from ..telemetry import slo as _slo
                    try:
                        qs = parse_qs(urlparse(self.path).query)
                        n = max(1, min(1024, int(qs.get("n", ["64"])[0])))
                    except ValueError:
                        n = 64
                    try:
                        self._json(200, _slo.debug_state(tail=n))
                    except Exception as e:
                        self._json(500, {"error": str(e)})
                elif self.path.split("?")[0] == "/debugz":
                    try:
                        self._json(200, outer._debugz())
                    except Exception as e:
                        self._json(500, {"error": str(e)})
                elif self.path.split("?")[0] == "/sketch":
                    # §20 workload-sketch artifact: serve the recorder's
                    # CANONICAL bytes verbatim (re-dumping would break
                    # the byte-identity determinism contract)
                    from ..telemetry import profiling as _profiling
                    try:
                        body = _profiling.get_sketch().to_json() \
                            .encode("utf-8")
                        _metrics.HTTP_REQUESTS.inc(route="/sketch",
                                                   code="200")
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/json")
                        self.send_header("Content-Length",
                                         str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    except Exception as e:
                        self._json(500, {"error": str(e)})
                else:
                    self._json(404, {"error": f"no route {self.path}"})

            def do_POST(self):
                if self.path == "/stats/reset":
                    # zero hot-loop counters on every stage (benchmarks
                    # call this after compile warmup for steady-state
                    # numbers — the statsreset control message as HTTP)
                    if hasattr(outer.backend, "reset_stats"):
                        outer.backend.reset_stats()
                        if outer.request_path is not None:
                            outer.request_path.reset()
                        self._json(200, {"reset": True})
                    else:
                        self._json(501, {"error": "backend has no "
                                                  "reset_stats"})
                    return
                if self.path == "/classify":
                    self._classify()
                    return
                if self.path != "/generate":
                    self._json(404, {"error": f"no route {self.path}"})
                    return
                # the request's passage (docs/DESIGN.md §16): `t_accept`
                # is its first line, and its end books the layer's
                # series and spans whatever the answer was
                self._passage = _Passage(outer.request_path, self.headers)
                try:
                    self._generate()
                finally:
                    self._passage.finish(
                        getattr(outer.backend, "tracer", None))

            def _generate(self):
                # gateway trace propagation (docs/DESIGN.md §16): a
                # proxied request carries the gateway's trace id — echo
                # it on every response; the passage's two spans land in
                # the replica's recorder under it, so one id joins the
                # gateway's spans, the handler's, the engine's and the
                # client's copy of the response
                tid = self.headers.get("X-DWT-Trace-Id")
                self._trace_id = tid[:64] if tid else None
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    ids = outer._prompt_ids(req)
                    max_new = int(req.get("max_new_tokens",
                                          outer.default_max_new))
                    seed = int(req.get("seed", 0))
                    image = req.get("image")
                except (ValueError, KeyError) as e:
                    self._json(400, {"error": str(e)})
                    return
                self._passage.parsed(ids, bool(req.get("stream")))
                # tenant identity (docs/DESIGN.md §7): body field wins
                # over the gateway-forwarded header; either way it rides
                # the batching rows into the per-tenant SLO ledger
                self._tenant = (req.get("tenant")
                                or self.headers.get("X-DWT-Tenant"))
                if image is not None:
                    # honor-or-reject: only a multimodal backend takes
                    # an image, and images don't stream (the fused
                    # multimodal program emits all tokens at once)
                    if req.get("stream"):
                        self._json(501, {"error": "image input does not "
                                                  "support stream"})
                        return
                    if not _accepts_kwarg(outer.backend.generate, "image"):
                        self._json(501, {"error": "backend does not "
                                                  "support image input"})
                        return
                resume = req.get("resume")
                if resume is not None:
                    # mid-stream failover resumption (docs/DESIGN.md
                    # §23): the gateway re-POSTs the journaled request
                    # with the delivered prefix; the engine replays it
                    # silently and streams the suffix bit-identically.
                    # Honor-or-reject: only the batching engine carries
                    # the submit_resumed path
                    err_code, err = None, None
                    if not req.get("stream"):
                        err_code, err = 400, "resume requires stream"
                    elif (image is not None or req.get("stop") is not None
                          or req.get("logprobs")):
                        err_code, err = 501, ("resume does not support "
                                              "image, stop, or logprobs")
                    elif not _accepts_kwarg(outer.backend.generate_stream,
                                            "resume"):
                        err_code, err = 501, ("backend does not support "
                                              "resume")
                    elif not isinstance(resume, dict):
                        err_code, err = 400, "resume must be an object"
                    if err is None:
                        delivered = resume.get("delivered_tokens")
                        if (not isinstance(delivered, (list, tuple))
                                or not delivered
                                or not all(isinstance(t, int)
                                           for t in delivered)):
                            err_code, err = 400, (
                                "resume.delivered_tokens must be a "
                                "non-empty list of token ids")
                        elif int(resume.get("rng_step_offset",
                                            len(delivered))) \
                                != len(delivered):
                            # the rng fast-forward replays one sampler
                            # split per delivered token — an offset
                            # that disagrees with the prefix length
                            # cannot be bit-identical
                            err_code, err = 400, (
                                "resume.rng_step_offset must equal "
                                "len(delivered_tokens)")
                    if err is not None:
                        self._json(err_code, {"error": err})
                        return
                stop = req.get("stop")
                if stop is not None:
                    if isinstance(stop, str):
                        stop = [stop]
                    if (not isinstance(stop, list) or not stop
                            or not all(isinstance(s, str) and s
                                       for s in stop)):
                        self._json(400, {
                            "error": "stop must be a non-empty string "
                                     "or list of non-empty strings"})
                        return
                    # honor-or-reject: stop strings need server-side
                    # text; they compose with blocking AND streaming
                    unsupported = [w for w, on in [
                        ("a server-side tokenizer (none attached)",
                         outer.tokenizer is None),
                        ("image", image is not None)] if on]
                    if unsupported:
                        self._json(501, {
                            "error": "stop does not support "
                                     + ", ".join(unsupported)})
                        return
                    want_lp = bool(req.get("logprobs"))
                    if want_lp and not _accepts_kwarg(
                            outer.backend.generate_stream, "logprobs"):
                        # both stop paths consume the STREAM surface, so
                        # streaming logprob support is the one capability
                        # they need (honor-or-reject, never drop)
                        self._json(501, {
                            "error": "backend does not support "
                                     "logprobs with stop"})
                        return
                    if req.get("stream"):
                        self._stream_stop(ids, max_new, seed, stop,
                                          logprobs=want_lp)
                        return
                    try:
                        self._generate_stop(ids, max_new, seed, stop,
                                            logprobs=want_lp)
                    except SchedulerOverloaded as e:
                        self._shed(e)
                    except TimeoutError as e:   # --request-timeout: the
                        self._json(504, {"error": str(e) or  # stop path
                                         "request deadline exceeded"})
                    except ValueError as e:
                        self._json(400, {"error": str(e)})
                    except Exception as e:
                        self._json(500, {"error": str(e)})
                    return
                try:
                    if req.get("stream"):
                        want_lp = bool(req.get("logprobs"))
                        if want_lp and not _accepts_kwarg(
                                outer.backend.generate_stream, "logprobs"):
                            # honor-or-reject, never silently drop
                            self._json(501, {
                                "error": "backend does not support "
                                         "logprobs with stream"})
                            return
                        self._stream(ids, max_new, seed, logprobs=want_lp,
                                     resume=resume)
                    else:
                        kwargs = {}
                        if image is not None:
                            kwargs["image"] = image
                        if req.get("logprobs"):
                            if not _accepts_kwarg(outer.backend.generate,
                                                  "logprobs"):
                                self._json(501, {
                                    "error": "backend does not support "
                                             "logprobs"})
                                return
                            kwargs["logprobs"] = True
                        if (outer.request_timeout
                                and _accepts_kwarg(outer.backend.generate,
                                                   "timeout")):
                            # per-request deadline: the batching engine
                            # cancels through Request.cancel() on expiry
                            # (slot freed), surfacing as TimeoutError
                            kwargs["timeout"] = outer.request_timeout
                        kwargs.update(
                            self._obs_kwargs(outer.backend.generate))
                        res = outer.backend.generate(ids, max_new,
                                                     seed=seed, **kwargs)
                        self._passage.tokens = int(res.tokens.size)
                        out = {"tokens": res.tokens.tolist()}
                        if getattr(res, "logprobs", None) is not None:
                            out["logprobs"] = [_round_lps(row)
                                               for row in res.logprobs]
                        if getattr(res, "generation", None) is not None:
                            out["generation"] = res.generation
                        if outer.tokenizer is not None:
                            out["text"] = [outer.tokenizer.decode(row)
                                           for row in res.tokens.tolist()]
                        self._json(200, out)
                except SchedulerOverloaded as e:
                    self._shed(e)
                except TimeoutError as e:   # --request-timeout expired;
                    self._json(504, {"error": str(e) or  # request was
                                     "request deadline exceeded"})  # shed
                except ValueError as e:     # capacity etc.
                    self._json(400, {"error": str(e)})
                except Exception as e:      # e.g. a stalled pipeline's
                    self._json(500, {"error": str(e)})  # TransportTimeout

            def _classify(self):
                """``{"prompt_ids"|"prompt", "label_token_ids": [...]}`` →
                ``{"labels": [...]}`` — the classification task endpoint
                (reference ``task_type`` classification,
                ``inference.cpp:220-270``)."""
                if not hasattr(outer.backend, "classify"):
                    self._json(501, {"error": "backend has no classify"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    ids = outer._prompt_ids(req)
                    label_ids = req["label_token_ids"]
                except (ValueError, KeyError) as e:
                    self._json(400, {"error": f"bad request: {e}"})
                    return
                try:
                    pred = outer.backend.classify(ids, label_ids)
                    self._json(200, {"labels": np.asarray(pred).tolist()})
                except ValueError as e:
                    self._json(400, {"error": str(e)})
                except Exception as e:      # stalled pipeline etc. -> 500
                    self._json(500, {"error": str(e)})

            def _generate_stop(self, ids, max_new, seed, stop,
                               logprobs=False):
                """Blocking generation with STOP SEQUENCES: rows end at
                the earliest occurrence of any stop string (which is
                excluded from the output — the OpenAI convention), and
                the batch stops consuming once every row finished
                (stream backends with resumable dispatches skip the
                remaining decode; fused/pipeline backends finish their
                in-flight program in the background).  With
                ``logprobs=True`` each row additionally carries its
                per-token logprobs, truncated at EXACTLY the same token
                index as the tokens (_StopSession owns the one cut).
                Matching, token
                truncation, and eos handling live in ONE owner shared
                with the streaming path (_StopSession); rows are
                RAGGED.  ``stop_reason`` per row: "stop", "eos" (the
                backend's eos ended the row first; the eos token is
                included, engine convention), or "length"."""
                kwargs = {"logprobs": True} if logprobs else {}
                if (outer.request_timeout
                        and _accepts_kwarg(outer.backend.generate_stream,
                                           "timeout")):
                    # the same per-request deadline as the plain branch:
                    # a wedged scheduler surfaces as 504, never a hang
                    kwargs["timeout"] = outer.request_timeout
                kwargs.update(
                    self._obs_kwargs(outer.backend.generate_stream))
                gen = outer.backend.generate_stream(ids, max_new,
                                                    seed=seed, **kwargs)
                ses = _StopSession(outer.tokenizer, stop, len(ids),
                                   getattr(outer.backend, "eos_id", None),
                                   logprobs=logprobs)
                for item in gen:
                    ses.consume(item)
                    if all(ses.done):
                        gen.close()
                        break
                ses.finish()
                self._passage.tokens = sum(len(t) for t in ses.toks)
                out = {"tokens": ses.toks, "text": ses.texts,
                       "stop_reason": ses.reason}
                if logprobs:
                    out["logprobs"] = [_round_lps(row) for row in ses.lps]
                self._json(200, out)

            def _stream_stop(self, ids, max_new, seed, stop,
                             logprobs=False):
                """STREAMING generation with stop sequences: chunked
                JSONL where each line carries per-row TEXT deltas only
                (tokens would mislead — text is authoritative under
                stop, and characters that might begin a stop string are
                held back until they provably aren't part of one, so
                nothing ever has to be retracted).  A final line carries
                the truncated token rows + per-row ``stop_reason`` (+
                per-row logprob rows cut at the same token index, with
                ``logprobs=True`` — deltas can't carry them: a logprob
                belongs to a token, and tokens aren't streamed here)."""
                kwargs = {"logprobs": True} if logprobs else {}

                def lines(items, gen):
                    ses = _StopSession(
                        outer.tokenizer, stop, len(ids),
                        getattr(outer.backend, "eos_id", None),
                        logprobs=logprobs)
                    step = 0
                    for item in items:
                        pieces = ses.consume(item)
                        if any(pieces):
                            yield {"step": step, "text": pieces}
                        step += 1
                        if all(ses.done):
                            gen.close()
                            break
                    tail = ses.finish()
                    if any(tail):
                        yield {"step": step, "text": tail}
                    final = {"done": True, "tokens": ses.toks,
                             "stop_reason": ses.reason}
                    if logprobs:
                        final["logprobs"] = [_round_lps(row)
                                             for row in ses.lps]
                    yield final

                self._stream_lines(ids, max_new, seed, kwargs, lines)

            def _stream_lines(self, ids, max_new, seed, kwargs,
                              lines_fn):
                """ONE owner of the chunked-JSONL framing shared by the
                plain and stop streaming paths: open the backend's
                stream (``kwargs`` are the path's own; the passage's
                and ``all_ready`` are added here for a backend that
                takes them), pull the FIRST backend
                item before committing to 200 + chunked (validation
                errors surface on first next() and must become a clean
                400/500, not a status line spliced into an open chunked
                body), then emit ``lines_fn(first, gen)``'s dict lines;
                a mid-stream failure becomes an {"error": ...} line so
                the framing stays intact, and the terminating chunk
                always goes out.  ``lines_fn(items, gen)`` receives the
                first item already spliced back into ``items`` (one
                owner of that dance too); ``gen`` rides along only for
                early ``gen.close()``.

                A line is never written while another for the same
                socket is at hand: the lines of everything the backend
                had ready go out as ONE chunk in ONE ``wfile.write``
                (``wfile`` is unbuffered: one ``send``), just before
                the backend is asked for more and may block.  A
                backend whose ``generate_stream`` takes ``all_ready``
                (found as ``on_submit`` is, by its signature) yields
                lists of items, each all that was ready; one that
                cannot say gives one item at a time, so a line a
                chunk.  Nothing waits for more: a backend that holds
                one item has one line written.  The lines are what they
                were, one a step; a chunk holds as many as there were.
                The terminating chunk rides with whatever lines are
                left when the backend ends (a text tail, the stop
                path's last line, an error's), else it goes alone."""
                import itertools
                fn = outer.backend.generate_stream
                kwargs.update(self._obs_kwargs(fn))
                batched = _accepts_kwarg(fn, "all_ready")
                if batched:
                    kwargs["all_ready"] = True
                gen = fn(ids, max_new, seed=seed, **kwargs)
                first = None
                try:
                    first = next(gen)
                except StopIteration:
                    pass
                except SchedulerOverloaded as e:
                    self._shed(e)       # still before headers: clean 503
                    return
                except ValueError as e:
                    self._json(400, {"error": str(e)})
                    return
                except Exception as e:
                    # e.g. a TransportTimeout from a stalled pipeline —
                    # still before headers, so a clean 500 is possible
                    self._json(500, {"error": str(e)})
                    return
                passage = self._passage
                held: list = []         # lines formed and not yet written

                def flush(end: bytes = b"") -> None:
                    """What is held, as one chunk in one write, ``end``
                    (the terminating chunk) behind it; then the egress
                    account: integer adds a write, and a commit where
                    a hand-off's last line is out."""
                    if held:
                        data = "".join(held).encode("utf-8")
                        self.wfile.write(b"%x\r\n%b\r\n%b"
                                         % (len(data), data, end))
                        passage.lines += len(held)
                        passage.writes += 1
                        passage.bytes += len(data)
                        held.clear()
                        if passage.consumed >= passage.edge:
                            passage.commit()
                    elif end:
                        self.wfile.write(end)

                rest = itertools.chain([] if first is None else [first],
                                       gen)
                items = passage.counted(
                    rest if batched else ([item] for item in rest), flush)

                # counted like every other answer (`_json` counts its own)
                _metrics.HTTP_REQUESTS.inc(route="/generate", code="200")
                self.send_response(200)
                self.send_header("Content-Type", "application/jsonl")
                self.send_header("Transfer-Encoding", "chunked")
                tid = getattr(self, "_trace_id", None)
                if tid:
                    self.send_header("X-DWT-Trace-Id", tid)
                self.end_headers()

                dumps = json.dumps
                try:
                    for line in lines_fn(items, gen):
                        held.append(dumps(line) + "\n")
                except OSError:
                    return      # client went away; the socket is dead
                except Exception as e:
                    # generator failure mid-stream: an error JSONL line
                    # keeps the chunked framing intact for the client
                    held.append(dumps({"error": str(e)}) + "\n")
                try:
                    flush(b"0\r\n\r\n")     # terminating chunk
                    self.wfile.flush()
                except OSError:
                    pass

            def _stream(self, ids, max_new, seed, logprobs=False,
                        resume=None):
                kwargs = {"logprobs": True} if logprobs else {}
                if resume is not None:
                    kwargs["resume"] = resume
                # a resumed stream continues the dead replica's step
                # numbering so the client's concatenated stream reads
                # seamlessly (delivered prefix ends at step k-1)
                step0 = len(resume["delivered_tokens"]) if resume else 0

                def lines(items, gen):
                    # incremental detokenization, per row: the "text"
                    # field carries printable deltas
                    # (tokenizer.StreamDetokenizer — one owner of the
                    # boundary/holdback rules, shared with the chat REPL)
                    from ..tokenizer import StreamDetokenizer
                    detoks: dict = {}

                    def row_text(r, tok):
                        if r not in detoks:
                            detoks[r] = StreamDetokenizer(outer.tokenizer)
                        return detoks[r].push(tok)

                    n_steps = 0
                    for i, item in enumerate(items):
                        toks, lps = item if logprobs else (item, None)
                        toks = np.asarray(toks).tolist()
                        line = {"step": step0 + i, "tokens": toks}
                        if lps is not None:
                            line["logprobs"] = _round_lps(np.asarray(lps))
                        if outer.tokenizer is not None:
                            line["text"] = [row_text(r, t)
                                            for r, t in enumerate(toks)]
                        yield line
                        n_steps = i + 1
                    if outer.tokenizer is not None and detoks:
                        # flush text held back by the U+FFFD guard: a
                        # stream ending on a split (or genuinely
                        # replacement-decoding) token must not silently
                        # drop its final characters
                        rem = [detoks[r].flush() if r in detoks else ""
                               for r in range(max(detoks) + 1)]
                        if any(rem):
                            yield {"step": step0 + n_steps, "tokens": [],
                                   "text": rem}

                self._stream_lines(ids, max_new, seed, kwargs, lines)

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self.httpd.server_address
        self._thread: Optional[threading.Thread] = None

    def _debugz(self) -> dict:
        """``GET /debugz``: live black-box state — flight-recorder tail,
        backend anomaly-detector state (when the backend has one), and
        the postmortem bundles written so far.  Read-only and bounded:
        an operator can hit it during an incident without touching the
        pipeline (unlike /stats, it never polls remote stages)."""
        from ..telemetry import flightrecorder, postmortem
        out = {"flight": flightrecorder.debug_state()}
        debug_state = getattr(self.backend, "debug_state", None)
        if callable(debug_state):
            out["backend"] = debug_state()
        out["postmortem"] = postmortem.debug_state()
        return out

    def _prompt_ids(self, req: dict) -> np.ndarray:
        if "prompt_ids" in req:
            ids = np.asarray(req["prompt_ids"], dtype=np.int32)
            if ids.ndim == 1:
                ids = ids[None, :]
            if ids.ndim != 2 or ids.size == 0:
                raise ValueError("prompt_ids must be a non-empty 1D/2D list")
            return ids
        if "prompt" in req:
            if self.tokenizer is None:
                raise ValueError(
                    "text prompt given but no tokenizer is attached; "
                    "send prompt_ids or start the server with --tokenizer")
            ids = self.tokenizer.encode(str(req["prompt"]))
            return np.asarray([ids], dtype=np.int32)
        raise ValueError("request needs 'prompt_ids' or 'prompt'")

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def shutdown(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=10)
