"""Distributed request tracing: spans across the ring, Chrome trace export.

Each generate/classify request is assigned a 64-bit trace id at the header.
The id (plus the sender's span id as parent) rides every data-plane hop as
a wire trailer (``comm/wire.py`` ``FLAG_TRACE_CONTEXT``), so every stage
tags its ``recv_wait`` / ``compute`` / ``send`` spans — and the header its
``ring_rtt`` span — to the request that caused them.  Worker spans flow
back to the header on the existing ``statsreq`` control path
(``runtime/distributed.py``), and the merged set exports as Chrome
trace-event JSON (``to_chrome_trace``) loadable in Perfetto /
``chrome://tracing``.

Timestamps are epoch microseconds (``time.time()``); durations come from
``perf_counter`` deltas.  Within one host the span chain for a token step
nests exactly; across hosts it is as aligned as the hosts' clocks — good
enough for "which hop ate the time", which is the question this exists to
answer.

The two clocks are never mixed: :class:`SpanClock` captures the
wall-clock start ONCE at span open and measures the duration on
``perf_counter``, so a span's start cannot drift when NTP steps the wall
clock mid-span (reconstructing start as ``time.time() - dur`` at close
would move it by exactly the step).

Beside the request spans sits the scheduler's own record,
:class:`DispatchTrace`: one row per mixed dispatch on ``time.monotonic()``
with the host phases around it, read by ``/stats`` and mirrored as
``sched.*`` annotations into any ``jax.profiler`` capture.  A request's
``engine.prefill`` span carries the ``seq`` of the dispatches that served
it, which ties the two together.  On the same clock, :class:`RequestPath`
is the HTTP replica's record of a request's way to the engine and of its
tokens' way from the scheduler's hand-off to the socket.
"""

from __future__ import annotations

import contextlib
import copy
import gc
import itertools
import os
import random
import resource
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional

from .flightrecorder import get_flight_recorder

_MAX_SPANS = 8192          # bounded: long runs keep O(1) memory

# Trace/span ids must stay unique across processes that FORKED from one
# parent: the module-level ``random`` generator's state is copied by
# fork, so two replicas forked after import would mint the *same* id
# sequence and their traces would merge into one request at the gateway.
# ``SystemRandom`` reads the kernel CSPRNG per call — no Python-level
# state to inherit.
_SYS_RANDOM = random.SystemRandom()


def new_trace_id() -> int:
    """Random nonzero 64-bit trace id (collision odds are irrelevant at
    any realistic request volume).  Drawn from ``os.urandom`` via
    ``SystemRandom`` so ids stay distinct across forked replicas."""
    return _SYS_RANDOM.getrandbits(64) | 1


class SpanClock:
    """Span timing with the clocks kept apart: ``ts`` is the wall-clock
    start captured once at construction (span open); ``seconds`` is the
    elapsed ``perf_counter`` duration, frozen on first read or on context
    exit.  The one timing helper for instrumented spans
    (``with SpanClock() as t: ...`` then ``t.ts`` / ``t.seconds``)."""

    __slots__ = ("ts", "_t0", "_dur")

    def __init__(self):
        self.ts = time.time()
        self._t0 = time.perf_counter()
        self._dur: Optional[float] = None

    def stop(self) -> float:
        if self._dur is None:
            self._dur = time.perf_counter() - self._t0
        return self._dur

    @property
    def seconds(self) -> float:
        return self.stop()

    def __enter__(self) -> "SpanClock":
        return self

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False


class TraceRecorder:
    """Bounded per-process span sink.

    ``record()`` returns the new span's id so the caller can thread it as
    the parent of downstream spans (the wire trailer's second field).
    ``drain()`` pops everything recorded so far — the statsrep /
    export path — so each span is exported exactly once.
    """

    def __init__(self, proc: str, max_spans: int = _MAX_SPANS):
        self.proc = proc
        self._spans: "deque[dict]" = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        # span ids: process-unique base + counter, so two stages' ids
        # cannot collide when merged at the header.  SystemRandom for the
        # same reason as new_trace_id(): a fork must not clone the base.
        self._base = (_SYS_RANDOM.getrandbits(32) << 24) ^ (os.getpid() << 8)
        self._seq = itertools.count(1)

    def next_span_id(self) -> int:
        return (self._base + next(self._seq)) & ((1 << 63) - 1)

    def record(self, name: str, trace_id: int, parent_id: int = 0,
               ts: Optional[float] = None, dur: float = 0.0,
               span_id: Optional[int] = None,
               clock: Optional[SpanClock] = None, **args) -> int:
        """Record a completed span.  Preferred timing source is a
        :class:`SpanClock` opened at span start (``clock=``); explicit
        ``ts`` (epoch-seconds start) + ``dur`` (seconds) also work.  With
        neither, ``ts`` defaults to the call time — NOT ``now - dur``,
        which would reconstruct the start by mixing the wall clock with a
        perf_counter duration and drift whenever NTP steps the clock."""
        sid = span_id if span_id is not None else self.next_span_id()
        if clock is not None:
            ts, dur = clock.ts, clock.seconds
        if ts is None:
            ts = time.time()
        span = {"name": name, "proc": self.proc,
                "trace_id": int(trace_id), "span_id": int(sid),
                "parent_id": int(parent_id),
                "ts_us": int(ts * 1e6),
                "dur_us": max(0, int(dur * 1e6))}
        if args:
            span["args"] = {k: v for k, v in args.items() if v is not None}
        with self._lock:
            self._spans.append(span)
        return sid

    def drain(self) -> List[dict]:
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


# ---------------------------------------------------------------------------
# the scheduler's dispatch record (docs/DESIGN.md §20)

DISPATCH_PHASES = ("bookkeeping", "intake", "pack", "launch", "wait",
                   "drain")
# the three phases that belong to the dispatch that was launched last;
# the other three to the one that is launched next
_LAUNCHED_PHASES = ("launch", "wait", "drain")
# a record's last three columns.  ``ahead``: the seconds of host work
# for this dispatch that ran under the previous execution instead of in
# the gap before this one (0 unless it was launched as prepared:
# DispatchTrace.ahead).  ``late``: 1 if the device had already finished
# it when the host came to read (DispatchTrace.awaiting): this dispatch
# the host held the device up.  ``await``: the seconds the host was then
# blocked in that read, the end of ``wait``
# ``prefill_pages_walked`` (after ``prefill_tokens``): the pages the
# prefill kernel's page loop walks for the slab, summed over the query
# tiles of the packed segments, one layer call of the full (or only)
# kind of block (``ops.paged_attention.prefill_pages_walked``: host
# arithmetic on the segments' starts); 0 on a decode-only dispatch
# ``head_rows`` (after it): the rows of hidden state the LM head ran
# over in the execution, one a segment of the slab (the position it
# samples, PR 48: not the chunk's every position) and one a slot at
# each decode step (host arithmetic on the launched program's shapes)
# ``slab_carried_step`` (the last of them): the rows whose first decode
# step of the dispatch rode its slab's pass over the weights (the rows
# that were decoding when a dispatch with a slab was packed); 0 where
# the slab carried no step (no row was decoding) or there was no slab.
# ``steps`` counts the carried step among the decode steps that ran
DISPATCH_FIELDS = (("seq", "t_launch", "t_done") + DISPATCH_PHASES
                   + ("with_finals", "segments", "finals",
                      "prefill_tokens", "prefill_pages_walked",
                      "head_rows", "active_rows", "steps",
                      "kv_tokens", "ahead", "late", "await",
                      "slab_carried_step"))
# a record's very last column, after what the model adds: 1 if the
# dispatch was enqueued behind its predecessor, before that one had
# returned (``commit``'s ``early``; runtime.batching, docs/DESIGN.md §19)
DISPATCH_LAST_FIELDS = ("early",)
# what a launched dispatch keeps until its commit: its phases' seconds
# and the two columns its blocking read fills
_OWN = DISPATCH_PHASES + ("await", "late")
# the spans of ``/stats.dispatch_trace.spans`` (wall and thread CPU
# seconds each): the phases but ``wait``, which is no work of the
# host's, the four kinds of work done under an execution
# (``phase_s["ahead"]`` is their sum; ``ahead_launch``: the call of a
# dispatch that is enqueued early; ``deliver``: the hand-off to the
# streams of what the gap before a launch recorded, a dispatch drained
# there first of all, made directly behind that launch), and the
# blocking read
DISPATCH_SPANS = (tuple(p for p in DISPATCH_PHASES if p != "wait")
                  + ("ahead_plan", "ahead_drain", "ahead_launch", "deliver",
                     "await"))
# the number of the dispatch a span of work under an execution belongs
# to, from the one launched last when the span begins: the one being
# prepared, the one before, the one about to be enqueued, the one before
_AHEAD_OF = {"ahead_plan": 1, "ahead_drain": -1, "ahead_launch": 1,
             "deliver": -1}
# why a dispatch that followed another at once was packed in the gap and
# not under its predecessor (runtime.batching, docs/DESIGN.md §19)
AHEAD_MISS_REASONS = ("arrival", "finish", "cancel", "export", "other")
# what a model with experts adds to a record (runtime.batching): the
# token-expert rows the execution routed over all its passes and layers
# (the device's count), those of real tokens (the host's: a live
# segment's prompt tokens, an active slot's steps; the two are equal, a
# row that holds no token enters no expert's group), the experts with
# >= 1 row summed over the execution's layer calls, and the fullest
# expert's rows in any one layer call
MOE_DISPATCH_FIELDS = ("moe_rows", "moe_valid_rows", "moe_touched",
                       "moe_load_max")
# what a looped model (``ut_steps > 1``) adds: the passes of the layer
# stack the execution ran, ((1 if it packed a segment else 0) + steps,
# less the step that rode the slab's pass) x ut_steps
LOOP_DISPATCH_FIELDS = ("ut_passes",)
# what a latent-attention model adds: the (query, cached token) pairs
# the slab's prompt tokens attend over, each token its predecessors and
# itself (what the prefill kernel's arithmetic is proportional to, as
# ``kv_tokens x steps`` is for the decode kernel's)
LATENT_DISPATCH_FIELDS = ("prefill_kv_tokens",)
# a model with a window kind of block: what its window kernels had to
# read.  ``kv_window_tokens``: the sum over the rows that decode of
# min(tokens held, window); ``prefill_window_pairs``: the (query, key)
# pairs inside the window that the slab's prompt tokens attend over;
# ``prefill_window_pages_walked``: ``prefill_pages_walked`` of the
# window kind, the pages its tiles' windows meet
WINDOW_DISPATCH_FIELDS = ("kv_window_tokens", "prefill_window_pairs",
                          "prefill_window_pages_walked")
# a model with a summarised cache (``eva_window``): what its kernels
# had to read.  ``kv_attended_rows``: the sum over the rows that decode
# of the rows of the pool their next query attends (a page of summaries a
# closed window and the open window's exact keys), ``kv_summary_rows``
# the summaries among them; ``prefill_attended_rows``: the (query, row)
# pairs the slab's prompt tokens attend over; ``windows_closed``: the
# windows the dispatch's tokens closed (host arithmetic on the rows'
# positions, as ``kv_tokens`` is)
EVA_DISPATCH_FIELDS = ("kv_attended_rows", "kv_summary_rows",
                       "prefill_attended_rows", "windows_closed")
# a model with a recurrent state a request (a state kind of block): what
# its two ops advanced, under the kind's name.  ``<kind>_row_steps``: rows x
# steps that moved a state in the decode loop (a row inside its budget);
# ``<kind>_chunk_tokens``: the prompt tokens that went through the chunk
# form (host arithmetic)
STATE_DISPATCH_FIELDS = {kind: (f"{kind}_row_steps", f"{kind}_chunk_tokens")
                         for kind in ("kda", "ssd", "lightning")}
# a model with a block-sparse kind (``ops.sparse_attention``), a kv head a
# sparse block, summed over the execution's queries (host arithmetic on
# their positions): the blocks of 64 tokens their contexts hold, the blocks
# their folds keep (all of them under ``dense_len``) and the pooled keys
# their selections score; and the decoding rows' part of the last two (the
# slab's is the rest).  Last, NOT host arithmetic: the blocks the program's
# selections kept, counted on the device where the mask is handed to the
# fold (``ops.sparse_attention.kept_counts``), in the same unit
SPARSE_DISPATCH_FIELDS = ("sparse_blocks_live", "sparse_blocks_kept",
                          "sparse_index_rows", "sparse_decode_blocks_kept",
                          "sparse_decode_index_rows",
                          "sparse_device_blocks_kept")
# a model with more than one residual stream (``hc_streams``): the token
# rows the residual path's two kernels computed in the execution, the
# slab's rows and every slot at every decode step, a slab's pass with the
# slots' rows in it padded to the kernels' whole tiles (host arithmetic on
# the launched program's shapes, as ``head_rows`` is)
HC_DISPATCH_FIELDS = ("hc_rows",)
_DISPATCH_RING = 128       # x ~135 bytes a row: /stats stays under 18 KB
# a span that is work (every one but ``await``) and lasts this long is a
# stall: ten times the longest ordinary span (four chips' ``ahead``,
# 4.65 ms) and longer than the shortest execution (four chips' 30 ms),
# so a span that long has certainly held the device up
STALL_S = 0.05
# the blocking read is the device's time when all is well, so it leaves
# a row only from a second on (no execution of any cell is a tenth of
# that): a standstill inside the read, counted apart from the stalls of
# the host's own work (``await_stall_count``, not ``stall_s``)
AWAIT_STALL_S = 1.0
STALL_FIELDS = ("seq", "span", "t0", "wall", "cpu", "proc_cpu", "gc",
                "nivcsw", "cause")
# where a stall's seconds went, by rule and in this order: a garbage
# collection (>= half of the wall seconds; any thread's holds the GIL),
# the span's own Python or C work (thread CPU >= half), another thread
# of the process (process CPU less the thread's >= half: the GIL's
# holder), else blocked in a call or taken off the core (``nivcsw``, the
# thread's involuntary context switches, says which)
STALL_CAUSES = ("gc", "own_cpu", "other_threads", "off_cpu")
_STALL_RING = 32
_IDLE_RING = 64            # engine-empty waits of >= _IDLE_MIN_S
_IDLE_MIN_S = 0.001


class LoopCounters:
    """The ``/stats.loop`` section of a looped model: what a token costs
    (``ut_steps`` passes, ``kv_planes`` planes, ``kv_bytes_per_token``)
    and running sums of the passes run, the slab's apart from the decode
    steps'.  Scheduler thread writes (one :meth:`add` a mixed dispatch),
    ``/stats`` reads."""

    def __init__(self, ut_steps: int, kv_planes: int,
                 kv_bytes_per_token: int):
        self.ut_steps = ut_steps
        self.kv_planes = kv_planes
        self.kv_bytes_per_token = kv_bytes_per_token
        self.reset()

    def reset(self) -> None:
        self.dispatches = 0
        self.slab_passes = 0
        self.decode_passes = 0

    def add(self, slab: bool, steps: int) -> dict:
        """Fold one execution in; returns its ``LOOP_DISPATCH_FIELDS``."""
        self.dispatches += 1
        self.slab_passes += self.ut_steps * slab
        self.decode_passes += self.ut_steps * steps
        return dict(ut_passes=self.ut_steps * (slab + steps))

    def snapshot(self) -> dict:
        return {"ut_steps": self.ut_steps, "kv_planes": self.kv_planes,
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "dispatches": self.dispatches,
                "slab_passes": self.slab_passes,
                "decode_passes": self.decode_passes}


class MoeCounters:
    """Running sums of the routing counters of a model with experts: the
    ``/stats.moe`` section.  Scheduler thread writes (one :meth:`add` a
    mixed dispatch), ``/stats`` reads."""

    def __init__(self, num_experts: int, routed: Optional[int] = None):
        """``num_experts``: the experts whose matrices are HERE;
        ``routed``: the experts the router scores (more, where this chip
        holds a share: ``ModelConfig.experts_held``)."""
        self.num_experts = num_experts
        self.routed = routed if routed is not None else num_experts
        self.reset()

    def reset(self) -> None:
        self.dispatches = 0
        self.rows = 0
        self.valid_rows = 0
        self.touched = 0
        self.load_max = 0
        self.layer_calls = 0
        self.expert_rows = [0] * self.num_experts

    def add(self, expert_rows, touched: int, load_max: int,
            layer_calls: int, valid_rows: int) -> dict:
        """Fold one execution in; returns its ``MOE_DISPATCH_FIELDS``."""
        rows = int(sum(expert_rows))
        self.expert_rows = [a + int(n) for a, n in zip(self.expert_rows,
                                                       expert_rows)]
        self.dispatches += 1
        self.rows += rows
        self.valid_rows += valid_rows
        self.touched += touched
        self.load_max = max(self.load_max, load_max)
        self.layer_calls += layer_calls
        return dict(moe_rows=rows, moe_valid_rows=valid_rows,
                    moe_touched=touched, moe_load_max=load_max)

    def snapshot(self) -> dict:
        share = ({"experts_routed": self.routed,
                  "rows_absent": self.valid_rows - self.rows}
                 if self.routed != self.num_experts else {})
        return {"experts": self.num_experts, **share,
                "dispatches": self.dispatches, "rows": self.rows,
                "valid_rows": self.valid_rows, "touched": self.touched,
                "load_max": self.load_max,
                "layer_calls": self.layer_calls,
                "expert_rows": list(self.expert_rows)}


class DispatchTrace:
    """One record per mixed dispatch that reached the device, and the
    host phases of the scheduler iteration around it.

    Always on; written by the scheduler thread only; read by ``/stats``
    through :meth:`snapshot`.  Instants are ``time.monotonic()`` (the
    clock a profiler capture can be placed on: stamp it beside
    ``jax.profiler.start_trace``), durations differences of it.

    The scheduler walks a cursor through the phases of an iteration:
    :meth:`enter` ends the phase in progress and starts the next at the
    same instant (one clock read a boundary, so the phases tile the
    iteration without holes), and each phase is also a
    ``jax.profiler.TraceAnnotation("sched.<phase>", seq=...)`` — inert
    unless a capture runs, then a row on the ``/host:CPU`` plane above
    the device lines it explains.  ``bookkeeping``, ``intake`` and
    ``pack`` accrue to the dispatch that is launched next, ``launch``,
    ``wait`` and ``drain`` to the one launched last: entering ``launch``
    is the cut, and the scheduler may launch dispatch n+1 before it has
    committed n (it drains n under n+1's execution), and even before n
    has returned (an early launch: n+1 waits behind n in the device's
    queue), so each launched dispatch keeps its own seconds until its
    :meth:`commit` turns them into one row of :data:`DISPATCH_FIELDS`.  An iteration that
    dispatched nothing carries its seconds into the next record.  The
    blocking wait of an idle engine is no phase (:meth:`idle`), and host
    work done under an execution is no seventh tile (:meth:`ahead`).

    ``wait`` is not "the device busy all the while": it runs from the
    call's return to the first blocking read and holds the host's work
    under the execution, which may outlast it.  :meth:`awaiting` says
    for every dispatch whether it did (``late``).  Where n+1 was
    launched early, n's ``wait`` ends at that launch and n+1's begins
    there, the call included (span ``ahead_launch``: the record's
    ``launch``, like its ``pack``, is 0, for the device waited for
    neither): the read of n (its ``await`` and ``late``, booked to n
    all the same), n's drain and the plan of n+2
    then lie in n+1's ``wait``, and n's ``t_done``
    (:meth:`returned`) after n+1's ``t_launch``.  A record's two
    instants still hold its execution between them; n+1's execution
    starts no earlier than n's ``t_done`` less the host's wake-up.  Beside the record,
    every boundary also reads the thread's and the process's CPU time,
    the thread's involuntary context switches and the seconds garbage
    collections have taken (:meth:`watch_gc`), so that :attr:`spans`
    holds wall and CPU seconds of each kind of host work and a span of
    :data:`STALL_S` or more leaves a row in :attr:`stalls` that says
    where its seconds went (:data:`STALL_CAUSES`); so does a blocking
    read of :data:`AWAIT_STALL_S` or more (``span: await``), counted in
    ``await_stall_count`` alone."""

    def __init__(self, extra_fields: tuple = ()):
        """``extra_fields``: columns after :data:`DISPATCH_FIELDS`
        (``MOE_DISPATCH_FIELDS`` for a model with experts,
        ``LOOP_DISPATCH_FIELDS`` for a looped one), passed to
        :meth:`commit` by name."""
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self.extra_fields = tuple(extra_fields)
        self._names = {p: f"sched.{p}" for p in DISPATCH_SPANS + ("wait",)}
        self._phase: Optional[str] = None
        self._at: tuple = ()        # the boundary the phase started at
        self._seq = 0               # the dispatch it belongs to
        self._ann = None
        self._await = None          # (boundary, annotation, the
                                    # dispatch's seconds, its number)
        self._gc_t0: Optional[float] = None
        self.recent: "deque[tuple]" = deque(maxlen=_DISPATCH_RING)
        # (number, t_launch) of the early dispatches not yet committed
        self._opened: "deque[tuple]" = deque()
        self.idles: "deque[tuple]" = deque(maxlen=_IDLE_RING)
        self.stalls: "deque[dict]" = deque(maxlen=_STALL_RING)
        self.seq = self.launched = 0
        self.reset()

    def reset(self) -> None:
        self.recent.clear()
        self.idles.clear()
        self.stalls.clear()
        # a dispatch in flight commits after the reset, as number 1
        self.launched -= self.seq
        self._opened = deque((n - self.seq, t) for n, t in self._opened)
        self.seq = 0
        self.phase_s = dict.fromkeys(DISPATCH_PHASES + ("ahead",), 0.0)
        # name -> (n, wall seconds, thread CPU seconds, longest)
        self.spans = dict.fromkeys(DISPATCH_SPANS, (0, 0.0, 0.0, 0.0))
        self._next = dict.fromkeys(_OWN, 0.0)
        self._last = dict.fromkeys(_OWN, 0.0)
        self._prior = self._last
        self._into = self._next
        self.idle_wait_s = 0.0
        self.decode_only = 0
        self.prefill = 0
        self.kv_token_steps = 0
        self.prefill_tokens = 0
        self.slab_rows = 0
        self.prefill_pages_walked = 0
        self.prefill_pages_grid = 0
        self.head_rows = 0
        self.slab_carried_steps = self.slab_carried_rows = 0
        self.queue_wait_ms_sum = 0.0
        self.queue_wait_count = 0
        self.ahead_hits = self.ahead_hits_slab = self.ahead_early = 0
        self.ahead_misses = dict.fromkeys(AHEAD_MISS_REASONS, 0)
        self.ahead_first = 0
        # the hand-offs to the requests' streams (the scheduler adds to
        # them: runtime.batching ``_deliver``): dispatches drained in
        # the gap whose tokens went out behind their successor's launch,
        # wake-ups (one a stream a hand-off) and tokens
        self.delivered_after_launch = 0
        self.delivered_streams = 0
        self.delivered_tokens = 0
        self.late_reads = 0
        self.stall_s = 0.0
        self.stall_count = 0
        self.await_stall_count = 0
        self.gc_pause_s = 0.0
        self.gc_max_pause_s = 0.0
        self.gc_collections = [0, 0, 0]

    def _stamp(self) -> tuple:
        """One boundary: ``(monotonic, the thread's CPU seconds, the
        process's, the thread's involuntary context switches, the
        seconds collections have taken so far)``.  Two system calls:
        the thread's two numbers come from one ``getrusage`` (a call
        costs 6 us where the kernel is a sandbox's, 0.3 elsewhere)."""
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        return (time.monotonic(), ru.ru_utime + ru.ru_stime,
                time.process_time(), ru.ru_nivcsw, self.gc_pause_s)

    def _span(self, name: str, a: tuple, b: tuple, seq: int) -> float:
        """Book the span ``name`` of dispatch ``seq`` between two
        boundaries; returns its wall seconds."""
        wall, cpu = b[0] - a[0], b[1] - a[1]
        n, wall_s, cpu_s, longest = self.spans[name]
        self.spans[name] = (n + 1, wall_s + wall, cpu_s + cpu,
                            max(longest, wall))
        if wall >= (AWAIT_STALL_S if name == "await" else STALL_S):
            proc, gc_s, half = b[2] - a[2], b[4] - a[4], wall / 2
            cause = ("gc" if gc_s >= half else "own_cpu" if cpu >= half
                     else "other_threads" if proc - cpu >= half
                     else "off_cpu")
            row = dict(zip(STALL_FIELDS, (
                seq, name, round(a[0], 5), round(wall, 5), round(cpu, 5),
                round(proc, 5), round(gc_s, 5), b[3] - a[3], cause)))
            self.stalls.append(row)
            if name == "await":
                self.await_stall_count += 1
            else:
                self.stall_s += wall
                self.stall_count += 1
            # the black box keeps it for a postmortem bundle
            get_flight_recorder().record("sched_stall", **row)
        return wall

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        elif self._gc_t0 is not None:
            pause = time.monotonic() - self._gc_t0
            self.gc_pause_s += pause
            self.gc_max_pause_s = max(self.gc_max_pause_s, pause)
            self.gc_collections[info["generation"]] += 1

    def watch_gc(self) -> None:
        """Time every garbage collection of the process from now on
        (one ``gc.callbacks`` hook, until :meth:`close`).  A collection
        on any thread holds the GIL, so the process-wide sum is what the
        scheduler thread lost."""
        gc.callbacks.append(self._on_gc)

    def close(self) -> None:
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(self._on_gc)

    def _cut(self) -> None:
        self.launched += 1
        self._prior = self._last
        self._last = self._next
        self._next = dict.fromkeys(_OWN, 0.0)

    def enter(self, phase: str, cut: bool = False) -> float:
        """Start ``phase`` now, ending the one in progress; returns the
        instant.  ``launch`` opens the next dispatch's own seconds, which
        :attr:`launched_phases` hands to its :meth:`commit`.  An early
        launch is made under an execution and is no tile of its own
        (the device waits for none of it): ``enter("wait", cut=True)``
        opens the next dispatch's seconds with its ``wait``, and the
        call is a span of :meth:`ahead` (``ahead_launch``)."""
        now = self._stamp()
        self._close(now)
        if phase == "launch" or cut:
            self._cut()
        after = phase in _LAUNCHED_PHASES
        self._into = self._last if after else self._next
        self._phase, self._at = phase, now
        self._seq = self.launched if after else self.launched + 1
        self._ann = self._annotate(self._names[phase], seq=self._seq)
        self._ann.__enter__()
        return now[0]

    @property
    def launched_phases(self) -> dict:
        """The seconds of the dispatch launched last, for ``commit``."""
        return self._last

    def abandon(self) -> None:
        """The dispatch launched last never reached the device: its
        seconds go on to the next record, and the one launched before it
        is the last one launched again (still in flight if this one was
        prepared under it: ``drain`` and its commit then find its
        seconds)."""
        self.launched -= 1
        for p, v in self._last.items():
            self._next[p] += v
        self._last = self._prior
        if self._phase == "wait":
            # an early launch: the cursor is under the one before again
            self._into, self._seq = self._last, self.launched
        elif self._phase in _LAUNCHED_PHASES:
            self._into = self._next

    def leave(self) -> float:
        """End the phase in progress; returns the instant."""
        now = self._stamp()
        self._close(now)
        return now[0]

    def _end_await(self, now: tuple) -> None:
        if self._await is not None:
            at, ann, own, seq = self._await
            own["await"] += self._span("await", at, now, seq)
            ann.__exit__(None, None, None)
            self._await = None

    def returned(self) -> float:
        """The blocking read has returned and the cursor stays where it
        is, in the ``wait`` of the dispatch that was enqueued behind the
        one read: ends ``await``; returns the instant, that one's
        ``t_done``."""
        now = self._stamp()
        self._end_await(now)
        return now[0]

    def _close(self, now: tuple) -> None:
        if self._phase is None:
            return
        self._end_await(now)             # the read ends with `wait`
        dt = now[0] - self._at[0]
        self._into[self._phase] += dt
        self.phase_s[self._phase] += dt
        if self._phase != "wait":
            self._span(self._phase, self._at, now, self._seq)
        self._ann.__exit__(None, None, None)
        self._phase = self._ann = None

    def awaiting(self, ready: bool, own: Optional[dict] = None) -> None:
        """The host is about to block on an output of a dispatch in
        flight: the one launched last (the cursor is in its ``wait``),
        or, given its ``own`` seconds (``launched_phases`` as its launch
        left them), the one before it, behind which the last was
        enqueued early.  ``ready``: the output is there already, so the
        device finished before the host came to read (the record's
        ``late``; it then stood idle since, unless the early one was
        waiting behind it).  The read, span ``await``, ends at the
        record's ``t_done``: the next :meth:`enter`, where ``wait`` ends
        too, or :meth:`returned`."""
        own = self._into if own is None else own
        seq = self._seq - (own is not self._into)
        own["late"] = late = int(ready)
        self.late_reads += late
        ann = self._annotate(self._names["await"], seq=seq)
        ann.__enter__()
        self._await = (self._stamp(), ann, own, seq)

    @contextlib.contextmanager
    def idle(self):
        """Around a wait with nothing to do: books ``idle_wait_s``,
        keeps the wait out of the phase it interrupts and, from a
        millisecond on, its two instants in :attr:`idles`: the device is
        then idle because no request has reached the engine."""
        phase = self._phase
        t0 = self.leave()
        try:
            yield
        finally:
            t1 = time.monotonic()
            self.idle_wait_s += t1 - t0
            if t1 - t0 >= _IDLE_MIN_S:
                self.idles.append((round(t0, 5), round(t1, 5)))
            if phase is not None:
                self.enter(phase)

    @contextlib.contextmanager
    def ahead(self, span: str = "ahead_plan"):
        """Around host work done while the device executes: the next
        dispatch prepared (span ``ahead_plan``), the last one drained
        (``ahead_drain``), the next one's call where it is enqueued
        early (``ahead_launch``) or, behind a launch made in the gap,
        the hand-off of what the gap recorded for the streams
        (``deliver``).  The cursor stays in ``wait``, which still
        runs from the call's return to ``t_done``, and the seconds are
        booked to ``phase_s["ahead"]``, so the six phases keep tiling
        the iteration and ``phase_s`` without ``wait`` is still all the
        host did.  Yields a one-element list that holds the seconds once
        the block has ended."""
        spent = [0.0]
        seq = self.launched + _AHEAD_OF[span]
        t0 = self._stamp()
        with self._annotate(self._names[span], seq=seq):
            try:
                yield spent
            finally:
                spent[0] = self._span(span, t0, self._stamp(), seq)
                self.phase_s["ahead"] += spent[0]

    def opened(self, t_launch: float) -> None:
        """The dispatch launched last was enqueued early and reached the
        device's queue: from now until its :meth:`commit` the snapshot's
        ``recent`` ends with a row that holds its number, its
        ``t_launch``, ``early`` and nothing else (``t_done`` 0: not
        returned).  Its execution may begin before its predecessor's
        ``t_done``, so a reader that places executions on the records'
        clock must know of it from its launch on, or it would give that
        execution to the predecessor, whose interval holds its start."""
        self._opened.append((self.launched, round(t_launch, 5)))

    def queue_wait(self, seconds: float) -> None:
        """A request's submit -> launch of its first dispatch."""
        self.queue_wait_ms_sum += seconds * 1e3
        self.queue_wait_count += 1

    def commit(self, *, t_launch: float, t_done: float, with_finals: bool,
               segments: int, finals: int, prefill_tokens: int,
               active_rows: int, steps: int, kv_tokens: int,
               ahead: float = 0.0, how: Optional[str] = None,
               phases: Optional[dict] = None, slab_rows: int = 0,
               prefill_pages_walked: int = 0, prefill_pages_grid: int = 0,
               head_rows: int = 0, early: bool = False,
               slab_carried_step: int = 0, **extra: int) -> int:
        """A dispatch that reached the device is drained: one record.
        ``slab_rows``: the rows of the prefill slab its program computed
        (segments of the launched variant x the chunk), of which
        ``prefill_tokens`` held a token; both are summed, no column.
        ``prefill_pages_walked`` (a column, and summed) beside
        ``prefill_pages_grid`` (summed): the pages the prefill kernel's
        loop walks for the slab, and the steps a grid of one page of the
        table a step would have had, its tiles x the table's width.
        ``head_rows`` (a column, and summed): the rows the LM head ran
        over, one a segment of the slab and one a slot a decode step.
        ``slab_carried_step`` (a column): the rows whose first step
        rode the slab's pass; the dispatches with any are counted in
        ``slab_carried_steps`` and the rows summed in
        ``slab_carried_rows``.
        ``phases``: its own seconds (``launched_phases`` as they were
        when the NEXT dispatch had not been launched yet; by default the
        last launched one's).  ``how``: ``"hit"`` (launched as prepared
        under its predecessor's execution, ``ahead`` seconds of it;
        counted in ``ahead_hits``, and in ``ahead_hits_slab`` too if it
        carried a segment), one of :data:`AHEAD_MISS_REASONS` (it
        followed its predecessor at once and was packed in the gap), or
        ``"first"`` (nothing was executing before it).  ``early``: a hit
        that was enqueued before its predecessor had returned (the last
        column, and counted in ``ahead_early``).  Returns its ``seq``."""
        if phases is None:
            self.leave()
            if self.launched == self.seq:    # no phase of a launch seen
                self._cut()
            phases = self._last
        self.seq += 1
        self.recent.append((
            self.seq, round(t_launch, 5), round(t_done, 5),
            *(round(phases[p], 5) for p in DISPATCH_PHASES),
            int(with_finals), segments, finals, prefill_tokens,
            prefill_pages_walked, head_rows, active_rows, steps, kv_tokens,
            round(ahead, 5),
            int(phases["late"]), round(phases["await"], 5),
            slab_carried_step,
            *(extra[f] for f in self.extra_fields), int(early)))
        if early:
            self._opened.popleft()   # the row above is its record now
        if segments:
            self.prefill += 1
        else:
            self.decode_only += 1
        self.kv_token_steps += kv_tokens * steps
        self.prefill_tokens += prefill_tokens
        self.slab_rows += slab_rows
        self.prefill_pages_walked += prefill_pages_walked
        self.prefill_pages_grid += prefill_pages_grid
        self.head_rows += head_rows
        self.slab_carried_steps += bool(slab_carried_step)
        self.slab_carried_rows += slab_carried_step
        if how == "hit":
            self.ahead_hits += 1
            self.ahead_hits_slab += bool(segments)
            self.ahead_early += bool(early)
        elif how == "first":
            self.ahead_first += 1
        elif how is not None:
            self.ahead_misses[how] += 1
        return self.seq

    def snapshot(self) -> dict:
        """The ``/stats`` section.  ``recent`` is the ring as rows of
        numbers in the order of ``fields``; ``idles`` rows of ``[t0,
        t1]``; ``stalls`` rows by name (:data:`STALL_FIELDS`).
        ``copy.copy`` of a deque is atomic under the GIL; iterating it
        would race the scheduler's appends.  After the ring: a row for
        each early dispatch in flight (:meth:`opened`)."""
        fields = DISPATCH_FIELDS + self.extra_fields + DISPATCH_LAST_FIELDS
        in_flight = [[n, t, 0.0] + [0] * (len(fields) - 4) + [1]
                     for n, t in copy.copy(self._opened)]
        return {"seq": self.seq,
                "phase_s": {p: round(v, 6)
                            for p, v in self.phase_s.items()},
                "idle_wait_s": round(self.idle_wait_s, 6),
                "decode_only": self.decode_only,
                "prefill": self.prefill,
                "kv_token_steps": self.kv_token_steps,
                "prefill_tokens": self.prefill_tokens,
                "slab_rows": self.slab_rows,
                "prefill_pages_walked": self.prefill_pages_walked,
                "prefill_pages_grid": self.prefill_pages_grid,
                "head_rows": self.head_rows,
                "slab_carried_steps": self.slab_carried_steps,
                "slab_carried_rows": self.slab_carried_rows,
                "queue_wait_ms_sum": round(self.queue_wait_ms_sum, 3),
                "queue_wait_count": self.queue_wait_count,
                "ahead_hits": self.ahead_hits,
                "ahead_hits_slab": self.ahead_hits_slab,
                "ahead_early": self.ahead_early,
                "ahead_misses": dict(self.ahead_misses),
                "ahead_first": self.ahead_first,
                "delivered_after_launch": self.delivered_after_launch,
                "delivered_streams": self.delivered_streams,
                "delivered_tokens": self.delivered_tokens,
                "late_reads": self.late_reads,
                "spans": {name: {"n": n, "wall_s": round(wall, 6),
                                 "cpu_s": round(cpu, 6),
                                 "max_s": round(longest, 6)}
                          for name, (n, wall, cpu, longest)
                          in self.spans.items()},
                "gc": {"pause_s": round(self.gc_pause_s, 6),
                       "max_pause_s": round(self.gc_max_pause_s, 6),
                       "collections": list(self.gc_collections)},
                "stall_s": round(self.stall_s, 6),
                "stall_count": self.stall_count,
                "await_stall_count": self.await_stall_count,
                "stalls": list(copy.copy(self.stalls)),
                "idles": [list(r) for r in copy.copy(self.idles)],
                "fields": list(fields),
                "recent": [list(r) for r in copy.copy(self.recent)]
                + in_flight}


# ---------------------------------------------------------------------------
# the request's path outside the engine (docs/DESIGN.md §16, §19)

# a row of ``/stats.request_path.recent``: the instant the gateway took
# the request (``t_accept`` less the seconds its header says it held
# it; ``t_accept`` itself for a direct request), the handler's entry,
# the body read and decoded, the engine's own submit stamp; then the
# prompt's tokens and whether the reply is streamed
# the header, beside ``X-DWT-Trace-Id``, in which the gateway tells the
# replica the seconds it held the request before forwarding it
GATEWAY_HELD_HEADER = "X-DWT-Gateway-Held-S"
REQUEST_PATH_FIELDS = ("t_gateway", "t_accept", "t_parsed", "t_submit",
                       "prompt_tokens", "streamed")
_REQUEST_RING = 256        # x ~60 bytes a row


class RequestPath:
    """What a request costs between the socket and the engine, both
    ways: the record beside :class:`DispatchTrace`, on its clock
    (``time.monotonic()``; the engine's ``Request`` stamps are
    ``time.perf_counter()``, on Linux the same ``CLOCK_MONOTONIC``).

    Always on; written by the HTTP handler threads, one short lock a
    call; read by ``/stats`` through :meth:`snapshot`.  **Ingress**, one
    :meth:`ingress` a request the engine took: the seconds the gateway
    held it, the handler's read and parse, the engine's submit, and a
    row of :data:`REQUEST_PATH_FIELDS`.  **Egress**, one :meth:`egress`
    a hand-off of the scheduler's (``_deliver``: the tokens a drain
    recorded for one stream) once the handler's write of the hand-off's
    last line has returned: the seconds a token lay between the hand-off
    and the socket, what was written, and the handler thread's own CPU
    seconds since its last call (``time.thread_time()``: at most what
    the handlers took of the GIL).  The counters advance at every
    hand-off, not at a request's end, so the difference of two
    snapshots is exact to one hand-off a stream."""

    def __init__(self):
        self._lock = threading.Lock()
        self.recent: "deque[tuple]" = deque(maxlen=_REQUEST_RING)
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.recent.clear()
            self.ingress_count = 0
            self.gateway_s = self.read_parse_s = self.submit_s = 0.0
            self.handoffs = self.tokens = self.lines = 0
            self.writes = self.bytes = 0
            self.egress_s = self.egress_max_s = self.handler_cpu_s = 0.0

    def ingress(self, gateway_s: float, t_accept: float, t_parsed: float,
                t_submit: float, prompt_tokens: int, streamed: bool) -> None:
        with self._lock:
            self.ingress_count += 1
            self.gateway_s += gateway_s
            self.read_parse_s += t_parsed - t_accept
            self.submit_s += t_submit - t_parsed
            self.recent.append((
                round(t_accept - gateway_s, 5), round(t_accept, 5),
                round(t_parsed, 5), round(t_submit, 5), prompt_tokens,
                int(streamed)))

    def egress(self, stamps, now: float, tokens: int, lines: int,
               writes: int, nbytes: int, cpu_s: float) -> None:
        """``stamps``: the instants of the hand-offs whose last line was
        on the socket at ``now`` (none: a request's end, which commits
        what the handler wrote and used since its last hand-off)."""
        with self._lock:
            for t in stamps:
                self.egress_s += now - t
                self.egress_max_s = max(self.egress_max_s, now - t)
            self.handoffs += len(stamps)
            self.tokens += tokens
            self.lines += lines
            self.writes += writes
            self.bytes += nbytes
            self.handler_cpu_s += cpu_s

    def snapshot(self) -> dict:
        """The ``/stats.request_path`` section; ``recent`` as rows of
        numbers in the order of ``fields``."""
        with self._lock:        # one consistent reading; built outside
            recent = list(self.recent)
            out = {"ingress_count": self.ingress_count,
                   "gateway_s": round(self.gateway_s, 6),
                   "read_parse_s": round(self.read_parse_s, 6),
                   "submit_s": round(self.submit_s, 6),
                   "handoffs": self.handoffs,
                   "egress_s": round(self.egress_s, 6),
                   "egress_max_s": round(self.egress_max_s, 6),
                   "tokens": self.tokens, "lines": self.lines,
                   "writes": self.writes, "bytes": self.bytes,
                   "handler_cpu_s": round(self.handler_cpu_s, 6)}
        return {**out, "fields": list(REQUEST_PATH_FIELDS),
                "recent": [list(r) for r in recent]}


def to_chrome_trace(spans: Iterable[dict]) -> dict:
    """Merge span dicts (from any number of TraceRecorders / statsrep
    payloads) into a Chrome trace-event JSON object.

    Layout choices for Perfetto readability: one "process" row per stage
    (``proc``), one "thread" lane per trace id within it — so a request's
    hops line up vertically and concurrent requests stack as lanes.
    """
    spans = list(spans)
    pids: Dict[str, int] = {}
    tids: Dict[int, int] = {}
    events: List[dict] = []
    for s in spans:
        proc = s.get("proc", "?")
        if proc not in pids:
            pids[proc] = len(pids) + 1
            events.append({"ph": "M", "name": "process_name",
                           "pid": pids[proc], "tid": 0,
                           "args": {"name": proc}})
        trace_id = int(s.get("trace_id", 0))
        if trace_id not in tids:
            tids[trace_id] = len(tids) + 1
        args = dict(s.get("args") or {})
        args["trace_id"] = f"{trace_id:016x}"
        if s.get("parent_id"):
            args["parent_span_id"] = f"{int(s['parent_id']):016x}"
        args["span_id"] = f"{int(s.get('span_id', 0)):016x}"
        events.append({
            "ph": "X", "name": s.get("name", "?"),
            "cat": "ring", "pid": pids[proc], "tid": tids[trace_id],
            "ts": int(s.get("ts_us", 0)), "dur": int(s.get("dur_us", 0)),
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def merge_chrome_traces(traces: Iterable[dict]) -> dict:
    """Merge already-exported Chrome trace objects (``{"traceEvents":
    [...]}``) into one.

    Each input was built by :func:`to_chrome_trace` in a *different*
    process (replica ``/trace`` exports plus the gateway's own), so their
    small-integer pids collide.  Pids are renumbered per input object;
    ``process_name`` metadata rows are deduplicated by name so the merged
    view shows one row per distinct proc, and duration events whose proc
    already has a row reuse it — a request's gateway-proxy, engine, and
    migration spans land in one file, joined by the ``trace_id`` arg the
    per-span export already carries.
    """
    name_pids: Dict[str, int] = {}
    events: List[dict] = []
    next_pid = 1
    for trace in traces:
        remap: Dict[int, int] = {}
        pending: List[dict] = []   # events seen before their meta row
        for ev in (trace or {}).get("traceEvents", []):
            pid = int(ev.get("pid", 0))
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                proc = str((ev.get("args") or {}).get("name", "?"))
                if proc in name_pids:
                    remap[pid] = name_pids[proc]
                else:
                    name_pids[proc] = remap[pid] = next_pid
                    next_pid += 1
                    events.append(dict(ev, pid=remap[pid]))
                continue
            pending.append(ev)
        for ev in pending:
            pid = int(ev.get("pid", 0))
            if pid not in remap:
                remap[pid] = next_pid
                next_pid += 1
            events.append(dict(ev, pid=remap[pid]))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, spans: Iterable[dict]) -> None:
    """Export spans to ``path`` as Chrome trace JSON (open in Perfetto:
    ui.perfetto.dev → "Open trace file")."""
    import json
    with open(path, "w") as f:
        json.dump(to_chrome_trace(spans), f)
