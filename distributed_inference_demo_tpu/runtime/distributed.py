"""Distributed pipeline inference: the ring token loop over a transport.

TPU-native redesign of the reference's hot path (``Communication.running``
→ ``multiSteps`` → ``OneStep``, ``Communication.java:389-928``; SURVEY.md
§3.3): header embeds + runs its layer range, hidden states hop stage to
stage, the tail samples, and the token id rides the ring back to the
header.  Differences by design:

- **KV-cached decode** at every stage — each step moves a [b, 1, H] hidden
  row, not a re-run of the whole prefix (the reference re-runs modules
  statelessly and feeds only the last token, defect #3).
- **In-flight samples are tags, not socket sets**: ``pool_size`` requests
  interleave through the same transport edges, each with its own per-stage
  KV cache slot (the reference allocates a socket set per concurrency slot,
  ``Communication.java:930-970``).
- **Sampling fused at the tail** (jit) with a deterministic
  ``fold_in(rid, step)`` rng — no host round-trip for top-k.
- All receives carry timeouts (reference defect #7: indefinite blocking).

Message tags (payloads are wire.py tensor messages):

- ``h:{rid}:{step}``   hidden chunk (step 0 = prefill, else one token row)
- ``tok:{rid}:{step}`` sampled [b] token ids, tail → header
- ``c:{rid}``          classification chunk: [hidden, label_token_ids];
  the tail answers ``ctok:{rid}`` with argmax-over-labels indices (the
  reference's binary-classification variant,
  ``inference.cpp:220-270`` / ``native-lib.cpp:1305-1366``)
- ``end:{rid}``        free the request's cache, forwarded along the chain
- ``stop``             shut down the worker loop, forwarded along the chain
- ``statsreq``         forwarded along the chain; every non-header stage
  replies to the header with a ``statsrep:{device_id}`` JSON snapshot
  (the reference's per-device timer dump, ``Communication.java:650-661``,
  as a pollable message instead of stdout)
"""

from __future__ import annotations

import json
import logging
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..comm import wire
from ..comm.transport import (BaseTransport, TransportTimeout,
                              record_corrupt_frame)
from ..models.base import (KVCache, ModelConfig, StageParams, StageSpec,
                           require_kv_pair, require_one_kind,
                           require_token_rows,
                           require_single_pass)
from ..ops.sampling import SamplingParams, sample_logits
from ..telemetry import postmortem
from ..telemetry import profiling as _profiling
from ..telemetry.flightrecorder import get_flight_recorder
from ..telemetry.tracing import SpanClock, TraceRecorder, new_trace_id
from .stats import StageStats

log = logging.getLogger(__name__)

DEFAULT_STEP_TIMEOUT = 120.0  # generous: first jit compile can be slow


class StageRuntime:
    """Jitted compute for one stage + per-request KV cache slots."""

    def __init__(self, cfg: ModelConfig, spec: StageSpec, params: StageParams,
                 max_seq: int, sampling: SamplingParams = SamplingParams(),
                 seed: int = 0, mesh=None, kv_cache_dtype=None,
                 kv_dtype=None):
        """``mesh``: a local tp mesh — this stage's layer range then runs
        with Megatron-sliced weights and a kv-head-sharded cache on this
        host's chips (pipeline across hosts x tensor parallelism within
        one, each worker choosing its own tp independently — the
        activations on the wire stay replicated [b, s, H] either way).

        ``kv_cache_dtype``: reduced-precision storage for this stage's
        request cache slots (e.g. "float8_e4m3fn"), same insert-cast /
        read-upcast contract as InferenceEngine's — each pipeline stage
        halves its own cache bytes independently.

        Every request's cache is backed by ONE per-stage page pool
        (docs/DESIGN.md §14): blocks are allocated per chunk actually
        run (a request holding 40 tokens holds ceil(40/bt) pages, not a
        max_seq row) and returned on
        ``end:{rid}``, so concurrent rids (``pool_size`` dynamic
        batching) share the pool instead of each reserving worst-case
        rows.  Pool size: ``DWT_STAGE_KV_BLOCKS`` (default
        ``DWT_STAGE_KV_ROWS`` = 16 rows' worth); exhaustion raises
        loudly rather than silently evicting live KV."""
        if spec.num_stages > 1:
            require_single_pass(cfg, "a pipeline of stages")
            require_kv_pair(cfg, "a pipeline of stages")
            require_token_rows(cfg, "a pipeline of stages")
            require_one_kind(cfg, "a pipeline of stages")
        self.cfg = cfg
        self.spec = spec
        self.max_seq = max_seq
        self.sampling = sampling
        self.mesh = mesh
        self.kv_cache_dtype = (jnp.dtype(kv_cache_dtype)
                               if kv_cache_dtype else None)
        from ..ops.quant import resolve_kv_dtype
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        if self.kv_dtype != "bf16" and self.kv_cache_dtype is not None:
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r} quantizes the stage page "
                "pool and cannot compose with a kv_cache_dtype storage "
                f"cast ({self.kv_cache_dtype}); drop one of the two knobs")
        self._rng_base = jax.random.PRNGKey(seed)

        import math

        from ..parallel.tensor import make_paged_forward_seam
        from ..telemetry._env import env_int
        from .kvcache import resolve_kvcache_config
        # the last stage samples from the chunk's final position and its
        # head runs on that position alone (``logits_at = s - 1``); a
        # stage that is not last hands on every position's hidden state
        take_last = spec.is_last
        _, bt = resolve_kvcache_config(None, None)
        g = math.lcm(8, bt)
        S = -(-max_seq // g) * g
        self._bt, self._table_width = bt, S // bt
        rows = env_int("DWT_STAGE_KV_ROWS", 16)
        n_blocks = env_int("DWT_STAGE_KV_BLOCKS",
                           rows * self._table_width)
        fwd, bind, pool_sharding = make_paged_forward_seam(
            cfg, spec, mesh, params, bt)
        if pool_sharding is not None:
            from .engine import shard_engine_params
            params = shard_engine_params(params, cfg, mesh)
        self.params = params
        from ..ops.quant import alloc_kv_pool
        page_dtype = self.kv_cache_dtype or cfg.dtype
        self._pk, self._pv = alloc_kv_pool(
            (spec.num_layers * cfg.ut_steps, n_blocks,
             cfg.num_kv_heads, bt, cfg.head_dim), self.kv_dtype,
            page_dtype, pool_sharding)
        self._sentinel = n_blocks
        self._pool_free = list(range(n_blocks - 1, -1, -1))
        self._tables: Dict[int, np.ndarray] = {}
        self._rid_len: Dict[int, int] = {}
        self._rid_blocks: Dict[int, int] = {}

        @jax.jit
        def forward_p(params, inputs, pk, pv, table, length):
            bind(table, "stage_forward")
            cache = KVCache(pk, pv, length)
            b, s = inputs.shape[0], inputs.shape[1]
            pos = length + jnp.broadcast_to(jnp.arange(s), (b, s))
            out, cache = fwd(params, inputs, cache, pos, s - 1)
            return ((out[:, -1] if take_last else out),
                    cache.keys, cache.values)

        @jax.jit
        def forward_sample_p(params, inputs, pk, pv, table, length,
                             rng):
            """Paged tail hot path: layer range + LM head + in-jit
            sampling in ONE dispatch over the page pool — same rng,
            same sample_logits as the split pair (§13)."""
            bind(table, "stage_forward_sample")
            cache = KVCache(pk, pv, length)
            b, s = inputs.shape[0], inputs.shape[1]
            pos = length + jnp.broadcast_to(jnp.arange(s), (b, s))
            out, cache = fwd(params, inputs, cache, pos, s - 1)
            return (sample_logits(out[:, -1], rng, sampling),
                    cache.keys, cache.values)

        self._forward_p = forward_p
        self._forward_sample_p = forward_sample_p

        @jax.jit
        def sample(last_logits, rng):
            return sample_logits(last_logits, rng, sampling)

        self._sample = sample
        # the socket ring's topology caps the circuit at ONE token (the
        # stage cut severs the token -> embed dependency; §13), so the
        # tail's device-side win is dispatch FUSION, not K-fusion —
        # DWT_RING_FUSED_TAIL=0 restores the split pair (the parity
        # reference the fused program is pinned against)
        self.fused_tail = (spec.is_last
                           and env_int("DWT_RING_FUSED_TAIL", 1) != 0)
        # §20 observatory handles: the tail's fused dispatch is profiled
        # under the "ring_chunk_sample" program class; the stage page
        # pool feeds the HBM watermark ledger per chunk served.
        self._prof = _profiling.get_profiler()
        self._kv_token_bytes = _profiling.kv_dispatch_bytes(
            1, spec.num_layers * cfg.ut_steps, cfg.num_kv_heads,
            cfg.head_dim, self.kv_dtype,
            (self.kv_cache_dtype or cfg.dtype))

    def _paged_chunk_state(self, rid: int, batch: int, s: int):
        """(table, length) for this rid's next ``s``-token chunk,
        growing its block table from the stage pool first — pages are
        reserved per chunk actually run, never per max_seq row.  Pool
        exhaustion raises loudly (evicting live KV would decode wrong
        tokens); the header's capacity check bounds per-rid growth."""
        tbl = self._tables.get(rid)
        if tbl is None:
            tbl = np.full((batch, self._table_width), self._sentinel,
                          np.int32)
            self._tables[rid] = tbl
        cur = self._rid_len.get(rid, 0)
        need = -(-(cur + s) // self._bt)
        have = self._rid_blocks.get(rid, 0)
        if need > self._table_width:
            raise RuntimeError(
                f"rid {rid} needs {need} KV blocks but the stage table "
                f"is {self._table_width} wide (max_seq {self.max_seq})")
        grow = (need - have) * batch
        if grow > len(self._pool_free):
            # all-or-nothing grow: popping a partial set into the table
            # before raising would leak pages if the chunk is retried
            # (the table entries would be overwritten by fresh pops)
            raise RuntimeError(
                "stage page pool exhausted: raise "
                "DWT_STAGE_KV_BLOCKS (or DWT_STAGE_KV_ROWS) — "
                "refusing to evict live request KV")
        for j in range(have, need):
            for row in range(batch):
                tbl[row, j] = self._pool_free.pop()
        self._rid_blocks[rid] = max(have, need)
        return tbl, cur

    def _sample_stage_hbm(self) -> None:
        """One HBM-watermark sample for this stage's page pool (§20) —
        host-side integer math only, called per chunk served."""
        used = self._sentinel - len(self._pool_free)
        _profiling.get_hbm_watermarks().sample(
            "stage_pool", used * self._bt * self._kv_token_bytes)

    def run_chunk(self, rid: int, inputs: np.ndarray) -> jax.Array:
        """Run this stage on a chunk; updates the request's cache in place.
        Returns hidden [b,s,H] (or last-position logits on the tail)."""
        x = jnp.asarray(inputs)
        tbl, cur = self._paged_chunk_state(rid, x.shape[0], x.shape[1])
        out, self._pk, self._pv = self._forward_p(
            self.params, x, self._pk, self._pv, jnp.asarray(tbl),
            jnp.int32(cur))
        self._rid_len[rid] = cur + x.shape[1]
        self._sample_stage_hbm()
        return out

    def sample_tokens(self, rid: int, step: int,
                      last_logits: jax.Array) -> np.ndarray:
        rng = jax.random.fold_in(jax.random.fold_in(self._rng_base, rid),
                                 step)
        return np.asarray(self._sample(last_logits, rng))

    def run_chunk_sample(self, rid: int, step: int,
                         inputs: np.ndarray) -> np.ndarray:
        """Tail-only fused step: run this stage AND sample in one
        dispatch.  The rng is the same ``fold_in(rid, step)`` stream
        :meth:`sample_tokens` draws, so the fused and split tails emit
        bit-identical tokens."""
        x = jnp.asarray(inputs)
        rng = jax.random.fold_in(jax.random.fold_in(self._rng_base, rid),
                                 step)
        b, s = x.shape[0], x.shape[1]
        _sig = _profiling.dispatch_signature(
            "ring_chunk_sample", batch=b, chunk=s, kv_dtype=self.kv_dtype)
        _t0 = self._prof.begin(_sig)
        tbl, cur = self._paged_chunk_state(rid, b, s)
        tok, self._pk, self._pv = self._forward_sample_p(
            self.params, x, self._pk, self._pv, jnp.asarray(tbl),
            jnp.int32(cur), rng)
        self._rid_len[rid] = cur + s
        tok = np.asarray(tok)
        if _t0 is not None:
            # the asarray above synced; the chunk attends the rid's
            # whole KV prefix and writes s new tokens
            self._prof.end(_sig, _t0, hbm_bytes=(
                b * (cur + s) * self._kv_token_bytes))
        self._sample_stage_hbm()
        return tok

    def free(self, rid: int) -> None:
        tbl = self._tables.pop(rid, None)
        self._rid_len.pop(rid, None)
        self._rid_blocks.pop(rid, None)
        if tbl is not None:
            self._pool_free.extend(
                int(v) for v in tbl.flat if v != self._sentinel)

    def reset_caches(self) -> None:
        """Drop every request's cache state (reshard/restart): the
        tables hand their pages back to the stage pool (clearing the
        dict alone would leak them)."""
        for rid in list(self._tables):
            self.free(rid)


def _h_tag(rid: int, step: int) -> str:
    return f"h:{rid}:{step}"


def _tok_tag(rid: int, step: int) -> str:
    return f"tok:{rid}:{step}"


class PipelineWorker:
    """A non-header stage: recv → run layer range → send onward; the tail
    additionally samples and returns tokens to the header (the worker /
    tailer roles of ``OneStep``, ``Communication.java:682-928``)."""

    def __init__(self, runtime: StageRuntime, transport: BaseTransport,
                 next_id: Optional[str], header_id: str,
                 step_timeout: float = DEFAULT_STEP_TIMEOUT):
        self.rt = runtime
        self.transport = transport
        self.next_id = next_id          # None on the tail
        self.header_id = header_id
        self.step_timeout = step_timeout
        role = "tail" if runtime.spec.is_last else "worker"
        self.stats = StageStats(role=role)
        self.tracer = TraceRecorder(f"{role}:{transport.device_id}")
        self.flight = get_flight_recorder()
        self.tail_dispatches = 0   # host dispatches spent sampling (§13)
        self._last_wait: Optional[float] = None  # serve loop's recv wait
        self._last_wait_start: Optional[float] = None  # its wall start
        # per-rid expected next step: the KV cache is append-only, so a
        # DUPLICATED or out-of-order hidden chunk (transport retry, chaos
        # duplicate/reorder) must be dropped, never run twice into the
        # cache.  The first frame of a request (or post-reshard relaunch,
        # where the re-prefill arrives at a mid-stream step) is accepted
        # at any step; after that, steps must advance by exactly one.
        self._next_step: Dict[int, int] = {}

    def _forward_control(self, tag: str, payload: bytes = b"") -> None:
        if self.next_id is not None:
            self.transport.send(self.next_id, tag, payload)

    def _count_tail_dispatches(self, dispatches: int) -> None:
        """Per-token host-dispatch accounting on the tail (the ring's
        share of the dwt_engine_* dispatch-floor series): 1 on the
        fused forward+sample path, 2 on the split reference pair."""
        from .engine import count_device_loop
        self.tail_dispatches += dispatches
        count_device_loop("PipelineWorkerTail", 1, dispatches)

    # tag factories — overridable (the elastic runtime appends a reshard
    # epoch so stale pre-reshard traffic is identifiable and droppable)
    def _make_h_tag(self, rid: int, step: int) -> str:
        return _h_tag(rid, step)

    def _make_tok_tag(self, rid: int, step: int) -> str:
        return _tok_tag(rid, step)

    def serve_forever(self, idle_timeout: Optional[float] = None) -> None:
        """Loop until a ``stop`` message arrives; returns cleanly if
        ``idle_timeout``/step_timeout expires with no traffic at all."""
        while True:
            t0_wall = time.time()       # recv_wait span start (wall clock
            t0 = time.perf_counter()    # captured at open, never derived)
            try:
                tag, payload = self.transport.recv_any(
                    timeout=idle_timeout or self.step_timeout)
            except TransportTimeout:
                log.info("worker %s: idle timeout, exiting",
                         self.transport.device_id)
                return
            wait = time.perf_counter() - t0
            self.stats.record_recv(wait, len(payload))
            self._last_wait = wait      # recv_wait span source (tracing)
            self._last_wait_start = t0_wall
            if not self.handle_message(tag, payload):
                return

    def handle_message(self, tag: str, payload: bytes) -> bool:
        """Process one message; returns False on ``stop``."""
        kind, _, rest = tag.partition(":")
        if kind == "stop":
            self.flight.record("worker_stop",
                               stage=self.transport.device_id)
            self._forward_control(tag)
            return False
        if kind == "end":
            rid = int(rest.split(":")[0])
            self.rt.free(rid)
            self._next_step.pop(rid, None)
            self._forward_control(tag)
            return True
        if kind == "statsreq":
            from ..comm.transport import TransportError
            snap = dict(self.stats.snapshot(include_samples=True),
                        device_id=self.transport.device_id,
                        seq=rest)  # echo the poll sequence id
            spans = None
            if payload == b"spans":
                # trace collection rides the stats poll: spans drain into
                # the reply AT MOST ONCE (a reply missing the header's
                # poll window is dropped there; only a locally failed
                # send re-buffers them for the next poll)
                spans = snap["spans"] = self.tracer.drain()
            try:
                self.transport.send(
                    self.header_id,
                    f"statsrep:{self.transport.device_id}",
                    json.dumps(snap).encode("utf-8"))
            except TransportError:
                if spans:
                    for s in spans:      # keep them for the next poll
                        self.tracer.record(
                            s["name"], s["trace_id"], s["parent_id"],
                            ts=s["ts_us"] / 1e6, dur=s["dur_us"] / 1e6,
                            span_id=s["span_id"], **(s.get("args") or {}))
                raise
            self._forward_control(tag, payload)
            return True
        if kind == "statsreset":
            self.stats.reset()
            self._forward_control(tag)
            return True
        if kind == "c":
            self._run_classify(int(rest.split(":")[0]), payload)
            return True
        if kind != "h":
            log.warning("worker %s: unexpected tag %r",
                        self.transport.device_id, tag)
            return True
        fields = rest.split(":")
        rid, step = int(fields[0]), int(fields[1])
        self._run_and_forward(rid, step, payload)
        return True

    def _record_hop_spans(self, ctx, compute_span: int, t_wall: float,
                          compute_s: float, rid: int, step: int) -> None:
        """recv_wait + compute spans for one traced hop; ``compute_span``
        was minted before serialization so the outbound trailer could
        name it as the downstream parent."""
        trace_id, parent = ctx
        if self._last_wait is not None:
            # the wall start was captured at recv open (serve_forever) —
            # never reconstructed as now-minus-duration across clocks
            start = (self._last_wait_start
                     if self._last_wait_start is not None
                     else t_wall - self._last_wait)
            self.tracer.record("recv_wait", trace_id, parent, ts=start,
                               dur=self._last_wait, rid=rid, step=step)
            self._last_wait = None       # consumed; never double-reported
            self._last_wait_start = None
        self.tracer.record("compute", trace_id, parent, ts=t_wall,
                           dur=compute_s, span_id=compute_span,
                           rid=rid, step=step)

    def _traced_send(self, ctx, compute_span: int, dest: str, tag: str,
                     body: bytes, rid: int, step: int) -> None:
        t_s = SpanClock()
        with t_s:
            self.transport.send(dest, tag, body)
        self.stats.record_send(t_s.seconds, len(body))
        self.flight.record("hop_send", stage=self.transport.device_id,
                           rid=rid, step=step, dest=dest,
                           nbytes=len(body))
        if ctx is not None:
            self.tracer.record("send", ctx[0], compute_span, clock=t_s,
                               rid=rid, step=step, dest=dest)

    def _run_and_forward(self, rid: int, step: int, payload: bytes) -> None:
        expected = self._next_step.get(rid)
        if expected is not None and step != expected:
            # duplicate (retry, chaos) or out-of-order frame: running it
            # would append to the KV cache twice and poison every later
            # token — drop; a genuinely lost frame surfaces as a stall
            # and the elastic reshard retransmits
            self.flight.record("dup_frame_dropped",
                               stage=self.transport.device_id,
                               rid=rid, step=step, expected=expected)
            log.info("worker %s: dropping duplicate/out-of-order frame "
                     "rid=%d step=%d (expected %d)",
                     self.transport.device_id, rid, step, expected)
            return
        self.flight.record("hop_recv", stage=self.transport.device_id,
                           rid=rid, step=step, nbytes=len(payload))
        try:
            tensors, ctx = wire.split_trace_context(
                wire.deserialize_tensors(payload))
        except wire.WireIntegrityError as e:
            # counted + flight-recorded, then DROPPED: the header's
            # step-timeout -> reshard path recovers this step; running a
            # corrupt activation forward would decode a wrong token
            record_corrupt_frame(self.transport.device_id,
                                 self._make_h_tag(rid, step),
                                 len(payload), e)
            return
        t_c = SpanClock()
        with t_c:
            [x] = tensors
            if self.rt.fused_tail:
                # ONE dispatch: layers + head + sample (dispatch-floor
                # fusion, §13); the split pair below is its pinned
                # parity reference
                toks = self.rt.run_chunk_sample(rid, step, x)
                self._next_step[rid] = step + 1
                self._count_tail_dispatches(1)
                result = [toks]
                dest, tag = self.header_id, self._make_tok_tag(rid, step)
            else:
                out = self.rt.run_chunk(rid, x)
                # the cache consumed this chunk: only step+1 may run next
                self._next_step[rid] = step + 1
                if self.rt.spec.is_last:
                    result = [self.rt.sample_tokens(rid, step, out)]
                    self._count_tail_dispatches(2)
                    dest, tag = self.header_id, self._make_tok_tag(rid,
                                                                   step)
                else:
                    result = [np.asarray(out)]
                    dest, tag = self.next_id, self._make_h_tag(rid, step)
            compute_span = self.tracer.next_span_id() if ctx else 0
            body = (wire.serialize_tensors_traced(result, ctx[0],
                                                  compute_span)
                    if ctx else wire.serialize_tensors(result))
        self.stats.record_compute(t_c.seconds)
        if ctx is not None:
            self._record_hop_spans(ctx, compute_span, t_c.ts, t_c.seconds,
                                   rid, step)
        self._traced_send(ctx, compute_span, dest, tag, body, rid, step)

    def _run_classify(self, rid: int, payload: bytes) -> None:
        """Classification hop: payload = [chunk, label_token_ids].  The
        tail answers the header with argmax-over-label-logits indices
        (reference ``inference.cpp:220-270``); other stages forward."""
        self.flight.record("hop_recv", stage=self.transport.device_id,
                           rid=rid, step=0, nbytes=len(payload),
                           classify=True)
        try:
            tensors, ctx = wire.split_trace_context(
                wire.deserialize_tensors(payload))
        except wire.WireIntegrityError as e:
            record_corrupt_frame(self.transport.device_id, f"c:{rid}",
                                 len(payload), e)
            return
        t_c = SpanClock()
        with t_c:
            x, label_ids = tensors
            out = self.rt.run_chunk(rid, x)
            if self.rt.spec.is_last:
                logits = np.asarray(out)        # [b, V] last position
                sub = logits[:, label_ids.astype(np.int64)]
                pred = np.argmax(sub, axis=-1).astype(np.int32)
                result = [pred]
                dest, tag = self.header_id, f"ctok:{rid}"
            else:
                result = [np.asarray(out), label_ids]
                dest, tag = self.next_id, f"c:{rid}"
            compute_span = self.tracer.next_span_id() if ctx else 0
            body = (wire.serialize_tensors_traced(result, ctx[0],
                                                  compute_span)
                    if ctx else wire.serialize_tensors(result))
        self.stats.record_compute(t_c.seconds)
        if ctx is not None:
            self._record_hop_spans(ctx, compute_span, t_c.ts, t_c.seconds,
                                   rid, 0)
        self._traced_send(ctx, compute_span, dest, tag, body, rid, 0)


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray                 # [b, s] int32
    max_new_tokens: int
    tokens: List[np.ndarray] = None    # collected [b] arrays
    step: int = 0
    done: bool = False
    trace_id: int = 0                  # telemetry: ring-propagated id

    def __post_init__(self):
        if self.tokens is None:
            self.tokens = []


class PipelineHeader:
    """The header role: owns stage 0, tokenized inputs, the request window,
    and token collection (``Communication.running``'s driver half)."""

    def __init__(self, runtime: StageRuntime, transport: BaseTransport,
                 next_id: str, eos_id: Optional[int] = None,
                 step_timeout: float = DEFAULT_STEP_TIMEOUT):
        if not runtime.spec.is_first:
            raise ValueError("header must own stage 0")
        self.rt = runtime
        self.transport = transport
        self.next_id = next_id
        self.eos_id = eos_id
        self.step_timeout = step_timeout
        self._next_rid = 0
        self.stats = StageStats(role="header")
        self.tracer = TraceRecorder(f"header:{transport.device_id}")
        self.flight = get_flight_recorder()
        self._sent_at: Dict[tuple, float] = {}  # (rid, step) -> send time
        # (rid, step) -> (trace_id, send span id, epoch ts of send end);
        # the ring_rtt span's start/identity when the token comes back
        self._rtt_ctx: Dict[tuple, tuple] = {}
        self._next_stats_seq = 0

    # -- single-stage degenerate case is the engine's job, not ours --------

    def _make_h_tag(self, rid: int, step: int) -> str:
        return _h_tag(rid, step)

    def _send_hidden(self, rid: int, step: int, hidden,
                     trace_id: int = 0, parent_id: int = 0) -> None:
        send_span = self.tracer.next_span_id() if trace_id else 0
        body = wire.serialize_tensors_traced(
            [np.asarray(hidden)], trace_id or None, send_span)
        t_s = SpanClock()
        with t_s:
            self.transport.send(self.next_id, self._make_h_tag(rid, step),
                                body)
        self.stats.record_send(t_s.seconds, len(body))
        self.flight.record("hop_send", stage=self.transport.device_id,
                           rid=rid, step=step, dest=self.next_id,
                           nbytes=len(body))
        self._sent_at[(rid, step)] = time.perf_counter()
        if trace_id:
            self.tracer.record("send", trace_id, parent_id, clock=t_s,
                               span_id=send_span, rid=rid, step=step)
            self._rtt_ctx[(rid, step)] = (trace_id, send_span, time.time())

    def _prefill_array(self, req: _Request) -> np.ndarray:
        """Stage-0 prefill input for this request — token ids by default;
        the multimodal header substitutes a pre-embedded prefix
        (runtime/multimodal.py)."""
        return req.prompt.astype(np.int32)

    def _launch(self, req: _Request) -> None:
        t_c = SpanClock()
        with t_c:
            hidden = self.rt.run_chunk(req.rid, self._prefill_array(req))
            hidden = np.asarray(hidden)
        self.stats.record_compute(t_c.seconds)
        parent = 0
        if req.trace_id:
            parent = self.tracer.record(
                "compute", req.trace_id, clock=t_c,
                rid=req.rid, step=0, phase="prefill")
        self._send_hidden(req.rid, 0, hidden, req.trace_id, parent)

    def _record_rtt(self, rid: int, step: int) -> None:
        """Token (or classify reply) returned: close the ring-RTT timer
        and its span."""
        sent = self._sent_at.pop((rid, step), None)
        rtt_ctx = self._rtt_ctx.pop((rid, step), None)
        if sent is None:
            return
        dt = time.perf_counter() - sent
        self.stats.record_rtt(dt)
        if rtt_ctx is not None:
            trace_id, send_span, ts0 = rtt_ctx
            self.tracer.record("ring_rtt", trace_id, send_span, ts=ts0,
                               dur=dt, rid=rid, step=step)

    def _advance(self, req: _Request, toks: np.ndarray) -> None:
        """Got step's tokens; either issue the next decode chunk or finish."""
        self._record_rtt(req.rid, req.step)
        req.tokens.append(toks)
        req.step += 1
        if req.step >= req.max_new_tokens or (
                self.eos_id is not None
                and bool(np.all(toks == self.eos_id))):
            req.done = True
            self.transport.send(self.next_id, f"end:{req.rid}", b"")
            self.rt.free(req.rid)
            self._sent_at = {k: v for k, v in self._sent_at.items()
                             if k[0] != req.rid}
            self._rtt_ctx = {k: v for k, v in self._rtt_ctx.items()
                             if k[0] != req.rid}
            return
        t_c = SpanClock()
        with t_c:
            hidden = self.rt.run_chunk(req.rid,
                                       toks[:, None].astype(np.int32))
            hidden = np.asarray(hidden)
        self.stats.record_compute(t_c.seconds)
        parent = 0
        if req.trace_id:
            parent = self.tracer.record(
                "compute", req.trace_id, clock=t_c,
                rid=req.rid, step=req.step, phase="decode")
        self._send_hidden(req.rid, req.step, hidden, req.trace_id, parent)

    def _make_requests(self, prompts: Sequence[np.ndarray],
                       max_new_tokens) -> List[_Request]:
        """Capacity-check every prompt and mint _Requests with fresh rids.

        ``max_new_tokens``: one int for every prompt, or a per-prompt
        sequence (each _Request already carries its own budget — the
        dynamic-batching backend groups requests with different
        lengths into one window)."""
        if isinstance(max_new_tokens, (int, np.integer)):
            per = [max_new_tokens] * len(prompts)
        else:
            per = [int(n) for n in max_new_tokens]
            if len(per) != len(prompts):
                raise ValueError(
                    f"{len(per)} max_new_tokens for {len(prompts)} prompts")
        for p, mn in zip(prompts, per):
            need = p.shape[1] + mn
            if need > self.rt.max_seq:
                raise ValueError(
                    f"prompt ({p.shape[1]}) + new ({mn}) = "
                    f"{need} exceeds KV capacity {self.rt.max_seq}")
        pending = [
            _Request(rid=self._next_rid + i, prompt=np.asarray(p),
                     max_new_tokens=mn, trace_id=new_trace_id())
            for i, (p, mn) in enumerate(zip(prompts, per))]
        self._next_rid += len(pending)
        return pending

    def _stall_postmortem(self, phase: str) -> None:
        """A ring step timed out with work in flight: record the stall
        into the flight ring and capture a postmortem bundle naming the
        requests still awaiting their reply — the offline analyzer
        (``tools/postmortem.py``) pins the offending hop from the
        ``hop_send``/``hop_recv`` events around each stalled (rid,
        step)."""
        in_flight = [[r, s] for r, s in sorted(self._sent_at.keys())]
        self.flight.record("pipeline_stall",
                           stage=self.transport.device_id, phase=phase,
                           in_flight=in_flight,
                           step_timeout_s=self.step_timeout)
        postmortem.trigger(
            "pipeline_stall",
            detail={"stage": self.transport.device_id, "phase": phase,
                    "in_flight": in_flight,
                    "step_timeout_s": self.step_timeout},
            spans=self.tracer.snapshot())

    def generate_many(self, prompts: Sequence[np.ndarray],
                      max_new_tokens: int,
                      pool_size: int = 1,
                      on_token=None) -> List[np.ndarray]:
        """Generate for all prompts with ``pool_size`` requests in flight
        (the reference's corePoolSize microbatching,
        ``Communication.java:425-437``).  Returns [b, new_tokens] arrays in
        prompt order.

        ``on_token(prompt_index, step, tokens)`` fires as each step's
        tokens arrive — the reference's partial-decode streaming to the UI
        (``DataRepository``, ``Communication.java:629-638``) as a hook.
        """
        pending = self._make_requests(prompts, max_new_tokens)
        rid_to_index = {req.rid: i for i, req in enumerate(pending)}
        queue = list(pending)
        in_flight: Dict[int, _Request] = {}

        while queue or in_flight:
            while queue and len(in_flight) < pool_size:
                req = queue.pop(0)
                in_flight[req.rid] = req
                self._launch(req)
            t0 = time.perf_counter()
            try:
                tag, payload = self.transport.recv_any(
                    timeout=self.step_timeout)
            except TransportTimeout:
                self._stall_postmortem("generate")
                raise
            self.stats.record_recv(time.perf_counter() - t0, len(payload))
            kind, _, rest = tag.partition(":")
            if kind != "tok":
                log.warning("header: unexpected tag %r", tag)
                continue
            fields = rest.split(":")
            rid, tok_step = int(fields[0]), int(fields[1])
            req = in_flight.get(rid)
            if req is None or tok_step != req.step:
                continue    # finished request, or a duplicate/stale step
                # (transport retry / chaos duplicate): advancing twice on
                # one step would append the same token twice
            self.flight.record("tok_recv", stage=self.transport.device_id,
                               rid=rid, step=req.step)
            try:
                tensors, _ = wire.split_trace_context(
                    wire.deserialize_tensors(payload))
            except wire.WireIntegrityError as e:
                # dropped: this step's token is lost and the step times
                # out (static pipeline) — never a garbage token appended
                record_corrupt_frame(self.transport.device_id, tag,
                                     len(payload), e)
                continue
            [toks] = tensors
            step = req.step
            self._advance(req, toks)
            if on_token is not None:
                on_token(rid_to_index[rid], step, toks)
            if req.done:
                del in_flight[rid]

        return [np.stack(r.tokens, axis=1) for r in pending]

    def generate(self, prompt_ids: np.ndarray,
                 max_new_tokens: int) -> np.ndarray:
        """Single request; returns [b, new_tokens]."""
        return self.generate_many([prompt_ids], max_new_tokens)[0]

    def classify_many(self, prompts: Sequence[np.ndarray],
                      label_token_ids: Sequence[int],
                      pool_size: int = 1) -> List[np.ndarray]:
        """Classify each prompt batch over the pipeline: one prefill hop,
        the tail argmaxes the last-position logits restricted to
        ``label_token_ids``, and the predicted label index rides back (the
        reference's classification run, ``BackgroundService.java:233-245``
        over ``inference.cpp:220-270``).  Returns [b] int32 label-index
        arrays, prompt order.

        Unlike ``generate_many`` on the elastic header, this loop does NOT
        reshard on failure — a dead worker surfaces as a TransportTimeout
        after ``step_timeout`` and the caller retries.  Classification is
        a single stateless hop per request, so retry-from-outside loses
        nothing (no partial tokens to preserve)."""
        label_ids = np.asarray(label_token_ids, np.int32)
        if label_ids.ndim != 1 or label_ids.size < 2:
            raise ValueError("label_token_ids must be >= 2 token ids")
        if (label_ids < 0).any() or (label_ids
                                     >= self.rt.cfg.vocab_size).any():
            # validated HERE: an out-of-range id reaching the tail would
            # IndexError inside its serve loop and poison the pipeline
            raise ValueError(
                f"label_token_ids out of range [0, "
                f"{self.rt.cfg.vocab_size})")
        for p in prompts:
            if p.shape[1] > self.rt.max_seq:
                raise ValueError(
                    f"prompt ({p.shape[1]}) exceeds KV capacity "
                    f"{self.rt.max_seq}")
        rids = list(range(self._next_rid, self._next_rid + len(prompts)))
        self._next_rid += len(prompts)
        trace_ids = {rid: new_trace_id() for rid in rids}
        results: Dict[int, np.ndarray] = {}
        queue = list(zip(rids, prompts))
        in_flight: Dict[int, int] = {}   # rid -> queue index (for order)

        def launch(rid: int, prompt: np.ndarray) -> None:
            trace_id = trace_ids[rid]
            t_c = SpanClock()
            with t_c:
                hidden = self.rt.run_chunk(rid, prompt.astype(np.int32))
                send_span = self.tracer.next_span_id()
                body = wire.serialize_tensors_traced(
                    [np.asarray(hidden), label_ids], trace_id, send_span)
            self.stats.record_compute(t_c.seconds)
            parent = self.tracer.record(
                "compute", trace_id, clock=t_c,
                rid=rid, step=0, phase="classify")
            t_s = SpanClock()
            with t_s:
                self.transport.send(self.next_id, f"c:{rid}", body)
            self.stats.record_send(t_s.seconds, len(body))
            self.flight.record("hop_send",
                               stage=self.transport.device_id,
                               rid=rid, step=0, dest=self.next_id,
                               nbytes=len(body), classify=True)
            self.tracer.record("send", trace_id, parent, clock=t_s,
                               span_id=send_span, rid=rid, step=0)
            # rtt tracked like generate steps: the tail records one
            # compute sample per classify hop, so the header must record
            # one rtt — otherwise mixed classify+generate workloads skew
            # the index-paired activation-hop estimate (stats.snapshot)
            self._sent_at[(rid, 0)] = time.perf_counter()
            self._rtt_ctx[(rid, 0)] = (trace_id, send_span, time.time())

        while queue or in_flight:
            while queue and len(in_flight) < pool_size:
                rid, prompt = queue.pop(0)
                in_flight[rid] = rid
                launch(rid, np.asarray(prompt))
            t0 = time.perf_counter()
            try:
                tag, payload = self.transport.recv_any(
                    timeout=self.step_timeout)
            except TransportTimeout:
                self._stall_postmortem("classify")
                raise
            self.stats.record_recv(time.perf_counter() - t0, len(payload))
            kind, _, rest = tag.partition(":")
            if kind != "ctok":
                log.warning("header: unexpected tag %r during classify", tag)
                continue
            rid = int(rest.split(":")[0])
            if rid not in in_flight:
                continue
            self.flight.record("tok_recv", stage=self.transport.device_id,
                               rid=rid, step=0, classify=True)
            try:
                tensors, _ = wire.split_trace_context(
                    wire.deserialize_tensors(payload))
            except wire.WireIntegrityError as e:
                record_corrupt_frame(self.transport.device_id, tag,
                                     len(payload), e)
                continue
            self._record_rtt(rid, 0)
            [pred] = tensors
            results[rid] = pred.astype(np.int32)
            self.transport.send(self.next_id, f"end:{rid}", b"")
            self.rt.free(rid)
            del in_flight[rid]

        return [results[r] for r in rids]

    def collect_stats(self, num_stages: int,
                      timeout: float = 10.0,
                      include_spans: bool = False) -> List[dict]:
        """Poll every downstream stage for its stats snapshot.

        Sends ``statsreq`` down the chain; each stage replies directly to
        the header and forwards the request.  Returns the header's own
        snapshot first, then one dict per responding stage (may be fewer
        than ``num_stages - 1`` on timeout).  Call outside of generation —
        replies share the transport with token traffic.

        ``include_spans`` asks every stage to drain its trace spans into
        the reply (the :meth:`collect_trace` path — at-most-once
        delivery: a reply that misses this poll's window loses its
        spans).
        """
        seq = str(self._next_stats_seq)
        self._next_stats_seq += 1
        self.transport.send(self.next_id, f"statsreq:{seq}",
                            b"spans" if include_spans else b"")
        mine = dict(self.stats.snapshot(include_samples=True),
                    device_id=self.transport.device_id)
        # keyed by device_id + filtered by seq: a stale reply from an
        # earlier timed-out poll can neither satisfy nor displace this one
        replies: Dict[str, dict] = {}
        deadline = time.monotonic() + timeout
        want = num_stages - 1
        while len(replies) < want:
            left = deadline - time.monotonic()
            if left <= 0:
                break
            try:
                tag, payload = self.transport.recv_any(timeout=left)
            except TransportTimeout:
                break
            if tag.startswith("statsrep:"):
                snap = json.loads(payload.decode("utf-8"))
                if snap.get("seq") == seq:
                    replies[snap.get("device_id", tag)] = snap
            else:
                log.warning("header: unexpected tag %r during stats poll",
                            tag)
        return [mine] + list(replies.values())

    def collect_trace(self, num_stages: int,
                      timeout: float = 10.0) -> dict:
        """Drain every stage's spans (plus the header's own) and export
        as a Chrome trace-event JSON object (Perfetto-loadable).  Spans
        ride the ``statsreq`` control path, so like :meth:`collect_stats`
        this must run outside of generation.  Draining means consecutive
        calls return disjoint span sets; worker spans are at-most-once
        (a stage whose reply misses the poll timeout loses that batch)."""
        from ..telemetry.tracing import to_chrome_trace
        stats = self.collect_stats(num_stages, timeout,
                                   include_spans=True)
        spans = self.tracer.drain()
        for s in stats:
            spans.extend(s.pop("spans", None) or [])
        return to_chrome_trace(spans)

    def reset_stats(self) -> None:
        """Zero our counters and every downstream stage's (e.g. after a
        compile warmup, so benchmarks report steady state only)."""
        self.stats.reset()
        self._sent_at.clear()
        self._rtt_ctx.clear()
        self.transport.send(self.next_id, "statsreset", b"")

    def shutdown_pipeline(self) -> None:
        """Send ``stop`` down the chain (Finish→Close analogue for the data
        plane)."""
        self.transport.send(self.next_id, "stop", b"")
