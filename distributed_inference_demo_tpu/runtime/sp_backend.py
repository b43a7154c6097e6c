"""``serve --sp N``: long-context sequence-parallel serving backend.

The reference has no long-context serving story (its max context is a
single device's attention; SURVEY §5.7 names sequence parallelism a
framework goal).  This backend puts the ring-attention / Ulysses
generate fns (``parallel/sequence.py``, ``parallel/ulysses.py``) behind
the same HTTP surface every other serve mode uses, so a ≥32k-token
request is one POST /generate like any other.

Design notes:

- The sp generate fns bake ``num_new_tokens`` into the jitted program
  (fixed-trip decode scan inside ``shard_map``); the backend caches one
  built fn per requested ``max_new_tokens`` and lets jit re-specialize
  per prompt-length bucket as usual.  Long-context clients typically
  reuse one ``max_new_tokens``, so the cache stays tiny.
- Prompts must arrive padded to a multiple of sp.  That is the same
  rule ``generate --sp`` enforces: silent server-side padding would
  change what the model attends, so a bad length is an HTTP 400
  (``validate_sp_prompt``'s ValueError), never a silent fix-up.
- One request runs at a time (lock): the sp mesh owns every device in
  the group, so concurrent requests would interleave collectives from
  two programs on the same chips.
- The line behind that lock is BOUNDED and VISIBLE: ``/stats`` reports
  ``queue_depth``/``busy``, and a request arriving past
  ``max_queue_depth`` waiting requests is rejected with 429 +
  Retry-After (``SchedulerOverloaded``) instead of blocking silently
  for potentially minutes at 32k context (``DWT_SP_QUEUE_DEPTH`` /
  ``serve --sp-queue-depth``; 0 = unbounded, the old behavior).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Optional

import numpy as np

from ..models.base import ModelConfig
from ..ops.sampling import SamplingParams
from ..parallel.sequence import make_sp_generate_fn, validate_sp_prompt
from ..parallel.ulysses import make_ulysses_generate_fn
from .engine import GenerationResult
from .overload import SchedulerOverloaded

STRATEGIES = ("ring", "ulysses")


class SequenceParallelBackend:
    """Engine-like backend over a local sp mesh for InferenceHTTPServer."""

    def __init__(self, cfg: ModelConfig, params, mesh, *, max_seq: int,
                 strategy: str = "ring",
                 sampling: Optional[SamplingParams] = None,
                 kv_cache_dtype: Optional[str] = None,
                 eos_id: Optional[int] = None,
                 max_queue_depth: Optional[int] = None):
        """``max_queue_depth``: how many requests may WAIT behind the
        one running (the sp mesh serializes requests); one more and the
        arrival is rejected with 429 + Retry-After instead of blocking
        on the device lock unboundedly.  ``None`` defers to
        ``DWT_SP_QUEUE_DEPTH`` (default 8); 0 = unbounded.

        The sp cache is per-request scratch INSIDE the fused
        sequence-sharded program — allocated at dispatch, freed when
        the program returns, each chip holding its own ``max_seq/sp``
        shard — so there is no standing ``batch x max_seq`` reservation
        and no page pool (docs/DESIGN.md §14)."""
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown sp strategy {strategy!r}; "
                             f"known: {STRATEGIES}")
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.max_seq = max_seq
        self.strategy = strategy
        self.sampling = sampling
        self.kv_cache_dtype = kv_cache_dtype
        self.eos_id = eos_id
        self.sp = int(mesh.shape["sp"])
        self._fns: "OrderedDict" = OrderedDict()
        self._stream_pair = None
        self._lock = threading.Lock()
        # counters + fn-cache bookkeeping get their OWN lock: generate()
        # holds _lock for the whole device computation (minutes at 32k
        # context), and GET /stats must answer DURING a request, not
        # after it
        self._stats_lock = threading.Lock()
        self._served = 0
        self._decode_seconds = 0.0
        self._tokens_out = 0
        if max_queue_depth is None:
            from ..telemetry._env import env_int
            max_queue_depth = env_int("DWT_SP_QUEUE_DEPTH", 8)
        self.max_queue_depth = max(0, int(max_queue_depth))
        # requests admitted and not yet finished (running + waiting on
        # the device lock) — the /stats queue picture and the 429 bound
        self._active = 0
        # fail at CONSTRUCTION, not at the first request: the generate
        # fns' build-time checks (max_seq % sp, Ulysses head
        # divisibility) run here, so a misconfigured server errors
        # before it ever prints HTTP_READY — a launch mistake must not
        # surface as HTTP 400s blaming the clients
        self._build(1)

    def _build(self, num_new: int):
        make = (make_sp_generate_fn if self.strategy == "ring"
                else make_ulysses_generate_fn)
        return make(self.cfg, self.mesh, max_seq=self.max_seq,
                    num_new_tokens=num_new, sampling=self.sampling,
                    kv_cache_dtype=self.kv_cache_dtype)

    # each distinct max_new_tokens is its own jitted program (the decode
    # scan's trip count is baked in); the cache is LRU-bounded so a
    # client scanning max_new values can't grow compiled programs
    # without limit — evicted variants just recompile on next use
    MAX_COMPILED_VARIANTS = 8

    def _fn(self, num_new: int):
        # called with _lock held (one build at a time); the cache dict
        # itself mutates under _stats_lock so stats() can snapshot it
        # without waiting out a whole generation
        with self._stats_lock:
            fn = self._fns.get(num_new)
            if fn is not None:
                self._fns.move_to_end(num_new)
                return fn
        fn = self._build(num_new)
        with self._stats_lock:
            self._fns[num_new] = fn
            while len(self._fns) > self.MAX_COMPILED_VARIANTS:
                self._fns.popitem(last=False)
        return fn

    def _admit(self):
        """Bounded admission to the one-request-at-a-time queue: past
        ``max_queue_depth`` WAITING requests, reject NOW with 429 +
        Retry-After (estimated from this backend's own measured
        seconds/request) — a client must never discover saturation by
        silently blocking on the device lock for minutes.  Callers pair
        this with ``_leave`` in a finally."""
        with self._stats_lock:
            if (self.max_queue_depth
                    and self._active >= 1 + self.max_queue_depth):
                per_req = (self._decode_seconds / self._served
                           if self._served else 30.0)
                retry = min(600.0, max(1.0, per_req * self._active))
                raise SchedulerOverloaded(
                    f"sp queue full: {self._active - 1} request(s) "
                    f"already waiting behind the running one (bound "
                    f"{self.max_queue_depth}); retry later",
                    retry_after_s=retry, http_code=429)
            self._active += 1

    def _leave(self):
        with self._stats_lock:
            self._active -= 1

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 seed: int = 0) -> GenerationResult:
        ids = np.asarray(prompt_ids, dtype=np.int32)
        num_new = int(max_new_tokens)
        # ValueError renders as HTTP 400 with the rule spelled out
        validate_sp_prompt(ids.shape[1], self.sp, self.max_seq, num_new)
        self._admit()
        try:
            return self._generate_admitted(ids, num_new, seed)
        finally:
            self._leave()

    def _generate_admitted(self, ids: np.ndarray, num_new: int,
                           seed: int) -> GenerationResult:
        import jax

        if self.eos_id is not None:
            # eos early stop rides the step-split stream programs (the
            # fused fn has a baked trip count and no eos plumbing):
            # rows past their eos pad with eos, and decode dispatches
            # STOP once every row finished — at long context that skips
            # real compute, not just output.  Stats are recorded by the
            # stream itself.
            box = [0.0]
            steps = list(self._stream(ids, num_new, seed, box))
            toks = np.full((ids.shape[0], num_new), self.eos_id, np.int32)
            toks[:, :len(steps)] = np.stack(steps, axis=1)
            # device-only seconds, like the fused path (wall-clock would
            # fold in lock waits from interleaved streams)
            return GenerationResult(tokens=toks, prompt_len=ids.shape[1],
                                    num_new=num_new, seconds=box[0])
        with self._lock:
            fn = self._fn(num_new)
            t0 = time.perf_counter()
            with self.mesh:
                toks = np.asarray(
                    fn(self.params, ids, jax.random.PRNGKey(seed)))
            dt = time.perf_counter() - t0
        with self._stats_lock:
            self._served += 1
            self._decode_seconds += dt
            self._tokens_out += int(toks.size)
        return GenerationResult(tokens=toks, prompt_len=ids.shape[1],
                                num_new=num_new, seconds=dt)

    # tokens per streaming decode dispatch: large enough to amortize
    # dispatch latency (the block runs as one fused scan), small enough
    # that chunks reach the client every few steps
    STREAM_BLOCK = 8

    def _stream_fns(self):
        """The step-split (prefill_fn, decode_fn) pair — built once; ONE
        compiled pair serves every max_new_tokens (unlike the fused fns,
        which bake their trip count)."""
        if self._stream_pair is None:
            from ..parallel.sequence import make_sp_stream_fns
            from ..parallel.ulysses import make_ulysses_stream_fns
            make = (make_sp_stream_fns if self.strategy == "ring"
                    else make_ulysses_stream_fns)
            self._stream_pair = make(
                self.cfg, self.mesh, max_seq=self.max_seq,
                block=self.STREAM_BLOCK, sampling=self.sampling,
                kv_cache_dtype=self.kv_cache_dtype)
        return self._stream_pair

    def generate_stream(self, prompt_ids: np.ndarray, max_new_tokens: int,
                        seed: int = 0):
        """TRUE incremental sp streaming: one prefill dispatch yields
        token #1 immediately, then each STREAM_BLOCK-token decode
        dispatch yields as it lands — first-token latency is the prefill,
        not the whole generation.  The device lock is taken per DISPATCH
        and released before every yield, so a slow or stalled client
        never blocks other requests; concurrent streams interleave their
        block dispatches (each stream's state buffers are its own).
        Greedy streams are bit-identical to ``generate``; sampled streams
        are equally distributed but draw per-block sub-rngs (the engines'
        streaming contract).  Validation errors surface on the first
        ``next()`` (a clean 400), like every other backend — and so does
        the bounded-queue rejection (a clean 429, still pre-headers)."""
        yield from self._stream(np.asarray(prompt_ids, np.int32),
                                int(max_new_tokens), seed, [0.0],
                                admit=True)

    def _stream(self, ids: np.ndarray, num_new: int, seed: int,
                device_s_box: list, admit: bool = False):
        """generate_stream's body; ``device_s_box[0]`` accumulates pure
        device-dispatch seconds so the eos ``generate()`` path can report
        the same device-only timing the fused path does (wall-clock would
        fold in lock contention from interleaved streams)."""
        import jax

        validate_sp_prompt(ids.shape[1], self.sp, self.max_seq, num_new)
        if admit:
            # generate_stream entry: the generate() path admitted before
            # calling in (one admission per REQUEST, not per surface)
            self._admit()
        emitted, device_s = 0, 0.0
        try:
            # the device lock is held per DISPATCH, never across a yield:
            # a client that stops reading suspends the generator with the
            # lock RELEASED, so other requests (and streams) keep serving
            # — their programs touch none of this stream's state buffers
            eos = self.eos_id
            done = np.zeros((ids.shape[0],), bool)

            def mask_row_eos(tok):
                """The engines' row-wise eos rule (engine._mask_eos),
                applied host-side between dispatches: finished rows pad
                with eos; returns (masked tok, all rows finished)."""
                nonlocal done
                if eos is None:
                    return tok, False
                tok = np.where(done, eos, tok)
                done = done | (tok == eos)
                return tok, bool(done.all())

            with self._lock:
                pf, dec = self._stream_fns()
                t0 = time.perf_counter()
                with self.mesh:
                    out = pf(self.params, ids, jax.random.PRNGKey(seed))
                device_s += time.perf_counter() - t0
                # flush prefill time immediately: a generation that ends
                # at (or right after) prefill — num_new=1, instant eos —
                # must not report seconds=0 / tokens_per_second NaN
                device_s_box[0] = device_s
            state, rng = list(out[:-1]), out[-1]
            tok, stop = mask_row_eos(np.asarray(state[-1]))
            yield tok                               # token #1
            emitted = 1
            while emitted < num_new and not stop:
                rng, sub = jax.random.split(rng)
                with self._lock:
                    t0 = time.perf_counter()
                    with self.mesh:
                        out = dec(self.params, *state, sub)
                    device_s += time.perf_counter() - t0
                    device_s_box[0] = device_s
                state, toks = list(out[:-1]), np.asarray(out[-1])
                # per-dispatch width comes from the COMPILED program's
                # output, not the mutable STREAM_BLOCK attribute (the
                # cached pair keeps its build-time block forever)
                take = min(toks.shape[1], num_new - emitted)
                for j in range(take):
                    tok, stop = mask_row_eos(toks[:, j])
                    yield tok
                    emitted += 1
                    if stop:
                        break
        finally:
            # an abandoned stream (client disconnect, gen.close()) still
            # spent device time and emitted tokens: count what happened.
            # A stream that failed before its first token counts nothing,
            # matching generate()'s success-only accounting.  The box is
            # flushed here too so the caller's timing is complete however
            # the generator exits (eos mid-block, close, failure).
            device_s_box[0] = device_s
            if admit:
                self._leave()
            if emitted:
                with self._stats_lock:
                    self._served += 1
                    self._decode_seconds += device_s
                    self._tokens_out += emitted * ids.shape[0]

    def stats(self) -> dict:
        # _stats_lock only: /stats must answer WHILE a long-context
        # request holds the generation lock — that is exactly when a
        # client needs the queue picture
        with self._stats_lock:
            return {
                "mode": "sequence_parallel",
                "strategy": self.strategy,
                "sp": self.sp,
                "max_seq": self.max_seq,
                "requests_served": self._served,
                "tokens_out": self._tokens_out,
                "seconds_generating": round(self._decode_seconds, 3),
                "compiled_max_new_variants": sorted(self._fns),
                # the line behind the one-request-at-a-time device lock:
                # how deep it is, whether a request is running, and the
                # bound past which arrivals get 429 (0 = unbounded)
                "queue_depth": max(0, self._active - 1),
                "busy": self._lock.locked(),
                "queue_bound": self.max_queue_depth,
            }

    def reset_stats(self) -> None:
        with self._stats_lock:
            self._served = 0
            self._decode_seconds = 0.0
            self._tokens_out = 0
