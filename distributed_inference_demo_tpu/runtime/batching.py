"""Continuous batching: requests join and leave a running decode batch.

The plain :class:`InferenceEngine` serves one ``generate()`` at a time; under
concurrent load each request waits for the whole previous batch.  Serving
systems want *continuous* (in-flight) batching: a fixed pool of batch SLOTS
decodes in lockstep, and a new request is admitted into a free slot between
two decode steps — it never waits for the others to finish, and the chip
always steps the full batch.  The reference's closest concept is
``core_pool_size`` samples in flight over socket sets
(``Communication.java:425-437``); this is that idea rebuilt for a single
accelerator where batching, not sockets, is the concurrency mechanism.

TPU-first design:

- **One compiled step, static shapes.**  Every decode step runs the full
  ``[max_batch]`` slot array through one donated-pool jit; an ``active``
  mask keeps finished/empty slots harmless (their writes land on their own
  stale positions or drop through sentinel tables — see below).
  Admission never recompiles the step.
- **PAGED-NATIVE slot cache** (docs/DESIGN.md §11/§14): K/V live in one
  device-resident page pool ``[L, num_blocks, H, block_tokens, D]``
  addressed through per-slot block tables — HBM is reserved per page a
  request actually holds, never ``B x max_seq`` worst-case rows, and
  radix prefix hits are shared block-table entries (zero copies of any
  kind).  Every slot mode rides the pool: plain decode, the speculative
  proposers (the draft model pages its own scratch pool), tp meshes
  (the pool shards by kv head).  The dense batch cache is deleted.
- **Per-slot cache positions, no per-slot programs.**  Each slot fills
  its pages from position 0 independently.  The attention mask is
  per-row (``kv_pos <= q_position`` — ops/attention.py), so ragged slot
  lengths need no extra masking; writes scatter at
  ``(table[p // bt], p % bt)`` (ops/paged_attention.py).
- **Admission = PAGED prefill straight into the pool.**  The prompt is
  padded to a small set of bucket lengths (one compile per bucket,
  reused) and forwarded through the same block-table seam decode uses
  (ops/paged_attention.paged_prefill_attention): each chunk's K/V
  scatters directly into the request's reserved pages and its queries
  attend causally over the prior pages plus the in-chunk keys.  Matched
  prefix pages are shared table entries — no temp row, no
  gather/scatter round trip, zero H2D.  Under a token budget
  (``mixed_token_budget``) admission chunks ride INSIDE the decode
  dispatch: one jitted program packs every active row's fused decode
  tokens plus prefill chunk segments from one or more admitting
  prompts, so batch-mates never lose their decode fusion while a
  prompt streams in (Orca/Sarathi-style stall-free mixed batching).
- **Stale-slot safety** is the same invariant speculative decoding relies
  on: garbage KV only ever sits at positions >= a row's valid length, a
  query at position p attends only kv_pos <= p, and position p is always
  rewritten before any query reaches it.  Freed slots additionally route
  writes through sentinel table entries, which drop them.

Per-request ``seed`` is not honored (slots share one RNG stream — the
batch's sampling order depends on who else is in flight); the engine-level
seed makes single-request runs reproducible, and greedy decoding is
bit-exact vs InferenceEngine (pinned by tests).
"""

from __future__ import annotations

import base64
import contextlib
import copy
import itertools
import queue
import threading
import time
import types
import uuid
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import (KVCache, ModelConfig, StageParams,
                           StageSpec, eva_rows, pad_cache_capacity,
                           require_kv_pair, require_one_kind,
                           require_no_state, require_one_stream,
                           require_token_rows,
                           require_single_pass)
from ..ops.eva_attention import eva_positions
from ..ops.hyper_connection import whole_tiles
from ..ops.latent_attention import latent_tile_tokens
from ..ops.paged_attention import prefill_pages_walked, sub_chunk
from ..ops.sampling import SamplingParams, filtered_logits, sample_logits
from ..telemetry import postmortem
from ..telemetry import profiling as _profiling
from ..telemetry.anomaly import AnomalyMonitor
from ..telemetry.flightrecorder import get_flight_recorder
from ..telemetry.slo import get_slo_ledger, sanitize_tenant
from ..telemetry.tracing import (EVA_DISPATCH_FIELDS,
                                 HC_DISPATCH_FIELDS,
                                 LATENT_DISPATCH_FIELDS,
                                 LOOP_DISPATCH_FIELDS,
                                 MOE_DISPATCH_FIELDS,
                                 SPARSE_DISPATCH_FIELDS,
                                 STATE_DISPATCH_FIELDS,
                                 WINDOW_DISPATCH_FIELDS, DispatchTrace,
                                 LoopCounters, MoeCounters, TraceRecorder,
                                 to_chrome_trace)
from .engine import (GenerationResult, check_capacity,
                     make_paged_chunk_programs, validate_prefill_chunk)
from .speculative import verify_emit_per_row


def slot_attention_impl(q, k, v, k_cache, v_cache, positions, cache_start,
                        slopes):
    """Attention hook for ragged per-slot cache offsets.

    Ignores the scalar ``cache_start``; ``positions`` [b, s] carries each
    row's true insert offsets.  K/V land via advanced-index scatter (the
    two index arrays broadcast to [b, s] and the indexed result layout
    [b, s, nkv, hd] is exactly the projection layout ``k``/``v`` arrive
    in).  The mask side needs nothing: ``attention`` already bounds each
    row by its own q positions.
    """
    from ..ops.attention import attention
    b, s = positions.shape
    rows = jnp.arange(b)[:, None]
    k_cache = k_cache.at[rows, :, positions].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[rows, :, positions].set(v.astype(v_cache.dtype))
    max_seq = k_cache.shape[2]
    out = attention(q, k_cache, v_cache, positions,
                    jnp.asarray(max_seq, jnp.int32), slopes)
    return out, k_cache, v_cache


class _BlocksExhausted(Exception):
    """Paged admission could not allocate its pages (pool pressure with
    every evictable block pinned by in-flight tables): the request goes
    back to pending and retries when a completion frees pages — the
    paged twin of 'no free slot', never a request failure."""


# queue sentinel that wakes an idle scheduler without enqueueing work
# (export_request posts it so a checkpoint never waits on the blocking
# get of a truly idle loop)
_WAKE = object()


@partial(jax.jit, static_argnums=(2, 3))
def _state_rows(pool, row, head_step: int, key_step: int):
    """Row ``row`` of a state pool ``[planes, rows, heads, key, value]``:
    every ``head_step``-th head's every ``key_step``-th key, float32."""
    mine = jax.lax.dynamic_index_in_dim(pool, row, 1, keepdims=False)
    return mine[:, ::head_step, ::key_step].astype(jnp.float32)


class TokenStream(queue.Queue):
    """A request's stream: tokens, then ``None``.  Both ends move a
    hand-off at a time: the scheduler hands over all of a drain's items
    at once (``put_many``), and the consumer that writes them out takes
    all that is there at once (``get_all``), so a hand-off of four
    tokens costs each side one turn at the lock and its consumer one
    wake-up.  ``get`` still hands out one item, as a ``queue.Queue``
    does."""

    def put_many(self, items) -> None:
        """``put`` every item of ``items``, in order, under one lock and
        one notify: a consumer blocked in ``get`` wakes once."""
        with self.mutex:
            self.queue.extend(items)
            self.unfinished_tasks += len(items)
            self.not_empty.notify(len(items))

    def get_all(self, timeout: Optional[float] = None) -> list:
        """Every item that is queued, in order, under one lock; blocks
        only while the queue is empty, as ``get(timeout=timeout)`` does
        (``queue.Empty`` once ``timeout`` seconds have passed with
        nothing to take).  It waits for no more than is there: one item
        queued is one item returned.  Like ``get`` it leaves
        ``unfinished_tasks`` to ``task_done``."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self.not_empty:
            while not self.queue:
                left = None if deadline is None else (
                    deadline - time.monotonic())
                if left is not None and left <= 0.0:
                    raise queue.Empty
                self.not_empty.wait(left)
            items = list(self.queue)
            self.queue.clear()
            return items


# the last item of a request's outbox where the request has ended: it
# completed (the hand-off stamps ``t_done``, books its latencies and
# closes its timeline) or was failed or cancelled.  Its stream gets
# ``None`` for either.
_COMPLETED, _FAILED = object(), object()


@dataclass
class Request:
    """One in-flight generation request (row-level)."""
    prompt: np.ndarray                 # [s] int32
    max_new: int
    tokens: List[int] = field(default_factory=list)
    lps: List[float] = field(default_factory=list)   # logprobs (plain mode)
    # latency markers (perf_counter seconds), set by submit()/scheduler
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    stream: TokenStream = field(default_factory=TokenStream)
    done: threading.Event = field(default_factory=threading.Event)
    # what the scheduler has recorded for ``stream`` and not yet handed
    # over (``ContinuousBatchingEngine._deliver``): tokens in order and,
    # last, ``_COMPLETED`` or ``_FAILED`` if the request has ended
    outbox: Optional[list] = None
    # ``(the instant, the tokens)`` of every hand-off made to ``stream``
    # (``_deliver`` appends one before its ``put_many``, with the clock
    # read it has taken anyway); the stream's consumer takes them off as
    # it writes the tokens out (``telemetry.tracing.RequestPath``)
    handoffs: deque = field(default_factory=deque)
    error: Optional[BaseException] = None
    cancelled: bool = False
    # engine-unique request id (auto-assigned by submit when the caller
    # passes none) — the address live migration exports/aborts by
    rid: Optional[str] = None
    # fleet observability (docs/DESIGN.md §7): tenant identity and the
    # gateway-propagated trace id ride the request through batching rows
    # AND the migration export/import seam; the wall-clock submit plus
    # the scheduler-pickup marker decompose TTFT into queue wait vs
    # prefill, and migration_pause accumulates freeze→first-relayed-
    # token gaps so a migrated request's timeline still sums to e2e
    tenant: str = "default"
    trace_id: int = 0
    t_submit_wall: float = 0.0     # epoch seconds at admission
    t_sched: float = 0.0           # perf_counter at scheduler pickup
    # mixed path: the dispatches (DispatchTrace.seq) that carried the
    # request's first and its final prefill segment
    first_seq: int = 0
    final_seq: int = 0
    migration_pause: float = 0.0   # accumulated seconds frozen
    migrated: bool = False         # was live-migrated out at least once
    # adopted (migrated-IN) requests never close a timeline here: the
    # source replica keeps the client connection, so its view is the
    # user-visible one — the adopting engine closing too would double-
    # count the tenant's tokens across the fleet
    adopted: bool = False
    # gateway-failover resume (docs/DESIGN.md §23): admitted via
    # submit_resumed on a survivor replica; resume_pause accumulates the
    # replay window (first recorded token to first VISIBLE token) so the
    # SLO timeline decomposes like a migration pause
    resumed: bool = False
    resume_pause: float = 0.0      # seconds spent re-deriving delivered
    # a model with a recurrent state: read a sample of the request's row
    # of the state pool when it completes (``generate(logprobs=True)``
    # asks; ``state`` is then ``_state_sample``'s record)
    state_readout: bool = False
    state: Optional[dict] = None

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self.done.wait(timeout):
            raise TimeoutError("request did not complete in time")
        if self.error is not None:
            raise self.error
        return np.asarray(self.tokens, np.int32)

    def cancel(self) -> None:
        """Ask the scheduler to drop this request: a queued request is
        skipped at admission; an in-flight one frees its slot after the
        current step.  Tokens already produced stay in ``tokens``."""
        self.cancelled = True


class ContinuousBatchingEngine:
    """Slot-based continuous batching over a single-stage model."""

    def __init__(self, cfg: ModelConfig, params: StageParams,
                 max_seq: Optional[int] = None, max_batch: int = 8,
                 sampling: SamplingParams = SamplingParams(),
                 eos_id: Optional[int] = None, seed: int = 0,
                 prompt_buckets: tuple = (32, 128, 512, 2048),
                 kv_cache_blocks: Optional[int] = None,
                 kv_block_tokens: Optional[int] = None,
                 mesh=None, kv_cache_dtype=None, kv_dtype=None,
                 draft_cfg: Optional[ModelConfig] = None,
                 draft_params: Optional[StageParams] = None,
                 num_draft: int = 4,
                 prompt_lookup: bool = False,
                 decode_block: int = 1,
                 prefill_chunk: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 mixed_token_budget: Optional[int] = None,
                 spec_adaptive: bool = True,
                 kv_host_tier_bytes: Optional[int] = None,
                 kv_disk_tier_path: Optional[str] = None,
                 kv_disk_tier_bytes: Optional[int] = None):
        """``kv_cache_blocks`` / ``kv_block_tokens``: the block-level KV
        cache (``runtime/kvcache``, docs/DESIGN.md §10) — automatic
        prefix reuse at ``kv_block_tokens`` granularity.  A new prompt
        sharing at least one whole block of leading tokens with ANY
        previously prefilled prompt (hits land mid-prompt, not just on
        full-prompt repeats) skips prefill for the shared run: the
        cached blocks load into the slot row and only the suffix runs
        (causality makes a prefix's KV independent of what follows, so
        the reuse is exact).  ``None`` defers to the ``DWT_KVCACHE_*``
        env knobs, then to the default (64 blocks x 16 tokens);
        ``kv_cache_blocks=0`` disables reuse entirely.

        ``mesh``: tp mesh — slot forwards run sharded (Megatron weights,
        kv-head-sharded cache); the per-slot scatter attn impl runs
        inside each shard on its local head planes, so ragged slots and
        tensor parallelism compose without extra machinery.

        ``kv_cache_dtype``: reduced-precision cache storage (e.g.
        "float8_e4m3fn") — the slot scatter casts on insert and attention
        upcasts on read, same contract as InferenceEngine's.

        ``draft_cfg``/``draft_params``: enable SPECULATIVE decoding inside
        the slot loop — the production serving shape (continuous batching
        x draft/verify).  Each lockstep iteration becomes one speculative
        round: the draft proposes ``num_draft`` tokens per slot, the
        target verifies all slots' proposals in ONE [B, K+1] forward, and
        each row advances by its OWN accepted count (no lockstep minimum —
        the slot cache's per-row positions make ragged advance free,
        unlike SpeculativeEngine's single-offset cache).  Greedy output
        stays bit-identical to the non-draft engine (pinned by tests);
        admission additionally prefills the prompt into a draft-side slot
        row (full prompt — the KV cache accelerates only the target
        side).

        ``prompt_lookup``: draft-FREE speculation in the slot loop — the
        proposer is an n-gram match over each slot's own token history
        (prompt_lookup.ngram_propose), verified the same per-row way.
        No second model, no second cache; exclusive with
        ``draft_cfg``.

        ``decode_block``: fuse N lockstep steps (or, in the speculative
        modes, N draft/verify ROUNDS — SpeculativeEngine's
        rounds_per_dispatch, slot-shaped) into one dispatch when no
        admission could land anyway (one host sync per block — the
        throughput mode for high-dispatch-latency devices).
        Admission/cancel latency grows to <= N steps/rounds; greedy
        output is unchanged (sampled streams differ from N=1 —
        per-request seeds are not honored either way, see above).

        ``prefill_chunk``: chunked ADMISSION — a prompt longer than C
        tokens prefills in C-token dispatches instead of one
        bucket-wide forward, and between chunks the scheduler runs one
        decode step (or speculative round) for the slots already in
        flight.  This bounds the decode stall a long prompt imposes on
        its batch-mates to one chunk's latency (the vLLM-style
        "chunked prefill" scheduling property), on top of the
        activation-memory bound the engines' chunked prefill gives.
        Admission is resumable scheduler state, not an inline loop:
        while one prompt streams its chunks, other queued requests keep
        admitting into free slots past it (no head-of-line blocking);
        further chunk-needing prompts wait their turn in arrival order.
        Streaming starts even while every slot is busy — only the final
        sampling prefill waits for a slot, so a long prompt's chunks
        overlap the busy batch's decode.
        Greedy output is unchanged: chunk boundaries only split where
        K/V is written, and the admitted row samples its first token
        from the same full-context logits (same invariant as
        InferenceEngine's chunked path, runtime/engine.py).  The
        draft-side admission prefill (speculative mode) stays one
        dispatch — the draft is small by construction.

        The scheduler is PAGED-NATIVE (docs/DESIGN.md §14): its slot
        cache IS a device-resident page pool
        ``[L, num_blocks, H, block_tokens, D]`` addressed through
        per-slot block tables.  HBM is reserved per page actually
        allocated instead of ``B x max_seq`` worst-case rows, radix
        prefix hits are shared block-table entries (zero H2D, zero
        copies of any kind), stores are in-place ownership adoptions
        (zero D2H), and EVERY slot mode rides the pool: plain decode,
        the draft-model and prompt-lookup speculative proposers (the
        draft gets its own scratch page pool, reserved and freed with
        the request), and tp meshes (the pool shards by kv head).
        ``kv_cache_blocks`` sizes the pool (0/None = ``B x
        table_width``, a row's worth a slot — there is no cache-off
        mode: the pool is the decode cache).

        ``max_queue_depth``: overload shedding — when the admission
        queue (submitted-but-unslotted requests) already holds this
        many, :meth:`submit` raises
        :class:`~.overload.SchedulerOverloaded` instead of queueing
        unboundedly (the HTTP layer maps it to ``503 + Retry-After``).
        ``None`` defers to ``DWT_MAX_QUEUE_DEPTH``; 0 (the default)
        keeps the queue unbounded.

        ``mixed_token_budget``: MIXED prefill+decode dispatch (docs/
        DESIGN.md §19) — each scheduler iteration becomes ONE jitted
        program packing every active decode row's ``decode_block``
        fused-loop tokens plus prefill chunk segments from one or more
        admitting prompts, up to this many tokens per dispatch.  Decode
        fusion survives admission (the serialized mode's fuse
        suppression is gone) and several prompts stream chunks
        concurrently.  Requires ``prefill_chunk``.  With a speculative
        proposer armed (draft model or prompt lookup) the dispatch's
        decode half runs ``decode_block`` draft/verify ROUNDS instead
        of plain steps (docs/DESIGN.md §22): a spec row is priced at
        ``(K_row + 1) * decode_block`` budget tokens and the remainder
        still packs prefill segments.  ``None`` defers to
        ``DWT_MIXED_TOKEN_BUDGET``; 0 (the default) keeps the
        serialized interleave, which is the bit-identity reference the
        mixed path is pinned against.

        ``spec_adaptive``: adaptive per-row draft length in the mixed
        dispatch (docs/DESIGN.md §22) — an EWMA of each row's
        acceptance rate shrinks/widens its ``K_row`` between iterations
        within a small static bucket set ({1, K/2, K}), so a collapsing
        acceptor degrades to near-plain decode instead of burning
        budget on rejected drafts.  False pins ``K_row = num_draft``
        (the serialized schedule's width — required for SAMPLED
        bit-identity against the serialized spec reference; greedy
        streams are K-invariant and stay bit-identical either way).

        ``kv_host_tier_bytes`` / ``kv_disk_tier_path`` /
        ``kv_disk_tier_bytes``: the TIERED KV capacity layer below the
        page pool (docs/DESIGN.md §21) — LRU-evicted radix leaves
        demote into a byte-budgeted host-RAM ring (plus an optional
        mmap'd disk segment below it) instead of vanishing, and a
        later prompt sharing the demoted prefix promotes the blocks
        back through the §15 adopt seam instead of re-prefilling.
        ``None`` defers to ``DWT_KV_HOST_TIER_BYTES`` /
        ``DWT_KV_DISK_TIER_PATH`` / ``DWT_KV_DISK_TIER_BYTES``; 0
        (the default) disables the tier — eviction discards, exactly
        as before."""
        if max_queue_depth is None:
            from ..telemetry._env import env_int
            max_queue_depth = env_int("DWT_MAX_QUEUE_DEPTH", 0)
        self.max_queue_depth = max(0, int(max_queue_depth))
        if mixed_token_budget is None:
            from ..telemetry._env import env_int
            mixed_token_budget = env_int("DWT_MIXED_TOKEN_BUDGET", 0)
        self.mixed_token_budget = max(0, int(mixed_token_budget))
        self.cfg, self.params = cfg, params
        self.max_seq = max_seq or cfg.max_seq_len
        self.max_batch = max_batch
        self.sampling = sampling
        self.eos_id = eos_id
        self.spec = StageSpec(0, 1, 0, cfg.num_layers)
        self.mesh = mesh
        self.draft_cfg, self.draft_params = draft_cfg, draft_params
        self.num_draft = num_draft
        self.prompt_lookup = prompt_lookup
        self.decode_block = decode_block
        self.prefill_chunk = validate_prefill_chunk(prefill_chunk,
                                                    self.max_seq)
        if decode_block < 1:
            raise ValueError("decode_block must be >= 1")
        if self.mixed_token_budget > 0:
            if self.prefill_chunk is None:
                raise ValueError(
                    "mixed_token_budget needs prefill_chunk: the budget "
                    "is packed with C-token prefill segments")
            if self.mixed_token_budget < self.prefill_chunk:
                raise ValueError(
                    f"mixed_token_budget ({self.mixed_token_budget}) must "
                    f"fit at least one prefill chunk "
                    f"({self.prefill_chunk} tokens)")
        if prompt_lookup and draft_cfg is not None:
            raise ValueError(
                "prompt_lookup and draft_cfg are exclusive proposers")
        if prompt_lookup and num_draft < 1:
            raise ValueError("num_draft must be >= 1")
        if (draft_cfg is None) != (draft_params is None):
            raise ValueError("draft_cfg and draft_params go together")
        if cfg.state_planes:
            # a recurrent state a request beside the page pool (docs/
            # DESIGN.md section 27): every token rewrites it, in order,
            # inside the mixed dispatch's programs.  What re-runs, rolls
            # back or splits a request's tokens refuses it
            if self.mixed_token_budget == 0:
                require_no_state(cfg, "the serialized interleave (no "
                                      "--mixed-token-budget)")
            if prompt_lookup or draft_cfg is not None:
                require_no_state(cfg, "speculation (a draft model or "
                                      "prompt lookup)")
            if mesh is not None and mesh.shape.get("tp", 1) > 1:
                require_no_state(cfg, "tensor parallelism (--tp)")
        if cfg.mixed_kinds:
            # a cache spec a kind of block (docs/DESIGN.md section 25):
            # one pool and one table a kind, window pages freed while the
            # request runs.  What is built for one pool refuses it
            if self.mixed_token_budget == 0:
                require_one_kind(cfg, "the serialized interleave (no "
                                      "--mixed-token-budget)")
            if prompt_lookup or draft_cfg is not None:
                require_one_kind(cfg, "speculation (a draft model or "
                                      "prompt lookup)")
            if mesh is not None and mesh.shape.get("tp", 1) > 1:
                require_one_kind(cfg, "tensor parallelism (--tp)")
        if cfg.summary_kv:
            # a window of exact rows and a summary a chunk in one pool
            # (docs/DESIGN.md section 26): what a row attends is a
            # function of its position that the mixed dispatch's programs
            # build on the device.  What is built for a row a token
            # refuses it
            if self.mixed_token_budget == 0:
                require_token_rows(cfg, "the serialized interleave (no "
                                        "--mixed-token-budget)")
            if prompt_lookup or draft_cfg is not None:
                require_token_rows(cfg, "speculation (a draft model or "
                                        "prompt lookup)")
            if (cfg.eva_window % self.prefill_chunk
                    or self.prefill_chunk % cfg.eva_chunk):
                raise ValueError(
                    f"--prefill-chunk {self.prefill_chunk} must divide the "
                    f"window ({cfg.eva_window}) and hold whole pooling "
                    f"chunks of {cfg.eva_chunk}: a chunk then lies in one "
                    f"window and completes every chunk it holds")
        if cfg.hc_streams:
            # n residual streams a token (docs/DESIGN.md section 28): the
            # stream's two kernels were compiled and measured inside the
            # one-chip forward alone.  The speculative programs and a
            # mesh refuse it
            if prompt_lookup or draft_cfg is not None:
                require_one_stream(cfg, "speculation (a draft model or "
                                        "prompt lookup)")
            if mesh is not None and mesh.shape.get("tp", 1) > 1:
                require_one_stream(cfg, "tensor parallelism (--tp)")
        if draft_cfg is not None:
            require_one_stream(draft_cfg, "the draft side of speculation")
            require_token_rows(draft_cfg, "the draft side of speculation")
            require_one_kind(draft_cfg, "the draft side of speculation")
            require_single_pass(draft_cfg, "the draft side of speculation")
            require_kv_pair(draft_cfg, "the draft side of speculation")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({draft_cfg.vocab_size}) != target vocab "
                    f"({cfg.vocab_size}); speculative decoding needs a "
                    "shared token space")
            if num_draft < 1:
                raise ValueError("num_draft must be >= 1")
        self.kv_cache_dtype = (jnp.dtype(kv_cache_dtype)
                               if kv_cache_dtype else None)
        # kv_dtype (docs/DESIGN.md §17): the page pool's QUANTIZED width
        # — int8/int4 pages with a per-token scale sidecar.  Exclusive
        # with the kv_cache_dtype storage CAST (same full-width layout,
        # different grid): one knob or the other.
        from ..ops.quant import resolve_kv_dtype
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        if self.kv_dtype != "bf16":
            require_kv_pair(cfg, f"a page pool of {self.kv_dtype} pages")
            require_token_rows(cfg, f"a page pool of {self.kv_dtype} pages")
            require_one_kind(cfg, f"a page pool of {self.kv_dtype} pages")
        if self.kv_dtype != "bf16" and self.kv_cache_dtype is not None:
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r} quantizes the page pool and "
                "cannot compose with a kv_cache_dtype storage cast; drop "
                "one of the two knobs")
        self.prompt_buckets = tuple(
            b for b in sorted(prompt_buckets) if b <= self.max_seq
        ) or (self.max_seq,)

        from .kvcache import resolve_kvcache_config
        n_blocks_arg, block_tokens = resolve_kvcache_config(
            kv_cache_blocks, kv_block_tokens, default_blocks=0)
        if block_tokens < 1:
            raise ValueError("kv_block_tokens must be >= 1")

        cfg_, spec_, samp_ = cfg, self.spec, sampling
        # S is a BUFFER capacity (temp prefill rows, block tables,
        # history), sublane-aligned for the flash kernel AND padded to
        # the page granule (lcm keeps both alignments); admission limits
        # still check the caller's max_seq.  The speculative slot modes
        # additionally fold in SLACK columns: a fused dispatch may write
        # up to decode_block*(K+1) positions past a row's last drained
        # length before the host learns the accepted counts, and every
        # such write must land in a page the request actually reserved
        # (an unreserved write would sentinel-drop K/V a later round
        # attends).
        import math
        B = max_batch
        spec_mode = prompt_lookup or draft_cfg is not None
        self._slack_tokens = (decode_block * (num_draft + 1)
                              if spec_mode else 0)
        # adaptive per-row draft length (docs/DESIGN.md §22): the mixed
        # dispatch prices a spec row at (K_row + 1) tokens per round and
        # an EWMA of its acceptance rate moves K_row between iterations
        # within this SMALL STATIC bucket set — the dispatch-wide draft
        # width is the max active bucket, so compiled variants stay
        # O(buckets) (re-pinned in the §20 CompileTracker budget below).
        # spec_adaptive=False pins K_row = num_draft (the serialized
        # schedule's width).
        K0 = max(1, int(num_draft))
        self._spec_buckets = tuple(sorted({1, max(1, K0 // 2), K0}))
        self.spec_adaptive = bool(spec_adaptive) and spec_mode
        self._spec_krow = np.full((B,), K0, np.int32)
        self._spec_ewma = np.ones((B,), np.float64)
        self._spec_ewma_alpha = 0.5
        g = math.lcm(8, block_tokens)
        S = -(-(pad_cache_capacity(self.max_seq)
                + self._slack_tokens) // g) * g

        from ..parallel.tensor import (make_forward_seam,
                                       make_paged_forward_seam)

        # ------------------------------------------------------------------
        # the DEVICE-resident page pool (docs/DESIGN.md §11/§14): HBM
        # holds num_blocks pages regardless of max_batch x max_seq, and
        # per-slot block tables (host numpy, the scheduler's source of
        # truth, shipped as a few hundred metadata bytes per dispatch)
        # address them.  Entry >= num_blocks = "no page": writes drop
        # (freed slots, fused-block overshoot), reads clamp into
        # causally-masked garbage.  Under a tp mesh the pool shards by
        # kv head (axis 2), exactly like the dense cache did.
        from .kvcache import PagedKVCacheManager
        from .kvcache.device import write_row_to_pages
        bt = block_tokens
        self._table_width = S // bt
        # a summarised cache: a request leases one summary page a window
        # and one window's pages, so a row of the table is
        # ``[S_0 .. S_{Ws-1} | P_0 .. P_{Pn-1}]`` and the device builds
        # the attended table from it at every call (ops.eva_attention)
        self._eva = None
        if cfg.summary_kv:
            if cfg.eva_window // cfg.eva_chunk != bt:
                raise ValueError(
                    f"a summary page is one closed window: "
                    f"--kv-block-tokens must be eva_window / eva_chunk = "
                    f"{cfg.eva_window // cfg.eva_chunk}, got {bt}")
            self._eva = types.SimpleNamespace(
                window=cfg.eva_window, chunk=cfg.eva_chunk,
                summary_pages=-(-S // cfg.eva_window),
                window_pages=cfg.eva_window // bt)
            self._table_width = (self._eva.summary_pages
                                 + self._eva.window_pages)
            self.eva_stats = {"windows_closed": 0, "summaries_written": 0,
                              "rows_held_peak": 0, "tokens_held_peak": 0}
        n_blocks = (n_blocks_arg if n_blocks_arg >= 1
                    else B * self._table_width)
        # the cache spec by kind of block: one POOL for the blocks that
        # read alike (``cfg.cache_kinds``, the full kind's first).
        # ``kv_cache`` is the full kind's manager, the pool that fills:
        # what ``--kv-cache-blocks`` sizes and /stats.kvcache's
        # ``blocks_used`` / ``blocks_total`` / ``bytes_per_token`` mean
        self._pool_specs = cfg.cache_kinds
        self.kv_cache = PagedKVCacheManager.for_model(
            cfg, n_blocks, bt, dtype=self.kv_cache_dtype,
            kv_dtype=self.kv_dtype, planes=self._pool_specs[0][1])
        N = self.kv_cache.num_blocks
        self._page_sentinel = N
        # a WINDOW pool (a kind that reads its last W tokens): a request
        # holds the pages its window and its dispatches in flight meet
        # and gives back what fell behind while it runs.  The engine
        # sizes it: ``_window_quota`` pages a request that can hold any
        # (the slots and one admission more), so an allocation inside
        # the quota never fails
        self._wmgr = None
        self._window = 0
        if len(self._pool_specs) > 1:
            if len(self._pool_specs) > 2:
                raise ValueError(
                    f"one window size a model: got the pools "
                    f"{self._pool_specs}")
            self._window, w_planes = self._pool_specs[1]
            span = max(decode_block, self.mixed_token_budget)
            self._window_quota = -(-(self._window + 2 * span) // bt) + 1
            self._window_reserved = 0
            self._wmgr = PagedKVCacheManager(
                w_planes, *cfg.kv_page_shape,
                (B + 1) * self._window_quota, bt,
                self.kv_cache_dtype or cfg.dtype, kv_dtype=self.kv_dtype)
            self._page_sentinel = max(N, self._wmgr.num_blocks)
            self.window_stats = {"pages_held_peak": 0, "pages_returned": 0,
                                 "pages_unwindowed_peak": 0}
        # a recurrent state a request (a state kind of block, kda or ssd:
        # the pool's shapes are the kind's, ``cfg.state_shapes``): B + 1 rows
        # (the slots and one admission more, which is all the intake lets
        # in), leased at admission like pages and held as long as the
        # request, and one last row that is nobody's, where a row that
        # holds no token points.  A request's row rides its table's last
        # column
        self._state_free: Optional[list] = None
        if cfg.state_planes:
            self._state_free = list(range(B + 1))
            self.state_stats = {"held_peak": 0, "zeroed": 0,
                                "row_steps": 0, "chunk_tokens": 0}
        # a row of a table: one table a pool, side by side
        self._table_cols = (len(self._pool_specs) * self._table_width
                            + (1 if cfg.state_planes else 0))
        page_dtype = self.kv_cache_dtype or cfg.dtype
        # which attention path each compiled program took, written at
        # trace time and served under /stats["attention_paths"]
        from ..ops.paged_attention import AttnPathRecord
        self.attn_paths = AttnPathRecord()
        seam_fwd, bind_tables, pool_sharding = make_paged_forward_seam(
            cfg, self.spec, mesh, params, bt, record=self.attn_paths)
        # ... and the shape and tiles of every grouped matmul a program
        # was traced with (a model with experts), served under
        # /stats["moe"]["gmm"]
        from ..ops.grouped_matmul import noting_calls
        self.gmm_calls: dict = {}

        def fwd_p(*args, **kw):
            with noting_calls(self.gmm_calls):
                return seam_fwd(*args, **kw)

        from ..ops.quant import alloc_kv_pool
        # a latent-attention model's pool is ``_pk`` alone, one row a
        # token a plane; ``_pv`` then holds no element
        heads, width = cfg.kv_page_shape
        if self._wmgr is None:
            self._pk, self._pv = alloc_kv_pool(
                (self._pool_specs[0][1], N, heads, bt, width),
                self.kv_dtype, page_dtype, pool_sharding,
                streams=cfg.kv_streams)
            if cfg.period:      # a period model's pools come as a tuple
                self._pk, self._pv = (self._pk,), (self._pv,)
        else:       # a tuple of pools, the full kind's first
            pools = [alloc_kv_pool((planes, n, heads, bt, width),
                                   self.kv_dtype, page_dtype)
                     for (_, planes), n in zip(
                         self._pool_specs, (N, self._wmgr.num_blocks))]
            self._pk, self._pv = (tuple(p[0] for p in pools),
                                  tuple(p[1] for p in pools))
        if cfg.sparse_kind is not None:
            # a sparse kind's index plane beside the full kind's pool
            # (``ModelConfig.index_shape``): a page's pooled keys under the
            # page's id, one element in its place among the values
            self._pk += (alloc_kv_pool(cfg.index_shape(N, bt), "bf16",
                                       page_dtype, streams=1)[0],)
            self._pv += (alloc_kv_pool((1,), "bf16", page_dtype,
                                       streams=1)[0],)
            # (``_sparse_account``: the scheduler's arithmetic on the
            # queries' positions, then what the DEVICE counted)
            self.sparse_stats = {"queries_dense": 0, "queries_sparse": 0,
                                 "blocks_live": 0, "blocks_kept": 0,
                                 "index_rows": 0, "device_queries_sparse": 0,
                                 "device_blocks_kept_sparse": 0,
                                 "device_blocks_kept": 0}
        if cfg.state_planes:
            # the state pool rides last among the keys' pools and the
            # convolution tails among the values': donated, aliased and
            # carried through ``mixed_step`` as the pages are
            s_shape, c_shape = cfg.state_shapes
            rows = (cfg.state_planes, B + 2)
            # (through the pools' allocator, so that a tool that sizes a
            # program from shapes alone allocates none of it)
            self._pk += (alloc_kv_pool(rows + s_shape, "bf16", jnp.float32,
                                       streams=1)[0],)
            self._pv += (alloc_kv_pool(rows + c_shape, "bf16", cfg.dtype,
                                       streams=1)[0],)
        self._tables = np.full((B, self._table_cols), self._page_sentinel,
                               np.int32)
        # write_row_to_pages survives for the DRAFT side only: the draft
        # prefill still runs a dense temp row (the draft is small by
        # construction) and scatters it into the scratch pool; the
        # TARGET's temp-row path is deleted — prefill pages directly
        self._write_row = write_row_to_pages

        # tiered KV (docs/DESIGN.md §21): the host-RAM/disk capacity
        # layer below the pool.  The demote hook closes over the LIVE
        # pool references (they rotate on every donating dispatch);
        # promotion runs in _reserve_pages, before the match.
        from .kvcache import (TieredKVStore, make_demote_hook,
                              resolve_tier_config)
        tier_host, tier_path, tier_disk = resolve_tier_config(
            kv_host_tier_bytes, kv_disk_tier_path, kv_disk_tier_bytes)
        self._kv_tier = None
        if tier_host > 0:
            require_kv_pair(cfg, "the host tier of the KV cache")
            require_token_rows(cfg, "the host tier of the KV cache")
            require_one_kind(cfg, "the host tier of the KV cache")
            self._kv_tier = TieredKVStore(
                tier_host, bt, disk_path=tier_path,
                disk_bytes=tier_disk)
            self.kv_cache.tier = self._kv_tier
            self.kv_cache.demote_hook = make_demote_hook(
                self._kv_tier, lambda: (self._pk, self._pv))

        def _emitted_logprob(logits, tok):
            """Raw log-softmax of the emitted token (the engines'
            OpenAI-style convention, engine.py decode) — one [B, V]
            reduction per step, a rounding error next to the forward."""
            return jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), -1),
                tok[:, None].astype(jnp.int32), axis=-1)[:, 0]

        def _sample_step(logits, lengths, last_tok, active, rng):
            """``(lengths', tok, lp)`` of one lockstep step from its
            logits [B, 1, V]: inactive rows keep their token."""
            with jax.named_scope("sampling"):
                tok = sample_logits(logits[:, 0], rng, samp_)
                tok = jnp.where(active, tok, last_tok)
                lp = _emitted_logprob(logits[:, 0], tok)
            return lengths + active.astype(jnp.int32), tok, lp

        def paged_one_step(params, cache, lengths, last_tok, active,
                           rng):
            """One paged lockstep decode step over all slots — the
            shared core of the per-step jit and the fused multi-step
            loop; mirrors the deleted dense ``one_step`` token for token
            (same rng spends, same masking) so paged-vs-plain-engine
            greedy parity is structural."""
            pos = lengths[:, None]
            logits, cache = fwd_p(params, last_tok[:, None], cache, pos, 0)
            return (cache, *_sample_step(logits, lengths, last_tok,
                                         active, rng))

        @partial(jax.jit, donate_argnums=(1, 2))
        def paged_step(params, pk, pv, tables, lengths, last_tok,
                       active, rng):
            bind_tables(tables, "paged_step")
            cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
            cache, lengths, tok, lp = paged_one_step(
                params, cache, lengths, last_tok, active, rng)
            return cache.keys, cache.values, lengths, tok, lp

        def _fused_loop(step_fn, params, cache, lengths, last_tok,
                        active, rng, eos, budget, num_steps,
                        done0=None, begun=None):
            """The device-resident fused-block loop shared by the dense
            and paged multi-step jits (docs/DESIGN.md §13): up to
            ``num_steps`` lockstep steps in one dispatch (one host sync
            per BLOCK, not per token), with EARLY EXIT the moment every
            active row
            is done — eos'd on device, or out of its remaining token
            ``budget`` — so a block whose rows all finish at step
            j < num_steps stops after j steps instead of decoding into
            stale positions for the rest.  The active mask stays frozen
            (admission still waits out the block); rows that finish
            while OTHERS run keep decoding into their own stale
            positions exactly as before, so the recorded tokens are
            bit-identical to the fixed-trip scan's.  Returns
            ``(cache, lengths, tok, toks [B, num_steps], lps,
            steps_ran)``; the host drain reads ``steps_ran`` columns —
            the on-device active count that tells it how many steps
            actually ran.  rng is pre-split per step (the fixed-trip
            scan's consumption order), so sampled fused blocks keep
            their exact historical streams.  ``done0``: rows already
            done at entry — a mixed dispatch's freshly installed row
            whose first sampled token hit eos.  ``begun``: ``(j0, toks,
            lps)`` of a block whose first ``j0`` steps (a traced count)
            ran before the loop, ``toks`` / ``lps`` holding their
            columns: the loop goes on from step ``j0`` with key ``j0``
            (a mixed dispatch whose slab's pass carried step 0)."""
            B = last_tok.shape[0]
            keys = jax.random.split(rng, num_steps)
            if begun is None:
                begun = (jnp.int32(0), jnp.zeros((B, num_steps), jnp.int32),
                         jnp.zeros((B, num_steps), jnp.float32))
            j0, toks0, lps0 = begun
            if done0 is None:
                done0 = jnp.zeros((B,), bool)

            def cond(carry):
                j, cache, lengths, tok, row_done, toks, lps = carry
                return (j < num_steps) & jnp.any(active & ~row_done)

            def body(carry):
                j, cache, lengths, tok, row_done, toks, lps = carry
                cache, lengths, tok, lp = step_fn(
                    params, cache, lengths, tok, active, keys[j])
                row_done = (row_done
                            | ((eos >= 0) & (tok == eos) & active)
                            | (j + 1 >= budget))
                toks = jax.lax.dynamic_update_slice(
                    toks, tok[:, None], (jnp.int32(0), j))
                lps = jax.lax.dynamic_update_slice(
                    lps, lp[:, None], (jnp.int32(0), j))
                return (j + 1, cache, lengths, tok, row_done, toks, lps)

            (steps, cache, lengths, tok, _, toks, lps) = \
                jax.lax.while_loop(
                    cond, body, (j0, cache, lengths, last_tok,
                                 done0, toks0, lps0))
            return cache, lengths, tok, toks, lps, steps

        @partial(jax.jit, donate_argnums=(1, 2), static_argnums=(10,))
        def paged_multi_step(params, pk, pv, tables, lengths,
                             last_tok, active, rng, eos, budget,
                             num_steps):
            """decode_block fusion: ``_fused_loop`` over
            ``paged_one_step``.  The tables are frozen for the block (no
            admission can land mid-block) and rows that finish while
            others run keep writing — through their own still-reserved
            pages, or through sentinel entries that drop the write (the
            paged stale-slot route)."""
            bind_tables(tables, "paged_multi_step")
            cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
            cache, lengths, tok, toks, lps, steps = _fused_loop(
                paged_one_step, params, cache, lengths, last_tok,
                active, rng, eos, budget, num_steps)
            return (cache.keys, cache.values, lengths, tok, toks, lps,
                    steps)

        @jax.jit
        def set_slot_state(lengths, last_tok, slot, new_len, new_tok):
            return (lengths.at[slot].set(new_len),
                    last_tok.at[slot].set(new_tok))

        kv_dtype = self.kv_cache_dtype

        # paged chunk programs: the SHARED factory
        # (engine.make_paged_chunk_programs — one owner of paged chunk
        # semantics).  Chunks write K/V straight into the request's
        # reserved pages through its block table — no dense temp row,
        # no gather/scatter round trip, zero H2D across cold admission.
        self._paged_chunk_mid, slab_body, slab_step_body = \
            make_paged_chunk_programs(fwd_p, bind_tables)

        @partial(jax.jit, donate_argnums=(1, 2))
        def paged_prefill(params, pk, pv, ids, table, start, real_len,
                          rng):
            """Batch-1 (suffix) PAGED prefill over a padded bucket at
            offset ``start``, straight through the request's block
            table [1, W]; samples token #1 at the prompt's true last
            position.

            Cold path: start=0.  Prefix-reuse path: start=m with the
            matched tree pages already in the table (reads only —
            writes begin at ``start``, which is at/past the shared
            pages' frontier).  Padded tail tokens write garbage K/V
            past ``start + real_len`` into the request's OWN reserved
            pages (or sentinel-drop past the reservation), and decode
            overwrites each such position before any query can attend
            it (stale-slot invariant above)."""
            bind_tables(table, "paged_prefill")
            b, s = ids.shape
            pos = start + jnp.broadcast_to(jnp.arange(s), (b, s))
            cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
            logits, cache = fwd_p(params, ids, cache, pos, real_len - 1)
            last = logits[:, 0]                                # [1, V]
            tok = sample_logits(last, rng, samp_)
            lp = _emitted_logprob(last, tok)
            return cache.keys, cache.values, tok[0], lp[0]

        # cost observatory (docs/DESIGN.md §20): every jitted program
        # class is wrapped for compile accounting at its assignment
        # site — cache growth across a call books one compile event.
        # Variant budgets document the compiled-variant invariants
        # (multi_step: the two round-count variants the warmup loop
        # pre-compiles); unbudgeted programs legitimately fork per
        # bucket/chunk shape and never feed recompile_storm.
        _ct = _profiling.get_compile_tracker()
        self._paged_chunk_mid = _ct.wrap("paged_chunk_mid",
                                         self._paged_chunk_mid)
        self._paged_prefill = _ct.wrap("paged_prefill", paged_prefill)
        self._paged_step = _ct.wrap("paged_step", paged_step)
        self._paged_multi_step = _ct.wrap("paged_multi_step",
                                          paged_multi_step,
                                          variant_budget=2)
        self._set_slot_state = set_slot_state

        # ------------------------------------------------------------------
        # the MIXED token-budget dispatch (docs/DESIGN.md §19): one jit
        # packing a [r, C] prefill slab (chunk segments from one or
        # more admitting prompts, final segments sampling token #1 and
        # installing their slot in-program) with the fused decode loop
        # over all active rows.  The slab is as many segments as the
        # dispatch packed: r = 1 .. budget // C, the shape of the
        # segment arrays, and a dispatch that packed none passes
        # ``seg=None`` and its program has no slab at all.  That gives
        # n_seg + 1 compiled variants (x num_steps, which is static per
        # decode_block), and every one is launched once before the
        # engine takes a request (``_warm_mixed_variants``), so none
        # compiles under traffic.  Whether a packed segment is a final
        # is NOT one more: the slab always samples its rows, because
        # what a warm-up launches is then a matter of shapes alone.
        self._mixed_step = None
        self._mixed_pld_step = None
        self._mixed_spec_step = None
        self._mixed_seg_cap = 0
        if self.mixed_token_budget > 0:
            C_mixed = self.prefill_chunk
            n_seg = max(1, self.mixed_token_budget // C_mixed)
            self._mixed_seg_cap = n_seg

        def slab_finals(logits, seg_keys):
            """Per-row batch-1 sampling of the packed finals' token #1 —
            shared by the plain and speculative mixed programs (each row
            its own key: the serialized final prefill's exact spend).
            ``logits`` [r, 1, V] is ``slab_body``'s: each segment's
            last token's, the one position the head ran on."""
            f_toks, f_lps = [], []
            for r in range(logits.shape[0]):
                last = logits[r]                           # [1, V]
                tok_r = sample_logits(last, seg_keys[r], samp_)
                f_toks.append(tok_r[0])
                f_lps.append(_emitted_logprob(last, tok_r)[0])
            return (jnp.stack(f_toks).astype(jnp.int32),
                    jnp.stack(f_lps))

        if self.mixed_token_budget > 0 and not spec_mode:
            # a model with experts returns one more small array, its
            # routing counters ([E + 3] int32: rows to each expert
            # summed over the execution's layer calls, then experts
            # touched, the fullest expert's rows in one layer call,
            # and the layer calls); a dense model's program is as it was
            state_ = cfg_.state_planes > 0
            # (a dense model with a recurrent state takes the same programs
            # for the row mask they carry; one with a sparse kind counts in
            # them what its selections kept, ``[3]`` int32 a block:
            # ``ops.sparse_attention.kept_counts``)
            moe_ = cfg_.num_experts > 0 or state_
            sparse_ = cfg_.sparse_kind is not None
            E_ = cfg_.experts_here      # the experts this chip holds
            sentinel_ = self._page_sentinel     # a table entry of no page

            def moe_acc0():
                return jnp.zeros((3 if sparse_ else E_ + 3,), jnp.int32)

            def moe_fold(acc, rows):
                """Fold one pass's ``[layers, E]`` row counts in."""
                if sparse_:
                    return acc + rows.sum(0)
                return jnp.concatenate([
                    acc[:E_] + rows.sum(0),
                    jnp.stack([acc[E_] + (rows > 0).sum(),
                               jnp.maximum(acc[E_ + 1], rows.max()),
                               acc[E_ + 2] + rows.shape[0]])
                ]).astype(jnp.int32)

            def paged_one_step_moe(params, carry, lengths, last_tok,
                                   active, rng):
                """``paged_one_step`` with the counters in the carry;
                a slot that does not decode enters no expert's group
                (``active`` is frozen for the block: a row that ends
                inside it steps on to the block's end)."""
                cache, acc, *limit = carry
                pos = lengths[:, None]
                # a model with a recurrent state also carries each row's
                # last length: a row past its budget steps on in the
                # block, and may move no state
                valid = active & (lengths < limit[0]) if limit else active
                logits, cache, rows = fwd_p(
                    params, last_tok[:, None], cache, pos, 0,
                    moe_stats=True, valid=valid[:, None])
                return ((cache, moe_fold(acc, rows), *limit),
                        *_sample_step(logits, lengths, last_tok, active,
                                      rng))

            @partial(jax.jit, donate_argnums=(1, 2), static_argnums=(11,))
            def mixed_step(params, pk, pv, seg, dec_tables, lengths,
                           last_tok, active, dec_rng, eos, budget,
                           num_steps):
                """One mixed dispatch.  ``seg`` is None where nothing was
                packed (the program is then the fused decode loop alone:
                no slab, no KV write of one, no prefill attention), else
                ``(seg_ids, seg_tables, seg_starts, seg_lens, seg_slot,
                seg_plen, seg_keys)`` over the ``r`` segments that were
                (the shapes are the program's key), and for a model with
                experts an eighth array, the tokens each segment holds.

                A slab's pass over the weights carries the decoding rows'
                first step (``slab_step_body``): ONE forward over the
                slab, row r of ``seg_ids`` [r, C] at positions
                ``seg_starts[r] + arange(C)`` through ``seg_tables[r]``
                (sentinel rows compute into dropped writes), and over the
                rows that were decoding when the dispatch was planned
                (``active``: the riding rows), each at its ``lengths``
                through ``dec_tables``.  Each segment samples token #1 at
                ``seg_lens[r] - 1``, the one position of the row the head
                runs on, from its OWN batch-1 rng key (``seg_keys[r]`` —
                the serialized prefill's exact spend) and installs itself
                at ``seg_slot[r]`` (slot = B = not-a-final, the install
                drops).  The riding rows sample as the loop's first
                iteration does (key 0 of ``dec_rng``'s ``num_steps``,
                ``eos`` and ``budget`` folded into done) and fill column
                0 of ``toks`` / ``lps``.  Then the fused decode loop runs
                steps 1 .. ``num_steps - 1`` over ``dec_tables`` with the
                updated row state.  A freshly installed row takes no part
                in step 0 (its token #1 is the slab's own output): it
                joins the loop at step 1, its tokens in columns 1 .. of
                its row, so it gets token #1 + ``num_steps - 1`` tokens in
                its first dispatch (a row whose token #1 was eos enters
                the loop already done).  Where no row rides (nothing was
                decoding), no step was carried: the loop runs all
                ``num_steps`` from column 0, the installed rows with it.
                ``steps``, as returned, counts the decode steps that ran,
                the carried one among them."""
                B_ = last_tok.shape[0]
                cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
                moe_acc = moe_acc0() if moe_ else None
                if seg is None:
                    final_toks = jnp.zeros((n_seg,), jnp.int32)
                    final_lps = jnp.zeros((n_seg,), jnp.float32)
                    done0 = begun = limit = None
                else:
                    (seg_ids, seg_tables, seg_starts, seg_lens, seg_slot,
                     seg_plen, seg_keys) = seg[:7]
                    riding = active
                    slab_kw = ({"moe_stats": True, "ntok": seg[7],
                                "riding": riding} if moe_ else {})
                    # a row that does not ride writes nowhere: its slot
                    # may be the one a final of this slab installs, whose
                    # table is live already
                    step_tables = jnp.where(riding[:, None], dec_tables,
                                            sentinel_)
                    lengths = lengths.at[seg_slot].set(
                        seg_plen, mode="drop")
                    # (a model with a recurrent state: a row's last length)
                    limit = ((lengths + budget,) if state_ else ())
                    # the scopes are metadata on the ops: a capture keeps
                    # each op's path (`jit(mixed_step)/decode_loop/...`)
                    # in the op's event metadata
                    with jax.named_scope("slab_body"):
                        logits, step_logits, cache, *moe = slab_step_body(
                            params, cache, seg_ids, seg_tables,
                            seg_starts, seg_lens - 1, last_tok,
                            step_tables, lengths, "mixed_step", **slab_kw)
                    if moe:
                        moe_acc = moe_fold(moe_acc, moe[0])
                    with jax.named_scope("slab_finals"):
                        final_toks, final_lps = slab_finals(
                            logits, seg_keys)
                    lengths, last_tok, lp = _sample_step(
                        step_logits, lengths, last_tok, riding,
                        jax.random.split(dec_rng, num_steps)[0])
                    # the loop's bookkeeping of its step 0, for the rows
                    # that took it
                    done0 = riding & (((eos >= 0) & (last_tok == eos))
                                      | (budget <= 1))
                    carried = jnp.any(riding).astype(jnp.int32)
                    zeros = jnp.zeros((B_, num_steps - 1), jnp.int32)
                    begun = (carried,
                             jnp.concatenate([last_tok[:, None], zeros], 1),
                             jnp.concatenate(
                                 [lp[:, None], zeros.astype(jnp.float32)],
                                 1))
                    last_tok = last_tok.at[seg_slot].set(
                        final_toks, mode="drop")
                    active = active.at[seg_slot].set(True, mode="drop")
                    done0 = done0.at[seg_slot].set(
                        (eos >= 0) & (final_toks == eos), mode="drop")
                    # a max_new=1 install has nothing left to decode:
                    # it enters the loop already done (pre-existing
                    # rows always have budget >= 1 — completed rows
                    # free their slot at drain time)
                    done0 = done0 | (budget <= 0)
                    # an installed row's budget counts from the step it
                    # joins at
                    budget = budget + jnp.zeros_like(budget).at[
                        seg_slot].set(carried, mode="drop")
                bind_tables(dec_tables, "mixed_step")
                with jax.named_scope("decode_loop"):
                    if moe_:
                        # the counters ride the loop's carry beside the
                        # cache, which _fused_loop never looks into
                        if limit is None:
                            limit = ((lengths + budget,) if state_ else ())
                        ((cache, moe_acc, *_), lengths, tok, toks, lps,
                         steps) = _fused_loop(
                            paged_one_step_moe, params,
                            (cache, moe_acc, *limit),
                            lengths, last_tok, active, dec_rng, eos,
                            budget, num_steps, done0=done0, begun=begun)
                        return (cache.keys, cache.values, lengths, tok,
                                final_toks, final_lps, toks, lps, steps,
                                moe_acc)
                    cache, lengths, tok, toks, lps, steps = _fused_loop(
                        paged_one_step, params, cache, lengths, last_tok,
                        active, dec_rng, eos, budget, num_steps,
                        done0=done0, begun=begun)
                return (cache.keys, cache.values, lengths, tok,
                        final_toks, final_lps, toks, lps, steps)

            # the §19 invariant the recompile_storm detector enforces:
            # (no slab, or one of 1 .. n_seg segments) x one static
            # num_steps = n_seg + 1 variants, all launched before ready
            self._mixed_step = _ct.wrap("mixed_step", mixed_step,
                                        variant_budget=n_seg + 1)
            # what a test composes the order before PR 61 from (the slab's
            # forward alone, then every step in the loop), the reference
            # the merged pass is held to: tests/test_mixed_batching.py
            self._mixed_parts = types.SimpleNamespace(
                slab_body=slab_body, slab_finals=slab_finals,
                fused_loop=_fused_loop, one_step=paged_one_step,
                one_step_moe=paged_one_step_moe, moe_acc0=moe_acc0,
                moe_fold=moe_fold, bind_tables=bind_tables)

        def verify_slots(params, cache, drafts, q_logits, lengths,
                         last_tok, active, rng, k_cap=None):
            """Target-verify all slots' proposals in ONE [B, K+1]
            forward over the PAGE POOL (the [B, K+1] chunk rides the
            paged impl's XLA-gather path; writes scatter through the
            frozen tables) + per-row accept + inactive-row masking — the
            verify half shared by the draft-model and prompt-lookup step
            jits (their host-side twin is _drain_spec_blocks).  Inactive
            rows' chunk writes route through their slots' sentineled
            tables and drop.  ``k_cap`` ([B] or None): per-row
            draft-length cap, the mixed dispatch's adaptive-K seam
            (speculative.accept_and_extra)."""
            K = drafts.shape[1]
            verify_in = jnp.concatenate([last_tok[:, None], drafts],
                                        axis=1)
            pos = lengths[:, None] + jnp.arange(K + 1)[None, :]
            t_logits, cache = fwd_p(params, verify_in, cache, pos, None)
            rng, sub_u, sub_x = jax.random.split(rng, 3)
            emitted, n, new_last = verify_emit_per_row(
                t_logits, drafts, q_logits, samp_, sub_u, sub_x,
                k_cap=k_cap)
            n = jnp.where(active, n, 0)
            new_last = jnp.where(active, new_last, last_tok)
            return cache, emitted, n, new_last, lengths + n

        # ------------------------------------------------------------------
        # draft-free speculative slot decoding (n-gram prompt lookup)
        self._pld_step = None
        if prompt_lookup:
            from .prompt_lookup import ngram_propose
            K = num_draft
            # emitted blocks write up to decode_block*(K+1) past a row's
            # history length before the host drains — S already folds
            # that slack in; +1 is the OOB routing column for inactive
            # rows
            hcap = S + 1

            @partial(jax.jit, donate_argnums=(1, 2, 3),
                     static_argnums=(9,))
            def pld_step(params, pk, pv, history, tables, lengths,
                         last_tok, active, rng, num_rounds):
                """``num_rounds`` prompt-lookup rounds over all slots,
                fused in one dispatch: n-gram propose per row, verify
                [B, K+1] in one paged forward, per-row accept, append
                the emitted block to each active row's history.  The
                K/V lands in each row's own reserved pages (the slack
                columns folded into S cover the fused overshoot)."""
                b = last_tok.shape[0]
                bind_tables(tables, "pld_step")
                cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))

                def one_round(carry, sub):
                    cache, history, lengths, last_tok = carry
                    hist_len = lengths + 1   # history = prompt + emitted
                    drafts = ngram_propose(history, hist_len, K)
                    # one-hot proposer (q_logits=None), like
                    # PromptLookupEngine
                    cache, emitted, n, new_last, new_lengths = \
                        verify_slots(params, cache, drafts, None, lengths,
                                     last_tok, active, sub)
                    # append emitted at cols hist_len..hist_len+K per
                    # row; inactive rows are routed out of bounds
                    # (scatter drops OOB updates) so a freed slot's stale
                    # lengths can't corrupt its row before re-admission
                    # rewrites it
                    rows = jnp.arange(b)[:, None]
                    cols = jnp.where(active[:, None],
                                     hist_len[:, None] + jnp.arange(K + 1),
                                     hcap)
                    history = history.at[rows, cols].set(emitted)
                    return (cache, history, new_lengths, new_last), \
                        (emitted, n)

                (cache, history, lengths, last_tok), (em, ns) = \
                    jax.lax.scan(one_round,
                                 (cache, history, lengths, last_tok),
                                 jax.random.split(rng, num_rounds))
                return (cache.keys, cache.values, history, lengths,
                        last_tok, em, ns)

            @partial(jax.jit, donate_argnums=(0,))
            def admit_h(history, row_ids, slot, plen, tok):
                """Seed a slot's history row: prompt + the first sampled
                token (pad-tail beyond it is masked by hist_len until
                overwritten)."""
                history = jax.lax.dynamic_update_slice(
                    history, row_ids, (slot, jnp.zeros((), jnp.int32)))
                return history.at[slot, plen].set(tok)

            self._pld_step, self._admit_h = pld_step, admit_h
            self._history = jnp.zeros((B, hcap), jnp.int32)

            if self.mixed_token_budget > 0:

                @partial(jax.jit, donate_argnums=(1, 2, 3),
                         static_argnums=(17, 18, 19))
                def mixed_pld_step(params, pk, pv, history, seg_ids,
                                   seg_tables, seg_starts, seg_lens,
                                   seg_slot, seg_plen, seg_keys,
                                   dec_tables, lengths, last_tok, active,
                                   dec_rng, k_row, k_disp, num_rounds,
                                   with_finals):
                    """One mixed SPECULATIVE dispatch, prompt-lookup
                    proposer (docs/DESIGN.md §22): the §19 prefill slab
                    (finals sample token #1 from their own per-row keys,
                    in pack order) followed by ``num_rounds``
                    draft/verify rounds over the PRE-EXISTING active
                    rows.  Freshly installed finals set only
                    lengths/last_tok in-program and stay OUT of the
                    rounds' active mask — their history row seeds
                    host-side after the dispatch (the serialized
                    admission's exact timing), and their sentinel decode
                    table drops any garbage verify write.  ``k_disp``
                    (static, a bucket) is the dispatch-wide draft width;
                    ``k_row`` [B] caps each row's acceptance below it
                    (adaptive K via verify_slots' k_cap) without
                    changing the rng spend."""
                    b = last_tok.shape[0]
                    cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
                    logits, cache = slab_body(params, cache, seg_ids,
                                              seg_tables, seg_starts,
                                              seg_lens - 1,
                                              "mixed_pld_step")
                    if with_finals:
                        final_toks, final_lps = slab_finals(
                            logits, seg_keys)
                        lengths = lengths.at[seg_slot].set(
                            seg_plen, mode="drop")
                        last_tok = last_tok.at[seg_slot].set(
                            final_toks, mode="drop")
                    else:
                        final_toks = jnp.zeros((n_seg,), jnp.int32)
                        final_lps = jnp.zeros((n_seg,), jnp.float32)
                    bind_tables(dec_tables, "mixed_pld_step")

                    def one_round(carry, sub):
                        cache, history, lengths, last_tok = carry
                        hist_len = lengths + 1
                        drafts = ngram_propose(history, hist_len, k_disp)
                        cache, emitted, n, new_last, new_lengths = \
                            verify_slots(params, cache, drafts, None,
                                         lengths, last_tok, active, sub,
                                         k_cap=k_row)
                        rows = jnp.arange(b)[:, None]
                        cols = jnp.where(
                            active[:, None],
                            hist_len[:, None] + jnp.arange(k_disp + 1),
                            hcap)
                        history = history.at[rows, cols].set(emitted)
                        return (cache, history, new_lengths, new_last), \
                            (emitted, n)

                    if num_rounds > 0:
                        (cache, history, lengths, last_tok), (em, ns) = \
                            jax.lax.scan(
                                one_round,
                                (cache, history, lengths, last_tok),
                                jax.random.split(dec_rng, num_rounds))
                    else:
                        em = jnp.zeros((0, b, k_disp + 1), jnp.int32)
                        ns = jnp.zeros((0, b), jnp.int32)
                    return (cache.keys, cache.values, history, lengths,
                            last_tok, final_toks, final_lps, em, ns)

                # §20/§22 variant invariant: with_finals x (each bucket's
                # k_disp with num_rounds=decode_block, plus the
                # rounds-free shape at k_disp=max bucket)
                self._mixed_pld_step = _ct.wrap(
                    "mixed_pld_step", mixed_pld_step,
                    variant_budget=2 * (len(self._spec_buckets) + 1))

        # ------------------------------------------------------------------
        # speculative slot decoding (draft model inside the slot loop)
        self._spec_step = None
        self._dmgr = None
        if draft_cfg is not None:
            # each fused round writes K+1 positions past a row's length
            # before the host learns how many were kept; rows advance
            # contiguously (n <= K+1 per round), so a query only ever
            # reaches a column in the round that writes it — the slack
            # columns folded into S (and into every request's page
            # reservation) are never attended stale, even across slot
            # reuse.  With decode_block rounds fused the overshoot
            # compounds — hence slack = decode_block*(K+1).
            K = num_draft
            dcfg_ = draft_cfg
            dspec = StageSpec(0, 1, 0, draft_cfg.num_layers)
            # dense temp-row prefill (slot impl) + paged decode seam —
            # the draft keeps the temp-row admission path the target
            # dropped (it is small by construction, and its pool is
            # pure scratch)
            fwd_d, dcache_sharding = make_forward_seam(
                draft_cfg, dspec, mesh, draft_params,
                attn_impl=slot_attention_impl)
            # draft rows are born on their kv-head shards under a mesh
            # (out_shardings None = unconstrained) so admission never
            # pays a reshard into the prefill shard_map
            drow_shardings = (None if dcache_sharding is None else
                              (dcache_sharding.keys,
                               dcache_sharding.values))
            fwd_dp, bind_dtables, dpool_sharding = \
                make_paged_forward_seam(draft_cfg, dspec, mesh,
                                        draft_params, bt,
                                        record=self.attn_paths)
            # the draft page pool: pure per-request SCRATCH — no radix
            # tree ever adopts draft pages (only the target's logits
            # gate emission, so reuse is a target-side property); the
            # manager is used for its free-list/accounting only, and
            # used_blocks == 0 whenever no request is in flight (the
            # draft half of the leak invariant)
            self._dmgr = PagedKVCacheManager.for_model(
                draft_cfg, n_blocks, bt, dtype=self.kv_cache_dtype,
                kv_dtype=self.kv_dtype)
            ND = self._dmgr.num_blocks
            self._dpage_sentinel = ND
            self._dpk, self._dpv = alloc_kv_pool(
                (draft_cfg.kv_planes, ND, draft_cfg.num_kv_heads, bt,
                 draft_cfg.head_dim), self.kv_dtype, page_dtype,
                dpool_sharding)
            self._dtables = np.full((B, self._table_width), ND, np.int32)

            @partial(jax.jit, donate_argnums=(2, 3, 4, 5),
                     static_argnums=(12,))
            def spec_step(params, dparams, pk, pv, dpk, dpv, tables,
                          dtables, lengths, last_tok, active, rng,
                          num_rounds):
                """``num_rounds`` speculative rounds over all slots,
                fused in one dispatch: draft K per row (through the
                draft page pool), verify [B, K+1] in one paged target
                forward, per-row accept (verify_emit_per_row).  Returns
                [R, B, K+1] emitted blocks + [R, B] counts for the host
                to drain; inactive rows advance by 0 and keep
                last_tok."""
                b = last_tok.shape[0]
                bind_tables(tables, "spec_step")
                bind_dtables(dtables, "spec_step/draft")
                cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
                dcache = KVCache(dpk, dpv, jnp.zeros((), jnp.int32))

                def one_round(carry, sub):
                    cache, dcache, lengths, last_tok = carry

                    # K proposals + one extra step inserting d_K's KV so
                    # an all-accept round leaves the draft cache fully
                    # populated (speculative.py's dstep, per-row
                    # positions)
                    def dstep(c, j):
                        tok, dc, r = c
                        pos = (lengths + j)[:, None]
                        logits, dc = fwd_dp(dparams, tok[:, None], dc,
                                            pos, 0)
                        logits = logits[:, 0]
                        r, s = jax.random.split(r)
                        if samp_.greedy:
                            d = jnp.argmax(logits, axis=-1).astype(
                                jnp.int32)
                            q = logits  # unused in greedy verify
                        else:
                            q = filtered_logits(logits, samp_)
                            d = jax.random.categorical(s, q, axis=-1)
                            d = d.astype(jnp.int32)
                        return (d, dc, r), (d, q)

                    sub, sub_d = jax.random.split(sub)
                    (_, dcache, _), (drafts, q_logits) = jax.lax.scan(
                        dstep, (last_tok, dcache, sub_d),
                        jnp.arange(K + 1))
                    drafts = drafts[:K].T                        # [b, K]
                    q_logits = jnp.swapaxes(q_logits[:K], 0, 1)

                    cache, emitted, n, new_last, lengths = verify_slots(
                        params, cache, drafts,
                        None if samp_.greedy else q_logits, lengths,
                        last_tok, active, sub)
                    return (cache, dcache, lengths, new_last), \
                        (emitted, n)

                (cache, dcache, lengths, last_tok), (em, ns) = \
                    jax.lax.scan(one_round,
                                 (cache, dcache, lengths, last_tok),
                                 jax.random.split(rng, num_rounds))
                return (cache.keys, cache.values, dcache.keys,
                        dcache.values, lengths, last_tok, em, ns)

            @partial(jax.jit, donate_argnums=(2, 3))
            def dprefill(dparams, ids, row_k, row_v):
                """Full-prompt draft-side prefill of a slot row (no
                sampling — the first token always comes from the TARGET's
                prefill logits).  Pad-tail garbage K/V is overwritten by
                the draft scan before any query can attend it (the same
                stale-slot invariant as the target prefill's)."""
                b, s = ids.shape
                pos = jnp.broadcast_to(jnp.arange(s), (b, s))
                dcache = KVCache(row_k, row_v, jnp.zeros((), jnp.int32))
                _, dcache = fwd_d(dparams, ids, dcache, pos, s - 1)
                return dcache.keys, dcache.values

            @partial(jax.jit, out_shardings=drow_shardings)
            def zero_row_d():
                row = KVCache.create(dcfg_, dcfg_.num_layers, 1, S,
                                     dtype=kv_dtype)
                return row.keys, row.values

            self._spec_step = spec_step
            self._dprefill, self._zero_row_d = dprefill, zero_row_d

            if self.mixed_token_budget > 0:

                @partial(jax.jit, donate_argnums=(2, 3, 4, 5),
                         static_argnums=(20, 21, 22))
                def mixed_spec_step(params, dparams, pk, pv, dpk, dpv,
                                    seg_ids, seg_tables, seg_starts,
                                    seg_lens, seg_slot, seg_plen,
                                    seg_keys, dec_tables, dec_dtables,
                                    lengths, last_tok, active, dec_rng,
                                    k_row, k_disp, num_rounds,
                                    with_finals):
                    """One mixed SPECULATIVE dispatch, draft-model
                    proposer (docs/DESIGN.md §22): the §19 prefill slab
                    + finals, then ``num_rounds`` draft/verify rounds
                    over the PRE-EXISTING active rows through the draft
                    scratch pool.  Fresh finals set only
                    lengths/last_tok — their draft cache prefills
                    host-side after the dispatch (their dtable row is
                    still all-sentinel here, so draft-side writes drop).
                    ``k_disp`` is the static dispatch-wide draft width
                    (drafting always runs the full sub-scan so the rng
                    spend matches the serialized spec_step); ``k_row``
                    caps per-row acceptance (adaptive K)."""
                    b = last_tok.shape[0]
                    cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
                    logits, cache = slab_body(params, cache, seg_ids,
                                              seg_tables, seg_starts,
                                              seg_lens - 1,
                                              "mixed_spec_step")
                    if with_finals:
                        final_toks, final_lps = slab_finals(
                            logits, seg_keys)
                        lengths = lengths.at[seg_slot].set(
                            seg_plen, mode="drop")
                        last_tok = last_tok.at[seg_slot].set(
                            final_toks, mode="drop")
                    else:
                        final_toks = jnp.zeros((n_seg,), jnp.int32)
                        final_lps = jnp.zeros((n_seg,), jnp.float32)
                    bind_tables(dec_tables, "mixed_spec_step")
                    bind_dtables(dec_dtables, "mixed_spec_step/draft")
                    dcache = KVCache(dpk, dpv, jnp.zeros((), jnp.int32))

                    def one_round(carry, sub):
                        cache, dcache, lengths, last_tok = carry

                        def dstep(c, j):
                            tok, dc, r = c
                            pos = (lengths + j)[:, None]
                            dlogits, dc = fwd_dp(dparams, tok[:, None],
                                                 dc, pos, 0)
                            dlogits = dlogits[:, 0]
                            r, s = jax.random.split(r)
                            if samp_.greedy:
                                d = jnp.argmax(dlogits, axis=-1).astype(
                                    jnp.int32)
                                q = dlogits
                            else:
                                q = filtered_logits(dlogits, samp_)
                                d = jax.random.categorical(s, q, axis=-1)
                                d = d.astype(jnp.int32)
                            return (d, dc, r), (d, q)

                        sub, sub_d = jax.random.split(sub)
                        (_, dcache, _), (drafts, q_logits) = jax.lax.scan(
                            dstep, (last_tok, dcache, sub_d),
                            jnp.arange(k_disp + 1))
                        drafts = drafts[:k_disp].T
                        q_logits = jnp.swapaxes(q_logits[:k_disp], 0, 1)

                        cache, emitted, n, new_last, lengths = \
                            verify_slots(
                                params, cache, drafts,
                                None if samp_.greedy else q_logits,
                                lengths, last_tok, active, sub,
                                k_cap=k_row)
                        return (cache, dcache, lengths, new_last), \
                            (emitted, n)

                    if num_rounds > 0:
                        (cache, dcache, lengths, last_tok), (em, ns) = \
                            jax.lax.scan(
                                one_round,
                                (cache, dcache, lengths, last_tok),
                                jax.random.split(dec_rng, num_rounds))
                    else:
                        em = jnp.zeros((0, b, k_disp + 1), jnp.int32)
                        ns = jnp.zeros((0, b), jnp.int32)
                    return (cache.keys, cache.values, dcache.keys,
                            dcache.values, lengths, last_tok,
                            final_toks, final_lps, em, ns)

                self._mixed_spec_step = _ct.wrap(
                    "mixed_spec_step", mixed_spec_step,
                    variant_budget=2 * (len(self._spec_buckets) + 1))
        self.spec_stats = {"rounds": 0, "drafted": 0, "accepted": 0}
        # disaggregated-join counters (docs/DESIGN.md §15): requests
        # admitted with premigrated KV + pages adopted on their behalf
        self.disagg_stats = {"premigrated_requests": 0,
                             "adopted_pages": 0}
        # gateway-failover resume counters (docs/DESIGN.md §23):
        # surfaced under stats()["resumed"], bridged onto
        # dwt_batching_resumed_requests_total by the catalog
        self.resume_stats = {"requests": 0, "replayed_tokens": 0,
                             "diverged": 0}

        self._lengths = jnp.zeros((B,), jnp.int32)
        self._last_tok = jnp.zeros((B,), jnp.int32)
        # on every chip of the mesh: where a call's small arguments live
        self._replicated = None if mesh is None else (
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        if mesh is not None:
            # born where every program hands them back: a program's
            # first call is then keyed like its later ones (one compiled
            # entry a variant)
            self._lengths, self._last_tok = jax.device_put(
                (self._lengths, self._last_tok), self._replicated)
        self._rng = jax.random.PRNGKey(seed)
        # the resume replay (§23) rewinds the engine stream to this key
        # so a survivor re-derives a sampled prefix bit-exactly
        self._seed = int(seed)
        self._step_count = 0
        # device-loop dispatch accounting (docs/DESIGN.md §13): one
        # host dispatch per fused block, device_loop_steps counts the
        # steps (or speculative rounds) that actually ran inside it —
        # early exit makes steps < decode_block visible here
        self.loop_stats = {"host_dispatches": 0, "device_loop_steps": 0}
        self._reset_chunk_stats()
        # resumable chunked admission.  Serialized mode (_adm): at most
        # ONE prompt streams its chunks at a time (scheduler state,
        # advanced one dispatch per loop iteration).  Mixed mode
        # (_adms): several admissions stream concurrently, their chunks
        # packed into each iteration's token-budget dispatch.  _pending
        # holds popped-but-unserved requests: chunk-needing prompts
        # waiting their streaming turn, and short prompts waiting for a
        # free slot — served FIFO each iteration, with serviceable
        # requests passing blocked ones
        self._adm: Optional[dict] = None
        self._adms: List[dict] = []
        self._pending: "deque[Request]" = deque()
        # completed-request latency reservoirs (seconds), bounded FIFO —
        # the /stats percentile source (reference analog: the per-stage
        # timer story, runtime/stats.py)
        self._lat = {"ttft": deque(maxlen=512), "e2e": deque(maxlen=512),
                     "per_token": deque(maxlen=512),
                     "queue_wait": deque(maxlen=512)}
        self._completed = 0
        # one record per mixed dispatch + the host phases around it
        # (docs/DESIGN.md §20); scheduler thread writes, /stats reads
        # a model with experts also counts its routing (tracing.
        # MoeCounters; mixed dispatches only: the path that is served)
        moe = cfg.num_experts > 0 and self._mixed_step is not None
        self.moe_counters = (MoeCounters(cfg.experts_here, cfg.num_experts)
                             if moe else None)
        # ... and its slab is told the tokens each segment holds, an
        # eighth segment array (`_blank_segments`)
        self._seg_arrays = (8 if moe or (cfg.state_planes
                                         and self._mixed_step is not None)
                            else 7)
        self._set_row = jax.jit(lambda rows, r, row: rows.at[r].set(row))
        # a looped model counts its passes (tracing.LoopCounters); a
        # one-pass model's record and /stats are as they were
        loop = cfg.ut_steps > 1 and self._mixed_step is not None
        self.loop_counters = LoopCounters(
            cfg.ut_steps, cfg.kv_planes,
            self.kv_cache.block_bytes // bt) if loop else None
        # a latent-attention model's record says what its prefill kernel
        # attended over
        latent = cfg.latent_kv and self._mixed_step is not None
        # the query tiles a pool's prefill kernel cuts a segment into
        # (``prefill_pages_walked`` of the dispatch record; the mixed
        # path's, which alone has a record)
        self._prefill_tiles = (self._tiles_a_pool() if self.prefill_chunk
                               else ())
        self.dispatch_trace = DispatchTrace(
            (MOE_DISPATCH_FIELDS if moe else ())
            + (LOOP_DISPATCH_FIELDS if loop else ())
            + (LATENT_DISPATCH_FIELDS if latent else ())
            + (WINDOW_DISPATCH_FIELDS if self._wmgr is not None else ())
            + (EVA_DISPATCH_FIELDS if self._eva is not None else ())
            + (STATE_DISPATCH_FIELDS[cfg.state_kind.attn]
               if self._state_free is not None else ())
            + (SPARSE_DISPATCH_FIELDS if cfg.sparse_kind is not None
               and self._mixed_step is not None else ())
            + (HC_DISPATCH_FIELDS if cfg.hc_streams else ()))
        # a model with n residual streams: the token rows its residual
        # path computed (every row of a slab and every slot of a decode
        # step, tokens or not: the kernels run over all of them), and how
        # far the doubly-stochastic maps stand from 1 (``_hc_probe``,
        # once, when the programs are warm)
        self.hc_stats = ({"rows": 0, "sinkhorn_residual_max": None}
                         if cfg.hc_streams else None)

        # (mixed mode never dispatches the serialized step programs: it
        # launches every variant of mixed_step instead, below)
        if self.decode_block > 1 and self.mixed_token_budget == 0:
            # compile BOTH round-count variants now: the non-fused
            # variant's first use otherwise lands as a multi-second
            # XLA compile in the middle of serving (all-inactive mask:
            # state is unchanged where it matters, rows are unadmitted).
            # Real executions on purpose — jit's AOT path
            # (.lower().compile()) returns a separate executable and
            # does NOT seed the call cache the serving loop hits.
            # all-sentinel tables: writes drop, state holds
            idle = jnp.zeros((B,), bool)
            warm_rng = jax.random.PRNGKey(0)
            tbl = jnp.asarray(self._tables)
            for n_r in (1, self.decode_block):
                if self._pld_step is not None:
                    (self._pk, self._pv, self._history, self._lengths,
                     self._last_tok, _, _) = self._pld_step(
                        self.params, self._pk, self._pv, self._history,
                        tbl, self._lengths, self._last_tok, idle,
                        warm_rng, n_r)
                elif self._spec_step is not None:
                    (self._pk, self._pv, self._dpk, self._dpv,
                     self._lengths, self._last_tok, _, _) = \
                        self._spec_step(
                            self.params, self.draft_params, self._pk,
                            self._pv, self._dpk, self._dpv, tbl,
                            jnp.asarray(self._dtables), self._lengths,
                            self._last_tok, idle, warm_rng, n_r)
                elif n_r > 1:
                    (self._pk, self._pv, self._lengths,
                     self._last_tok, _, _, _) = self._paged_multi_step(
                        self.params, self._pk, self._pv, tbl,
                        self._lengths, self._last_tok, idle,
                        warm_rng, self._eos_scalar(),
                        jnp.zeros((B,), jnp.int32), n_r)
                else:
                    (self._pk, self._pv, self._lengths,
                     self._last_tok, _) = self._paged_step(
                        self.params, self._pk, self._pv, tbl,
                        self._lengths, self._last_tok, idle,
                        warm_rng)
        if self._mixed_step is not None:
            self._warm_mixed_variants()

        self._slots: List[Optional[Request]] = [None] * B
        self._queue: "queue.Queue" = queue.Queue()
        # live-migration seam (docs/DESIGN.md §18): rid -> Request for
        # export_request/active_requests addressing (entries die with
        # their request), plus the export mailbox the scheduler thread
        # services between steps (a foreign thread must never touch the
        # donated pool buffers)
        self._by_rid: dict = {}
        self._rid_salt = uuid.uuid4().hex[:8]
        self._rid_counter = itertools.count()
        self._export_q: "deque" = deque()
        # why the mixed loop's last dispatch was not followed by one
        # prepared under it (tracing.AHEAD_MISS_REASONS); None while
        # nothing executes
        self._ahead_miss: Optional[str] = None
        # the requests that hold tokens or an end their streams have yet
        # to get (``Request.outbox``), in the order they were recorded
        # (``_post`` / ``_deliver``): scheduler thread only, and empty
        # whenever that thread may block or leave.  ``_gap_drained``: a
        # dispatch of the mixed loop was drained in the gap since the
        # last hand-off
        self._outbox: List[Request] = []
        self._gap_drained = False
        self.migration_stats = {"exported_requests": 0,
                                "imported_requests": 0,
                                "detached_requests": 0}
        self._flight = get_flight_recorder()
        # per-engine span sink for the fleet trace stitch (docs/DESIGN.md
        # §7): prefill/decode spans tagged with the gateway-propagated
        # trace id, exported by GET /trace and merged by /trace/fleet.
        # The rid salt keeps proc rows distinct when tests co-locate
        # several engines in one process.
        self.tracer = TraceRecorder(f"engine:{self._rid_salt}")
        # co-located span sources (the migration worker registers its
        # recorder here) drain through export_trace alongside our own,
        # so one replica /trace carries engine AND migration spans
        self._aux_tracers: list = []
        # online anomaly watch over the same stats() surface /stats
        # serves; throttled to ~1 Hz inside the scheduler loop, and
        # bundles only materialize when postmortem capture is configured
        # (DWT_POSTMORTEM_DIR) — detection itself always feeds the
        # dwt_anomaly_* series and the flight ring
        self.anomaly = AnomalyMonitor(config={
            "engine": type(self).__name__, "max_batch": max_batch,
            "max_seq": self.max_seq, "decode_block": decode_block,
            "prefill_chunk": prefill_chunk,
            "mixed_token_budget": self.mixed_token_budget})
        # cost observatory handles (docs/DESIGN.md §20): the sampled
        # dispatch profiler (off-path free: an unsampled dispatch is
        # one dict increment, zero added syncs), the HBM watermark
        # ledger (this engine's owners reset on close()), and the
        # workload sketch recorder feeding GET /sketch
        self._prof = _profiling.get_profiler()
        self._sketch = _profiling.get_sketch()
        self._hbm = _profiling.get_hbm_watermarks()
        self._hbm_owners: set = set()
        # per-token KV byte attribution for achieved-GB/s: K+V over all
        # layers incl. the quantized sidecar, via the pool's block
        # accounting (the one-owner ops/quant.py math)
        self._kv_bytes_per_token = max(
            1, self.kv_cache.block_bytes // self.kv_cache.block_tokens)
        self._running = True
        self._scheduler_error: Optional[str] = None
        # serializes submit() against close(): no request can be enqueued
        # after close() returns, so none can slip past the shutdown drain
        self._submit_lock = threading.Lock()
        self.dispatch_trace.watch_gc()     # until close()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------
    # public API

    def submit(self, prompt_ids, max_new_tokens: int,
               _staged: Optional[dict] = None,
               _replay: Optional[dict] = None,
               request_id: Optional[str] = None,
               tenant: Optional[str] = None,
               trace_id: int = 0, state_readout: bool = False) -> Request:
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        check_capacity(self.max_seq, len(prompt), max_new_tokens)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            # admission records the first sampled token unconditionally,
            # so a 0-token request would still produce one
            raise ValueError("max_new_tokens must be >= 1")
        # the page-pool twin of check_capacity: a request whose full
        # table (incl. the speculative modes' fused-overshoot slack)
        # can never be allocated would wait in pending forever
        bt = self.kv_cache.block_tokens
        need = sum(self._pages_needed(len(prompt) + max_new_tokens
                                      + self._slack_tokens))
        pool_bound = self.kv_cache.num_blocks
        if self._dmgr is not None:
            # the draft pool cannot evict (no tree), so it binds too
            pool_bound = min(pool_bound, self._dmgr.num_blocks)
        if need > pool_bound:
            raise ValueError(
                f"request needs {need} KV blocks (prompt "
                f"{len(prompt)} + new {max_new_tokens} + slack "
                f"{self._slack_tokens} at {bt} tokens/block) but the "
                f"paged pool holds only {pool_bound}; raise "
                "kv_cache_blocks")
        if self.max_queue_depth:
            depth = self._queue.qsize() + len(self._pending)
            if depth >= self.max_queue_depth:
                from .overload import SchedulerOverloaded
                self._flight.record("admission_shed", depth=depth,
                                    limit=self.max_queue_depth)
                raise SchedulerOverloaded(
                    f"admission queue full ({depth} waiting >= "
                    f"--admission-queue-depth {self.max_queue_depth}); "
                    "shedding instead of queueing unboundedly",
                    retry_after_s=1.0)
        req = Request(prompt=prompt, max_new=max_new_tokens,
                      t_submit=time.perf_counter(),
                      t_submit_wall=time.time(),
                      tenant=sanitize_tenant(tenant),
                      trace_id=int(trace_id or 0),
                      state_readout=(state_readout
                                     and self._state_free is not None))
        # every request gets a migration-addressable id: caller-supplied,
        # or engine-salted auto id (the salt keeps auto rids distinct
        # across replicas sharing a transport namespace).  Wire frame
        # tags are colon-delimited, so rids must not contain ':'.
        if request_id is not None and ":" in request_id:
            raise ValueError(f"request_id {request_id!r} contains ':'")
        req.rid = (request_id if request_id is not None
                   else f"r{self._rid_salt}-{next(self._rid_counter)}")
        # staged premigrated blocks (submit_premigrated) attach BEFORE
        # the queue put: the scheduler thread may pop the request the
        # instant it lands, and a late-attached staging would silently
        # cold-prefill the full prompt instead of importing
        if _staged is not None:
            req._staged = _staged
        if _replay is not None:
            # resume replay state (submit_resumed) attaches before the
            # queue put for the same reason as _staged: the scheduler
            # may pop the request instantly, and a late attach would
            # stream the replayed prefix to the client a second time
            req.resumed = True
            req._suppress = _replay["suppress"]
            req._rng_rewind = _replay["rewind"]
        with self._submit_lock:
            if not self._running:
                raise RuntimeError("engine is closed")
            self._by_rid[req.rid] = req
            self._queue.put(req)
        # workload sketch: admitted arrivals only (shed requests above
        # never became workload); t_submit doubles as the interarrival
        # clock so the sketch is a pure fold over the request trace
        self._sketch.record_request(len(prompt), tenant=req.tenant,
                                    now=req.t_submit)
        return req

    def submit_premigrated(self, prompt_ids, max_new_tokens: int,
                           k_blocks, v_blocks) -> Request:
        """Decode-side JOIN of a disaggregated request (docs/DESIGN.md
        §15): the prompt's whole-block K/V was computed by a prefill
        worker and migrated here as host block payloads
        ``[n, L, H, bt, D]``.  Admission first lands the blocks in the
        page pool (one device scatter, ``adopt_blocks_into_pages``) and
        ADOPTS them into the radix tree (``store_shared`` — the §11
        ownership-transfer seam, so the invariant `every page owned by
        tree xor one request` is preserved verbatim); the request then
        admits through the ordinary paged path, whose ``match`` finds
        the adopted prefix as block-table references — zero dense-row
        H2D — and only the ≤ one-block suffix prefills here.  The
        import runs ON the scheduler thread between steps (the pool
        buffers are donated every dispatch; a foreign-thread write
        would race them).

        ``k_blocks=None`` (a short prompt with no migratable whole
        block) degrades to a plain :meth:`submit`.

        Quantized migrations (docs/DESIGN.md §17) arrive as
        :class:`~..ops.quant.QuantizedKVPages` payloads — narrow bytes +
        scale sidecar, adopted VERBATIM into a matching quantized pool
        (the decode side holds bit-identical pages to the prefill
        side); a full-width payload into a quantized pool quantizes at
        the adopt scatter."""
        if k_blocks is None:
            return self.submit(prompt_ids, max_new_tokens)
        require_kv_pair(self.cfg, "a premigrated prefill (disaggregation)")
        require_token_rows(self.cfg, "a premigrated prefill (disaggregation)")
        require_one_kind(self.cfg, "a premigrated prefill (disaggregation)")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        from ..ops.quant import QuantizedKVPages
        if isinstance(k_blocks, QuantizedKVPages):
            if (not isinstance(self._pk, QuantizedKVPages)
                    or self._pk.bits != k_blocks.bits):
                raise ValueError(
                    f"premigrated int{k_blocks.bits} blocks need a "
                    f"matching quantized pool; this engine's pages are "
                    f"kv_dtype={self.kv_dtype!r}")
        else:
            k_blocks = np.asarray(k_blocks)
            v_blocks = np.asarray(v_blocks)
        bt = self.kv_cache.block_tokens
        want = (self.cfg.kv_planes, self.cfg.num_kv_heads, bt,
                self.cfg.head_dim)
        if (k_blocks.shape != v_blocks.shape or k_blocks.ndim != 5
                or k_blocks.shape[1:] != want):
            raise ValueError(
                f"premigrated blocks must be [n, L, H, bt, D] = "
                f"[n, {want[0]}, {want[1]}, {want[2]}, {want[3]}]; got "
                f"K {k_blocks.shape} / V {v_blocks.shape}")
        if k_blocks.shape[0] > len(prompt) // bt:
            raise ValueError(
                f"{k_blocks.shape[0]} migrated blocks exceed the "
                f"prompt's {len(prompt) // bt} whole blocks")
        return self.submit(prompt, max_new_tokens,
                           _staged={"k": k_blocks, "v": v_blocks,
                                    "imported": False})

    def submit_resumed(self, prompt_ids, max_new_tokens: int,
                       delivered_tokens, *,
                       request_id: Optional[str] = None,
                       tenant: Optional[str] = None,
                       trace_id: int = 0) -> Request:
        """Admit a stream that already delivered tokens on a dead
        replica (docs/DESIGN.md §23): re-derive the delivered prefix
        through the NORMAL paged admission — mixed dispatch, prefix
        reuse, speculation all included — verify it token-by-token
        against the journal, and stream only the suffix.  The caller
        passes the ORIGINAL ``prompt_ids`` / ``max_new_tokens`` plus
        the delivered token ids, so the resumed stream is bit-identical
        to the unfailed run:

        - **greedy** engines extend the prompt with ``delivered[:-1]``
          and prefill it like any other prompt (a delivered token's KV
          is exact regardless of whether prefill or decode produced
          it); admission's argmax re-derives ``delivered[-1]`` and the
          suppress queue verifies it.  Exact on ANY survivor, warm or
          busy.
        - **sampled** engines re-submit the original prompt and rewind
          the engine rng to the constructor seed immediately before
          this request's token-#1 split, replaying the exact per-step
          split schedule (admission split, then one decode split per
          dispatch) that produced the delivered tokens.  Exact when the
          survivor replays the original run's dispatch pattern — same
          engine config and seed, request decoding alone from slot 0
          (the §18/§19 single-stream pinning scope); any deviation is
          caught by the verify queue and fails the request instead of
          streaming a silently-wrong suffix.

        Replayed tokens append to ``tokens`` (budget/page math stays
        exact) but never re-enter the stream queue; the replay window
        is recorded as ``resume_pause`` (the migration-pause analog) so
        the SLO decomposition still sums."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        delivered = [int(t) for t in
                     np.asarray(delivered_tokens, np.int64).reshape(-1)]
        k = len(delivered)
        if k == 0:
            raise ValueError("resume needs at least one delivered token")
        if k >= max_new_tokens:
            raise ValueError(
                f"{k} delivered tokens leave nothing to resume "
                f"(max_new_tokens={max_new_tokens})")
        if self.eos_id is not None and self.eos_id in delivered:
            raise ValueError(
                "delivered tokens contain eos — the stream already "
                "completed and has nothing to resume")
        if self.sampling.greedy:
            ext = np.concatenate(
                [prompt, np.asarray(delivered[:-1], np.int32)])
            replay = {"suppress": deque([delivered[-1]]),
                      "rewind": False}
            req = self.submit(ext, max_new_tokens - (k - 1),
                              _replay=replay, request_id=request_id,
                              tenant=tenant, trace_id=trace_id)
        else:
            replay = {"suppress": deque(delivered), "rewind": True}
            req = self.submit(prompt, max_new_tokens, _replay=replay,
                              request_id=request_id, tenant=tenant,
                              trace_id=trace_id)
        self.resume_stats["requests"] += 1
        self._flight.record("resume_admit", rid=req.rid, delivered=k,
                            greedy=bool(self.sampling.greedy))
        return req

    def _import_staged(self, req: Request) -> None:
        """Land a premigrated request's staged blocks in the pool and
        adopt them into the tree — scheduler thread only, once, before
        the ordinary ``match``/alloc admission runs.  On pool pressure
        (alloc infeasible even with eviction) the request goes back to
        pending via :class:`_BlocksExhausted`, staged data intact."""
        st = getattr(req, "_staged", None)
        if st is None or st["imported"]:
            return
        mgr = self.kv_cache
        n = st["k"].shape[0]
        ids = mgr.alloc(n)
        if ids is None:
            req._pkv_blocked = (mgr.epoch, mgr.free_blocks)
            raise _BlocksExhausted()
        from .kvcache.device import adopt_blocks_into_pages
        bt = mgr.block_tokens
        _sig = _profiling.dispatch_signature(
            "disagg_adopt", batch=n, chunk=bt,
            kv_dtype=self.kv_cache.kv_dtype)
        _t0 = self._prof.begin(_sig)
        self._pk, self._pv = adopt_blocks_into_pages(
            self._pk, self._pv, jax.tree.map(jnp.asarray, st["k"]),
            jax.tree.map(jnp.asarray, st["v"]),
            jnp.asarray(np.asarray(ids, np.int32)))
        self._prof.end(_sig, _t0, out=(self._pk, self._pv),
                       hbm_bytes=n * bt * self._kv_bytes_per_token)
        adopted, lease = mgr.store_shared(req.prompt[:n * bt], ids)
        adopted_set = set(adopted)
        leftovers = [b for b in ids if b not in adopted_set]
        if leftovers:
            # another request's store covered some blocks first: the
            # redundant pages go straight back to the pool (the tree
            # kept the incumbent's copies)
            mgr.free(leftovers)
        if lease is not None:
            # adoption is complete and the pages are tree-owned; the
            # admission's own match() re-pins them on this same thread
            # before any other mutation can evict them
            lease.release()
        st["imported"] = True
        st["k"] = st["v"] = None       # staged host buffers are done
        self.disagg_stats["premigrated_requests"] += 1
        self.disagg_stats["adopted_pages"] += len(adopted)
        self._flight.record("disagg_engine_adopt", blocks=len(adopted),
                            prompt_len=len(req.prompt))

    # ------------------------------------------------------------------
    # live migration (docs/DESIGN.md §18): checkpoint a decoding row out
    # of this engine / adopt one into it

    def export_request(self, rid, *, detach: bool = False,
                       timeout: Optional[float] = 30.0) -> dict:
        """Snapshot everything a decoding row owns — used KV pages
        (verbatim, quantized pools included), emitted tokens/logprobs,
        the sampler rng key, valid length + last token, budget and
        kv_dtype tags — as a host-side checkpoint dict
        :meth:`import_request` resumes from.

        Runs ON the scheduler thread between steps (posted via a
        mailbox; the caller blocks up to ``timeout``), so the snapshot
        is step-consistent: no token is half-recorded and the page
        gather can't race a donated-pool dispatch.

        ``detach=True`` additionally removes the request from the
        engine — slot freed, pages released back to the pool — while
        leaving its ``stream`` OPEN and ``done`` unset: the caller now
        owns delivery (the migration relay feeds the stream from the
        target replica).  Detach is the atomic-handoff freeze point: the
        row decodes up to the step before the snapshot and never after
        it, so the target resuming AT the snapshot replays at most the
        in-flight step — never skips one.

        Speculative rows export at a VERIFY BOUNDARY (exports are
        serviced between dispatches, where no draft is in flight): the
        checkpoint carries per-row adaptive-K state (``spec_k`` +
        acceptance EWMA) but NOT the draft scratch pages or n-gram
        history — the importer rebuilds proposer state from
        prompt+tokens, which is cheap and exact (docs/DESIGN.md §22)."""
        require_kv_pair(self.cfg, "export_request (migration)")
        require_token_rows(self.cfg, "export_request (migration)")
        require_one_kind(self.cfg, "export_request (migration)")
        req = rid if isinstance(rid, Request) else self._by_rid.get(rid)
        if req is None:
            raise KeyError(f"unknown request id {rid!r}")
        box = {"req": req, "detach": detach, "ckpt": None, "err": None,
               "claimed": False, "abandoned": False,
               "event": threading.Event()}
        with self._submit_lock:
            if not self._running:
                raise RuntimeError("engine is closed")
            self._export_q.append(box)
            self._queue.put(_WAKE)
        if not box["event"].wait(timeout):
            # a scheduler stalled past the timeout (first-step jit
            # compile, pool-pressure wave) may still service this box
            # LATER — with detach=True that would orphan the request:
            # pages released, stream never fed, no caller left to own
            # delivery.  Abandon the box so a late service is a no-op;
            # if the scheduler claimed it in the race window the export
            # is executing right now, so wait the result out instead.
            with self._submit_lock:
                if not box["claimed"]:
                    box["abandoned"] = True
            if box["abandoned"]:
                raise TimeoutError(
                    "export_request timed out waiting for the "
                    "scheduler; the export was abandoned and the "
                    "request left untouched")
            box["event"].wait()
        if box["err"] is not None:
            raise box["err"]
        return box["ckpt"]

    def _service_exports(self) -> None:
        """Serve queued export_request mailboxes — scheduler thread,
        once per iteration, between steps.  The claimed/abandoned
        handshake (under ``_submit_lock``) makes a timed-out caller's
        box a no-op: servicing it anyway could detach a row nobody
        owns."""
        if self._export_q:
            # an export reads whether the request has ended, and a
            # detaching one hands its stream to the relay
            self._deliver()
        while self._export_q:
            box = self._export_q.popleft()
            with self._submit_lock:
                if box.get("abandoned"):
                    continue
                box["claimed"] = True
            try:
                box["ckpt"] = self._export_one(box["req"], box["detach"])
            except BaseException as e:
                box["err"] = e
            box["event"].set()

    def _export_one(self, req: Request, detach: bool) -> dict:
        if req.done.is_set():
            raise ValueError(f"request {req.rid!r} already finished")
        if req.cancelled:
            raise ValueError(f"request {req.rid!r} was cancelled")
        slot = next((i for i, r in enumerate(self._slots) if r is req),
                    None)
        mid_adm = ((self._adm is not None and self._adm["req"] is req)
                   or any(a["req"] is req for a in self._adms))
        if slot is None and mid_adm:
            raise ValueError(
                f"request {req.rid!r} is mid-chunked-admission; retry "
                "after its final prefill lands")
        ckpt = {"rid": req.rid,
                "prompt": np.asarray(req.prompt, np.int32),
                "max_new": int(req.max_new),
                "tokens": list(req.tokens), "lps": list(req.lps),
                "kv_dtype": self.kv_dtype,
                "block_tokens": int(self.kv_cache.block_tokens),
                "eos_id": self.eos_id,
                # observability identity rides the checkpoint so the
                # adopting replica's spans/accounting stay attributed
                "tenant": req.tenant, "trace_id": int(req.trace_id),
                "t_submit_wall": float(req.t_submit_wall),
                "migration_pause": float(req.migration_pause)}
        if slot is None:
            # still queued: a cold checkpoint (no pages, nothing
            # emitted) — the importer degrades it to a plain submit
            ckpt.update(length=0, last_tok=0, k=None, v=None, rng=None)
            n_used = 0
        else:
            # KV validity: prefill writes [0, plen) and samples token 1;
            # each decode step writes last_tok's KV at `lengths` then
            # increments — after T emitted tokens lengths = plen + T - 1
            # and KV [0, lengths) is valid.  The partial tail block
            # ships verbatim: its columns past `lengths` hold garbage
            # the stale-slot invariant already covers (decode rewrites
            # them before any query attends).
            length = int(np.asarray(self._lengths)[slot])
            last_tok = int(np.asarray(self._last_tok)[slot])
            bt = self.kv_cache.block_tokens
            n_used = -(-length // bt)
            ids = np.asarray(self._tables[slot][:n_used], np.int32)
            from .kvcache.device import export_blocks_from_pages
            k_run, v_run = export_blocks_from_pages(
                self._pk, self._pv, jnp.asarray(ids))
            ckpt.update(length=length, last_tok=last_tok,
                        k=jax.tree.map(np.asarray, k_run),
                        v=jax.tree.map(np.asarray, v_run),
                        rng=np.asarray(self._rng).copy())
            if self._spec_step is not None or self._pld_step is not None:
                # verify-boundary freeze (§22): adaptive-K state ships;
                # draft scratch / history do not (importer rebuilds)
                ckpt["spec_k"] = int(self._spec_krow[slot])
                ckpt["spec_ewma"] = float(self._spec_ewma[slot])
        self.migration_stats["exported_requests"] += 1
        if detach:
            if slot is not None:
                self._slots[slot] = None
                self._sentinel_slot(slot)
                self._release_request_kv(req)
            else:
                try:
                    self._pending.remove(req)
                except ValueError:
                    pass
            if req.rid is not None and self._by_rid.get(req.rid) is req:
                del self._by_rid[req.rid]
            req._detached = True
            # freeze point: the migration pause runs from here until the
            # first RELAYED token lands on the request's stream (the
            # relay's _on_tok closes it) — the timeline's pause field
            req.migrated = True
            req._pause_t0 = time.perf_counter()
            self.migration_stats["detached_requests"] += 1
        self._flight.record("migration_export", rid=req.rid,
                            tokens=len(req.tokens), blocks=n_used,
                            detach=detach)
        return ckpt

    def import_request(self, ckpt: dict,
                       request_id: Optional[str] = None) -> Request:
        """Adopt an :meth:`export_request` checkpoint: the shipped pages
        land in freshly allocated pool pages (one device scatter, the
        same ``adopt_blocks_into_pages`` join premigrated prefills use),
        whole-PROMPT blocks are adopted into the radix tree (pages
        holding generated tokens stay request-private — `page owned by
        tree xor request` holds verbatim), and decode resumes at the
        checkpointed length with NO prefill dispatch and zero dense-row
        h2d.  Restoring the rng key makes a single-request resume
        sample-exact; greedy streams are bit-identical regardless."""
        require_kv_pair(self.cfg, "import_request (migration)")
        require_token_rows(self.cfg, "import_request (migration)")
        require_one_kind(self.cfg, "import_request (migration)")
        rid = request_id if request_id is not None else ckpt.get("rid")
        if not ckpt.get("tokens") or int(ckpt.get("length") or 0) <= 0:
            # cold checkpoint: nothing decoded yet — plain admission
            # (still marked adopted: the source relay owns the client-
            # visible timeline even for a cold handoff)
            req = self.submit(ckpt["prompt"], ckpt["max_new"],
                              request_id=rid,
                              tenant=ckpt.get("tenant"),
                              trace_id=int(ckpt.get("trace_id") or 0))
            req.adopted = True
            return req
        if ckpt.get("kv_dtype", "bf16") != self.kv_dtype:
            raise ValueError(
                f"checkpoint kv_dtype {ckpt.get('kv_dtype')!r} does not "
                f"match this engine's {self.kv_dtype!r} pages")
        bt = self.kv_cache.block_tokens
        if int(ckpt.get("block_tokens", bt)) != bt:
            raise ValueError(
                f"checkpoint block_tokens {ckpt.get('block_tokens')} != "
                f"pool block_tokens {bt}")
        prompt = np.asarray(ckpt["prompt"], np.int32).reshape(-1)
        max_new = int(ckpt["max_new"])
        tokens = [int(t) for t in ckpt["tokens"]]
        if len(tokens) >= max_new:
            raise ValueError("checkpointed request has no budget left")
        check_capacity(self.max_seq, len(prompt), max_new)
        need = -(-(len(prompt) + max_new + self._slack_tokens) // bt)
        if need > self.kv_cache.num_blocks:
            raise ValueError(
                f"checkpoint needs {need} KV blocks but the pool holds "
                f"only {self.kv_cache.num_blocks}")
        length = int(ckpt["length"])
        if length != len(prompt) + len(tokens) - 1:
            raise ValueError(
                f"checkpoint length {length} != prompt {len(prompt)} + "
                f"emitted {len(tokens)} - 1")
        n_used = -(-length // bt)
        n_shipped = jax.tree.leaves(ckpt["k"])[0].shape[0]
        if n_shipped != n_used:
            raise ValueError(
                f"checkpoint ships {n_shipped} blocks; length "
                f"{length} needs {n_used}")
        req = Request(prompt=prompt, max_new=max_new,
                      t_submit=time.perf_counter(),
                      tenant=sanitize_tenant(ckpt.get("tenant")),
                      trace_id=int(ckpt.get("trace_id") or 0),
                      t_submit_wall=float(ckpt.get("t_submit_wall") or 0),
                      migration_pause=float(
                          ckpt.get("migration_pause") or 0),
                      migrated=True, adopted=True)
        req.rid = rid
        req.tokens = tokens
        req.lps = [float(x) for x in (ckpt.get("lps") or [])]
        req.t_first = time.perf_counter()
        req._resume = {"k": ckpt["k"], "v": ckpt["v"], "length": length,
                       "last_tok": int(ckpt["last_tok"]),
                       "rng": ckpt.get("rng"),
                       "spec_k": int(ckpt.get("spec_k") or 0),
                       "spec_ewma": float(ckpt.get("spec_ewma") or 0.0)}
        with self._submit_lock:
            if not self._running:
                raise RuntimeError("engine is closed")
            if rid is not None:
                self._by_rid[rid] = req
            self._queue.put(req)
        return req

    def get_request(self, rid: str) -> Optional[Request]:
        """The live Request registered under ``rid`` (None once it
        finished or was detached) — the migration relay grabs the handle
        BEFORE the detaching export removes the registration."""
        return self._by_rid.get(rid)

    def active_requests(self) -> list:
        """``[(rid, emitted, remaining)]`` for currently decoding slots
        — the migration controller's load view.  Racy read-only snapshot
        (any thread); rows mid-admission or queued are excluded."""
        out = []
        for r in list(self._slots):
            if r is not None and r.rid is not None and not r.cancelled:
                out.append((r.rid, len(r.tokens),
                            r.max_new - len(r.tokens)))
        return out

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 seed: int = 0, timeout: Optional[float] = None,
                 logprobs: bool = False, tenant: Optional[str] = None,
                 trace_id: int = 0, on_submit=None) -> GenerationResult:
        """Engine-surface convenience: submit each row as its own request
        (they batch with whatever else is in flight) and wait for all.
        ``seed`` is accepted for surface compatibility but not honored —
        see the module docstring.  On ``timeout`` the requests are
        cancelled (slots freed) before TimeoutError propagates.
        ``on_submit``: called with the rows' :class:`Request` objects
        once every row is admitted (the HTTP handler reads their
        ``t_submit`` there).

        ``logprobs=True`` additionally returns each emitted token's raw
        log-softmax probability (the engines' OpenAI-style convention) —
        plain slot decoding only; the speculative proposers' verify
        rounds do not score emitted tokens.  Rows that finished early
        pad logprobs with 0.0 alongside their eos-padded tokens."""
        if logprobs and (self._spec_step is not None
                         or self._pld_step is not None):
            raise ValueError(
                "logprobs are not supported with speculative slot "
                "decoding (draft or prompt-lookup proposers)")
        ids = np.asarray(prompt_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        t0 = time.perf_counter()
        reqs = self._submit_rows(ids, max_new_tokens, tenant=tenant,
                                 trace_id=trace_id, state_readout=logprobs)
        if on_submit is not None:
            on_submit(reqs)
        try:
            rows = [r.wait(timeout=timeout) for r in reqs]
        except TimeoutError:
            for r in reqs:
                r.cancel()
            raise
        width = max(len(r) for r in rows)
        pad_id = self.eos_id if self.eos_id is not None else 0
        toks = np.full((len(rows), width), pad_id, np.int32)
        lps = np.zeros((len(rows), width), np.float32) if logprobs else None
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
            if logprobs:
                lps[i, :len(r)] = reqs[i].lps
        # with the log-probabilities a model says what a check of them
        # cannot see: one with a recurrent state the state each sequence
        # ended in (docs/DESIGN.md section 27) and its log-probabilities
        # once more, for a check that holds them to a limit of the
        # model's own (section 29); one with several residual streams how
        # far its served maps stood from doubly stochastic (section 28:
        # the start-up reading, no device call here)
        said = None
        if logprobs and self._state_free is not None:
            said = [{f"{self.cfg.state_kind.attn}_state": r.state,
                     "logprobs": [float(v) for v in r.lps]} for r in reqs]
        elif logprobs and self.hc_stats is not None:
            said = [{"hc_sinkhorn_residual":
                     self.hc_stats["sinkhorn_residual_max"]}] * len(reqs)
        return GenerationResult(tokens=toks, prompt_len=ids.shape[1],
                                num_new=width,
                                seconds=time.perf_counter() - t0,
                                logprobs=lps, generation=said)

    _HC_PROBE_ROWS = 128

    def _hc_probe(self) -> None:
        """``/stats.hc.sinkhorn_residual_max``: the largest ``|row or
        column sum - 1|`` of the first block's attention map over
        ``_HC_PROBE_ROWS`` token rows (ids 1 .. 128), through the same
        ``hc_pre``, the same leaves and the same iteration count as the
        served blocks (the kernel on the chip): that the iterations ran,
        in a number.  One small program of its own, run ONCE, at the
        end of the warm-up: the maps never leave ``mixed_step``, so the
        timed programs are not what it reads, and no request pays for
        it.  A reply with
        log-probabilities repeats the number (``generate``) and the
        benchmark's reference check holds it."""
        from ..models.decoder import hc_sinkhorn_probe
        ids = (np.arange(1, 1 + self._HC_PROBE_ROWS, dtype=np.int32)
               % self.cfg.vocab_size)
        probe = jax.jit(partial(hc_sinkhorn_probe, cfg=self.cfg))
        self.hc_stats["sinkhorn_residual_max"] = float(
            probe(self.params, ids=jnp.asarray(ids)))

    def _submit_rows(self, ids: np.ndarray, max_new_tokens: int,
                     tenant: Optional[str] = None,
                     trace_id: int = 0, state_readout: bool = False) -> list:
        """Submit every row or none: if a later row is shed by the
        admission-depth gate, rows already admitted are cancelled before
        the SchedulerOverloaded propagates — a 503'd multi-row request
        must not leave orphan rows burning slots while the server sheds
        load."""
        reqs = []
        try:
            for row in ids:
                reqs.append(self.submit(row, max_new_tokens,
                                        tenant=tenant, trace_id=trace_id,
                                        state_readout=state_readout))
        except Exception:
            for r in reqs:
                r.cancel()
            raise
        return reqs

    def generate_stream(self, prompt_ids: np.ndarray, max_new_tokens: int,
                        seed: int = 0, timeout: Optional[float] = None,
                        tenant: Optional[str] = None, trace_id: int = 0,
                        resume: Optional[dict] = None, on_submit=None,
                        all_ready: bool = False):
        """Yield [batch] token arrays per step (HTTP streaming surface).
        Single-row streaming only batches trivially; multi-row prompts
        stream in lockstep of the slowest admitted row.  With
        ``all_ready`` it yields LISTS of those arrays instead: every
        step that is ready when the consumer comes for more (a hand-off
        of four tokens is one list of four; for a multi-row prompt the
        steps every unfinished row has), never an empty list, and it
        waits only when no step is ready: the consumer that writes the
        steps out can then write them in one piece.  Either way a row's
        stream is taken whole, one turn at its lock a hand-off
        (``TokenStream.get_all``).  An ABANDONED
        stream (client disconnect, or a stop-sequence early exit closing
        the generator) cancels its in-flight requests, freeing their
        slots after the current step instead of decoding to max_new.
        ``timeout``: overall wall-clock deadline — on expiry the
        requests are cancelled (slots freed) and TimeoutError raised,
        so a consumer with a deadline never blocks on a wedged
        scheduler (the --request-timeout contract).

        ``resume``: ``{"delivered_tokens": [...], "rng_step_offset":
        N}`` — gateway-failover resumption (docs/DESIGN.md §23,
        single-row only): the stream yields only the tokens AFTER the
        delivered prefix, which :meth:`submit_resumed` re-derives and
        verifies bit-exactly.

        ``on_submit``: called with the rows' :class:`Request` objects
        once every row is admitted, before the first token is waited
        for: the consumer that writes the stream out reads their
        ``t_submit`` and, as it goes, their ``handoffs``."""
        ids = np.asarray(prompt_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        deadline = None if not timeout else time.monotonic() + timeout
        if resume is not None:
            if ids.shape[0] != 1:
                raise ValueError("resume supports a single prompt row")
            delivered = resume.get("delivered_tokens")
            if not isinstance(delivered, (list, tuple)) or not delivered:
                raise ValueError(
                    "resume.delivered_tokens must be a non-empty list")
            off = resume.get("rng_step_offset", len(delivered))
            if int(off) != len(delivered):
                raise ValueError(
                    f"resume.rng_step_offset ({off}) must equal "
                    f"len(delivered_tokens) ({len(delivered)}) — the "
                    "rng schedule is derived from the delivered count")
            reqs = [self.submit_resumed(ids[0], max_new_tokens,
                                        delivered, tenant=tenant,
                                        trace_id=trace_id)]
        else:
            reqs = self._submit_rows(ids, max_new_tokens, tenant=tenant,
                                     trace_id=trace_id)
        fetched = [[] for _ in reqs]
        finished = [False] * len(reqs)   # row's None sentinel was consumed
        pad = self.eos_id if self.eos_id is not None else 0

        def take(i: int) -> None:
            """All that row ``i``'s stream holds, in one turn at its
            lock; waits, within the deadline, only if it holds nothing."""
            try:
                items = reqs[i].stream.get_all(
                    timeout=None if deadline is None else
                    max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise TimeoutError(
                    f"request deadline ({timeout}s) exceeded") from None
            if items[-1] is None:       # end sentinel: EOS, or failure
                finished[i] = True
                del items[-1]
            fetched[i].extend(items)

        try:
            if on_submit is not None:
                on_submit(reqs)
            step_i = 0
            while step_i < max_new_tokens:
                for i, r in enumerate(reqs):
                    # a row that lacks this step waits for its stream;
                    # one that has it takes what else is there
                    while not finished[i] and (len(fetched[i]) <= step_i
                                               or r.stream.queue):
                        take(i)
                # the steps every unfinished row has (the longest row's,
                # once all have ended: shorter rows are padded)
                have = [len(f) - step_i for f in fetched]
                live = [h for h, end in zip(have, finished) if not end]
                n = min(min(live) if live else max(have),
                        max_new_tokens - step_i)
                for r, h, end in zip(reqs, have, finished):
                    if end and r.error is not None:
                        # a scheduler/device failure must surface to the
                        # streaming consumer, not end the stream as a
                        # cleanly-truncated generation (siblings cancel
                        # in the finally below): behind the tokens the
                        # row had, which are yielded first
                        if h <= 0:
                            raise r.error
                        n = min(n, h)
                if n <= 0:
                    return
                steps = [np.asarray([f[s] if s < len(f) else pad
                                     for f in fetched], np.int32)
                         for s in range(step_i, step_i + n)]
                step_i += n
                if all_ready:
                    yield steps
                else:
                    yield from steps
        finally:
            for r in reqs:
                if not r.done.is_set():
                    r.cancel()

    def _pending_prefill_tokens(self) -> int:
        """Queued + mid-admission prompt tokens still awaiting prefill —
        the gateway's bounded-load router weighs this BACKLOG, not just
        request counts (one 10k-token prompt loads a replica far more
        than ten 30-token chats, docs/DESIGN.md §19).  Racy snapshot
        reads of scheduler-owned state: a gauge, not an invariant."""
        import copy as _copy
        total = 0
        # queue.Queue's underlying deque: __copy__ is atomic under the
        # GIL (same idiom as the latency reservoirs below); sentinels
        # (_WAKE, shutdown None) are filtered by the isinstance check
        for r in _copy.copy(self._queue.queue):
            if isinstance(r, Request):
                total += len(r.prompt)
        for r in _copy.copy(self._pending):
            total += len(r.prompt)
        adm = self._adm
        if adm is not None:
            total += max(0, len(adm["req"].prompt) - adm["start"])
        for a in list(self._adms):
            total += max(0, len(a["req"].prompt) - a["start"])
        return total

    def _spec_backlog_tokens(self) -> int:
        """Per-iteration speculative token cost of the ACTIVE rows —
        Σ (K_row + 1) · decode_block — the spec twin of the prefill
        backlog above: the gateway's bounded-load router weighs it so a
        replica mid-speculation (whose budget the spec rows are eating)
        stops looking as idle as a plain-decode one (§22).  Racy
        snapshot of scheduler-owned state: a gauge, not an invariant."""
        if self._spec_step is None and self._pld_step is None:
            return 0
        total = 0
        for i, r in enumerate(self._slots):
            if r is not None:
                k = (int(self._spec_krow[i]) if self.spec_adaptive
                     else int(self._spec_buckets[-1]))
                total += (k + 1) * self.decode_block
        return total

    def stats(self) -> dict:
        """Scheduler counters for the HTTP ``/stats`` surface."""
        import copy as _copy

        from .stats import _percentile
        out = {"slots": self.max_batch, "steps": self._step_count,
               # live occupancy for the /metrics gauges: submitted-but-
               # unslotted requests vs slots mid-decode (racy reads of
               # scheduler-owned state — gauges, not invariants)
               "queue_depth": self._queue.qsize() + len(self._pending),
               "pending_prefill_tokens": self._pending_prefill_tokens(),
               "active_slots": sum(1 for s in self._slots
                                   if s is not None)}
        if self.kv_cache is not None:
            out["kvcache"] = self.kv_cache.snapshot()
            if self._wmgr is not None:
                # by kind of block.  The keys above keep one meaning, the
                # full kind's pool (the one that fills); the window kind's
                # pool is its own entry, with what its window saved
                full = {k: out["kvcache"][k] for k in (
                    "blocks_used", "blocks_total", "bytes_per_token")}
                w = self._wmgr.snapshot()
                ws = self.window_stats
                out["kvcache"]["kinds"] = {
                    "full": dict(full, window=None),
                    "window": {
                        "window": self._window,
                        "blocks_used": w["blocks_used"],
                        "blocks_total": w["blocks_total"],
                        "bytes_per_token": w["bytes_per_token"],
                        "pages_held_peak": ws["pages_held_peak"],
                        "pages_returned": ws["pages_returned"],
                        "pages_unwindowed_peak":
                            ws["pages_unwindowed_peak"],
                        "quota_pages": self._window_quota}}
            if self._state_free is not None:
                # the state pool: a row a request whatever its length
                # (the last row, nobody's, is not counted), the rows held
                # now and at most, the first segments that started a row
                # from zero, and what the two ops advanced
                out["kvcache"].setdefault("kinds", {})["state"] = dict(
                    self.state_stats, slots=self.max_batch + 1,
                    bytes_per_slot=self.cfg.state_bytes_per_slot,
                    held=self._state_held())
            if self._eva is not None:
                # the two roles of row in the one pool: what closed and
                # what was pooled, and at the pool's fullest the rows it
                # held for the running requests beside their tokens
                out["kvcache"]["eva"] = dict(
                    self.eva_stats, window=self._eva.window,
                    chunk=self._eva.chunk,
                    window_pages=self._eva.window_pages)
        if self.cfg.sparse_kind is not None:
            # the sparse kind's queries by rule, and the blocks their
            # contexts held against the blocks their folds kept (a kv head
            # a sparse block; ``_sparse_account``): by the scheduler's
            # arithmetic, and ``device_*`` as the programs' selections
            # counted them (summed over kv heads and sparse blocks, so
            # divided by them here)
            kind = self.cfg.sparse_kind
            per = self.cfg.num_kv_heads * self.cfg.sparse_blocks
            dev = {k: v / per for k, v in self.sparse_stats.items()
                   if k.startswith("device_")}
            out["sparse"] = dict(
                self.sparse_stats, **dev,
                device_kept_a_sparse_query=(
                    dev["device_blocks_kept_sparse"]
                    / dev["device_queries_sparse"]
                    if dev["device_queries_sparse"] else None),
                block=kind.sparse_block,
                topk=kind.sparse_topk, dense_len=kind.sparse_dense_len,
                kept_at_most=(kind.sparse_init
                              + kind.sparse_local // kind.sparse_block
                              + kind.sparse_topk))
        # dispatch-floor picture (§13): dispatches vs device steps —
        # steps/dispatches ≈ decode_block when fusion is engaging
        out["device_loop"] = dict(self.loop_stats,
                                  decode_block=self.decode_block)
        if any(not k.mlp or k.attn == "none" for k in self.cfg.period):
            # a period of blocks of ONE sublayer: the blocks a kind (by
            # the name its stacks and paths carry) and the planes a pool;
            # a block without a mixer holds a plane of neither
            cfg = self.cfg
            out["blocks"] = {
                "kinds": {name: cfg.num_layers * len(at)
                          for name, _, at in cfg.kinds},
                "planes": {"pages": [planes for _, planes
                                     in self._pool_specs],
                           "state": cfg.state_planes},
                "with_experts": cfg.mlp_blocks}
        # kernel routing made visible: per compiled program, which
        # attention path each of its chunk shapes was traced onto
        out["attention_paths"] = self.attn_paths.snapshot()
        # and how the pool reached it: "stacked" (addressed in place in
        # the scan's carry) or "plane" (a layer's plane was copied out)
        out["pool_addressing"] = self.attn_paths.addressing()
        # and, over latent pages, the pages one fold iteration of the
        # compiled kernel takes (read off the call's shapes)
        out["fold_pages"] = self.attn_paths.fold_pages()
        # completed is the MONOTONIC count; the reservoirs are bounded
        # (the last 512 samples feed the percentiles).  deque.__copy__ is
        # atomic under the GIL — plain iteration would race the
        # scheduler thread's appends and raise "deque mutated".
        lat = {"completed": self._completed}
        for name, res in self._lat.items():
            xs = sorted(_copy.copy(res))   # one sort; _percentile's own
            if xs:                         # sort is then O(n) on sorted
                lat[f"{name}_p50_ms"] = round(_percentile(xs, 50) * 1e3, 3)
                lat[f"{name}_p95_ms"] = round(_percentile(xs, 95) * 1e3, 3)
        out["latency"] = lat
        if self.prefill_chunk is not None:
            cs = self.chunk_stats
            out["chunked_prefill"] = {
                "chunk": self.prefill_chunk,
                "chunks": cs["chunks"],
                "interleaved_steps": cs["interleaved_steps"]}
        if self.mixed_token_budget > 0:
            cs = self.chunk_stats
            out["mixed"] = {
                "token_budget": self.mixed_token_budget,
                "dispatches": cs["mixed_dispatches"],
                "prefill_tokens": cs["mixed_prefill_tokens"],
                # fraction of offered budget actually carried (prefill
                # segment tokens + fused decode tokens per dispatch)
                "budget_utilization": (
                    round(cs["mixed_packed_tokens"]
                          / cs["mixed_budget_tokens"], 4)
                    if cs["mixed_budget_tokens"] else None)}
            out["dispatch_trace"] = self.dispatch_trace.snapshot()
            if self.moe_counters is not None:
                # list(): the scheduler thread may be tracing a variant
                out["moe"] = dict(self.moe_counters.snapshot(),
                                  gmm=list(self.gmm_calls.values()))
            if self.loop_counters is not None:
                out["loop"] = self.loop_counters.snapshot()
        if self.hc_stats is not None:
            out["hc"] = dict(
                self.hc_stats, streams=self.cfg.hc_streams,
                sinkhorn_iters=self.cfg.hc_sinkhorn_iters)
        if self.disagg_stats["premigrated_requests"]:
            out["disagg"] = dict(self.disagg_stats)
        if self.resume_stats["requests"]:
            out["resumed"] = dict(self.resume_stats)
        if any(self.migration_stats.values()):
            out["migration"] = dict(self.migration_stats)
        # compile ledger (docs/DESIGN.md §20): the recompile_storm
        # detector below reads this fragment, and /stats readers get
        # the per-program compile picture for free
        compile_snap = _profiling.get_compile_tracker().snapshot()
        if compile_snap:
            out["compile"] = compile_snap
        if self._spec_step is not None or self._pld_step is not None:
            s = self.spec_stats
            # per-bucket occupancy of the ACTIVE rows' adaptive K_row —
            # the observable shrink signal (§22): a low-acceptance
            # workload walks mass toward bucket 1
            k_buckets = {
                str(b): int(sum(
                    1 for i, r in enumerate(self._slots)
                    if r is not None and int(self._spec_krow[i]) == b))
                for b in self._spec_buckets}
            out["speculative"] = {
                "proposer": ("prompt_lookup" if self._pld_step is not None
                             else "draft"),
                "num_draft": self.num_draft, "rounds": s["rounds"],
                "drafted": s["drafted"], "accepted": s["accepted"],
                "adaptive": bool(self.spec_adaptive),
                "k_row_buckets": k_buckets,
                "acceptance_rate": (round(s["accepted"] / s["drafted"], 4)
                                    if s["drafted"] else None)}
            out["spec_backlog_tokens"] = self._spec_backlog_tokens()
        # per-tenant SLO rollup (goodput + burn rates) rides the same
        # stats surface: the gateway's health prober stores it per
        # replica (the /debugz fleet summary) and the anomaly layer's
        # slo_burn detector consumes it below
        try:
            out["slo"] = get_slo_ledger().summary()
        except Exception:
            pass
        # anomaly watch rides every stats() reader as well as the
        # scheduler loop: an HTTP /metrics scrape runs on its OWN thread,
        # so the stalled-pipeline watchdog still observes (and fires)
        # when the scheduler thread itself is wedged inside a dispatch.
        # No recursion: the monitor's throttle window swallows the inner
        # observation its own stats() build would trigger.
        self.anomaly.observe(out)
        return out

    def health(self) -> dict:
        """``/health`` fragment: "ok" while the scheduler thread serves;
        once it has died (a failed dispatch drained every request with
        the error) the status says so and carries that error."""
        if self._scheduler_error is not None:
            return {"status": "scheduler_dead",
                    "error": self._scheduler_error}
        if not (self._running and self._thread.is_alive()):
            return {"status": "closed"}
        return {"status": "ok"}

    def debug_state(self) -> dict:
        """Backend fragment of ``GET /debugz``: anomaly-detector state
        (thresholds, streaks, recent firings, bundles written) + the KV
        cache picture (occupancy, LRU leaves, leased nodes)."""
        out = {"anomaly": self.anomaly.state(),
               "observatory": _profiling.observatory_state()}
        if self.kv_cache is not None:
            out["kvcache"] = self.kv_cache.debug_state()
        if self.disagg_stats["premigrated_requests"]:
            out["disagg"] = dict(self.disagg_stats)
        if any(self.migration_stats.values()):
            out["migration"] = dict(self.migration_stats)
        return out

    def reset_stats(self) -> None:
        self._step_count = 0
        self.loop_stats = {"host_dispatches": 0, "device_loop_steps": 0}
        if self.kv_cache is not None:
            self.kv_cache.reset_stats()
        self.spec_stats = {"rounds": 0, "drafted": 0, "accepted": 0}
        self._reset_chunk_stats()
        self.dispatch_trace.reset()
        if self.moe_counters is not None:
            self.moe_counters.reset()
        if self.loop_counters is not None:
            self.loop_counters.reset()
        self._completed = 0
        for res in self._lat.values():
            res.clear()

    def _reset_chunk_stats(self) -> None:
        """ONE owner of the chunk/mixed counter shape — __init__ and
        reset_stats both call it, so the two sites cannot drift.
        ``mixed_packed_tokens`` counts prefill + decode tokens a mixed
        dispatch actually carried; ``mixed_budget_tokens`` the budget it
        was offered — their ratio is the budget-utilization gauge."""
        self.chunk_stats = {"chunks": 0, "interleaved_steps": 0,
                            "mixed_dispatches": 0,
                            "mixed_prefill_tokens": 0,
                            "mixed_packed_tokens": 0,
                            "mixed_budget_tokens": 0}

    def close(self):
        self._running = False
        self._queue.put(None)              # wake the scheduler
        self._thread.join(timeout=30)
        self.dispatch_trace.close()
        # the tier dies with its pool: demoted entries reference a page
        # layout the successor engine may not share, and the host ring /
        # mmap'd segment must not outlive the engine that budgeted them
        if self._kv_tier is not None:
            self._kv_tier.close()
        # reset-on-close: this engine's pool owners leave the process
        # watermark ledger (a successor engine's pools start a fresh
        # high-water history; other engines' owners are untouched)
        for owner in self._hbm_owners:
            self._hbm.reset(owner)
        self._hbm_owners.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    # scheduler

    def _bucket(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        return self.max_seq

    def _reserve_pages(self, req: Request) -> int:
        """Paged admission, phase 1: reserve the request's pages and
        build its block table; returns the matched-prefix length m.

        - ``match`` returns page IDS for the matched prefix (pinned by a
          lease held until the request completes: the slot's table will
          reference those shared pages for its whole lifetime);
        - the private remainder — enough pages for prompt + max_new (+
          the speculative modes' fused-overshoot slack) — is allocated
          up front (LRU tree leaves evict under pressure), so decode can
          never run out of pages mid-flight; the draft pool (speculative
          mode) reserves the same span of scratch pages atomically with
          the target's; if even eviction cannot free enough,
          :class:`_BlocksExhausted` sends the request back to pending
          (a completion will free pages);
        - prefill then runs THROUGH the table (paged_prefill /
          _paged_chunk_mid / the mixed slab): a prefix hit reads the
          shared pages in place and writes start at the private
          frontier — zero bytes through the host,
          ``dwt_kvcache_h2d_bytes`` stays 0 on this path by
          construction."""
        mgr = self.kv_cache
        bt = mgr.block_tokens
        plen = len(req.prompt)
        n_total, n_summary = self._pages_needed(
            plen + req.max_new + self._slack_tokens)
        n_total += n_summary
        # retry gate for a previously blocked admission: only re-attempt
        # once the pool could have changed (a completion frees at least
        # one private page — n_total strictly exceeds the adoptable
        # full-prompt blocks — and stores/evictions bump the epoch).
        # Without this, every scheduler iteration would re-run match(),
        # inflating hit/miss/reuse counters and flooding the flight ring
        # with phantom lookups while the request just waits.
        state = (mgr.epoch, mgr.free_blocks)
        if getattr(req, "_pkv_blocked", None) == state:
            raise _BlocksExhausted()
        # disaggregated join: land migrated blocks + adopt BEFORE the
        # match below, which then finds them as an ordinary prefix hit
        self._import_staged(req)
        # tier promotion (docs/DESIGN.md §21) rides the same seam: a
        # demoted continuation of the prompt's device-covered prefix
        # adopts back into the pool here, so the match below finds it
        # as an ordinary hit.  Best-effort: pool pressure skips it and
        # the suffix prefills (never _BlocksExhausted — a cold prefill
        # beats waiting on a warm one).
        if self._kv_tier is not None:
            from .kvcache import promote_prefix
            self._pk, self._pv, _ = promote_prefix(
                mgr, self._kv_tier, self._pk, self._pv, req.prompt,
                profiler=self._prof)
        # prefix sharing is OFF for a model with a window kind: a shared
        # prefix's window pages are gone by the time it could be hit; and
        # for a summarised cache, whose window pages the next window
        # writes over
        # ... and for a model with a recurrent state: a prefix's state is
        # not a block of tokens the tree could hold
        lease = (mgr.match(req.prompt)
                 if self._wmgr is None and self._eva is None
                 and self._state_free is None else None)
        m = lease.tokens if lease is not None else 0
        n_pref = m // bt
        if (self._wmgr is not None and self._window_reserved
                + self._window_quota > self._wmgr.num_blocks):
            # the page gate by kind: no quota of window pages is left
            # (a completion returns one, and frees full-kind pages too)
            req._pkv_blocked = (mgr.epoch, mgr.free_blocks)
            raise _BlocksExhausted()
        if self._state_free is not None and not self._state_free:
            # no row of the state pool is free (a completion returns one,
            # and pages with it)
            req._pkv_blocked = (mgr.epoch, mgr.free_blocks)
            raise _BlocksExhausted()
        private = mgr.alloc(n_total - n_pref)
        if private is None:
            if lease is not None:
                lease.release()
            req._pkv_blocked = (mgr.epoch, mgr.free_blocks)
            raise _BlocksExhausted()
        dprivate = None
        if self._dmgr is not None:
            # draft scratch pages, reserved atomically with the
            # target's: a half-reserved admission must not wedge pages
            # while it waits for the other pool
            dprivate = self._dmgr.alloc(n_total)
            if dprivate is None:
                mgr.free(private)
                if lease is not None:
                    lease.release()
                req._pkv_blocked = (mgr.epoch, mgr.free_blocks)
                raise _BlocksExhausted()
        req._pkv_blocked = None
        table = np.full((self._table_cols,), self._page_sentinel,
                        np.int32)
        if lease is not None:
            table[:n_pref] = lease.block_ids
        if self._eva is not None:
            # a summary page a window, the pending one included, then the
            # window's pages, behind the summary columns
            table[:n_summary] = private[:n_summary]
            at = self._eva.summary_pages
            table[at:at + n_total - n_summary] = private[n_summary:]
        else:
            table[n_pref:n_total] = private
        if self._wmgr is not None:
            self._window_reserved += self._window_quota
        state_row = None
        if self._state_free is not None:
            state_row = table[-1] = self._state_free.pop(0)
            st = self.state_stats
            st["held_peak"] = max(st["held_peak"], self._state_held())
        dtable = None
        if dprivate is not None:
            dtable = np.full((self._table_width,), self._dpage_sentinel,
                             np.int32)
            dtable[:n_total] = dprivate
        req._pkv = {"lease": lease, "store_lease": None,
                    "private": private, "adopted": (), "n_pref": n_pref,
                    "table": table, "dprivate": dprivate,
                    "dtable": dtable, "released": False,
                    # the window kind's pages by block of the table, and
                    # the first block it may still read
                    "wpages": {}, "wfirst": 0,
                    # its row of the state pool (None: the model has none)
                    "state_row": state_row}
        # workload sketch: prefix-hit share = matched / prompt tokens,
        # recorded once per SUCCESSFUL reservation (a _BlocksExhausted
        # retry re-runs match and must not double-count)
        self._sketch.record_prefix(m, plen)
        return m

    def _pages_needed(self, n: int) -> tuple:
        """``(pages of tokens, summary pages)`` a request of ``n`` tokens
        leases at admission: a page a block of tokens; under a summarised
        cache one window's pages at most and a summary page a window
        (``min(Pn, ceil(n / bt)) + ceil(n / W)``)."""
        bt = self.kv_cache.block_tokens
        if self._eva is None:
            return -(-n // bt), 0
        return (min(self._eva.window_pages, -(-n // bt)),
                -(-n // self._eva.window))

    def _release_request_kv(self, req: Request) -> None:
        """Return a paged request's KV resources: release its pins
        (matched prefix + stored path), free the private pages the
        tree did not adopt, and free the draft pool's scratch pages
        (never adopted by anything).  Idempotent — completion, cancel,
        failure, and the shutdown drain all funnel here."""
        st = getattr(req, "_pkv", None)
        if st is None or st["released"]:
            return
        st["released"] = True
        if st["lease"] is not None:
            st["lease"].release()
        if st["store_lease"] is not None:
            st["store_lease"].release()
        adopted = set(st["adopted"])
        self.kv_cache.free([b for b in st["private"]
                            if b not in adopted])
        if st["dprivate"] is not None:
            self._dmgr.free(st["dprivate"])
        if self._wmgr is not None:
            self._wmgr.free(list(st["wpages"].values()))
            st["wpages"].clear()
            self._window_reserved -= self._window_quota
        if st.get("state_row") is not None:
            # the row goes back as it is: the next request's first
            # segment starts from zero whatever the row holds
            self._state_free.append(st["state_row"])

    def _state_held(self) -> int:
        """Rows of the state pool that requests hold."""
        return self.max_batch + 1 - len(self._state_free)

    def _state_sample(self, row: int) -> dict:
        """A sample of row ``row`` of the state pool as it stands: of every
        plane, four heads' every eighth key with all its values, the
        pool's own numbers widened to float32 (little-endian, base64) and
        the pool's dtype by name.  The request that held the row has just
        completed: nothing has moved its state since its last token but
        one was absorbed, and no dispatch in flight writes it (it holds no
        token there).  The read waits for that dispatch."""
        pool, _ = self.cfg.state_arrays(self._pk, self._pv)
        hs, ks = max(1, pool.shape[2] // 4), 8
        got = np.asarray(_state_rows(pool, jnp.int32(row), hs, ks))
        return {"pool_dtype": str(pool.dtype),
                "heads": list(range(0, pool.shape[2], hs)),
                "keys": list(range(0, pool.shape[3], ks)),
                "shape": list(got.shape),
                "float32_b64": base64.b64encode(
                    got.astype("<f4").tobytes()).decode("ascii")}

    def _sparse_account(self, plan, steps: int, device) -> dict:
        """The record's ``SPARSE_DISPATCH_FIELDS`` of a returned dispatch
        and ``/stats.sparse``'s sums: what the sparse kind's selections
        scored and its folds kept for the slab's prompt tokens and the
        decoding rows' steps inside their budgets (a final's after its
        token #1), a kv head a sparse block, from the queries' positions
        alone (the least any program does for them: the kernels' roofline
        readers count it); and ``device``, the dispatch's own counters
        (``ops.sparse_attention.kept_counts`` summed over its layer calls:
        the pairs that selected, the blocks those kept, the blocks all
        kept), which say what the program DID keep."""
        from ..ops.sparse_attention import blocks_kept
        sizes = self.cfg.sparse_kind.sparse_sizes

        def of(t):
            live, kept, rows = blocks_kept(t, sizes)
            dense = int((t < sizes[6]).sum())
            return (int(live.sum()), int(kept.sum()), int(rows.sum()),
                    dense, len(t) - dense)

        ntok, starts = plan.seg[7], plan.seg[2]
        slab = [np.arange(int(starts[r0]), int(starts[r0]) + int(ntok[r0]))
                for (r0, _, _, _) in plan.packed]
        # a slot's steps inside its budget (as the state's ``row_steps``),
        # from the position its next token is fed at
        took = np.maximum(np.minimum(plan.budget_vec,
                                     steps - plan.first_col), 0)
        at = [(slot, len(s[0].prompt) + s[1] - 1)
              for slot, s in enumerate(plan.rows) if s is not None]
        at += [(slot, len(req.prompt)) for req, slot in plan.finals]
        rows = [np.arange(t, t + int(took[slot])) for slot, t in at]
        zero = np.zeros((0,), np.int64)
        a = of(np.concatenate(slab or [zero]))
        b = of(np.concatenate(rows or [zero]))
        st = self.sparse_stats
        for key, i in (("blocks_live", 0), ("blocks_kept", 1),
                       ("index_rows", 2), ("queries_dense", 3),
                       ("queries_sparse", 4)):
            st[key] += a[i] + b[i]
        for key, n in zip(("device_queries_sparse",
                           "device_blocks_kept_sparse",
                           "device_blocks_kept"), device):
            st[key] += int(n)
        per = self.cfg.num_kv_heads * self.cfg.sparse_blocks
        return dict(zip(SPARSE_DISPATCH_FIELDS,
                        (a[0] + b[0], a[1] + b[1], a[2] + b[2], b[1], b[2],
                         int(device[2]) / per)))

    def _tiles_a_pool(self) -> tuple:
        """``(tile tokens, window)`` a pool, the full (or only) kind's
        first: the query tiles its prefill kernel cuts a segment of
        ``prefill_chunk`` tokens into.  A period model's kinds cut a
        chunk into sub-chunks (``sub_chunk``: the first kind of each
        window stands for its pool), the latent kernel into tiles of its
        own, and every other model's chunk is one tile."""
        cfg, C = self.cfg, self.prefill_chunk
        if cfg.latent_kv:
            return ((latent_tile_tokens(C, cfg.num_heads), 0),)
        if not cfg.period:
            return ((C, 0),)
        kinds = ([cfg.lead_kind] if cfg.lead_kind is not None else []
                 ) + [k for k in cfg.period if k.has_pages]
        return tuple(
            (sub_chunk(C, next(k for k in kinds if k.window == window)
                       .num_heads // cfg.num_kv_heads), window)
            for window, _ in self._pool_specs)

    def _window_hold(self, req: Request, lo: int, hi: int) -> None:
        """The window kind's pages for the tokens ``[lo, hi)`` the next
        dispatch writes for ``req``: a page a block of the table not held
        yet, into the request's table row (its window columns).  Inside
        the request's quota, so the pool has them.  Called when a
        dispatch is PACKED: a plan that is never launched leaves the
        request pages it will need next."""
        st, bt, W = req._pkv, self.kv_cache.block_tokens, self._table_width
        need = [j for j in range(lo // bt, min(W, -(-hi // bt)))
                if j not in st["wpages"] and j >= st["wfirst"]]
        if not need:
            return
        pages = self._wmgr.alloc(len(need))
        assert pages is not None, "window pages are inside a quota"
        for j, page in zip(need, pages):
            st["wpages"][j] = page
            st["table"][W + j] = page
        ws = self.window_stats
        ws["pages_held_peak"] = max(ws["pages_held_peak"],
                                    self._wmgr.used_blocks)

    def _window_release(self, req: Request, lo: int) -> None:
        """Give back the window kind's pages that no query at ``lo`` or
        later can see (blocks wholly before token ``lo - window + 1``).
        Called when the dispatch whose first write for ``req`` is ``lo``
        is LAUNCHED: every earlier dispatch has been enqueued, the device
        runs them in order, and a page given back here is written again
        by a later dispatch at the earliest."""
        st, bt, W = req._pkv, self.kv_cache.block_tokens, self._table_width
        first = max(0, lo - self._window + 1) // bt
        gone = [j for j in st["wpages"] if j < first]
        if gone:
            self._wmgr.free([st["wpages"].pop(j) for j in gone])
            st["table"][[W + j for j in gone]] = self._page_sentinel
            self.window_stats["pages_returned"] += len(gone)
        st["wfirst"] = max(st["wfirst"], first)

    def _needs_stream(self, req: Request) -> bool:
        """Does this prompt need the one-at-a-time chunk stream, or can
        it admit in a single dispatch?  Classified by the EFFECTIVE
        suffix (a KV-cache hit may shrink a long prompt to one
        dispatch — it must not wait behind an unrelated stream).  Pure
        peek: hit/miss accounting stays with ``_reserve_pages``.

        The decision is memoized on the request (``_stream_cls``),
        validated against the manager's mutation epoch: a blocked
        request is NOT rescanned every scheduler iteration, but a
        store/eviction invalidates the memo — a classification must
        never outlive the cache content it relied on (an evicted prefix
        would otherwise send a long prompt down the one-dispatch path,
        voiding the chunked activation-memory bound; evictions only
        happen inside stores, which bump the epoch)."""
        C = self.prefill_chunk
        if C is None:
            return False
        if getattr(req, "_resume", None) is not None:
            # a live-migration resume never prefills: its checkpoint IS
            # the row state, one adopt scatter regardless of prompt size
            return False
        st = getattr(req, "_staged", None)
        if st is not None and not st["imported"]:
            # premigrated join: the effective suffix after the adopt is
            # at most prompt - n_blocks*bt tokens regardless of what the
            # tree holds right now (the import lands before admission);
            # once imported, the normal peek below sees the adopted
            # prefix in the tree and classifies the same way
            return (len(req.prompt)
                    - st["k"].shape[0] * self.kv_cache.block_tokens) > C
        epoch = self.kv_cache.epoch if self.kv_cache is not None else 0
        cls = getattr(req, "_stream_cls", None)
        if cls is not None and cls[0] == epoch:
            return cls[1]
        needs = len(req.prompt) > C
        if needs and self.kv_cache is not None:
            m = self.kv_cache.peek(req.prompt)
            if m and len(req.prompt) - m <= C:
                needs = False
        req._stream_cls = (epoch, needs)
        return needs

    def _admit_request(self, slot: int, req: Request):
        # scheduler pickup: everything before this is queue wait,
        # everything from here to the first token is prefill (the
        # timeline ledger's TTFT decomposition)
        if req.t_sched == 0.0:
            req.t_sched = time.perf_counter()
        if getattr(req, "_resume", None) is not None:
            self._admit_resume(slot, req)
            return
        start = self._reserve_pages(req)
        self._finish_admission(slot, req, start, req.prompt[start:])

    def _admit_resume(self, slot: int, req: Request) -> None:
        """Adopt a live-migration checkpoint into a free slot (docs/
        DESIGN.md §18): scatter the shipped blocks into freshly
        allocated pages, adopt the whole-PROMPT blocks into the radix
        tree (pages holding generated tokens stay request-private), and
        install the slot state at the checkpointed length/last-token —
        no prefill dispatch, decode resumes exactly where the source
        froze.  Restoring the rng key hands over the sampler state (the
        pre-split order makes the key the whole of it)."""
        rs = req._resume
        mgr = self.kv_cache
        bt = mgr.block_tokens
        plen = len(req.prompt)
        n_total = -(-(plen + req.max_new + self._slack_tokens) // bt)
        # same pool-pressure retry gate as _reserve_pages
        state = (mgr.epoch, mgr.free_blocks)
        if getattr(req, "_pkv_blocked", None) == state:
            raise _BlocksExhausted()
        ids = mgr.alloc(n_total)
        if ids is None:
            req._pkv_blocked = state
            raise _BlocksExhausted()
        dids = None
        if self._dmgr is not None:
            # draft scratch, atomically with the target's pages (same
            # rule as _reserve_pages): the checkpoint does NOT ship
            # draft KV — it is rebuilt below from prompt + tokens
            dids = self._dmgr.alloc(n_total)
            if dids is None:
                mgr.free(ids)
                req._pkv_blocked = state
                raise _BlocksExhausted()
        req._pkv_blocked = None
        length = rs["length"]
        n_used = -(-length // bt)
        from .kvcache.device import adopt_blocks_into_pages
        self._pk, self._pv = adopt_blocks_into_pages(
            self._pk, self._pv, jax.tree.map(jnp.asarray, rs["k"]),
            jax.tree.map(jnp.asarray, rs["v"]),
            jnp.asarray(np.asarray(ids[:n_used], np.int32)))
        adopted, store_lease = (), None
        if plen // bt >= 1:
            adopted, store_lease = mgr.store_shared(
                req.prompt, ids[:plen // bt])
        table = np.full((self._table_width,), self._page_sentinel,
                        np.int32)
        table[:n_total] = ids
        dtable = None
        if dids is not None:
            dtable = np.full((self._table_width,), self._dpage_sentinel,
                             np.int32)
            dtable[:n_total] = dids
        req._pkv = {"lease": None, "store_lease": store_lease,
                    "private": ids, "adopted": tuple(adopted),
                    "n_pref": 0, "table": table, "dprivate": dids,
                    "dtable": dtable, "released": False}
        self._tables[slot] = table
        self._lengths, self._last_tok = self._set_slot_state(
            self._lengths, self._last_tok, jnp.int32(slot),
            jnp.int32(length), jnp.int32(rs["last_tok"]))
        if rs.get("rng") is not None:
            self._rng = jnp.asarray(np.asarray(rs["rng"]))
        if self._spec_step is not None or self._pld_step is not None:
            # §22 verify-boundary resume: the proposers' state is NOT in
            # the checkpoint — rebuild it exactly from prompt + emitted
            # tokens (KV [0, length) = prompt + tokens[:-1]; tokens[-1]
            # is last_tok, whose KV the next round's verify writes)
            hist = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.tokens[:-1], np.int32)])
            if self._spec_step is not None:
                dbucket = self._bucket(length)
                dpad = np.zeros((1, dbucket), np.int32)
                dpad[0, :length] = hist
                drow_k, drow_v = self._dprefill(
                    self.draft_params, jnp.asarray(dpad),
                    *self._zero_row_d())
                self._dpk, self._dpv = self._write_row(
                    self._dpk, self._dpv, drow_k, drow_v,
                    jnp.asarray(dtable))
                self._dtables[slot] = dtable
            if self._pld_step is not None:
                hpad = np.zeros((1, self._bucket(length)), np.int32)
                hpad[0, :length] = hist
                self._history = self._admit_h(
                    self._history, jnp.asarray(hpad), jnp.int32(slot),
                    jnp.int32(length), jnp.int32(rs["last_tok"]))
            k = int(rs.get("spec_k") or 0)
            self._spec_krow[slot] = next(
                (b for b in self._spec_buckets if b >= k),
                self._spec_buckets[-1]) if k > 0 \
                else self._spec_buckets[-1]
            self._spec_ewma[slot] = (float(rs.get("spec_ewma") or 0.0)
                                     or 1.0)
        self._slots[slot] = req
        req._resume = None          # staged host buffers are done
        self.migration_stats["imported_requests"] += 1
        self._flight.record("migration_import", slot=slot, rid=req.rid,
                            length=length, tokens=len(req.tokens),
                            blocks=n_used)

    def _start_admission(self, req: Request) -> bool:
        """Park a chunk-needing prompt as the in-progress admission the
        scheduler advances one dispatch per iteration (chunked admission
        is resumable state, NOT an inline loop: between dispatches the
        loop keeps decoding in-flight rows AND admitting other queued
        requests into free slots, so a long prompt head-blocks
        neither).  Returns False when the paged pool cannot reserve the
        request's pages yet — the caller requeues it (everything else,
        including failure, is handled here)."""
        try:
            start = self._reserve_pages(req)
        except _BlocksExhausted:
            return False
        except BaseException as e:
            self._fail_request(req, e)
            return True
        self._adm = {"req": req, "start": start, "m": start,
                     "suffix": req.prompt[start:]}
        return True

    def _advance_admission(self, free: list) -> None:
        """One dispatch of the in-progress admission: the next C-token
        chunk through the logits-free mid-chunk program, or — once the
        remainder fits one dispatch — the sampling final prefill into a
        free slot (parked until one frees).  Intermediate chunks are
        always full, so the next chunk overwrites the previous
        dispatch's padded tail exactly (stale-slot invariant)."""
        a = self._adm
        if a is None:
            return
        req, C = a["req"], self.prefill_chunk
        if req.cancelled:
            # bound cancel latency to one chunk, same property the
            # interleaving gives decode
            self._adm = None
            self._fail_request(req, None)
            return
        if len(a["suffix"]) > C:
            try:
                head = jnp.asarray(
                    np.asarray(a["suffix"][:C], np.int32)[None])
                _sig = _profiling.dispatch_signature(
                    "paged_chunk_mid", batch=1, chunk=C,
                    kv_dtype=self.kv_cache.kv_dtype)
                _t0 = self._prof.begin(_sig)
                self._pk, self._pv = self._paged_chunk_mid(
                    self.params, self._pk, self._pv, head,
                    jnp.asarray(req._pkv["table"][None]),
                    jnp.int32(a["start"]))
                self._prof.end(_sig, _t0, out=self._pk,
                               hbm_bytes=C * self._kv_bytes_per_token)
            except BaseException as e:
                # a per-request failure fails that request, never the
                # engine — same contract as every other admission
                # dispatch ("surface to the waiter")
                self._adm = None
                self._fail_request(req, e)
                return
            a["start"] += C
            a["suffix"] = a["suffix"][C:]
            self.chunk_stats["chunks"] += 1
        elif free:
            self._adm = None
            try:
                self._finish_admission(free.pop(0), req, a["start"],
                                       a["suffix"], prefix_reused=a["m"])
            except BaseException as e:
                self._fail_request(req, e)

    def _finish_admission(self, slot: int, req: Request, start: int,
                          suffix, prefix_reused: Optional[int] = None
                          ) -> None:
        """The sampling final prefill + slot install, shared by one-shot
        admission and the last dispatch of a chunked one.  The prefill
        runs THROUGH the request's block table straight into its
        reserved pages (no temp row, no scatter round trip); writes
        begin at ``start``, at/past the matched-prefix frontier, so the
        tree-owned shared pages are read-only by construction
        (prepare_kv_chunk's write contract)."""
        plen = len(req.prompt)
        st = req._pkv
        bucket = self._bucket(len(suffix))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(suffix)] = suffix
        if getattr(req, "_rng_rewind", False):
            # §23 sampled resume: rewind the engine stream to the seed
            # key immediately before this request's token-#1 split, so
            # the replayed split schedule (this admission split, then
            # one decode split per dispatch) re-derives the delivered
            # tokens bit-exactly; the suppress queue verifies each one
            self._rng = jax.random.PRNGKey(self._seed)
            req._rng_rewind = False
        self._rng, sub = jax.random.split(self._rng)
        _sig = _profiling.dispatch_signature(
            "paged_prefill", batch=1, chunk=bucket,
            kv_dtype=self.kv_cache.kv_dtype)
        _t0 = self._prof.begin(_sig)
        self._pk, self._pv, tok, lp0 = self._paged_prefill(
            self.params, self._pk, self._pv, jnp.asarray(padded),
            jnp.asarray(st["table"][None]), jnp.int32(start),
            jnp.int32(len(suffix)), sub)
        self._prof.end(_sig, _t0, out=tok,
                       hbm_bytes=len(suffix) * self._kv_bytes_per_token)
        # store at PREFILL time, by ADOPTION: the tree takes
        # ownership of the full-prompt pages it was missing — the
        # next shared-prefix request block-table-references the
        # very same pages this one decodes against
        if plen // self.kv_cache.block_tokens >= 1:
            adopted, store_lease = self.kv_cache.store_shared(
                req.prompt,
                st["table"][:plen // self.kv_cache.block_tokens])
            st["adopted"] = adopted
            st["store_lease"] = store_lease
        self._tables[slot] = st["table"]
        self._lengths, self._last_tok = self._set_slot_state(
            self._lengths, self._last_tok, jnp.int32(slot),
            jnp.int32(plen), tok.astype(jnp.int32))
        if self._spec_step is not None:
            # draft-side pages: always the FULL prompt (prefix reuse
            # applies to the target cache only; the draft is cheap) —
            # prefilled into a dense temp row, scattered into the
            # request's reserved draft scratch pages, zero D2H
            dbucket = self._bucket(plen)
            dpad = np.zeros((1, dbucket), np.int32)
            dpad[0, :plen] = req.prompt
            drow_k, drow_v = self._dprefill(
                self.draft_params, jnp.asarray(dpad), *self._zero_row_d())
            self._dpk, self._dpv = self._write_row(
                self._dpk, self._dpv, drow_k, drow_v,
                jnp.asarray(st["dtable"]))
            self._dtables[slot] = st["dtable"]
        if self._pld_step is not None:
            # seed the slot's n-gram history: full prompt + first token
            hpad = np.zeros((1, self._bucket(plen)), np.int32)
            hpad[0, :plen] = req.prompt
            self._history = self._admit_h(
                self._history, jnp.asarray(hpad), jnp.int32(slot),
                jnp.int32(plen), tok.astype(jnp.int32))
        # fresh acceptor starts at the widest bucket (adaptive K re-learns
        # from this row's own acceptance; §22)
        self._spec_krow[slot] = self._spec_buckets[-1]
        self._spec_ewma[slot] = 1.0
        self._slots[slot] = req
        self._flight.record("batch_admit", slot=slot, prompt_len=plen,
                            max_new=req.max_new,
                            prefix_reused=(start if prefix_reused is None
                                           else prefix_reused))
        # lps stay empty (not a stale 1-entry list) in the speculative
        # modes, whose drains never score emitted tokens
        plain = self._spec_step is None and self._pld_step is None
        self._record_token(slot, req, int(tok),
                           float(lp0) if plain else None)

    def _record_row_blocks(self, em_np, counts, lps_np=None,
                           first=None) -> None:
        """Record per-row emitted token blocks into the slots' requests
        (columns ``first[i]``, 0 by default, to ``counts[i]`` of row i),
        stopping a row the moment it finishes (max_new/eos frees the
        slot mid-block — the stale-slot guard shared by the speculative
        rounds and the fused decode-block path).  ``lps_np``: matching
        per-token logprobs (plain mode; the speculative drains pass
        none)."""
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            for j in range(0 if first is None else int(first[i]),
                           int(counts[i])):
                if self._slots[i] is None:
                    break              # row hit max_new or eos mid-block
                self._record_token(
                    i, req, int(em_np[i, j]),
                    None if lps_np is None else float(lps_np[i, j]))

    def _drain_spec_blocks(self, em_np, ns_np, k_vec=None) -> None:
        """Record one speculative round's per-row emitted blocks +
        acceptance stats — shared by the draft-model and prompt-lookup
        step branches.  Both counters come from the slots still OCCUPIED
        at drain time, so rounds after a row finished mid-block (fused
        decode_block) inflate neither drafted nor accepted.  ``k_vec``
        ([B] or None): the mixed dispatch's per-row draft widths —
        adaptive K prices drafted by what each row actually offered."""
        self._step_count += 1
        self.spec_stats["rounds"] += 1
        live = [i for i, r in enumerate(self._slots) if r is not None]
        self.spec_stats["drafted"] += (
            self.num_draft * len(live) if k_vec is None
            else int(sum(int(k_vec[i]) for i in live)))
        self.spec_stats["accepted"] += int(
            sum(int(ns_np[i]) - 1 for i in live))
        self._record_row_blocks(em_np, ns_np)

    def _record_token(self, slot: int, req: Request, tok: int,
                      lp: Optional[float] = None):
        sup = getattr(req, "_suppress", None)
        if sup:
            # §23 resume replay: the regenerated token must match the
            # journal exactly — append it (budget/page math counts it)
            # but never re-stream it.  A mismatch means the survivor's
            # replay diverged (foreign config, or a concurrent stream
            # reordered the rng spend): fail loudly, never emit a
            # silently-wrong suffix.
            expect = sup.popleft()
            if tok != expect:
                self.resume_stats["diverged"] += 1
                self._flight.record("resume_diverged", slot=slot,
                                    expect=expect, got=tok,
                                    replayed=len(req.tokens))
                self._slots[slot] = None
                self._fail_request(req, RuntimeError(
                    f"resume replay diverged at replayed token "
                    f"{len(req.tokens) + 1}: journal says {expect}, "
                    f"survivor regenerated {tok} (engine config/seed or "
                    "rng schedule differs from the original replica)"))
                self._sentinel_slot(slot)
                return
            req.tokens.append(tok)
            if lp is not None:
                req.lps.append(lp)
            if len(req.tokens) == 1:
                req.t_first = time.perf_counter()
            self.resume_stats["replayed_tokens"] += 1
            return
        if req.resumed and req.resume_pause == 0.0 and req.t_first:
            # first VISIBLE token of a resumed stream: the replay
            # window ends here, recorded like a migration pause so the
            # SLO timeline decomposition still sums exactly
            req.resume_pause = max(
                1e-9, time.perf_counter() - req.t_first)
        req.tokens.append(tok)
        if lp is not None:
            req.lps.append(lp)
        self._post(req, tok)
        hit_eos = self.eos_id is not None and tok == self.eos_id
        if len(req.tokens) >= req.max_new or hit_eos:
            self._completed += 1
            if req.state_readout:
                req.state = self._state_sample(req._pkv["state_row"])
            self._post(req, _COMPLETED)
            # workload sketch: realized decode length at completion
            self._sketch.record_decode(len(req.tokens))
            if req.rid is not None and self._by_rid.get(req.rid) is req:
                del self._by_rid[req.rid]
            self._slots[slot] = None
            # completion frees the pages: pins released, private
            # non-adopted pages back to the pool (target AND draft),
            # the slot's table rows sentineled so post-finish stale
            # writes drop
            self._release_request_kv(req)
            self._sentinel_slot(slot)
            self._flight.record("batch_done", slot=slot,
                                tokens=len(req.tokens),
                                reason="eos" if hit_eos else "length")

    def _post(self, req: Request, item) -> None:
        """Record ``item`` (a token, or the request's end) for ``req``'s
        stream: it goes out with the next :meth:`_deliver`."""
        if req.outbox is None:
            req.outbox = []
            self._outbox.append(req)
        req.outbox.append(item)

    def _deliver(self) -> None:
        """Hand every request in the outbox what was recorded for its
        stream since the last hand-off: all its tokens and, after them,
        its end, under one lock and one wake-up of its consumer
        (docs/DESIGN.md section 19).  ``t_first`` and ``t_done`` are
        stamped here, the instants a consumer could have the token, and
        a completed request's latencies and timeline follow them.

        The scheduler calls it wherever it may block or leave with
        tokens recorded, and otherwise where the hand-off costs the
        device nothing: under an execution."""
        trace = self.dispatch_trace
        self._gap_drained = False
        outbox, self._outbox = self._outbox, []
        for req in outbox:
            items, req.outbox = req.outbox, None
            ended = items[-1] is _COMPLETED or items[-1] is _FAILED
            now = time.perf_counter()
            if not req.t_first and len(items) > ended:
                req.t_first = now
            if items[-1] is _COMPLETED:
                req.t_done = now
                self._lat["ttft"].append(req.t_first - req.t_submit)
                self._lat["e2e"].append(now - req.t_submit)
                if len(req.tokens) > 1:
                    self._lat["per_token"].append(
                        (now - req.t_first) / (len(req.tokens) - 1))
                self._close_timeline(req)
            trace.delivered_streams += 1
            trace.delivered_tokens += len(items) - ended
            req.handoffs.append((now, len(items) - ended))
            if ended:
                items[-1] = None
            req.stream.put_many(items)
            if ended:
                req.done.set()

    def _fail_request(self, req: Request, err: Optional[BaseException]):
        """Finish a request (with an error, or cleanly for err=None).
        Releases any paged pages/pins the request reserved — cancel,
        failure, and the shutdown drain all reach KV cleanup through
        here (the slot's table row is reset by the callers that own
        one)."""
        self._release_request_kv(req)
        req.error = err
        self._post(req, _FAILED)
        if req.rid is not None and self._by_rid.get(req.rid) is req:
            del self._by_rid[req.rid]
        if err is not None:
            get_flight_recorder().record(
                "batch_fail", error=type(err).__name__,
                tokens=len(req.tokens))
        self._close_timeline(
            req, error=(type(err).__name__ if err is not None
                        else ("cancelled" if req.cancelled else None)))

    def _close_timeline(self, req: Request,
                        error: Optional[str] = None) -> None:
        """Close ``req`` into the process SLO ledger exactly once —
        completion, failure, cancel, and the migration relay's fin (on
        the SOURCE replica, which owns the client connection) all funnel
        here.  Adopted (migrated-in) requests are skipped so a tenant's
        tokens are never double-counted across the fleet.  Best-effort:
        accounting must never add a failure to the request path."""
        if req.adopted or getattr(req, "_timeline_closed", False):
            return
        req._timeline_closed = True
        t_done = req.t_done if req.t_done else time.perf_counter()
        t_first = req.t_first if req.t_first else t_done
        t_sched = req.t_sched if req.t_sched else t_first
        try:
            get_slo_ledger().close_request(
                rid=req.rid or "", tenant=req.tenant,
                trace_id=req.trace_id,
                t_submit_wall=req.t_submit_wall,
                queue_wait_s=max(0.0, t_sched - req.t_submit),
                ttft_s=max(0.0, t_first - req.t_submit),
                e2e_s=max(0.0, t_done - req.t_submit),
                tokens=len(req.tokens),
                migration_pause_s=req.migration_pause,
                migrated=req.migrated,
                resume_pause_s=req.resume_pause,
                resumed=req.resumed, replica=self.tracer.proc,
                error=error)
            if req.trace_id:
                # engine spans for the fleet trace stitch: wall-clock
                # starts are reconstructed from the submit wall time +
                # perf_counter offsets, so the spans line up with the
                # gateway's proxy span without mixing clocks mid-span
                base = req.t_submit_wall or (time.time()
                                             - (t_done - req.t_submit))
                self.tracer.record(
                    "engine.prefill", req.trace_id,
                    ts=base + max(0.0, t_sched - req.t_submit),
                    dur=max(0.0, t_first - t_sched),
                    rid=req.rid, tenant=req.tenant,
                    # the span begins where the wait in the queue ends
                    queue_wait_ms=round(
                        1e3 * max(0.0, t_sched - req.t_submit), 3),
                    # the dispatches that served it (dispatch_trace.seq)
                    first_seq=req.first_seq or None,
                    final_seq=req.final_seq or None)
                if t_done > t_first and len(req.tokens) > 1:
                    self.tracer.record(
                        "engine.decode", req.trace_id,
                        ts=base + max(0.0, t_first - req.t_submit),
                        dur=t_done - t_first, rid=req.rid,
                        tenant=req.tenant, tokens=len(req.tokens))
        except Exception:
            pass

    def register_aux_tracer(self, tracer) -> None:
        """Attach a co-located recorder (e.g. the migration worker's)
        so :meth:`export_trace` drains it with the engine's own spans."""
        self._aux_tracers.append(tracer)

    def export_trace(self) -> dict:
        """Chrome trace of the engine's span sink plus any registered
        auxiliary recorders (the replica ``GET /trace`` surface;
        ``/trace/fleet`` merges these across replicas).  Drains: each
        span exports exactly once."""
        spans = self.tracer.drain()
        for t in self._aux_tracers:
            try:
                spans.extend(t.drain())
            except Exception:
                pass
        return to_chrome_trace(spans)

    def _drain_all(self, err: BaseException):
        """Fail every in-flight slot, mid-admission, backlogged, and
        queued request with ``err``.  A crash may have left dispatches
        in the device's queue (two, after an early launch, §19): they
        are waited out first, so that no page goes back under one.  At
        ``close()`` nothing is in flight by then."""
        with contextlib.suppress(Exception):
            self._last_tok.block_until_ready()
        for i, req in enumerate(self._slots):
            if req is not None:
                self._fail_request(req, err)
                self._slots[i] = None
                self._sentinel_slot(i)
        if self._adm is not None:
            self._fail_request(self._adm["req"], err)
            self._adm = None
        for a in self._adms:
            self._fail_request(a["req"], err)
        self._adms = []
        while self._pending:
            self._fail_request(self._pending.popleft(), err)
        while self._export_q:
            box = self._export_q.popleft()
            box["err"] = err
            box["event"].set()
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None and req is not _WAKE:
                self._fail_request(req, err)
        self._deliver()

    def _sweep_cancelled(self) -> None:
        """Free the slots of requests cancelled mid-flight — run once per
        scheduler iteration and between admission chunks, so a cancel's
        latency is bounded by one step/chunk either way."""
        for i, req in enumerate(self._slots):
            if req is not None and req.cancelled:
                self._fail_request(req, None)
                self._slots[i] = None
                self._sentinel_slot(i)

    def _sentinel_slot(self, slot: int) -> None:
        """Route a freed slot's future writes to nowhere: sentinel its
        block-table row(s) so post-finish stale writes drop."""
        self._tables[slot] = self._page_sentinel
        if self._dmgr is not None:
            self._dtables[slot] = self._dpage_sentinel

    def _eos_scalar(self):
        """eos_id as the traced sentinel scalar (-1 = disabled) — the
        fused loop's on-device eos check (engine.py convention)."""
        return jnp.int32(self.eos_id if self.eos_id is not None else -1)

    def _budget_vec(self) -> jnp.ndarray:
        """[B] remaining-token budget per slot (0 for empty slots): the
        fused loop's on-device row-done bound, so a block whose rows
        all reach max_new at step j < decode_block exits at j."""
        return jnp.asarray(
            [(r.max_new - len(r.tokens)) if r is not None else 0
             for r in self._slots], jnp.int32)

    def _count_loop(self, steps: int) -> None:
        from .engine import count_device_loop
        self.loop_stats["host_dispatches"] += 1
        self.loop_stats["device_loop_steps"] += steps
        count_device_loop(type(self).__name__, steps)

    def _sample_hbm(self) -> None:
        """Feed the HBM watermark ledger one scheduler-iteration sample
        per pool owner.  Pool accounting is host-side integers (no
        device sync); owners are remembered so close() can retire their
        watermarks (reset-on-close)."""
        snap = self.kv_cache.snapshot()
        self._hbm.sample("kv_page_pool",
                         snap.get("device_resident_bytes", 0)
                         + snap.get("quant_scale_bytes", 0))
        self._hbm_owners.add("kv_page_pool")
        if self._dmgr is not None:
            d = self._dmgr.snapshot()
            self._hbm.sample("draft_scratch",
                             d.get("device_resident_bytes", 0)
                             + d.get("quant_scale_bytes", 0))
            self._hbm_owners.add("draft_scratch")
        if self._kv_tier is not None:
            # host RAM, not HBM — but the same ledger answers the same
            # postmortem question ("how big did this pool get"), and
            # reset-on-close retires it with the engine's other owners
            self._hbm.sample("host_tier",
                             self._kv_tier.host_resident_bytes)
            self._hbm_owners.add("host_tier")

    def _decode_kv_bytes(self, active_mask, steps: int) -> int:
        """KV bytes one fused decode dispatch touched (achieved-GB/s
        attribution, SAMPLED dispatches only — the lengths readback
        here is a host sync the unsampled path must never pay): every
        active row re-reads its history each step and writes one
        token per step, priced by the pool's per-token byte math."""
        lens = np.asarray(self._lengths)[active_mask]
        return int((int(lens.sum()) + active_mask.sum())
                   * max(1, steps) * self._kv_bytes_per_token)

    def _step_active(self, rounds: int) -> None:
        """Run up to ``rounds`` lockstep decode steps (plain mode) or
        draft/verify rounds (speculative / prompt-lookup modes) over the
        currently occupied slots and record the emitted tokens.  Shared
        by the scheduler loop and chunked admission's between-chunk
        interleaving (``prefill_chunk``).  The plain fused block may run
        FEWER than ``rounds`` steps (on-device early exit when every
        row eos'd or exhausted its budget); the device-reported step
        count drives the drain."""
        active_mask = np.array([s is not None for s in self._slots])
        self._rng, sub = jax.random.split(self._rng)
        if self._pld_step is not None or self._spec_step is not None:
            if self._pld_step is not None:
                (self._pk, self._pv, self._history, self._lengths,
                 tok, em, ns) = self._pld_step(
                    self.params, self._pk, self._pv, self._history,
                    jnp.asarray(self._tables), self._lengths,
                    self._last_tok, jnp.asarray(active_mask), sub,
                    rounds)
            else:
                (self._pk, self._pv, self._dpk, self._dpv,
                 self._lengths, tok, em, ns) = self._spec_step(
                    self.params, self.draft_params, self._pk,
                    self._pv, self._dpk, self._dpv,
                    jnp.asarray(self._tables),
                    jnp.asarray(self._dtables), self._lengths,
                    self._last_tok, jnp.asarray(active_mask), sub,
                    rounds)
            self._last_tok = tok
            self._count_loop(rounds)
            em_np, ns_np = np.asarray(em), np.asarray(ns)
            for r in range(rounds):
                self._drain_spec_blocks(em_np[r], ns_np[r])
        elif rounds > 1:
            _sig = _profiling.dispatch_signature(
                "paged_multi_step", batch=int(active_mask.sum()),
                chunk=rounds, kv_dtype=self.kv_cache.kv_dtype)
            _t0 = self._prof.begin(_sig)
            (self._pk, self._pv, self._lengths, tok,
             blocks, lps, steps) = self._paged_multi_step(
                self.params, self._pk, self._pv,
                jnp.asarray(self._tables), self._lengths,
                self._last_tok, jnp.asarray(active_mask), sub,
                self._eos_scalar(), self._budget_vec(), rounds)
            self._last_tok = tok
            steps = int(steps)       # the on-device active count
            if _t0 is not None:
                # sampled only (int(steps) above already synced): the
                # dominant KV traffic is each active row re-reading its
                # history every step, plus one written token/row/step
                self._prof.end(_sig, _t0, out=tok,
                               hbm_bytes=self._decode_kv_bytes(
                                   active_mask, steps))
            self._count_loop(steps)
            self._step_count += steps
            self._record_row_blocks(
                np.asarray(blocks), np.full(len(self._slots), steps),
                np.asarray(lps))
        else:
            _sig = _profiling.dispatch_signature(
                "paged_step", batch=int(active_mask.sum()), chunk=1,
                kv_dtype=self.kv_cache.kv_dtype)
            _t0 = self._prof.begin(_sig)
            (self._pk, self._pv, self._lengths, tok,
             lp) = self._paged_step(
                self.params, self._pk, self._pv,
                jnp.asarray(self._tables), self._lengths,
                self._last_tok, jnp.asarray(active_mask), sub)
            self._last_tok = tok
            if _t0 is not None:
                self._prof.end(_sig, _t0, out=tok,
                               hbm_bytes=self._decode_kv_bytes(
                                   active_mask, 1))
            self._count_loop(1)
            tok_np, lp_np = np.asarray(tok), np.asarray(lp)
            self._step_count += 1
            for i, req in enumerate(self._slots):
                if req is not None:
                    self._record_token(i, req, int(tok_np[i]),
                                       float(lp_np[i]))

    def _mixed_iteration(self) -> None:
        """One MIXED-mode scheduler iteration (docs/DESIGN.md §19):
        intake, start concurrent admissions, then ONE token-budget
        dispatch carrying every active row's fused decode block plus
        packed prefill segments.  The serialized loop's per-iteration
        bookkeeping (cancel sweep, export service) rides along at the
        same points.

        While that dispatch executes the thread prepares the next one
        from the state this one will leave (``_plan_ahead``); when it
        returns and the prepared dispatch is what this order would have
        packed (``_ahead_refusal``), the thread launches it first and
        drains afterwards, under the new execution, and stays in this
        call.  Otherwise it drains and returns, and the next iteration
        is this one again from the top.

        A prepared dispatch that is ``closed`` (``_plan_ahead``: no
        arrival could change it and nothing the device has yet to say
        could refute it) is launched as soon as it is made, before the
        one in flight has returned: the device's queue is then two deep
        and goes from one execution to the next with no host in
        between.  The thread awaits and drains the first under the
        second, as on any hit."""
        trace = self.dispatch_trace
        trace.enter("intake")
        free = [i for i, s in enumerate(self._slots) if s is None]
        # block for work only when truly idle: nothing decoding, no
        # admission mid-stream, nothing waiting to be served
        timeout = (None if not (any(self._slots) or self._adms
                                or self._pending)
                   else 0.0)
        while True:
            try:
                if timeout is None:
                    # nothing executes: the next dispatch is a first
                    self._ahead_miss = None
                    self._deliver()
                    with trace.idle():     # nobody's host time
                        req = self._queue.get()
                else:
                    req = self._queue.get(timeout=timeout)
            except queue.Empty:
                break
            timeout = 0.0
            if req is _WAKE:               # export_request nudge
                continue
            if req is None:                # close() sentinel
                break
            self._pending.append(req)
        # serve pending FIFO.  Live-migration resumes adopt straight
        # into a free slot (their checkpoint IS the row state — no
        # prefill to pack); everything else becomes a concurrent
        # admission whose chunks the dispatch packs.  Admissions are
        # capped at the free-slot count (reserving pages for prompts
        # that cannot land yet just wedges the pool), floor 1 so a
        # fully busy batch still streams one prompt's chunks (the
        # serialized path's overlap property).  Serviceable requests
        # pass blocked ones.
        still: "deque[Request]" = deque()
        for req in self._pending:
            if req.cancelled:              # dropped while waiting
                self._fail_request(req, None)
            elif getattr(req, "_resume", None) is not None:
                if free:
                    slot = free.pop(0)
                    try:
                        self._admit_request(slot, req)
                    except _BlocksExhausted:
                        free.insert(0, slot)
                        still.append(req)
                    except BaseException as e:
                        self._fail_request(req, e)
                else:
                    still.append(req)      # waiting for a slot
            elif len(self._adms) < max(1, len(free)):
                try:
                    start = self._reserve_pages(req)
                except _BlocksExhausted:
                    still.append(req)      # retry when pages free up
                    continue
                except BaseException as e:  # surface to the waiter
                    self._fail_request(req, e)
                    continue
                self._adms.append({"req": req, "start": start,
                                   "m": start,
                                   "suffix": req.prompt[start:]})
            else:
                still.append(req)
        self._pending = still
        # drop cancelled admissions between dispatches (cancel latency
        # bounded by one dispatch, the serialized path's property)
        for a in list(self._adms):
            if a["req"].cancelled:
                self._adms.remove(a)
                self._fail_request(a["req"], None)
        self._sweep_cancelled()
        self._service_exports()
        if not any(self._slots) and not self._adms:
            self._ahead_miss = None
            self._deliver()
            return
        trace.enter("pack")
        plan = self._pack_mixed(
            [(s, len(s.tokens)) if s is not None else None
             for s in self._slots], self._adms,
            [i for i, s in enumerate(self._slots) if s is None],
            self._rng, self._tables.copy())
        plan.how = self._ahead_miss or "first"
        flight = self._launch_mixed(plan)
        del plan       # the flight holds it, and lets it go when drained
        if flight is None:
            self._deliver()
            return
        # what the gap recorded for the streams (the drain of this
        # dispatch's predecessor, a cancel's end) goes out now that the
        # device has work: the consumers it wakes take the GIL under
        # this execution and not in front of it
        with trace.ahead("deliver"):
            trace.delivered_after_launch += self._gap_drained
            self._deliver()
        while flight is not None:
            nxt, why = self._plan_ahead(flight)
            ahead = None
            if (nxt is not None and nxt.closed
                    and self._ahead_refusal(flight) is None):
                with trace.ahead("ahead_launch"):
                    ahead = self._launch_mixed(nxt, early=True)
                if ahead is None:           # (as below)
                    nxt, why = None, "other"
            self._await_mixed(flight)
            if ahead is not None:
                ahead.t_begin = flight.t_done = trace.returned()
                # here the old order looked for news, and its intake
                # would have dropped these before it packed `ahead`
                ahead.gone = {id(req) for req in (
                    [s[0] for s in nxt.rows if s is not None]
                    + [a["req"] for (_, a, _, _) in nxt.packed])
                    if req.cancelled}
                if flight.steps != flight.steps_ahead:
                    raise RuntimeError(
                        f"dispatch {trace.launched - 1} ran {flight.steps} "
                        f"steps where its successor, enqueued behind it, "
                        f"was packed for {flight.steps_ahead}")
            elif nxt is not None:
                # the validation is the next dispatch's whole `pack`
                flight.t_done = trace.enter("pack")
                why = self._ahead_refusal(flight)
                if why is None:
                    ahead = self._launch_mixed(nxt)
                    # None: its slab failed its requests and it never
                    # reached the device: `flight` is drained as after a
                    # miss
                    why = "other"
            if ahead is not None:
                with trace.ahead("ahead_drain"):
                    record = self._drain_mixed(flight)
                    self._deliver()
                trace.commit(phases=flight.phases, **record)
                flight = ahead
                continue
            if nxt is not None:
                trace.enter("drain")
            else:
                flight.t_done = trace.enter("drain")
            record = self._drain_mixed(flight)
            self._ahead_miss = why
            # its tokens stay in the outbox until the next iteration has
            # launched its successor (or finds nothing to launch).
            # Committed here, after the flight is let go: `drain` then
            # holds the teardown too (freeing the dispatch's device
            # arrays drops the GIL)
            self._gap_drained = True
            flight = nxt = None
            trace.commit(**record)

    def _blank_segments(self) -> tuple:
        """The slab's arrays over ``n_seg`` segments that hold nothing:
        ``(ids, tables, starts, lens, slot, plen, keys, ntok)``, every
        table sentinel and every slot ``B`` (all writes and installs
        drop).  ``ntok``, the tokens a segment holds, is the eighth;
        ``mixed_step`` takes the first ``_seg_arrays``."""
        n_seg, C, i32 = self._mixed_seg_cap, self.prefill_chunk, np.int32
        return (np.zeros((n_seg, C), i32),
                np.full((n_seg, self._table_cols), self._page_sentinel,
                        i32),
                np.zeros((n_seg,), i32), np.ones((n_seg,), i32),
                np.full((n_seg,), self.max_batch, i32),
                np.zeros((n_seg,), i32), np.zeros((n_seg, 2), np.uint32),
                np.zeros((n_seg,), i32))

    def _slab_of(self, seg: tuple, r: int) -> tuple:
        """The first ``r`` segments of the arrays ``mixed_step`` takes:
        the slab of a dispatch that packed ``r``."""
        return tuple(x[:r] for x in seg[:self._seg_arrays])

    def _pack_mixed(self, rows: list, adms: list, free: list, rng,
                    tables: np.ndarray) -> types.SimpleNamespace:
        """Pack ONE mixed token-budget dispatch from a view of the
        scheduler's state and return it as a plan; **commits nothing**
        (``_launch_mixed`` does, if and when the plan is launched).

        The view: ``rows[i]`` is ``(request, tokens it holds)`` of slot
        i or None, ``adms`` the admissions in flight, ``free`` the slots
        a final may take, ``rng`` the sampler key, ``tables`` a copy of
        the ``[B, W]`` decode tables, which becomes the plan's (the
        transfer may read it as long as the dispatch runs, and the
        launch of the next one, which may come before this one has
        returned, writes ``_tables``).  ``_mixed_iteration`` passes the
        state as it is; ``_plan_ahead`` the state the dispatch in flight
        will leave.

        Packing policy (docs/DESIGN.md §19): every active decode row
        contributes its ``decode_block`` fused-loop tokens off the top
        of the budget; the remainder packs C-token prefill segments
        FIFO over the concurrent admissions — each contributes its
        sequential chunks, and its bucket-free FINAL segment (sampling
        token #1, installing the slot in-program) once a free slot
        pops.  At least one segment is always packed when an admission
        is in flight, so a saturated decode batch cannot starve
        prefill.  rng split order: one batch-1 split per packed final
        in pack order, then ONE decode split iff any row decodes —
        exactly the serialized path's spend, which keeps cold-start
        sampled streams bit-identical."""
        B = self.max_batch
        C = self.prefill_chunk
        n_seg = self._mixed_seg_cap
        live0 = [i for i, s in enumerate(rows) if s is not None]
        n_active = len(live0)
        spec_mixed = (self._mixed_pld_step is not None
                      or self._mixed_spec_step is not None)
        if spec_mixed:
            # §22 pricing: a speculative row costs (K_row + 1) tokens
            # per round — K_row drafts + the verify/bonus token — times
            # the decode_block fused rounds.  Adaptive K shrinks a
            # collapsing acceptor toward K_row = 1 (≈ plain decode)
            # so it stops burning budget the prefill slab could use.
            k_vec = (self._spec_krow.copy() if self.spec_adaptive
                     else np.full((B,), self._spec_buckets[-1],
                                  np.int32))
            room = max(0, self.mixed_token_budget - sum(
                (int(k_vec[i]) + 1) * self.decode_block for i in live0))
        else:
            k_vec = None
            room = max(0, self.mixed_token_budget
                       - n_active * self.decode_block)
        want = min(n_seg, max(1, room // C)) if adms else 0
        seg = self._blank_segments()
        (seg_ids, seg_tables, seg_starts, seg_lens, seg_slot, seg_plen,
         seg_keys, seg_ntok) = seg
        packed = []          # (row, admission, is_final, slot)
        advance = []         # (admission, its start, its suffix) after
        rewound = []         # requests whose §23 rewind this spends
        keys = []            # (row, a final's sampling key on the device)
        free = list(free)
        chunks = 0
        prefill_tokens = 0
        # (query, cached token) pairs the slab's tokens attend over: a
        # token at position p, its p predecessors and itself
        prefill_kv_tokens = 0
        r = 0
        for a in adms:
            if r >= want:
                break
            req = a["req"]
            start, suffix = a["start"], a["suffix"]
            if self._wmgr is not None and r < want:
                # every segment of this admission the dispatch can carry
                self._window_hold(req, start, min(
                    len(req.prompt), start + (want - r) * C))
            # a summarised cache: a window's pages are written again by
            # the next window, so a request's segments of one dispatch
            # lie in one window, whose earlier segments' queries still
            # see what it wrote
            at_edge = lambda: (self._eva is not None and start != a["start"]
                               and start % self._eva.window == 0)
            while r < want and len(suffix) > C and not at_edge():
                seg_ids[r, :] = np.asarray(suffix[:C], np.int32)
                seg_tables[r] = req._pkv["table"]
                seg_starts[r] = start
                seg_ntok[r] = C
                packed.append((r, a, False, -1))
                prefill_tokens += C
                prefill_kv_tokens += C * start + C * (C + 1) // 2
                start += C
                suffix = suffix[C:]
                chunks += 1
                r += 1
            if start != a["start"]:
                advance.append((a, start, suffix))
            if r >= want or len(suffix) > C or at_edge():
                break
            if not free:
                continue     # final parked until a slot frees; later
                             # admissions may still pack their chunks
            slot = free.pop(0)
            n = len(suffix)
            seg_ids[r, :n] = np.asarray(suffix, np.int32)
            seg_tables[r] = req._pkv["table"]
            seg_starts[r] = start
            seg_lens[r] = n
            seg_ntok[r] = n
            seg_slot[r] = slot
            seg_plen[r] = len(req.prompt)
            # the final's batch-1 sampling key: the serialized
            # prefill's exact split, spent in pack order
            if getattr(req, "_rng_rewind", False):
                # §23 sampled resume rewind — same hook as the
                # serialized _finish_admission, mixed-dispatch shape
                rng = jax.random.PRNGKey(self._seed)
                rewound.append(req)
            rng, sub = jax.random.split(rng)
            keys.append((r, sub))     # its row of `seg_keys`: _put_slab
            packed.append((r, a, True, slot))
            prefill_tokens += n
            prefill_kv_tokens += n * start + n * (n + 1) // 2
            r += 1
        finals = [(a["req"], slot) for (_, a, f, slot) in packed if f]
        # every segment the budget allows is packed: an admission after
        # these (the loop above never came to it) would get none here
        all_packed = r == want >= 1
        # the slab is the r segments that were packed: the arrays' shape
        # picks ``mixed_step``'s variant (the speculative programs keep
        # the full slab); a model with experts is also told how many
        # tokens each segment holds
        slab_segs = n_seg if spec_mixed else r
        seg = self._slab_of(seg, slab_segs)
        active_mask = np.array([s is not None for s in rows])
        # ``mixed_step``'s slab carries the first decode step of the rows
        # that decode already; a final it installs then joins the loop a
        # step later, and its tokens begin at column 1 of its row
        carried = int(bool(slab_segs) and n_active > 0 and not spec_mixed)
        first_col = np.zeros((B,), np.int32)
        first_col[[slot for _, slot in finals]] = carried
        # budget: remaining tokens per pre-existing row; a freshly
        # installed final's row has max_new - 1 left (token #1 came
        # from its prefill logits)
        budget_vec = np.array(
            [(s[0].max_new - s[1]) if s is not None else 0
             for s in rows], np.int32)
        # decode inside this dispatch pages through the installed
        # row — its table must be live BEFORE the dispatch; the
        # radix adoption (drain) waits until the pages hold data
        for req, slot in finals:
            budget_vec[slot] = req.max_new - 1
            tables[slot] = req._pkv["table"]
        # the window kind: a row's next steps write tokens [held - 1 ...),
        # and its table row is the request's, which the holds keep current
        kv_window_tokens = prefill_window_pairs = 0
        if self._wmgr is not None:
            Wn, steps = self._window, self.decode_block
            decoding = [(i, *s) for i, s in enumerate(rows) if s is not None]
            decoding += [(slot, req, 1) for req, slot in finals]
            for slot, req, k in decoding:
                at = len(req.prompt) + k - 1
                self._window_hold(req, at, at + steps)
                tables[slot] = req._pkv["table"]
                kv_window_tokens += min(at + 1, Wn)
            for (r0, a, _, _) in packed:
                n, s0 = int(seg_ntok[r0]), int(seg_starts[r0])
                # token at position p sees min(p + 1, W) keys
                full = max(0, min(n, Wn - 1 - s0))      # p + 1 < W
                prefill_window_pairs += (
                    full * s0 + full * (full + 1) // 2 + (n - full) * Wn)
        # what the prefill kernel's page loop walks for the packed
        # segments, a pool of one kind of block each (host arithmetic on
        # the starts; the kernel computes every row of a segment), and
        # the steps a grid of the table's width would have had for the
        # full kind's tiles
        walked = [sum(prefill_pages_walked(self._cache_row(
                                               int(seg_starts[r0])), C, tile,
                                           self.kv_cache.block_tokens,
                                           self._table_width, window)
                      for (r0, _, _, _) in packed)
                  for tile, window in self._prefill_tiles]
        # a summarised cache: the rows of the pool the decoding rows'
        # next query attends (the summaries among them apart), and the
        # (query, row) pairs of the slab's tokens, each its window's
        # earlier keys, itself and every closed window's summaries
        eva_cols = {}
        if self._eva is not None:
            Wn, Cn = self._eva.window, self._eva.chunk
            held = [len(s[0].prompt) + s[1] for s in rows if s is not None]
            # (a final's first step feeds its token #1, at ``plen``)
            held += [len(req.prompt) + 1 for req, _ in finals]
            attended = [eva_rows(Wn, Cn, n) for n in held]
            pairs = 0
            for (r0, _, _, _) in packed:
                n, s0 = int(seg_ntok[r0]), int(seg_starts[r0])
                pairs += (n * eva_rows(Wn, Cn, s0 + 1)[0]
                          + n * (s0 % Wn) + n * (n + 1) // 2)
            eva_cols = {"kv_attended_rows": sum(a + b for a, b in attended),
                        "kv_summary_rows": sum(a for a, _ in attended),
                        "prefill_attended_rows": pairs}
        pages_grid = (len(packed) * (C // self._prefill_tiles[0][0])
                      * self._table_width)
        if spec_mixed:
            # §22 rng rule: the decode split is spent iff spec rounds
            # run, i.e. iff a row was ALREADY active — a freshly
            # installed final spends only its pack-order batch-1 key
            # this dispatch (its proposer state seeds host-side after),
            # exactly the serialized final-split-then-step-split order.
            num_rounds = self.decode_block if n_active > 0 else 0
            k_disp = (max(int(k_vec[i]) for i in live0) if live0
                      else int(self._spec_buckets[-1]))
            if num_rounds > 0:
                rng, dec_sub = jax.random.split(rng)
            else:
                dec_sub = jax.random.PRNGKey(0)
        elif n_active > 0 or finals:
            # ONE decode split per dispatch that decodes — the
            # serialized loop's spend (it skips the split when no slot
            # is active)
            num_rounds, k_disp = 0, 0
            rng, dec_sub = jax.random.split(rng)
        else:
            num_rounds, k_disp = 0, 0
            dec_sub = jax.random.PRNGKey(0)   # prefill-only: loop is
                                              # a 0-step no-op
        # what the decode kernel has to read: tokens of KV held by the
        # rows that decode here (host state only, never the device's)
        kv_tokens = (sum(len(s[0].prompt) + s[1]
                         for s in rows if s is not None)
                     + sum(len(req.prompt) for req, _ in finals))
        return types.SimpleNamespace(
            rows=rows, packed=packed, advance=advance, rewound=rewound,
            finals=finals, chunks=chunks, rng=rng, dec_sub=dec_sub,
            adms_left=len(adms) - len(finals),
            seg=seg, keys=keys, slab_rows=slab_segs * C,
            tables=tables, active_mask=active_mask,
            budget_vec=budget_vec,
            prefill_tokens=prefill_tokens,
            prefill_kv_tokens=prefill_kv_tokens, n_active=n_active,
            kv_window_tokens=kv_window_tokens,
            prefill_window_pairs=prefill_window_pairs,
            prefill_pages_walked=walked, prefill_pages_grid=pages_grid,
            live0=live0, kv_tokens=kv_tokens, spec_mixed=spec_mixed,
            k_vec=k_vec, k_disp=k_disp, num_rounds=num_rounds,
            eva_cols=eva_cols, dev=None, how=None, ahead_s=0.0,
            full=all_packed, carried=carried, first_col=first_col)

    def _cache_row(self, position: int) -> int:
        """The row of its attended table that the token at ``position``
        sits at: the position itself, but under a summarised cache (a
        page of summary rows a closed window, then its place in the open
        one: ``ops.eva_attention.eva_positions``)."""
        if self._eva is None:
            return position
        return eva_positions(position, self._eva.window,
                             self.kv_cache.block_tokens)

    def _eva_account(self, plan, steps: int) -> int:
        """A drained dispatch's part in ``eva_stats``: the windows its
        tokens closed and the summaries they completed (a request whose
        cached tokens went from ``a`` to ``b`` closed ``b // W - a // W``
        windows and completed ``b // C - a // C`` chunks: a prompt
        segment's tokens, a row's decode steps within its budget), and
        at the pool's fullest the rows it held for the running requests
        beside their tokens.  Returns the windows closed."""
        Wn, Cn = self._eva.window, self._eva.chunk
        spans = [(int(plan.seg[2][r0]),
                  int(plan.seg[2][r0]) + int(plan.seg[3][r0] if f else
                                             self.prefill_chunk))
                 for (r0, _, f, _) in plan.packed]
        after = []
        # (a final joins the loop after the step its slab carried)
        for req, k, n in ([(*s, steps) for s in plan.rows if s is not None]
                          + [(req, 1, steps - plan.carried)
                             for req, _ in plan.finals]):
            a = len(req.prompt) + k - 1
            b = a + max(0, min(n, req.max_new - k))
            spans.append((a, b))
            after.append(b)
        closed = sum(b // Wn - a // Wn for a, b in spans)
        st = self.eva_stats
        st["windows_closed"] += closed
        st["summaries_written"] += sum(b // Cn - a // Cn for a, b in spans)
        done = {id(req) for req, _ in plan.finals}
        after += [a["start"] for a in self._adms if id(a["req"]) not in done]
        rows = sum(sum(eva_rows(Wn, Cn, n)) for n in after)
        if rows > st["rows_held_peak"]:
            st["rows_held_peak"], st["tokens_held_peak"] = rows, sum(after)
        return closed

    def _put_slab(self, plan, put) -> tuple:
        """The plan's segment arrays on the device, each final's
        sampling key written into its row of ``seg_keys`` there.  The
        key is split on the device (``_pack_mixed``), and the device
        runs its programs in order: a plan made under an execution that
        read the key back to the host would wait for that execution's
        end, where one more small program in the queue waits for
        nobody."""
        seg = [put(x) for x in plan.seg]
        for r, sub in plan.keys:
            seg[6] = self._set_row(seg[6], np.int32(r), sub)
        return tuple(seg)

    def _put_mixed(self, plan) -> tuple:
        """The plan's arrays on the device, as ``mixed_step`` takes them
        (once: a plan prepared ahead has them there when it is
        launched).  A slab of no segment: no segment array is
        transferred, and ``seg`` is None."""
        if plan.dev is None:
            put = jnp.asarray
            if self._replicated is not None:
                # where the call's other arguments live: every chip of
                # the mesh holds a copy, so the call spreads nothing
                put = partial(jax.device_put, device=self._replicated)
            plan.dev = (
                self._put_slab(plan, put) if plan.slab_rows else None,
                put(plan.tables), put(plan.active_mask),
                put(self._eos_scalar()), put(plan.budget_vec),
                put(plan.dec_sub))
        return plan.dev

    def _call_mixed_step(self, plan) -> list:
        """Enqueue ``mixed_step`` on ``plan``'s arrays: the pool and the
        rows' state become what it returns; the rest of its outputs
        (``final_toks, final_lps, toks, lps, steps[, moe_acc]``) are
        returned."""
        seg, tables, active, eos, budget, dec_sub = self._put_mixed(plan)
        (self._pk, self._pv, self._lengths, self._last_tok,
         *out) = self._mixed_step(
            self.params, self._pk, self._pv, seg, tables, self._lengths,
            self._last_tok, active, dec_sub, eos, budget,
            self.decode_block)
        return out

    def _warm_mixed_variants(self) -> None:
        """Launch every variant of ``mixed_step`` once, before the
        engine takes a request: the decode loop alone, then a slab of
        1 .. ``n_seg`` segments.  Which of them a deployment's (or a
        benchmark's) first prompts would launch is a matter of their
        lengths and timing, and a variant that met its first execution
        under traffic would compile there.  Real executions on purpose
        (an AOT compile does not seed the jit's call cache), through
        the serving path's own transfer and call.  Nothing is
        admitted: no row is active, every table is sentinel and every
        segment's slot is ``B``, so every write and install drops, as
        an unused row's always did, and the rows' state is returned as
        it was given."""
        idle = self._pack_mixed([None] * self.max_batch, [], [], self._rng,
                                np.full_like(self._tables,
                                             self._page_sentinel))
        blank = self._blank_segments()
        for r in range(self._mixed_seg_cap + 1):
            plan = copy.copy(idle)
            plan.seg = self._slab_of(blank, r)
            plan.slab_rows = r * self.prefill_chunk
            # ... and the program that writes a final's key into a slab
            # of this many rows (row 0's slot is `B`: nothing reads it)
            plan.keys = [(0, idle.dec_sub)] if r else []
            self._call_mixed_step(plan)
        jax.block_until_ready(self._last_tok)
        if self.hc_stats is not None:
            self._hc_probe()

    def _launch_mixed(self, plan, early: bool = False
                      ) -> Optional[types.SimpleNamespace]:
        """Commit ``plan``'s effects on the scheduler's state (rng
        spend, admissions' progress, installed table rows, counters,
        the requests' dispatch stamps) and enqueue its program; returns
        the dispatch in flight, or None if it failed its requests and
        never reached the device.  ``early``: its predecessor has not
        returned yet.  The program's pool and rows' state are that
        one's outputs, so the device runs it behind that one, with no
        host in between."""
        trace = self.dispatch_trace
        seq = trace.launched + 1     # this dispatch's number
        packed = plan.packed
        self._rng = plan.rng
        for req in plan.rewound:
            req._rng_rewind = False
        for a, start, suffix in plan.advance:
            a["start"], a["suffix"] = start, suffix
        for req, slot in plan.finals:
            self._tables[slot] = req._pkv["table"]
        if self._wmgr is not None:
            # what fell behind the window goes back to its pool, by the
            # first token this dispatch writes for each request.  At an
            # early launch the predecessor still reads those pages (and
            # a summarised cache's window, which is not freed but
            # written over, likewise): safe because the device runs its
            # queue in order, and a page freed here can be written by no
            # dispatch enqueued before this one
            first = {}
            for (r0, a, _, _) in packed:
                first.setdefault(id(a["req"]),
                                 (a["req"], int(plan.seg[2][r0])))
            for s in plan.rows:
                if s is not None:
                    first[id(s[0])] = (s[0], len(s[0].prompt) + s[1] - 1)
            for req, lo in first.values():
                self._window_release(req, lo)
            # what the same requests would hold here with no window: a
            # page a block of every token they hold (rows, and admissions
            # as far as their chunks have come)
            bt = self.kv_cache.block_tokens
            ws = self.window_stats
            ws["pages_unwindowed_peak"] = max(
                ws["pages_unwindowed_peak"],
                sum(-(-(len(s[0].prompt) + s[1]) // bt)
                    for s in plan.rows if s is not None)
                + sum(-(-a["start"] // bt) for a in self._adms))
            for i, s in enumerate(plan.rows):
                if s is not None:
                    self._tables[i] = s[0]._pkv["table"]
        self.chunk_stats["chunks"] += plan.chunks
        if self._state_free is not None:
            # a segment at position 0 starts its row of the state pool
            # from zero, in the program
            self.state_stats["zeroed"] += sum(
                int(plan.seg[2][r0]) == 0 for (r0, _, _, _) in packed)
        # a request's queue wait ends at the launch of the first
        # dispatch that carries one of its segments: pending, waiting
        # for pages, for budget, for the running execution to end
        now = time.perf_counter()
        for (_, a, is_final, _) in packed:
            req = a["req"]
            if req.first_seq == 0:
                req.first_seq = seq
                req.t_sched = now
                self._lat["queue_wait"].append(now - req.t_submit)
                trace.queue_wait(now - req.t_submit)
            if is_final:
                req.final_seq = seq
        spec_mixed = plan.spec_mixed
        prog = ("mixed_step" if not spec_mixed else
                "mixed_spec_step" if self._mixed_spec_step is not None
                else "mixed_pld_step")
        sig = _profiling.dispatch_signature(
            prog, batch=int(plan.active_mask.sum()),
            chunk=self.decode_block, kv_dtype=self.kv_cache.kv_dtype)
        # (an early launch is work under an execution, span
        # `ahead_launch`, and no phase of the gap)
        t_launch = (trace.enter("wait", cut=True) if early
                    else trace.enter("launch"))
        flight = types.SimpleNamespace(
            plan=plan, sig=sig, t_launch=t_launch, t_done=0.0,
            phases=trace.launched_phases, out=None, early=early,
            # the earliest its execution can have begun (an early one's:
            # when its predecessor returned), the device's count of its
            # steps, None until it has returned, and the requests that
            # an early one carries and the old order would not have
            t_begin=t_launch, steps=None, gone=())
        try:
            if not spec_mixed:
                with jax.profiler.StepTraceAnnotation("mixed_step",
                                                      step_num=seq):
                    flight.out = self._call_mixed_step(plan)
                # (final_toks, final_lps, toks, lps, steps[, moe_acc]):
                # small, and the drain reads them all
                for o in flight.out:
                    o.copy_to_host_async()
            else:
                seg_dev = self._put_slab(plan, jnp.asarray)
                if self._mixed_spec_step is not None:
                    (self._pk, self._pv, self._dpk, self._dpv,
                     self._lengths, self._last_tok,
                     *flight.out) = self._mixed_spec_step(
                        self.params, self.draft_params, self._pk,
                        self._pv, self._dpk, self._dpv, *seg_dev,
                        jnp.asarray(plan.tables),
                        jnp.asarray(self._dtables), self._lengths,
                        self._last_tok, jnp.asarray(plan.active_mask),
                        plan.dec_sub, jnp.asarray(plan.k_vec),
                        plan.k_disp, plan.num_rounds, bool(plan.finals))
                else:
                    (self._pk, self._pv, self._history, self._lengths,
                     self._last_tok,
                     *flight.out) = self._mixed_pld_step(
                        self.params, self._pk, self._pv, self._history,
                        *seg_dev, jnp.asarray(plan.tables),
                        self._lengths, self._last_tok,
                        jnp.asarray(plan.active_mask), plan.dec_sub,
                        jnp.asarray(plan.k_vec), plan.k_disp,
                        plan.num_rounds, bool(plan.finals))
        except BaseException as e:
            # a per-request failure fails the packed requests, never
            # the engine — same contract as the serialized admission
            # dispatches.  A pure-decode failure (nothing packed) IS an
            # engine failure: re-raise into the crash drain.
            if not packed:
                raise
            trace.abandon()
            failed = []
            for (_, a, is_final, slot) in packed:
                if a["req"] not in failed:
                    failed.append(a["req"])
                if is_final:
                    self._tables[slot] = self._page_sentinel
            self._adms = [a for a in self._adms
                          if a["req"] not in failed]
            for req in failed:
                self._fail_request(req, e)
            return None
        if early:
            trace.opened(t_launch)
        else:
            trace.enter("wait")      # until the first blocking read
        return flight

    def _await_mixed(self, flight) -> None:
        """Block until the dispatch in flight has returned: the first
        read of one of its outputs, which the record says was late if
        the output was there before the host came for it."""
        spec = flight.plan.spec_mixed
        self.dispatch_trace.awaiting(flight.out[2 if spec else 4].is_ready(),
                                     flight.phases)
        if spec:
            em, ns = flight.out[2:]
            flight.em_np, flight.ns_np = np.asarray(em), np.asarray(ns)
            flight.steps = flight.plan.num_rounds
        else:
            flight.steps = int(flight.out[4])   # the on-device count

    def _plan_ahead(self, flight) -> tuple:
        """While ``flight`` executes: the next dispatch, packed from the
        state ``flight`` will leave if no row of it ends by ``eos``, its
        arrays (its slab's too) already on the device; ``(plan, None)``,
        or ``(None, why)`` where the engine can see now that the next
        dispatch will not be that one.  Commits nothing.

        The projection.  Rows: a row holds ``min(steps, remaining)``
        more tokens after ``flight``, where ``steps`` is the fused
        loop's count (it runs while any row has budget left,
        ``decode_block`` at most); a final installed by ``flight`` holds
        its token #1 and then the same, less the step that ``flight``'s
        slab carried for the rows before it (``plan.first_col``), which
        also puts its last token one step later; a row whose budget ends in
        ``flight`` is gone, its table row sentinel (``_record_token`` +
        ``_sentinel_slot``).  Admissions: ``flight``'s launch has moved
        each to where its chunks in ``flight`` end, and its drain takes
        out those whose final it packed, so they are ``_adms`` less
        those, in order, and ``_pack_mixed`` gives them their next
        chunks and finals as the gap's pack would.  Free slots: the
        slots no row holds after ``flight``, those of rows that end in
        it included, as the gap's pack would see them; but such a slot
        and its pages come free only in ``flight``'s drain, which on a
        hit runs after this plan's launch has written the slot's table
        row, so a plan whose final took one is turned away (``finish``)
        and the gap packs it after the drain.

        What stays on the old order, by what the engine is or holds: the
        speculative programs (their pack reads what the drain learns),
        a resume replay among the rows or the admissions
        (its drain may fail the row), and a request in ``_pending`` that
        the intake skipped on a hit would act on: a row that ends in
        ``flight`` frees it a slot and pages; where the cap on
        admissions (fewer than the free slots, one at least) lets the
        intake try it, a final that ``flight`` installs
        (``store_shared`` moves the tree's epoch) or a page gate that
        is open already.

        The plan is ``closed`` iff the engine has no ``eos`` (the step
        count above is then the device's, and no token can end a row
        before its budget does) and it packed every segment its budget
        allows (``_pack_mixed``'s ``full``: an arrival joins ``_adms``
        at the end and the pack stops before it looks there, so the
        gap's pack with the arrival appended would be this plan,
        segment for segment, and the arrival's first chunk rides the
        dispatch after it either way).  Such a plan may be launched
        while ``flight`` still runs if ``_ahead_refusal`` sees no news
        then.  What lands after that launch waits one dispatch more
        than it would have: a cancel or an export is served at the next
        intake, after this plan's dispatch has drained, a resume is
        adopted there, and a request cancelled before ``flight`` returned
        rides this dispatch and gets none of its tokens
        (``_drain_mixed``).  A decode-only plan and a
        slab with a segment to spare are not closed: an arrival during
        ``flight`` is served in the next dispatch there."""
        plan = flight.plan
        if plan.spec_mixed:
            return None, "other"
        with self.dispatch_trace.ahead() as spent:
            rows = list(plan.rows)
            for req, slot in plan.finals:
                rows[slot] = (req, 1)
            live = [s for s in rows if s is not None]
            done = {id(req) for req, _ in plan.finals}
            adms = [a for a in self._adms if id(a["req"]) not in done]
            held = [req for req, _ in live] + [a["req"] for a in adms]
            news = self._ahead_news(held)
            if news is not None:
                return None, news
            if any(getattr(req, "_suppress", None) for req in held):
                return None, "other"
            # a final joins the loop a step late where the slab carried
            # one (``mixed_step``): it is at its last token that much later
            late = plan.first_col
            steps = min(self.decode_block,
                        max((s[0].max_new - s[1] + int(late[i])
                             for i, s in enumerate(rows) if s is not None),
                            default=0))
            ended = []
            for i, s in enumerate(rows):
                if s is None:
                    continue
                req, k = s
                k += max(0, min(steps - int(late[i]), req.max_new - k))
                rows[i] = (req, k) if k < req.max_new else None
                if rows[i] is None:
                    ended.append(i)
            if not any(rows) and not adms:
                return None, "finish"
            free = [i for i, s in enumerate(rows) if s is None]
            if self._pending:
                if ended:
                    return None, "finish"
                if any(getattr(req, "_resume", None) is not None
                       for req in self._pending):
                    return None, "other"
                if len(adms) < max(1, len(free)):
                    if plan.finals:
                        return None, "finish"
                    # what is left waits for pages behind
                    # `_reserve_pages`' retry gate, which opens when the
                    # pool changes: the intake would turn it away again
                    # and touch nothing
                    pool = (self.kv_cache.epoch, self.kv_cache.free_blocks)
                    if any(getattr(req, "_pkv_blocked", None) != pool
                           for req in self._pending):
                        return None, "other"
            self.anomaly.observe(self.stats)
            self._sample_hbm()
            tables = self._tables.copy()
            tables[ended] = self._page_sentinel
            nxt = self._pack_mixed(rows, adms, free, self._rng, tables)
            if any(slot in ended for _, slot in nxt.finals):
                return None, "finish"
            self._put_mixed(nxt)
        nxt.how, nxt.ahead_s = "hit", spent[0]
        nxt.closed = nxt.full and self.eos_id is None
        flight.steps_ahead = steps
        return nxt, None

    def _ahead_news(self, held) -> Optional[str]:
        """What has reached the scheduler that the next intake would act
        on, as a reason of ``tracing.AHEAD_MISS_REASONS``, or None: an
        export asked, a request or wake in the queue, the engine
        closing, a cancel among the requests ``held`` (the rows' and the
        admissions') or those in ``_pending``."""
        if self._export_q:
            return "export"
        if not self._queue.empty():
            return "arrival"
        if not self._running:
            return "other"
        if (any(req.cancelled for req in held)
                or any(req.cancelled for req in self._pending)):
            return "cancel"
        return None

    def _ahead_refusal(self, flight) -> Optional[str]:
        """None if the dispatch prepared under ``flight`` is what this
        order would pack now, else why not (one of
        ``tracing.AHEAD_MISS_REASONS``).  It is iff ``flight`` did what
        the projection assumed (the projected step count, no ``eos``
        among the tokens a row keeps) and nothing reached the scheduler
        meanwhile: no arrival or wake in the queue, no export asked, no
        request cancelled (a row's, an admission's, a waiting one), the
        engine not closing.  Asked before ``flight`` has returned, of a
        closed plan (``_plan_ahead``), it has only the news to look at:
        the projection is then the device's own arithmetic."""
        plan = flight.plan
        news = self._ahead_news(
            [s[0] for s in plan.rows if s is not None]
            + [a["req"] for a in self._adms])
        if news is not None:
            return news
        if flight.steps is None:
            return None
        if flight.steps != flight.steps_ahead:
            return "finish"
        if self.eos_id is not None:
            final_toks, _, toks = flight.out[:3]
            # a row keeps its budget's worth of the block (an empty
            # slot's budget is 0, a final's what token #1 leaves)
            first = plan.first_col
            kept = np.minimum(plan.budget_vec, flight.steps - first)
            block = np.asarray(toks)
            cols = np.arange(block.shape[1])[None, :] - first[:, None]
            if (((block == self.eos_id) & (cols >= 0)
                 & (cols < kept[:, None])).any()
                    or (plan.finals and any(
                        int(t) == self.eos_id
                        for t in np.asarray(final_toks)[
                            [r for (r, _, f, _) in plan.packed if f]]))):
                return "finish"
        return None

    def _drain_mixed(self, flight) -> dict:
        """Install what a returned dispatch produced: finals (host
        state, radix adoption, token #1), every row's tokens recorded
        (its stream gets them with the next ``_deliver``), the counters;
        returns the dispatch record's fields (``DispatchTrace.commit``)."""
        plan, steps = flight.plan, flight.steps
        packed, spec_mixed = plan.packed, plan.spec_mixed
        prefill_tokens, n_active = plan.prefill_tokens, plan.n_active
        final_toks, final_lps = flight.out[:2]
        # an early dispatch was enqueued before the intake that would
        # have dropped a request cancelled by then: that request rode
        # it, gets none of its tokens, and the next intake sweeps it
        # (by identity: a request compares by its fields)
        gone = flight.gone
        # the rows the head ran over: the slab's one a segment (a
        # speculative program samples its slab only where it packed a
        # final), and every slot's at each step (a verify round's chunk
        # is the dispatch's draft width + 1 positions of each)
        head_rows = (
            (len(plan.seg[0]) if plan.finals or not spec_mixed else 0)
            + steps * self.max_batch * (plan.k_disp + 1))
        record = dict(
            t_launch=flight.t_launch, t_done=flight.t_done,
            with_finals=bool(plan.finals), segments=len(packed),
            finals=len(plan.finals), prefill_tokens=prefill_tokens,
            active_rows=n_active, steps=steps,
            kv_tokens=plan.kv_tokens, ahead=plan.ahead_s, how=plan.how,
            early=flight.early, slab_rows=plan.slab_rows,
            prefill_pages_walked=plan.prefill_pages_walked[0],
            prefill_pages_grid=plan.prefill_pages_grid,
            head_rows=head_rows,
            slab_carried_step=n_active if plan.carried else 0)
        if self.moe_counters is not None:
            # real tokens: the live segments' prompt tokens, and the
            # steps of the slots that decoded (rows that finish inside
            # the block still step to its end); each is k rows a layer
            # that has experts (a period of blocks of one sublayer has
            # them in its ``E`` blocks alone: ``ModelConfig.mlp_blocks``).
            # They are the rows the device routed: a row that holds no
            # token enters no expert's group
            acc = np.asarray(flight.out[5])
            E = self.cfg.experts_here
            record.update(self.moe_counters.add(
                acc[:E], int(acc[E]), int(acc[E + 1]), int(acc[E + 2]),
                (prefill_tokens + n_active * steps
                 + len(plan.finals) * max(0, steps - plan.carried))
                * self.cfg.experts_per_token
                * self.cfg.mlp_blocks   # the blocks that have experts
                * self.cfg.ut_steps))
        if self.loop_counters is not None:
            # (a step that rode the slab's pass is no pass of its own)
            record.update(self.loop_counters.add(
                bool(packed), steps - plan.carried))
        if "prefill_kv_tokens" in self.dispatch_trace.extra_fields:
            record["prefill_kv_tokens"] = plan.prefill_kv_tokens
        if self._eva is not None:
            record.update(plan.eva_cols,
                          windows_closed=self._eva_account(plan, steps))
        if self._state_free is not None:
            # rows x steps that advanced a state in the decode loop (a
            # row inside its budget; a final's has what token #1 left)
            # and the prompt tokens through the chunk form
            row_steps = int(np.minimum(
                plan.budget_vec, steps - plan.first_col).sum())
            st = self.state_stats
            st["row_steps"] += row_steps
            st["chunk_tokens"] += prefill_tokens
            record.update(zip(
                STATE_DISPATCH_FIELDS[self.cfg.state_kind.attn],
                (row_steps, prefill_tokens)))
        if "sparse_blocks_kept" in self.dispatch_trace.extra_fields:
            record.update(self._sparse_account(
                plan, steps, np.asarray(flight.out[5])))
        if self.hc_stats is not None:
            # a slab's pass holds the slots' rows too (whether or not a
            # step rode it), padded to the kernels' whole tiles
            rows = steps * self.max_batch
            if plan.slab_rows:
                rows += (whole_tiles(plan.slab_rows + self.max_batch)
                         - plan.carried * self.max_batch)
            self.hc_stats["rows"] += rows
            record["hc_rows"] = rows
        if self._wmgr is not None:
            record["kv_window_tokens"] = plan.kv_window_tokens
            record["prefill_window_pairs"] = plan.prefill_window_pairs
            record["prefill_window_pages_walked"] = (
                plan.prefill_pages_walked[1])
        cs = self.chunk_stats
        cs["mixed_dispatches"] += 1
        cs["mixed_prefill_tokens"] += prefill_tokens
        cs["mixed_budget_tokens"] += self.mixed_token_budget
        _t0 = self._prof.begin(flight.sig)
        if _t0 is not None:
            # sampled only, and the sample is the record's own time
            # (less what an early dispatch spent queued behind its
            # predecessor): nothing is blocked on.  Packed prefill
            # writes + every active row's per-step history read (a
            # verify round reads the dispatch's draft width + 1
            # positions of each), from
            # the rows' host state as `_decode_kv_bytes` has it from
            # the device's lengths: after a hit those are the next
            # dispatch's, and a read of them waits for it
            held = sum(len(s[0].prompt) + s[1] + steps
                       for s in plan.rows if s is not None)
            self._prof.end(
                flight.sig, _t0, seconds=flight.t_done - flight.t_begin,
                hbm_bytes=(prefill_tokens + held * max(
                    1, steps * (plan.k_disp + 1)))
                * self._kv_bytes_per_token)
        # finals first: install host state + radix adoption, record
        # token #1.  The adoption waits until after the dispatch — the
        # tree must never serve pages whose K/V is still in flight.
        if plan.finals:
            final_toks_np = np.asarray(final_toks)
            final_lps_np = np.asarray(final_lps)
            for (r0, a, is_final, slot) in packed:
                if not is_final:
                    continue
                req = a["req"]
                self._adms.remove(a)
                st = req._pkv
                plen = len(req.prompt)
                bt = self.kv_cache.block_tokens
                if (plen // bt >= 1 and self._wmgr is None
                        and self._eva is None):
                    adopted, store_lease = self.kv_cache.store_shared(
                        req.prompt, st["table"][:plen // bt])
                    st["adopted"] = adopted
                    st["store_lease"] = store_lease
                if spec_mixed:
                    # the fresh row's proposer state seeds HOST-SIDE,
                    # exactly as _finish_admission does — during the
                    # dispatch its draft table row was all-sentinel (or
                    # its history row untouched: inactive rows scatter
                    # out of bounds), so nothing stale survives
                    if self._spec_step is not None:
                        dbucket = self._bucket(plen)
                        dpad = np.zeros((1, dbucket), np.int32)
                        dpad[0, :plen] = req.prompt
                        drow_k, drow_v = self._dprefill(
                            self.draft_params, jnp.asarray(dpad),
                            *self._zero_row_d())
                        self._dpk, self._dpv = self._write_row(
                            self._dpk, self._dpv, drow_k, drow_v,
                            jnp.asarray(st["dtable"]))
                        self._dtables[slot] = st["dtable"]
                    if self._pld_step is not None:
                        hpad = np.zeros((1, self._bucket(plen)),
                                        np.int32)
                        hpad[0, :plen] = req.prompt
                        self._history = self._admit_h(
                            self._history, jnp.asarray(hpad),
                            jnp.int32(slot), jnp.int32(plen),
                            jnp.int32(int(final_toks_np[r0])))
                    # fresh acceptor: start wide, re-learn
                    self._spec_krow[slot] = self._spec_buckets[-1]
                    self._spec_ewma[slot] = 1.0
                self._slots[slot] = req
                self._flight.record("batch_admit", slot=slot,
                                    prompt_len=plen,
                                    max_new=req.max_new,
                                    prefix_reused=a["m"])
                if id(req) not in gone:
                    self._record_token(
                        slot, req, int(final_toks_np[r0]),
                        None if spec_mixed else float(final_lps_np[r0]))
        if spec_mixed:
            num_rounds, k_vec, live0 = (plan.num_rounds, plan.k_vec,
                                        plan.live0)
            em_np, ns_np = flight.em_np, flight.ns_np
            emitted = int(ns_np[:, live0].sum()) if live0 else 0
            cs["mixed_packed_tokens"] += prefill_tokens + emitted
            if num_rounds > 0:
                self._count_loop(num_rounds)
                for r0 in range(num_rounds):
                    self._drain_spec_blocks(em_np[r0], ns_np[r0],
                                            k_vec=k_vec)
                if self.spec_adaptive:
                    self._update_spec_krow(live0, k_vec, ns_np,
                                           num_rounds)
            if num_rounds > 0 and plan.adms_left:
                cs["interleaved_steps"] += 1
            return record
        toks, lps = flight.out[2:4]
        cs["mixed_packed_tokens"] += (prefill_tokens
                                      + n_active * steps)
        if steps > 0:
            self._count_loop(steps)
            self._step_count += steps
            # (a final of a slab that carried a step: columns 1 ..)
            self._record_row_blocks(
                np.asarray(toks),
                [0 if req is None or id(req) in gone else steps
                 for req in self._slots],
                np.asarray(lps), first=plan.first_col)
        if steps > 0 and plan.adms_left:
            cs["interleaved_steps"] += 1
        return record

    def _update_spec_krow(self, live0, k_vec, ns_np, num_rounds: int
                          ) -> None:
        """EWMA acceptance feedback (docs/DESIGN.md §22): fold one
        dispatch's realized acceptance rate — per row live at dispatch
        START, extra tokens kept over drafts offered — into the row's
        EWMA, then re-bucket K_row to the smallest bucket covering
        ``ewma * num_draft``.  A collapsing acceptor walks down to
        K_row = 1 (plain decode's price); recovery walks it back up."""
        buckets = self._spec_buckets
        alpha = self._spec_ewma_alpha
        for i in live0:
            offered = num_rounds * max(1, int(k_vec[i]))
            kept = int(ns_np[:, i].sum()) - num_rounds
            rate = min(1.0, max(0.0, kept / offered))
            self._spec_ewma[i] = ((1.0 - alpha) * self._spec_ewma[i]
                                  + alpha * rate)
            want = self._spec_ewma[i] * self.num_draft
            self._spec_krow[i] = next(
                (b for b in buckets if b >= want), buckets[-1])

    def _loop(self):
        try:
            self._loop_body()
        except BaseException as e:
            # a failed decode step (device lost, OOM, ...) must not strand
            # every waiter on a dead thread: fail all in-flight and queued
            # requests with the underlying error, then refuse new work.
            # The submit lock orders the drain after any submit that
            # already saw _running True — its request lands before the
            # drain runs, so none can slip past onto the dead thread.
            # The flight ring holds the admissions/steps leading up to
            # the failure; capture them before the drain mutates state.
            self._scheduler_error = f"{type(e).__name__}: {e}"
            self._flight.record("scheduler_crash",
                                error=type(e).__name__, detail=str(e))
            postmortem.trigger(
                "scheduler_crash",
                detail={"error": f"{type(e).__name__}: {e}",
                        "active_slots": sum(1 for s in self._slots
                                            if s is not None),
                        "steps": self._step_count})
            with self._submit_lock:
                self._running = False
                self._drain_all(e)

    def _loop_body(self):
        if self.mixed_token_budget > 0:
            # MIXED mode: one token-budget dispatch per iteration —
            # decode fusion survives admission (no fuse suppression,
            # no one-admission-at-a-time rule).  The serialized loop
            # below is untouched: it is the bit-identity reference.
            while self._running:
                self.dispatch_trace.enter("bookkeeping")
                self.anomaly.observe(self.stats)
                self._sample_hbm()
                self._mixed_iteration()
            self.dispatch_trace.leave()
            self._drain_all(
                RuntimeError("engine closed while request in flight"))
            return
        while self._running:
            # anomaly watch rides the loop (throttled internally; the
            # stats() snapshot is only built when an observation is due)
            self.anomaly.observe(self.stats)
            self._sample_hbm()
            free = [i for i, s in enumerate(self._slots) if s is None]
            # one dispatch of the in-progress chunked admission (if any)
            self._advance_admission(free)
            # the streams get what a step recorded when it ends, here as
            # in the mixed loop one hand-off a stream: before the thread
            # may block, after the admissions, after the step
            self._deliver()
            # block for work only when truly idle: nothing decoding, no
            # admission mid-stream, nothing waiting to be served
            timeout = (None if not (any(self._slots) or self._adm
                                    or self._pending)
                       else 0.0)
            # drain newly queued requests behind the already-pending ones
            while True:
                try:
                    req = self._queue.get(timeout=timeout)
                except queue.Empty:
                    break
                timeout = 0.0
                if req is _WAKE:           # export_request nudge
                    continue
                if req is None:            # close() sentinel
                    break
                self._pending.append(req)
            # serve pending FIFO: a chunk-needing prompt starts streaming
            # slot-FREE (only its final sampling prefill needs a slot, so
            # its chunks overlap busy decode); short prompts admit into
            # free slots.  Serviceable requests pass blocked ones.
            still: "deque[Request]" = deque()
            for req in self._pending:
                if req.cancelled:          # dropped while waiting
                    self._fail_request(req, None)
                elif self._needs_stream(req):
                    if self._adm is None:
                        # consumes no slot; False = paged pool full now,
                        # wait for a completion to free pages
                        if not self._start_admission(req):
                            still.append(req)
                    else:
                        still.append(req)  # one stream at a time
                elif free:
                    slot = free.pop(0)
                    try:
                        self._admit_request(slot, req)
                    except _BlocksExhausted:
                        # give the slot back: a later pending request
                        # whose pages ARE available must take it this
                        # pass (serviceable requests pass blocked ones)
                        free.insert(0, slot)
                        still.append(req)  # retry when pages free up
                    except BaseException as e:  # surface to the waiter
                        self._fail_request(req, e)
                else:
                    still.append(req)      # waiting for a slot
            self._pending = still
            self._sweep_cancelled()
            # serve export checkpoints between steps: state is
            # consistent here (pending drained, cancels swept, no
            # dispatch in flight)
            self._service_exports()
            self._deliver()
            if not any(self._slots):
                continue

            # fuse a block whenever no admission DISPATCH could land
            # anyway: an admission mid-stream always lands one per
            # iteration, so streaming disables fusing outright (its next
            # chunk must not wait out a fused block — time-to-first-token
            # beats peak decode throughput for the stream's duration);
            # otherwise fuse when nothing is waiting, or when every slot
            # is busy (the saturated regime is exactly where the fused
            # path pays — a backlog must not silently disable it)
            all_busy = all(s is not None for s in self._slots)
            fuse = (self.decode_block > 1 and self._adm is None
                    and (not self._pending or all_busy))
            if self._adm is not None:
                self.chunk_stats["interleaved_steps"] += 1
            self._step_active(self.decode_block if fuse else 1)
            self._deliver()

        # drain: fail anything still queued or in flight
        self._drain_all(RuntimeError("engine closed while request in flight"))
