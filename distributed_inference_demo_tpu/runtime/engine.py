"""Single-stage inference engine: prefill + fused decode loop.

The reference's token loop does, *per token, per device*: socket recv ->
deserialize -> ORT session metadata reflection -> run -> serialize -> socket
send -> host-side sampling in C++ (``Communication.java:682-928``,
``inference.cpp:145-218``, ``decoding.cpp:24-66``).  The TPU-native engine
collapses all of it into two compiled programs:

- ``prefill``: one jit over the whole prompt chunk.
- ``decode_loop``: ONE ``lax.while_loop`` over the new tokens — sampling
  fused in, KV cache donated, on-device eos/stop-token matching with
  ALL-ROWS-DONE EARLY EXIT, zero host round-trips until the token block
  comes back.  Per-token host work is literally nothing, and an early
  eos no longer burns the remainder of a fixed block.

``generate_stream`` runs the same loop in K-token chunks
(``stream_block``): one host dispatch per K tokens instead of per token
(the host dispatch floor amortizes K-fold), flushing
early when the device reports all rows done; K=1 keeps the per-token
jitted step the loop is bit-identical to (the reference streams partial
strings to the UI via DataRepository, ``Communication.java:629-638``).

Also enforces the KV capacity bound host-side (prompt + new tokens <=
max_seq) — the traced path cannot (dynamic_update_slice clamps silently).
"""

from dataclasses import dataclass
from functools import partial
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import KVCache, ModelConfig, StageParams, StageSpec
from ..models.decoder import stage_forward
from ..ops.flash_attention import make_flash_attn_impl
from ..ops.sampling import (SamplingParams, match_stop_ids, pad_stop_ids,
                            sample_logits)
from ..telemetry import profiling as _profiling
from ..telemetry.flightrecorder import get_flight_recorder
from ..telemetry.runlog import get_run_log


def resolve_stream_block(stream_block) -> int:
    """The streaming decode-block size K, ONE owner for every engine
    that fuses K device-loop steps per host dispatch: ``None`` defers to
    the ``DWT_STREAM_BLOCK`` env knob, default 1 (the per-token path —
    the parity reference the device loop is pinned against)."""
    if stream_block is None:
        from ..telemetry._env import env_int
        stream_block = env_int("DWT_STREAM_BLOCK", 1)
    stream_block = int(stream_block)
    if stream_block < 1:
        raise ValueError(f"stream_block must be >= 1, got {stream_block}")
    return stream_block


def count_device_loop(engine_name: str, steps: int,
                      dispatches: int = 1) -> None:
    """Feed the device-loop telemetry pair: one host DISPATCH issued,
    ``steps`` decode steps executed inside it.  dispatches/token ≈ 1/K
    is the invariant ``tests/test_device_loop.py`` holds; the benchmark
    reads the pair from ``/stats`` as ``sched_steps_per_dispatch``."""
    from ..telemetry.catalog import (ENGINE_DEVICE_LOOP_STEPS,
                                     ENGINE_HOST_DISPATCHES)
    ENGINE_HOST_DISPATCHES.inc(dispatches, engine=engine_name)
    ENGINE_DEVICE_LOOP_STEPS.inc(steps, engine=engine_name)


def shard_engine_params(params: "StageParams", cfg: "ModelConfig", mesh):
    """Place a full parameter tree onto a tp mesh in the engine's layout
    (Megatron-sliced weights, replicated embed — the same specs the
    forward's shard_map consumes) — the companion to
    ``InferenceEngine(mesh=...)``.  Without this the engine is still
    correct (GSPMD reshards per call) but the weights waste HBM on every
    chip."""
    from ..parallel.sharding import shard_params

    return shard_params(params, cfg, mesh, vocab_parallel_embed=False)


def check_capacity(max_seq: int, prompt_len: int, max_new_tokens: int):
    """Host-side KV capacity bound shared by all engines (the traced path
    cannot enforce it — ``dynamic_update_slice`` clamps silently)."""
    need = prompt_len + max_new_tokens
    if need > max_seq:
        raise ValueError(
            f"prompt ({prompt_len}) + new tokens ({max_new_tokens}) = "
            f"{need} exceeds KV-cache capacity {max_seq}")


@dataclass
class GenerationResult:
    tokens: np.ndarray          # [batch, max_new_tokens] int32
    prompt_len: int
    num_new: int
    seconds: float = 0.0
    # model log-probabilities of the emitted tokens (raw log-softmax, NOT
    # the temperature/top-k-filtered sampling distribution — the
    # OpenAI-style convention), [batch, max_new_tokens] f32, or None
    logprobs: Optional[np.ndarray] = None
    # what the engine says, a sequence, of how it was generated beyond its
    # tokens (JSON values; the reply's ``generation``), or None: a model
    # with a recurrent state gives a sample of the state each ended in
    generation: Optional[list] = None
    # decode steps the device loop actually RAN (docs/DESIGN.md §13):
    # early exit on eos/stop can make this < num_new, in which case
    # token columns >= steps_computed are deterministic padding the
    # device never computed.  None = engines without the loop (every
    # step ran).
    steps_computed: Optional[int] = None

    @property
    def tokens_per_second(self) -> float:
        """Throughput over steps the device actually ran — an
        early-exited run must not claim rate for padding it skipped."""
        steps = (self.steps_computed if self.steps_computed is not None
                 else self.num_new)
        total = self.tokens.shape[0] * steps
        return total / self.seconds if self.seconds > 0 else float("nan")


def validate_prefill_chunk(prefill_chunk, max_seq: int):
    """The chunk-size rule, ONE owner for every engine that accepts
    ``prefill_chunk`` (plain / speculative / prompt-lookup)."""
    if prefill_chunk is not None and not (1 <= prefill_chunk <= max_seq):
        raise ValueError(
            f"prefill_chunk must be in [1, max_seq={max_seq}]")
    return prefill_chunk


def make_chunk_programs(fwd):
    """``(chunk_mid, chunk_last)`` jitted programs over a forward seam —
    ONE factory shared by InferenceEngine and SpeculativeEngine (which
    builds a pair per model), so the two engines' chunk programs cannot
    drift and :func:`run_chunked_prefill` has one set of semantics."""

    @partial(jax.jit, donate_argnums=(2,))
    def chunk_mid(params, ids, cache, start):
        """One non-final prompt chunk: extend the cache, drop logits."""
        b, s = ids.shape
        pos = start + jnp.broadcast_to(jnp.arange(s), (b, s))
        _, cache = fwd(params, ids, cache, pos, s - 1)
        return cache

    @partial(jax.jit, donate_argnums=(2,))
    def chunk_last(params, ids, cache, start, gather_idx):
        """Final (possibly pad-tailed) chunk: logits at the prompt's
        true last position, column ``gather_idx`` of the chunk (the
        head runs on that position alone)."""
        b, s = ids.shape
        pos = start + jnp.broadcast_to(jnp.arange(s), (b, s))
        logits, cache = fwd(params, ids, cache, pos, gather_idx)
        return logits[:, 0], cache

    return chunk_mid, chunk_last


def make_paged_chunk_programs(fwd_p, bind_tables):
    """``(chunk_mid, slab_body, slab_step_body)`` prefill programs over a
    PAGED forward seam (``make_paged_forward_seam``): chunks write K/V
    straight to the page pool through the block tables — no dense temp
    row, no gather/scatter round trip, ``dwt_kvcache_h2d_bytes_total``
    stays 0.

    ``chunk_mid`` is the jitted non-final-chunk program (pool donated,
    logits dropped) used by serialized chunked admission; ``slab_body``
    is the UNJITTED traced body for a [n_seg, C] slab of segments at
    per-row start offsets — the mixed token-budget dispatches compose it
    with their decode rounds inside ONE jit (batching's speculative
    mixed programs), so it must stay a plain function; ``slab_step_body``
    is the same slab with the decoding rows' next step in the same
    forward, which ``batching._mixed_step`` composes with the rest of
    the fused decode loop.  All rely on the paged attention
    path's prefill contract: in-chunk keys are written before the
    gather/kernel inside each layer, and causal masking keeps a
    segment's queries on its own prior pages plus in-chunk keys
    (ops/paged_attention.paged_prefill_attention)."""

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk_mid(params, pk, pv, ids, tables, start):
        """One non-final prompt chunk at global offset ``start``,
        written through ``tables`` [b, W]: extend the pool, drop
        logits."""
        bind_tables(tables, "paged_chunk_mid")
        b, s = ids.shape
        pos = start + jnp.broadcast_to(jnp.arange(s), (b, s))
        cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
        _, cache = fwd_p(params, ids, cache, pos, s - 1)
        return cache.keys, cache.values

    def slab_body(params, cache, ids, tables, starts, last, program,
                  moe_stats=False, ntok=None):
        """Traced slab forward: row r of ``ids`` [n, s] runs at
        positions ``starts[r] + arange(s)`` through ``tables[r]``;
        returns logits at ONE position a row, column ``last[r]`` (the
        last token the segment holds, the only one anybody samples:
        ``[n, 1, V]``, the head never sees the other ``s - 1``
        positions) and the extended cache, and with ``moe_stats`` the
        seam's expert row counts, over the first ``ntok[r]`` positions
        of each row (the tokens it holds; the rest enter no expert's
        group).  ``program`` names the jitted program composing the
        slab (the attention-path record's key)."""
        bind_tables(tables, program)
        b, s = ids.shape
        pos = starts[:, None] + jnp.arange(s)[None, :]
        if moe_stats:
            return fwd_p(params, ids, cache, pos, last, moe_stats=True,
                         valid=jnp.arange(s)[None, :] < ntok[:, None])
        logits, cache = fwd_p(params, ids, cache, pos, last)
        return logits, cache

    def slab_step_body(params, cache, ids, tables, starts, last, tok,
                       dec_tables, lengths, program, moe_stats=False,
                       ntok=None, riding=None):
        """``slab_body`` and one lockstep decode step of rows ``tok``
        [B] at positions ``lengths`` through ``dec_tables``, in ONE
        forward (docs/DESIGN.md section 19): the segments' ``n x s``
        positions and then the ``B`` decoding rows lie side by side in
        one row ``[1, n s + B]``, so everything that works a row at a
        time (the matrices, the experts, the head) runs once over both,
        and a mixer sublayer runs the slab through ``tables`` on its
        prefill route and the decoding rows through ``dec_tables`` on
        their decode route (``bind_tables`` takes the pair:
        ``ops.paged_attention.split_rows``).  Returns ``(slab logits
        [n, 1, V], step logits [B, 1, V], cache)``, and with
        ``moe_stats`` the expert row counts of the one pass, over each
        segment's first ``ntok`` positions and the rows ``riding`` [B]
        bool (the rows that decode)."""
        bind_tables((tables, dec_tables), program)
        n, s = ids.shape
        B = tok.shape[0]
        row = lambda slab, step: jnp.concatenate(
            [slab.reshape(n * s), step])[None]
        pos = row(starts[:, None] + jnp.arange(s)[None, :], lengths)
        at = jnp.concatenate([jnp.arange(n) * s + jnp.clip(last, 0, s - 1),
                              n * s + jnp.arange(B)])[None]
        kw = {}
        if moe_stats:
            kw = {"moe_stats": True,
                  "valid": row(jnp.arange(s)[None, :] < ntok[:, None],
                               riding)}
        logits, cache, *moe = fwd_p(params, row(ids, tok), cache, pos, at,
                                    **kw)
        return (logits[0, :n, None], logits[0, n:, None], cache, *moe)

    return chunk_mid, slab_body, slab_step_body


def run_chunked_prefill(params, ids, cache, C: int, max_seq: int,
                        chunk_mid, chunk_last=None, start: int = 0):
    """The chunked-prefill driver, shared by InferenceEngine and
    SpeculativeEngine (which runs it once per model).

    The prompt is zero-padded to a chunk multiple and every chunk runs
    through the same compiled programs (mid + last) — one chunk shape
    for ALL prompt lengths, short ones included.  The final chunk is
    left-shifted when the padded length would spill past ``max_seq``
    ("aligned last window"): the overlapped real tokens are recomputed
    and rewritten at their own positions (same values — K/V depend only
    on the prefix), so no pad slot is ever written beyond max_seq and
    ``dynamic_update_slice`` can never clamp into valid entries.  The
    cache's valid length is rewound to the true prompt length afterwards
    so decode's first insert overwrites the first pad slot (stale-slot
    invariant).

    ``chunk_last=None`` runs the final chunk through ``chunk_mid`` too
    and returns ``(None, cache)`` — the draft-model case, where only the
    filled cache matters and no logits are needed.

    ``start``: prefill SUFFIX mode — ``ids`` are the tokens from
    position ``start`` on, and the cache already holds exact K/V for
    columns ``[0, start)`` (a KV-cache block run, runtime/kvcache).
    Chunks run at global offsets and the aligned last window never
    left-shifts below ``start`` (the overlapped-recompute trick needs
    the overlapped ids, and the caller only has the suffix); when the
    room past ``start`` is smaller than one chunk, the suffix runs as a
    single unpadded dispatch instead."""
    b, plen = ids.shape
    cap = max_seq - start          # columns available at/after start
    cache = KVCache(cache.keys, cache.values, jnp.int32(start))
    if cap < C:
        # near-capacity seeded suffix: no room to pad to a whole chunk
        # without spilling past max_seq, and no room to left-shift
        # without the prefix ids — one unpadded dispatch (a per-length
        # compile, only reachable on prefix hits within C of max_seq)
        if chunk_last is None:
            cache = chunk_mid(params, ids, cache, jnp.int32(start))
            last = None
        else:
            last, cache = chunk_last(params, ids, cache, jnp.int32(start),
                                     jnp.int32(plen - 1))
        return last, KVCache(cache.keys, cache.values,
                             jnp.int32(start + plen))
    n_chunks = -(-plen // C)
    padded = jnp.zeros((b, n_chunks * C), jnp.int32)
    padded = jax.lax.dynamic_update_slice(padded, ids, (0, 0))
    for i in range(n_chunks - 1):
        cache = chunk_mid(params, jax.lax.dynamic_slice_in_dim(
            padded, i * C, C, axis=1), cache, jnp.int32(start + i * C))
    tail_start = min((n_chunks - 1) * C, cap - C)
    # the left shift must apply to the cache WRITE offset too (the
    # insert position is cache.length inside stage_forward), so the
    # column==position invariant holds; with the buffer padded past
    # max_seq (pad_cache_capacity) the old implicit
    # dynamic_update_slice start-clamp no longer realizes it
    cache = KVCache(cache.keys, cache.values, jnp.int32(start + tail_start))
    tail = jax.lax.dynamic_slice_in_dim(padded, tail_start, C, axis=1)
    if chunk_last is None:
        cache = chunk_mid(params, tail, cache, jnp.int32(start + tail_start))
        last = None
    else:
        last, cache = chunk_last(params, tail, cache,
                                 jnp.int32(start + tail_start),
                                 jnp.int32(plen - 1 - tail_start))
    cache = KVCache(cache.keys, cache.values, jnp.int32(start + plen))
    return last, cache


def run_seeded_prefill(params, ids, cache, C, max_seq, prefill,
                       chunk_mid, chunk_last, start: int = 0):
    """Whole-prompt or chunked prefill with an optional KV-cache-seeded
    prefix: the ONE dispatch rule shared by InferenceEngine and
    PromptLookupEngine (SpeculativeEngine drives the same pieces per
    model).  ``start`` > 0: the cache already holds exact K/V for
    columns ``[0, start)`` and only ``ids[:, start:]`` runs — one
    chunk-last dispatch (compiled per suffix length, no worse than the
    whole-prompt prefill's per-length compile), or the chunked driver's
    suffix mode."""
    if start:
        suffix = ids[:, start:]
        if C is not None:
            return run_chunked_prefill(params, suffix, cache, C, max_seq,
                                       chunk_mid, chunk_last, start=start)
        cache = KVCache(cache.keys, cache.values, jnp.int32(start))
        last, cache = chunk_last(params, suffix, cache, jnp.int32(start),
                                 jnp.int32(suffix.shape[1] - 1))
        return last, KVCache(cache.keys, cache.values,
                             jnp.int32(ids.shape[1]))
    if C is None:
        return prefill(params, ids, cache)
    return run_chunked_prefill(params, ids, cache, C, max_seq,
                               chunk_mid, chunk_last)


def resolve_cache_dtype_backend(kv_cache_dtype, attn_backend: str):
    """The reduced-precision-cache rule, ONE owner for every engine
    (plain / speculative / prompt-lookup / batching): a reduced-dtype KV
    cache forces the jnp attention path (the Pallas kernel is not
    exercised on f8 loads), and an explicit non-jnp kernel request
    errors rather than silently downgrading.  Returns
    ``(jnp.dtype | None, attn_backend)``."""
    dt = jnp.dtype(kv_cache_dtype) if kv_cache_dtype else None
    if dt is not None:
        if attn_backend not in ("auto", "jnp"):
            raise ValueError(
                f"attn_backend={attn_backend!r} is incompatible with "
                "kv_cache_dtype (the Pallas kernel is not exercised "
                "on reduced-precision cache loads); use 'auto' or "
                "'jnp'")
        attn_backend = "jnp"
    return dt, attn_backend


def resolve_attn_impl(kv_cache_dtype, attn_backend: str):
    """``(kv_cache_dtype, attn_backend, attn_impl)`` for the dense-cache
    engines (plain / speculative / prompt-lookup) — the ONE place that
    turns "auto" into a kernel choice: the Pallas flash kernel when
    JAX's default backend is a TPU, the jnp path elsewhere.  JAX falls
    back to the CPU without a word when it finds no TPU, so the
    resolved name is what callers print and report (``SERVE_ENGINE
    ... attn=``); a run that must be on the chip sets
    ``JAX_PLATFORMS=tpu`` so that a missing chip is an error.  Under a
    tp mesh the impl runs inside the shard on its local kv heads."""
    dt, attn_backend = resolve_cache_dtype_backend(kv_cache_dtype,
                                                   attn_backend)
    if attn_backend == "auto":
        attn_backend = ("flash" if jax.default_backend() == "tpu"
                        else "jnp")
    if attn_backend == "flash":
        return dt, attn_backend, make_flash_attn_impl()
    if attn_backend == "jnp":
        return dt, attn_backend, None
    raise ValueError(f"unknown attn_backend {attn_backend!r}; expected "
                     "'auto', 'flash', or 'jnp'")


class InferenceEngine:
    """KV-cached generation over a full model — single chip, or
    tensor-parallel over a tp mesh (``mesh=`` + :func:`shard_engine_params`)."""

    def __init__(self, cfg: ModelConfig, params: StageParams,
                 max_seq: Optional[int] = None,
                 sampling: SamplingParams = SamplingParams(),
                 eos_id: Optional[int] = None,
                 attn_backend: str = "auto",
                 kv_cache_dtype: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 mesh=None,
                 kv_cache_blocks: Optional[int] = None,
                 kv_block_tokens: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 stop_token_ids=None,
                 stream_block: Optional[int] = None):
        """``attn_backend``: "auto" (Pallas flash kernel on TPU, jnp
        elsewhere), "flash", or "jnp".

        ``kv_cache_blocks`` / ``kv_block_tokens``: the prefix-reuse pool
        behind the ``runtime/kvcache`` backend seam (docs/DESIGN.md
        §14), device-resident: hits gather pages into the fresh cache
        on device and stores scatter blocks back — zero bytes cross the
        host boundary either way.  The ONE request in flight decodes
        against a contiguous working cache its decode loop donates; the
        standing pool is where reserved HBM lives.

        ``mesh``: a ``jax.sharding.Mesh`` with a ``tp`` axis — every
        forward then runs inside a shard_map with Megatron-sliced weights
        and a kv-head-sharded cache (BASELINE config #3: attention-head
        shards across chips via ICI all-gather); activations/logits come
        back replicated so sampling and the decode scan are unchanged.
        Pass params through :func:`shard_engine_params` first so the
        weight shards live on their chips.  Forces the jnp attention path
        (the Pallas kernel is not exercised per-shard).

        ``prefill_chunk``: process prompts in fixed chunks of this many
        tokens instead of one whole-prompt program.  Bounds prefill
        activation memory (a 32k-token prompt's [b, s, I] MLP
        intermediates dwarf the weights) and keeps ONE compiled chunk
        shape regardless of prompt length — the prompt is padded up to a
        chunk multiple and the pad positions are overwritten by decode
        before anything can attend them (same stale-slot invariant as
        speculative rollback / batching admission).

        ``kv_cache_dtype``: store the KV cache at a reduced precision,
        e.g. "float8_e4m3fn" — HALF the cache bytes (and cache-read
        traffic, which rivals the weight stream at large batch x long
        context) with no scale bookkeeping, at a small accuracy cost.
        Attention math stays f32 (``ops.attention`` upcasts whatever the
        cache holds); inserts round via ``update_kv_cache``'s cast.
        Forces the jnp attention path (the Pallas kernel is not exercised
        on f8 loads).

        ``kv_dtype``: page WIDTH of the prefix-reuse pool behind the
        kvcache seam — "bf16" (full width, the default), "int8", or
        packed "int4" with a per-token scale sidecar riding the same
        block table (docs/DESIGN.md §17).  Resolved arg over
        ``DWT_KV_DTYPE`` over bf16 inside ``make_kv_backend``; mutually
        exclusive with the ``kv_cache_dtype`` storage cast.  The dense
        working cache for the one request in flight stays full width —
        quantization happens at the page boundary (store scatter), and
        seeds dequantize back to full rows.

        ``kv_cache_blocks`` / ``kv_block_tokens``: block-level KV prefix
        cache (``runtime/kvcache``, docs/DESIGN.md §10) for the
        single-request ``generate``/``generate_stream`` paths (batch 1):
        a prompt sharing whole leading blocks with any previously
        prefilled prompt seeds its cache from the stored blocks and
        prefills only the suffix; every prefill stores its full blocks
        back.  ``None`` defers to ``DWT_KVCACHE_*`` env knobs; default
        off (0) — the continuous-batching engine is the default-on
        consumer.

        ``stop_token_ids``: token ids that end a row ON DEVICE, inside
        the fused decode loop (docs/DESIGN.md §13) — single-token stop
        matching at zero host round-trips (text-level stop STRINGS stay
        a server-side concern, runtime/http_server.StopMatcher).  The
        stop token itself is emitted (the eos-include convention); the
        row then pads with eos like an eos finish.  With ``eos_id``
        UNSET there is no pad token: ``generate``'s fixed-width output
        pads with token 0 past the cut — read
        ``GenerationResult.steps_computed`` for where real output ends,
        or use ``generate_stream``, which simply stops.

        ``stream_block``: fuse this many decode steps per
        ``generate_stream`` host dispatch (K).  The device loop checks
        eos/stop and all-rows-done ON DEVICE, so an early finish exits
        after j <= K steps instead of burning the block; the host sees
        tokens in K-sized chunks (dispatches/token ≈ 1/K — the host
        dispatch floor amortizes K-fold).
        1 (default; ``DWT_STREAM_BLOCK`` env between) keeps the
        per-token path, which the fused loop is bit-identical to
        (greedy) by construction."""
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq or cfg.max_seq_len
        self.sampling = sampling
        self.eos_id = eos_id
        self.spec = StageSpec(0, 1, 0, cfg.num_layers)
        self.prefill_chunk = validate_prefill_chunk(prefill_chunk,
                                                    self.max_seq)
        self.stream_block = resolve_stream_block(stream_block)
        self._stop_ids = pad_stop_ids(stop_token_ids)
        self._has_stop_ids = bool(stop_token_ids)
        # host-dispatch / device-step counters for THIS engine instance
        # (the dwt_engine_* series aggregate across instances); ``stats()``
        # and the 1/K invariant test (tests/test_device_loop.py) read these
        self.loop_stats = {"host_dispatches": 0, "device_loop_steps": 0}
        self.mesh = mesh
        # kv_cache_dtype composes with a tp mesh: the insert cast
        # (update_kv_cache) and the read upcast (ops.attention) both run
        # INSIDE the shard on its local kv-head planes, and the cache
        # sharding specs are dtype-agnostic (parity pinned by
        # tests/test_engine.py)
        self.kv_cache_dtype, self.attn_backend, attn_impl = \
            resolve_attn_impl(kv_cache_dtype, attn_backend)

        self._attn_impl = attn_impl   # shared with MultimodalEngine

        from .kvcache import make_kv_backend
        self.kv_cache = make_kv_backend(
            cfg, kv_cache_blocks, kv_block_tokens,
            dtype=self.kv_cache_dtype, kv_dtype=kv_dtype,
            default_blocks=0)

        cfg_ = cfg
        spec_ = self.spec
        samp_ = sampling

        # forwards run through the seam from parallel/tensor.py (the one
        # owner of the manual-TP layout): a tp shard_map under a mesh,
        # plain stage_forward otherwise — the code above the seam
        # (sampling, scans, chunking) is mesh-oblivious either way
        from ..parallel.tensor import make_forward_seam
        fwd, self._cache_sharding = make_forward_seam(
            cfg, self.spec, mesh, params, attn_impl=attn_impl)

        @jax.jit
        def prefill(params, ids, cache):
            b, s = ids.shape
            pos = jnp.broadcast_to(jnp.arange(s), (b, s))
            # the LM head runs on the final position only ([b, 1, V]) — a
            # full [b, s, V] logits tensor at long prompts would burn GBs
            # of HBM and head-matmul FLOPs for nothing.
            logits, cache = fwd(params, ids, cache, pos, s - 1)
            return logits[:, 0], cache

        self._prefill_chunk_mid, self._prefill_chunk_last = \
            make_chunk_programs(fwd)

        def _mask_eos(tok, done, eos):
            """Shared eos row-padding rule (eos < 0 = disabled); the eos id
            is a TRACED scalar so ``engine.eos_id`` can change between
            calls without recompiling or re-baking closures."""
            live = eos >= 0
            tok = jnp.where(done, jnp.where(live, eos, tok), tok)
            done = done | (live & (tok == eos))
            return tok, done

        def _emitted_lp(logits, tok):
            return jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), -1),
                tok[:, None].astype(jnp.int32), axis=-1)[:, 0]

        @partial(jax.jit, donate_argnums=(2,), static_argnums=(8, 9))
        def decode_loop(params, last_logits, cache, rng, eos, stop_ids,
                        done, limit, num_steps, with_logprobs=False):
            """The device-resident decode loop (docs/DESIGN.md §13): up
            to ``limit`` fused sample+forward steps in ONE dispatch,
            with on-device eos masking, stop-token-ID matching, and
            ALL-ROWS-DONE EARLY EXIT — an eos at step j < limit ends
            the loop after j+1 steps instead of burning the remainder
            of a fixed block; the host is touched once per block.

            ``num_steps`` (static) sizes the token/logprob buffers;
            ``limit`` (traced) bounds the trip count, so one compiled
            program serves both full blocks and the stream's tail
            block.  Rows that finished keep emitting deterministic eos
            padding while others run (``_mask_eos`` row-wise — the
            per-token path's semantics, which this loop is greedy
            bit-identical to: same rng split order, same mask-then-
            score step order).  Returns ``(toks [b, num_steps],
            lps [b, num_steps], next_logits, cache, rng, done,
            steps_ran)``; buffer columns >= steps_ran are eos padding
            the host must not read past."""
            b = last_logits.shape[0]
            pad = jnp.where(eos >= 0, eos, 0).astype(jnp.int32)
            toks0 = jnp.broadcast_to(pad, (b, num_steps)).astype(jnp.int32)
            lps0 = jnp.zeros((b, num_steps), jnp.float32)

            def cond(carry):
                j, logits, cache, rng, done, toks, lps = carry
                return (j < limit) & ~jnp.all(done)

            def body(carry):
                j, logits, cache, rng, done, toks, lps = carry
                rng, sub = jax.random.split(rng)
                tok = sample_logits(logits, sub, samp_)
                tok, done = _mask_eos(tok, done, eos)
                done = done | match_stop_ids(tok, stop_ids)
                if with_logprobs:
                    lp = _emitted_lp(logits, tok)
                else:
                    lp = jnp.zeros((b,), jnp.float32)
                toks = jax.lax.dynamic_update_slice(
                    toks, tok[:, None], (jnp.int32(0), j))
                lps = jax.lax.dynamic_update_slice(
                    lps, lp[:, None], (jnp.int32(0), j))
                pos = jnp.broadcast_to(cache.length, (b, 1))
                out, cache = fwd(params, tok[:, None], cache, pos, None)
                return (j + 1, out[:, 0], cache, rng, done, toks, lps)

            (steps, logits, cache, rng, done, toks, lps) = \
                jax.lax.while_loop(
                    cond, body,
                    (jnp.int32(0), last_logits, cache, rng, done,
                     toks0, lps0))
            return toks, lps, logits, cache, rng, done, steps

        @partial(jax.jit, donate_argnums=(2,))
        def decode_one(params, last_logits, cache, rng, eos, stop_ids,
                       done):
            """One streamed step — the PER-TOKEN path the device loop is
            pinned against; eos masking, stop-id matching, and the
            logprob all happen HERE in the same order as the loop's body
            (mask first, then score the emitted token), so the two paths
            agree on (token, logprob, done) triples row-wise."""
            rng, sub = jax.random.split(rng)
            tok = sample_logits(last_logits, sub, samp_)
            tok, done = _mask_eos(tok, done, eos)
            done = done | match_stop_ids(tok, stop_ids)
            b = tok.shape[0]
            # per-token logprob rides along (one [b, V] reduction; the
            # streaming path is dispatch-bound, so it's in the noise)
            lp = _emitted_lp(last_logits, tok)
            pos = jnp.broadcast_to(cache.length, (b, 1))
            out, cache = fwd(params, tok[:, None], cache, pos, None)
            return tok, lp, out[:, 0], cache, rng, done

        # observatory seams (docs/DESIGN.md §20): compile accounting on
        # the jitted programs + the sampled dispatch profiler.
        # decode_loop legitimately forks per static (num_steps,
        # with_logprobs) pair, so it carries NO variant budget — only
        # programs with a documented invariant feed recompile_storm.
        _ct = _profiling.get_compile_tracker()
        self._prefill = _ct.wrap("prefill", prefill)
        self._decode_loop = _ct.wrap("decode_loop", decode_loop)
        self._decode_one = _ct.wrap("decode_one", decode_one)
        self._prof = _profiling.get_profiler()
        # dense-cache attribution: KV bytes one (row, token) touches
        self._kv_token_bytes = _profiling.kv_dispatch_bytes(
            1, cfg.kv_planes, cfg.num_kv_heads, cfg.head_dim,
            None, self.kv_cache_dtype)

    # ------------------------------------------------------------------

    def _check_capacity(self, prompt_len: int, max_new_tokens: int):
        check_capacity(self.max_seq, prompt_len, max_new_tokens)

    def _eos_scalar(self):
        """eos_id as the traced sentinel scalar (-1 = disabled), read at
        call time so eos_id assignment between calls takes effect."""
        return jnp.int32(self.eos_id if self.eos_id is not None else -1)

    def _count_loop(self, steps: int, dispatches: int = 1) -> None:
        """One decode dispatch left the host and ran ``steps`` device
        steps: feed the instance counters + the dwt_engine_* series."""
        self.loop_stats["host_dispatches"] += dispatches
        self.loop_stats["device_loop_steps"] += steps
        count_device_loop(type(self).__name__, steps, dispatches)

    def new_cache(self, batch: int) -> KVCache:
        # KVCache.create pads the buffer to the sublane granule; max_seq
        # stays the enforced capacity bound (check_capacity)
        cache = KVCache.create(self.cfg, self.cfg.num_layers, batch,
                               self.max_seq, dtype=self.kv_cache_dtype)
        if self._cache_sharding is not None:
            # commit the fresh (donatable) buffers to their kv-head shards
            # up front so the first forward doesn't pay a reshard
            cache = jax.device_put(cache, self._cache_sharding)
        return cache

    def _run_prefill(self, ids: jnp.ndarray, cache: KVCache,
                     start: int = 0):
        """Whole-prompt or chunked prefill → (last_logits [b, V], cache).
        Chunked semantics (padding, aligned last window, length rewind)
        live in :func:`run_chunked_prefill`; the seeded-suffix dispatch
        rule in :func:`run_seeded_prefill` — both shared with the
        speculative and prompt-lookup engines.  ``start`` > 0 is the
        KV-cache-seeded SUFFIX path: ``ids`` still carries the whole
        prompt, columns ``[0, start)`` of the cache already hold its
        prefix K/V, and only ``ids[:, start:]`` runs."""
        return run_seeded_prefill(
            self.params, ids, cache, self.prefill_chunk, self.max_seq,
            self._prefill, self._prefill_chunk_mid,
            self._prefill_chunk_last, start=start)

    # -- block KV cache (runtime/kvcache) seams ------------------------

    def _kv_seed(self, ids: jnp.ndarray, cache: KVCache):
        """(start, cache): seed a fresh batch-1 cache from the longest
        cached block-prefix of the prompt, or (0, cache) on a miss —
        the backend seam (kvcache/backend.py) owns the layout-specific
        copy path (dense: host gather + H2D; paged: device gather,
        zero H2D)."""
        if self.kv_cache is None:
            return 0, cache
        return self.kv_cache.seed(ids, cache)

    def _kv_store(self, ids: jnp.ndarray, cache: KVCache) -> None:
        """Store the prefilled prompt's full blocks (batch 1 only).
        Must run before the decode scan donates the cache buffers."""
        if self.kv_cache is not None:
            self.kv_cache.store(ids, cache)

    def _decode(self, params, last_logits, cache, rng, eos, num_steps,
                with_logprobs=False):
        """Back-compat fused-decode surface (multimodal engine): the
        device loop with ``limit == num_steps``
        — same output contract as the old fixed-trip scan, now with
        all-rows-done early exit.  Returns ``(toks, lps, cache)``."""
        b = last_logits.shape[0]
        toks, lps, _, cache, _, _, steps = self._decode_loop(
            params, last_logits, cache, rng, eos, self._stop_ids,
            jnp.zeros((b,), bool), jnp.int32(num_steps), num_steps,
            with_logprobs)
        self._count_loop(int(steps))
        return toks, lps, cache

    def scrape_stats(self) -> dict:
        """Metrics-scrape fragment (telemetry/catalog.scrape): the KV
        cache counters, when the cache is on.  Deliberately NOT
        ``stats()`` — the /stats route keeps its engine-less shape."""
        return ({"kvcache": self.kv_cache.snapshot()}
                if self.kv_cache is not None else {})

    def debug_state(self) -> dict:
        """``GET /debugz`` fragment: KV cache occupancy/LRU picture +
        the device-loop dispatch accounting (§13 runbook)."""
        out = {"device_loop": dict(self.loop_stats,
                                   stream_block=self.stream_block),
               "observatory": _profiling.observatory_state()}
        if self.kv_cache is not None:
            out["kvcache"] = self.kv_cache.debug_state()
        return out

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 seed: int = 0, logprobs: bool = False) -> GenerationResult:
        """Batch generation, fused decode scan (the throughput path).

        Runs exactly once; ``seconds`` includes compile on the first call
        for a given shape signature (jit-cached afterwards).  A caller
        wanting steady-state timing calls this twice and keeps the second
        result (``cli.py``'s ``bench`` does).  ``logprobs=True`` also returns each
        emitted token's raw log-softmax probability.
        """
        import time
        ids = jnp.asarray(prompt_ids, jnp.int32)
        b, plen = ids.shape
        self._check_capacity(plen, max_new_tokens)
        rng = jax.random.PRNGKey(seed)

        t0 = time.perf_counter()
        cache = self.new_cache(b)
        start, cache = self._kv_seed(ids, cache)
        last_logits, cache = self._run_prefill(ids, cache, start=start)
        self._kv_store(ids, cache)
        _sig = _profiling.dispatch_signature(
            "decode_loop", batch=b, chunk=max_new_tokens,
            kv_dtype=np.dtype(self.kv_cache_dtype).name)
        _t0 = self._prof.begin(_sig)
        toks, lps, _, _, _, _, steps = self._decode_loop(
            self.params, last_logits, cache, rng, self._eos_scalar(),
            self._stop_ids, jnp.zeros((b,), bool),
            jnp.int32(max_new_tokens), max_new_tokens, logprobs)
        toks = np.asarray(toks)
        steps = int(steps)
        if _t0 is not None:
            # the asarray above already synced; every device step reads
            # each row's prompt history and writes one token
            self._prof.end(_sig, _t0, hbm_bytes=(
                b * (plen + 1) * steps * self._kv_token_bytes))
        self._count_loop(steps)
        lps_np = np.asarray(lps) if logprobs else None
        dt = time.perf_counter() - t0
        result = GenerationResult(tokens=toks, prompt_len=plen,
                                  num_new=max_new_tokens, seconds=dt,
                                  logprobs=lps_np, steps_computed=steps)
        rl = get_run_log()
        if rl.enabled:   # per-request summary in the structured run log
            rl.event("generate", engine=type(self).__name__,
                     batch=b, prompt_len=plen,
                     new_tokens=max_new_tokens,
                     seconds=round(dt, 6),
                     tokens_per_sec=round(result.tokens_per_second, 2))
        get_flight_recorder().record(
            "engine_generate", engine=type(self).__name__, batch=b,
            prompt_len=plen, new_tokens=max_new_tokens,
            seconds=round(dt, 6))
        return result

    def classify(self, prompt_ids: np.ndarray,
                 label_token_ids) -> np.ndarray:
        """Classify each row: argmax of the last-position logits restricted
        to ``label_token_ids`` (verbalizer tokens, one per class).  Returns
        [batch] int32 label indices.  The reference's classification
        variant (``inference.cpp:220-270``) as a single prefill."""
        ids = jnp.asarray(prompt_ids, jnp.int32)
        label_ids = np.asarray(label_token_ids, np.int64)
        if label_ids.ndim != 1 or label_ids.size < 2:
            raise ValueError("label_token_ids must be >= 2 token ids")
        if (label_ids < 0).any() or (label_ids >= self.cfg.vocab_size).any():
            raise ValueError(
                f"label_token_ids out of range [0, {self.cfg.vocab_size})")
        self._check_capacity(ids.shape[1], 0)
        cache = self.new_cache(ids.shape[0])
        logits, _ = self._run_prefill(ids, cache)
        sub = np.asarray(logits)[:, label_ids]
        pred = np.argmax(sub, axis=-1).astype(np.int32)
        rl = get_run_log()
        if rl.enabled:
            rl.event("classify", engine=type(self).__name__,
                     batch=int(ids.shape[0]),
                     prompt_len=int(ids.shape[1]),
                     num_labels=int(label_ids.size))
        get_flight_recorder().record(
            "engine_classify", engine=type(self).__name__,
            batch=int(ids.shape[0]), prompt_len=int(ids.shape[1]))
        return pred

    def generate_stream(self, prompt_ids: np.ndarray, max_new_tokens: int,
                        seed: int = 0,
                        logprobs: bool = False) -> Iterator[np.ndarray]:
        """Yield one [batch] token array per step (UI streaming path);
        with ``logprobs=True`` yields ([batch] tokens, [batch] logprobs)
        pairs instead.

        With ``stream_block`` K > 1 the per-token dispatch is replaced
        by the device loop: ONE dispatch produces up to K tokens
        (buffered host-side and yielded one step at a time, so the
        consumer surface is unchanged), the stream flushes early the
        moment the device reports all rows done, and the host never
        pays a dispatch for steps the loop skipped.  Greedy output is
        bit-identical to K=1 (pinned by tests)."""
        ids = jnp.asarray(prompt_ids, jnp.int32)
        b, plen = ids.shape
        self._check_capacity(plen, max_new_tokens)
        cache = self.new_cache(b)
        rng = jax.random.PRNGKey(seed)
        start, cache = self._kv_seed(ids, cache)
        logits, cache = self._run_prefill(ids, cache, start=start)
        self._kv_store(ids, cache)
        done = jnp.zeros((b,), bool)
        K = self.stream_block
        if K > 1:
            remaining = max_new_tokens
            _sig = _profiling.dispatch_signature(
                "decode_loop", batch=b, chunk=K,
                kv_dtype=np.dtype(self.kv_cache_dtype).name)
            while remaining > 0:
                _t0 = self._prof.begin(_sig)
                toks, lps, logits, cache, rng, done, steps = \
                    self._decode_loop(
                        self.params, logits, cache, rng,
                        self._eos_scalar(), self._stop_ids, done,
                        jnp.int32(min(K, remaining)), K, logprobs)
                steps = int(steps)
                if _t0 is not None:
                    # int(steps) above already synced the dispatch; rows
                    # entered at length plen + tokens already streamed
                    self._prof.end(_sig, _t0, hbm_bytes=(
                        b * (plen + max_new_tokens - remaining + 1)
                        * steps * self._kv_token_bytes))
                self._count_loop(steps)
                if steps == 0:      # all rows were already done on entry
                    return
                tok_np = np.asarray(toks)
                lp_np = np.asarray(lps) if logprobs else None
                for j in range(steps):
                    yield ((tok_np[:, j], lp_np[:, j]) if logprobs
                           else tok_np[:, j])
                remaining -= steps
                if bool(np.asarray(done).all()):
                    return
            return
        for _ in range(max_new_tokens):
            tok, lp, logits, cache, rng, done = self._decode_one(
                self.params, logits, cache, rng, self._eos_scalar(),
                self._stop_ids, done)
            self._count_loop(1)
            tok_np = np.asarray(tok)
            yield (tok_np, np.asarray(lp)) if logprobs else tok_np
            if ((self.eos_id is not None or self._has_stop_ids)
                    and np.asarray(done).all()):
                return
