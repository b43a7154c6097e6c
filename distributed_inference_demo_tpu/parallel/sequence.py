"""Sequence/context parallelism: long-context generation over the ``sp`` axis.

Absent in the reference (SURVEY.md §5.7: ``max_length=40``, no KV cache, no
sequence parallelism).  Here long prompts are first-class: the prompt is
sharded into contiguous chunks over the ``sp`` mesh axis, prefill runs
**ring attention** (ops/ring_attention.py) so no device ever materializes the
full sequence, and the KV cache stays sharded by sequence for the whole
generation — decode combines per-shard partial attention with an exact
log-sum-exp reduction instead of moving KV.

Decode-token placement is stateless round-robin, derived from the carried
global length: the d-th decoded token's K/V lands on rank ``d % sp`` at slot
``chunk + d // sp``, so cache shards stay balanced with no coordination
traffic; the ``kv_pos`` position map (-1 = empty slot) drives causal masking.

The decoder block itself is shared with every other path via the
``attn_impl`` hook of ``models.decoder.stage_forward`` — sequence parallelism
swaps the attention/cache strategy, not the model math.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.base import (KVCache, ModelConfig, StageSpec,
                           require_kv_pair, require_one_kind,
                           require_token_rows,
                           require_single_pass)
from ..models.decoder import stage_forward
from ..ops.attention import update_kv_cache
from ..ops.norms import layer_norm, rms_norm
from ..ops.ring_attention import ring_self_attention, sp_decode_attention
from ..ops.sampling import SamplingParams, sample_logits


def _dynamic_set1(arr: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray):
    """arr[idx] = val for a traced scalar idx (1-element update slice)."""
    return jax.lax.dynamic_update_slice(arr, val[None].astype(arr.dtype),
                                        (idx,))


def _final_logits(params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Final norm + LM head on [b, l, H] hidden (stage_forward's tail,
    applied here to just the selected last position instead of on every
    rank's whole chunk)."""
    if cfg.attn_layernorm:
        h = layer_norm(h, params.final_norm["w"], params.final_norm["b"],
                       cfg.norm_eps)
    else:
        h = rms_norm(h, params.final_norm["w"], cfg.norm_eps)
    head = (params.embed["tokens"].T if cfg.tie_embeddings
            else params.lm_head["w"])
    return jnp.einsum("blh,hv->blv", h, head)


def _sample_first_token(params, cfg, hidden, idx, n, rng, sampling):
    """The global last prompt token lives on rank n-1: broadcast its hidden
    row via psum, run the head ONCE on that single position, sample token 0.
    Shared by the ring (make_sp_generate_fn) and Ulysses generate paths."""
    h_last = jnp.where(idx == n - 1, hidden[:, -1:, :].astype(jnp.float32),
                       0.0)
    h_last = jax.lax.psum(h_last, "sp").astype(cfg.dtype)
    last = _final_logits(params, cfg, h_last)[:, 0, :]
    rng, r0 = jax.random.split(rng)
    return sample_logits(last, r0, sampling), rng


def _decode_scan(step, carry, rng, num_new_tokens, tok0):
    """Scan ``step`` over per-step rngs and assemble [b, num_new] tokens."""
    rngs = jax.random.split(rng, num_new_tokens - 1) \
        if num_new_tokens > 1 else jnp.zeros((0, 2), jnp.uint32)
    _, rest = jax.lax.scan(step, carry, rngs)
    return jnp.concatenate([tok0[:, None], rest.T], axis=1) \
        if num_new_tokens > 1 else tok0[:, None]


def _wrap_sp_body(body, mesh: Mesh, sp: int, max_seq: int,
                  num_new_tokens: int):
    """shard_map + jit + host-side shape validation, shared by both
    sequence-parallel strategies (prompt sharded over sp's seq axis)."""
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "sp"), P()),
        out_specs=P(),
        check_vma=False,
    )

    @jax.jit
    def fn(params, prompt_ids, rng):
        return sharded(params, prompt_ids, rng)

    def checked(params, prompt_ids, rng):
        validate_sp_prompt(prompt_ids.shape[1], sp, max_seq,
                           num_new_tokens)
        return fn(params, prompt_ids, rng)

    return checked


def validate_sp_prompt(plen: int, sp: int, max_seq: int,
                       num_new_tokens: int) -> None:
    """The sp prompt-shape rule, owned here and shared by the generate
    fns' call-time check and any caller that wants to FAIL FAST before
    paying a checkpoint load (cli ``generate --sp``)."""
    if plen % sp:
        raise ValueError(
            f"prompt_len={plen} not divisible by sp={sp}; pad first")
    if plen + num_new_tokens > max_seq:
        raise ValueError(
            f"prompt {plen} + new {num_new_tokens} > max_seq {max_seq}")


def make_sp_generate_fn(cfg: ModelConfig, mesh: Mesh, *, max_seq: int,
                        num_new_tokens: int,
                        sampling: Optional[SamplingParams] = None,
                        kv_cache_dtype=None):
    """Build a jitted ``fn(params, prompt_ids, rng) -> tokens`` that runs
    ring-attention prefill + sp-sharded-cache decode over ``mesh``'s sp axis.

    Constraints (checked host-side): ``prompt_len % sp == 0`` (pad the prompt
    to a chunk multiple before calling) and
    ``prompt_len + num_new_tokens <= max_seq`` with ``max_seq % sp == 0``.
    Returns [batch, num_new_tokens] int32; greedy when ``sampling`` is None.

    ``kv_cache_dtype``: reduced-precision storage for the sequence-sharded
    cache (e.g. "float8_e4m3fn") — at long context the cache IS the memory
    bill, so this is where reduced precision pays most.  Same contract as
    every engine (one owner: runtime/engine.resolve_cache_dtype_backend):
    attention reads what the cache stores, so ring prefill rounds K/V
    through the cache dtype before attending — greedy output matches a
    single-device engine with the same cache dtype.
    """
    sp = mesh.shape["sp"]
    if max_seq % sp:
        raise ValueError(f"max_seq={max_seq} not divisible by sp={sp}")
    from ..runtime.engine import resolve_cache_dtype_backend
    kv_dtype, _ = resolve_cache_dtype_backend(kv_cache_dtype, "jnp")
    s_loc = max_seq // sp
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    sampling = sampling or SamplingParams(greedy=True)
    prefill_core, step_core = _make_ring_cores(cfg, spec, s_loc, sampling,
                                               kv_dtype)

    def body(params, ids, rng):
        carry, rng = prefill_core(params, ids, rng)
        tok0 = carry[-1]

        def step(c, r):
            return step_core(params, c, r)

        return _decode_scan(step, carry, rng, num_new_tokens, tok0)

    return _wrap_sp_body(body, mesh, sp, max_seq, num_new_tokens)


def _make_ring_cores(cfg: ModelConfig, spec: StageSpec, s_loc: int,
                     sampling: SamplingParams, kv_dtype):
    """``(prefill_core, step_core)`` — the ring-sp math, shared by the
    fused generate fn and the step-split stream fns so the two programs
    cannot drift.  Both run INSIDE the sp ``shard_map``.  The decode
    carry is ``(keys, values, kv_pos, plen, length, tok)``: ``plen``
    rides along explicitly so a decode dispatch needs no prompt shape
    (the fused path closes over it; the stream path cannot)."""
    require_single_pass(cfg, "ring sequence parallelism")
    require_kv_pair(cfg, "ring sequence parallelism")
    require_token_rows(cfg, "ring sequence parallelism")
    require_one_kind(cfg, "ring sequence parallelism")
    cache_dtype = kv_dtype if kv_dtype is not None else cfg.dtype

    def prefill_core(params, ids, rng):
        n = jax.lax.axis_size("sp")
        idx = jax.lax.axis_index("sp")
        b, chunk = ids.shape

        # ---- prefill: ring attention over the prompt chunks -------------
        def prefill_attn(q, k, v, kc, vc, pos, cache_start, slopes):
            kc, vc = update_kv_cache(kc, vc, k, v, jnp.zeros((), jnp.int32))
            if kv_dtype is not None:
                # attention reads what the cache stores (the engines'
                # reduced-precision contract): round K/V through the
                # cache dtype so prefill attends the same values decode
                # will read back from the fp8 shards
                k = k.astype(kv_dtype).astype(cfg.dtype)
                v = v.astype(kv_dtype).astype(cfg.dtype)
            out = ring_self_attention(q, k, v, "sp", slopes=slopes)
            return out, kc, vc

        shape = (spec.num_layers, b, cfg.num_kv_heads, s_loc, cfg.head_dim)
        cache = KVCache(keys=jnp.zeros(shape, cache_dtype),
                        values=jnp.zeros(shape, cache_dtype),
                        length=jnp.zeros((), jnp.int32))
        positions = jnp.broadcast_to(idx * chunk + jnp.arange(chunk),
                                     (b, chunk))
        # body spec (not last): prefill returns hidden states, and the LM
        # head runs once below on the single selected last position instead
        # of on every rank's whole [b, chunk, vocab] chunk.
        body_spec = StageSpec(0, 2, 0, cfg.num_layers)
        hidden, cache = stage_forward(params, cfg, body_spec, ids, cache,
                                      positions, attn_impl=prefill_attn)
        kv_pos = jnp.where(jnp.arange(s_loc) < chunk,
                           idx * chunk + jnp.arange(s_loc), -1).astype(jnp.int32)
        plen = jnp.asarray(n * chunk, jnp.int32)

        tok0, rng = _sample_first_token(params, cfg, hidden, idx, n, rng,
                                        sampling)
        return (cache.keys, cache.values, kv_pos, plen, plen, tok0), rng

    def step_core(params, carry, step_rng):
        # ---- decode: sharded cache + lse-combined partial attention -----
        kc_all, vc_all, kv_pos, plen, length, tok = carry
        n = jax.lax.axis_size("sp")
        idx = jax.lax.axis_index("sp")
        b = tok.shape[0]
        chunk = plen // n
        # stateless round-robin placement, derived from the carry: the
        # d-th decoded token (d = length - prompt_len) lands on rank
        # d % n at slot chunk + d // n.
        d = length - plen
        is_owner = idx == d % n
        slot = chunk + d // n
        kv_pos_new = jnp.where(
            is_owner, _dynamic_set1(kv_pos, slot, length), kv_pos)
        pos = jnp.broadcast_to(length, (b, 1))

        def dec_attn(q, k, v, kc, vc, pos_, cache_start, slopes):
            # kc/vc: [b, nkv, s_loc, hd] head-major; the new token's
            # k/v arrive as [b, 1, nkv, hd] — transpose to cache layout
            k_t = k.transpose(0, 2, 1, 3).astype(kc.dtype)
            v_t = v.transpose(0, 2, 1, 3).astype(vc.dtype)
            old_k = jax.lax.dynamic_slice(
                kc, (0, 0, slot, 0), (b, kc.shape[1], 1, kc.shape[3]))
            old_v = jax.lax.dynamic_slice(
                vc, (0, 0, slot, 0), (b, vc.shape[1], 1, vc.shape[3]))
            k_ins = jnp.where(is_owner, k_t, old_k)
            v_ins = jnp.where(is_owner, v_t, old_v)
            kc = jax.lax.dynamic_update_slice(kc, k_ins, (0, 0, slot, 0))
            vc = jax.lax.dynamic_update_slice(vc, v_ins, (0, 0, slot, 0))
            out = sp_decode_attention(q, kc, vc, kv_pos_new, pos_, "sp",
                                      slopes=slopes)
            return out, kc, vc

        cache = KVCache(kc_all, vc_all, length)
        logits, cache = stage_forward(params, cfg, spec, tok[:, None],
                                      cache, pos, attn_impl=dec_attn)
        nxt = sample_logits(logits[:, -1, :], step_rng, sampling)
        return ((cache.keys, cache.values, kv_pos_new, plen, length + 1,
                 nxt), nxt)

    return prefill_core, step_core


def make_sp_stream_fns(cfg: ModelConfig, mesh: Mesh, *, max_seq: int,
                       block: int,
                       sampling: Optional[SamplingParams] = None,
                       kv_cache_dtype=None):
    """``(prefill_fn, decode_fn)`` — the step-SPLIT ring-sp programs for
    INCREMENTAL long-context serving (runtime/sp_backend.py streaming):

    - ``prefill_fn(params, prompt_ids, rng) -> (*state, rng)`` runs ring
      prefill and samples token #1 (``state[-1]``); the returned state
      (sequence-sharded cache, kv position map, lengths, last token)
      stays on device, sharded.
    - ``decode_fn(params, *state, rng) -> (*state, toks[b, block])``
      advances ``block`` tokens in one dispatch (cache buffers donated).

    Same math as :func:`make_sp_generate_fn` (one core factory,
    ``_make_ring_cores``) — greedy streams are bit-identical to the
    fused fn.  One compiled pair serves EVERY ``max_new_tokens`` (the
    fused fn bakes its trip count into the program); first-token latency
    is one prefill dispatch instead of the whole generation.  Sampled
    streams draw per-block sub-rngs, so they are equally distributed but
    not sequence-identical to the fused fn (the engines' streaming
    contract).  A final partial block may scan past ``max_new``: the
    surplus steps write only into slots the discarded tokens own
    (the caller takes ``toks[:, :remaining]`` and drops the state)."""
    sp = mesh.shape["sp"]
    if max_seq % sp:
        raise ValueError(f"max_seq={max_seq} not divisible by sp={sp}")
    if block < 1:
        raise ValueError("block must be >= 1")
    from ..runtime.engine import resolve_cache_dtype_backend
    kv_dtype, _ = resolve_cache_dtype_backend(kv_cache_dtype, "jnp")
    s_loc = max_seq // sp
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    sampling = sampling or SamplingParams(greedy=True)
    prefill_core, step_core = _make_ring_cores(cfg, spec, s_loc, sampling,
                                               kv_dtype)

    cache_spec = P(None, None, None, "sp", None)
    state_specs = (cache_spec, cache_spec, P("sp"), P(), P(), P())
    return _wrap_stream_fns(prefill_core, step_core, mesh, state_specs,
                            block)


def _wrap_stream_fns(prefill_core, step_core, mesh: Mesh, state_specs,
                     block: int):
    """shard_map + jit scaffolding shared by BOTH strategies' stream-fn
    factories (one owner, like ``_wrap_sp_body`` for the fused fns):
    a prefill program emitting the sharded decode state, and a
    donated-cache decode program scanning ``block`` steps per dispatch.
    ``state_specs`` lead with the two cache buffers (donated)."""

    def prefill_body(params, ids, rng):
        carry, rng = prefill_core(params, ids, rng)
        return (*carry, rng)

    def decode_body(params, *state_rng):
        state, rng = state_rng[:-1], state_rng[-1]

        def step(c, r):
            return step_core(params, c, r)

        carry, toks = jax.lax.scan(step, state,
                                   jax.random.split(rng, block))
        return (*carry, jnp.swapaxes(toks, 0, 1))       # [b, block]

    prefill_fn = jax.jit(jax.shard_map(
        prefill_body, mesh=mesh,
        in_specs=(P(), P(None, "sp"), P()),
        out_specs=(*state_specs, P()), check_vma=False))
    decode_fn = jax.jit(jax.shard_map(
        decode_body, mesh=mesh,
        in_specs=(P(), *state_specs, P()),
        out_specs=(*state_specs, P()), check_vma=False),
        donate_argnums=(1, 2))
    return prefill_fn, decode_fn
