"""Ulysses-style all-to-all sequence parallelism over the ``sp`` axis.

Complements the ring-attention path (``parallel/sequence.py``) — the task's
long-context requirement names both strategies ("ring attention or
all-to-all sequence/context parallelism").  Here activations and QKV/MLP
projections stay SEQUENCE-sharded, and attention itself runs HEAD-sharded
over the full sequence after one ``all_to_all`` each way per layer
(DeepSpeed-Ulysses; PAPERS.md):

- prefill: q/k/v ``[b, s/n, heads, hd]`` → all_to_all (split heads, concat
  seq) → ``[b, s, heads/n, hd]``; plain causal attention per head block;
  reverse all_to_all on the output.
- the KV cache shards by HEAD (``[L, b, nkv/n, max_seq, hd]``) — an n-fold
  cache-memory saving, same as the TP layout.
- decode: the single replicated token needs no seq all_to_all; each rank
  slices its head block, attends over its cache shard, and the head
  outputs are all-gathered — 1 collective per layer per step.

vs ring attention: Ulysses moves activations (2 all_to_alls/layer) instead
of KV blocks around a ring; its comm volume is independent of context
length, at the cost of requiring ``num_heads % sp == 0`` (ring has no head
constraint and keeps the cache sequence-sharded).  Absent entirely in the
reference (SURVEY.md §5.7: max_length=40, no cache).
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..models.base import (KVCache, ModelConfig, StageSpec,
                           require_kv_pair, require_one_kind,
                           require_token_rows,
                           require_single_pass)
from ..models.decoder import stage_forward
from ..ops.attention import attention, update_kv_cache
from ..ops.sampling import SamplingParams, sample_logits
from .sequence import _decode_scan, _sample_first_token, _wrap_sp_body


def make_ulysses_generate_fn(cfg: ModelConfig, mesh: Mesh, *, max_seq: int,
                             num_new_tokens: int,
                             sampling: Optional[SamplingParams] = None,
                             kv_cache_dtype=None):
    """Build a jitted ``fn(params, prompt_ids, rng) -> tokens``: Ulysses
    prefill + head-sharded-cache decode over ``mesh``'s sp axis.

    Constraints (checked host-side): ``prompt_len % sp == 0``,
    ``num_heads % sp == 0``, ``num_kv_heads % sp == 0``,
    ``prompt_len + num_new_tokens <= max_seq``.  Greedy when ``sampling``
    is None; returns [batch, num_new_tokens] int32.

    ``kv_cache_dtype``: reduced-precision storage for the head-sharded
    cache — Ulysses attention (prefill AND decode) already reads from the
    cache, so the engines' "attend what the cache stores" contract holds
    with no extra rounding step (``update_kv_cache`` casts on insert,
    ``ops.attention`` upcasts on read).
    """
    sp = mesh.shape["sp"]
    if cfg.num_heads % sp or cfg.num_kv_heads % sp:
        raise ValueError(
            f"ulysses needs num_heads ({cfg.num_heads}) and num_kv_heads "
            f"({cfg.num_kv_heads}) divisible by sp={sp}")
    from ..runtime.engine import resolve_cache_dtype_backend
    kv_dtype, _ = resolve_cache_dtype_backend(kv_cache_dtype, "jnp")
    sampling = sampling or SamplingParams(greedy=True)
    prefill_core, step_core = _make_ulysses_cores(cfg, max_seq, sp,
                                                  sampling, kv_dtype)

    def body(params, ids, rng):
        carry, rng = prefill_core(params, ids, rng)
        tok0 = carry[-1]

        def step(c, r):
            return step_core(params, c, r)

        return _decode_scan(step, carry, rng, num_new_tokens, tok0)

    return _wrap_sp_body(body, mesh, sp, max_seq, num_new_tokens)


def _make_ulysses_cores(cfg: ModelConfig, max_seq: int, sp: int,
                        sampling: SamplingParams, kv_dtype):
    """``(prefill_core, step_core)`` — the Ulysses math, shared by the
    fused generate fn and the step-split stream fns (one owner, like the
    ring path's ``_make_ring_cores``).  Decode carry:
    ``(keys, values, length, tok)`` with the cache head-sharded."""
    require_single_pass(cfg, "Ulysses sequence parallelism")
    require_kv_pair(cfg, "Ulysses sequence parallelism")
    require_token_rows(cfg, "Ulysses sequence parallelism")
    require_one_kind(cfg, "Ulysses sequence parallelism")
    cache_dtype = kv_dtype if kv_dtype is not None else cfg.dtype
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    body_spec = StageSpec(0, 2, 0, cfg.num_layers)  # no head at prefill
    nh_loc = cfg.num_heads // sp
    nkv_loc = cfg.num_kv_heads // sp
    hd = cfg.head_dim

    def slice_slopes(slopes, idx):
        if slopes is None:
            return None
        return jax.lax.dynamic_slice_in_dim(slopes, idx * nh_loc,
                                            nh_loc, axis=0)

    def prefill_core(params, ids, rng):
        n = jax.lax.axis_size("sp")
        idx = jax.lax.axis_index("sp")
        b, chunk = ids.shape            # local contiguous prompt chunk
        S = n * chunk

        # ---- prefill: all_to_all to head-sharded full-sequence attention
        def prefill_attn(q, k, v, kc, vc, pos, cache_start, slopes):
            # [b, chunk, heads, hd] -> [b, S, heads/n, hd]: split the head
            # axis across ranks, gather every rank's seq chunk (rank order
            # == sequence order — contiguous prompt sharding)
            qf = jax.lax.all_to_all(q, "sp", split_axis=2, concat_axis=1,
                                    tiled=True)
            kf = jax.lax.all_to_all(k, "sp", split_axis=2, concat_axis=1,
                                    tiled=True)
            vf = jax.lax.all_to_all(v, "sp", split_axis=2, concat_axis=1,
                                    tiled=True)
            kc, vc = update_kv_cache(kc, vc, kf, vf, cache_start)
            qpos = jnp.broadcast_to(cache_start + jnp.arange(S), (b, S))
            out = attention(qf, kc, vc, qpos, cache_start + S,
                            slice_slopes(slopes, idx))
            # back to seq-sharded all-heads for the output projection
            out = jax.lax.all_to_all(out, "sp", split_axis=1, concat_axis=2,
                                     tiled=True)
            return out, kc, vc

        shape = (spec.num_layers, b, nkv_loc, max_seq, hd)
        cache = KVCache(keys=jnp.zeros(shape, cache_dtype),
                        values=jnp.zeros(shape, cache_dtype),
                        length=jnp.zeros((), jnp.int32))
        positions = jnp.broadcast_to(idx * chunk + jnp.arange(chunk),
                                     (b, chunk))
        hidden, cache = stage_forward(params, cfg, body_spec, ids, cache,
                                      positions, attn_impl=prefill_attn)

        tok0, rng = _sample_first_token(params, cfg, hidden, idx, n, rng,
                                        sampling)
        return (cache.keys, cache.values, jnp.asarray(S, jnp.int32),
                tok0), rng

    def step_core(params, carry, step_rng):
        # ---- decode: head-sharded cache, all_gather the head outputs ----
        keys, values, length, tok = carry
        idx = jax.lax.axis_index("sp")
        b = tok.shape[0]

        def slice_heads(x, loc):
            return jax.lax.dynamic_slice_in_dim(x, idx * loc, loc, axis=2)

        def dec_attn(q, k, v, kc, vc, pos_, cache_start, slopes):
            q_loc = slice_heads(q, nh_loc)     # [b, 1, nh_loc, hd]
            k_loc = slice_heads(k, nkv_loc)
            v_loc = slice_heads(v, nkv_loc)
            kc, vc = update_kv_cache(kc, vc, k_loc, v_loc, cache_start)
            out = attention(q_loc, kc, vc, pos_, cache_start + 1,
                            slice_slopes(slopes, idx))
            out = jax.lax.all_gather(out, "sp", axis=2, tiled=True)
            return out, kc, vc

        cache = KVCache(keys, values, length)
        pos = jnp.broadcast_to(length, (b, 1))
        logits, cache = stage_forward(params, cfg, spec, tok[:, None],
                                      cache, pos, attn_impl=dec_attn)
        nxt = sample_logits(logits[:, -1, :], step_rng, sampling)
        return (cache.keys, cache.values, length + 1, nxt), nxt

    return prefill_core, step_core


def make_ulysses_stream_fns(cfg: ModelConfig, mesh: Mesh, *, max_seq: int,
                            block: int,
                            sampling: Optional[SamplingParams] = None,
                            kv_cache_dtype=None):
    """Step-split Ulysses programs — ``(prefill_fn, decode_fn)`` with the
    same contract as :func:`parallel.sequence.make_sp_stream_fns` (state
    here: head-sharded cache + length + last token).  One compiled pair
    serves every ``max_new_tokens``; greedy parity with the fused fn."""
    sp = mesh.shape["sp"]
    if cfg.num_heads % sp or cfg.num_kv_heads % sp:
        raise ValueError(
            f"ulysses needs num_heads ({cfg.num_heads}) and num_kv_heads "
            f"({cfg.num_kv_heads}) divisible by sp={sp}")
    if block < 1:
        raise ValueError("block must be >= 1")
    from ..runtime.engine import resolve_cache_dtype_backend
    kv_dtype, _ = resolve_cache_dtype_backend(kv_cache_dtype, "jnp")
    sampling = sampling or SamplingParams(greedy=True)
    prefill_core, step_core = _make_ulysses_cores(cfg, max_seq, sp,
                                                  sampling, kv_dtype)

    from jax.sharding import PartitionSpec as P

    from .sequence import _wrap_stream_fns
    cache_spec = P(None, None, "sp", None, None)    # nkv head-sharded
    state_specs = (cache_spec, cache_spec, P(), P())
    return _wrap_stream_fns(prefill_core, step_core, mesh, state_specs,
                            block)
