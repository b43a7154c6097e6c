"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has **no** long-context support at all — ``max_length`` is 40
(``server.py:1001``) and there is no sequence parallelism of any kind
(SURVEY.md §5.7).  This module is the from-scratch TPU design: the sequence
dimension is sharded over the ``sp`` mesh axis, each device holds one
contiguous chunk, and causal self-attention is computed **blockwise** with an
online-softmax accumulator while K/V blocks rotate around the ring via
``lax.ppermute`` (one ICI hop per step).  Peak memory per device is
O(seq/sp_size) for activations and KV — sequence length scales linearly with
the mesh axis.

Two entry points:

- :func:`ring_self_attention` — causal self-attention for prefill/training,
  q/k/v sharded by sequence chunk.  FLOPs overlap with the ppermute transfer
  because XLA schedules the collective-permute asynchronously against the
  next block's matmuls.
- :func:`sp_decode_attention` — single-position decode against a
  sequence-sharded KV cache: every rank attends its local cache shard and
  the partial softmax statistics are combined exactly with a log-sum-exp
  reduction (``pmax`` + ``psum``) — no KV movement at all during decode.

Both support GQA (kv heads broadcast over query-head groups) and ALiBi bias
(bloom family), matching ``ops.attention``.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_NEG = -1e30


def _split_heads(q: jnp.ndarray, nkv: int) -> jnp.ndarray:
    """[b, l, nh, hd] -> [b, l, nkv, groups, hd] for GQA broadcast."""
    b, l, nh, hd = q.shape
    return q.reshape(b, l, nkv, nh // nkv, hd)


def _block_scores(qf: jnp.ndarray, kf: jnp.ndarray) -> jnp.ndarray:
    """qf [b,lq,nkv,g,hd] x kf [b,lk,nkv,hd] -> [b,nkv,g,lq,lk] (f32)."""
    return jnp.einsum("bqkgh,bskh->bkgqs", qf, kf)


def _bias_and_mask(scores: jnp.ndarray, q_pos: jnp.ndarray,
                   kv_pos: jnp.ndarray, kv_valid: jnp.ndarray,
                   slopes: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply causal mask (+ optional ALiBi) to a score block.

    q_pos: [lq] global positions.  kv_pos: [lk] global positions.
    kv_valid: [lk] bool (filled cache slots).  Returns (scores, valid) with
    masked entries set to _NEG; valid has shape [1,1,1,lq,lk].
    """
    b, nkv, g, lq, lk = scores.shape
    causal = kv_pos[None, :] <= q_pos[:, None]              # [lq, lk]
    valid = (causal & kv_valid[None, :])[None, None, None]  # [1,1,1,lq,lk]
    if slopes is not None:
        # slopes: [nh] == [nkv*g]; bias = -slope * (q_pos - kv_pos)
        dist = (q_pos[:, None] - kv_pos[None, :]).astype(jnp.float32)
        bias = -slopes.reshape(1, nkv, g, 1, 1) * dist[None, None, None]
        scores = scores + bias
    return jnp.where(valid, scores, _NEG), valid


def _online_update(o: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray,
                   scores: jnp.ndarray, valid: jnp.ndarray,
                   vf: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One flash-attention accumulator step.

    o: [b,nkv,g,lq,hd] unnormalized output, m: [b,nkv,g,lq] running max,
    l: [b,nkv,g,lq] running denominator.  scores already masked to _NEG;
    ``valid`` broadcastable to scores.  vf: [b,lk,nkv,hd] f32.
    """
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # exp(_NEG - m_new) would be exp(0)=1 when a whole row is masked and
    # m_new is still _NEG — zero masked probabilities explicitly.
    p = jnp.where(valid, jnp.exp(scores - m_new[..., None]), 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskh->bkgqh", p, vf)
    o_new = o * alpha[..., None] + pv
    return o_new, m_new, l_new


def ring_self_attention(
    q: jnp.ndarray,           # [b, lq, nh, hd] local sequence chunk
    k: jnp.ndarray,           # [b, lk, nkv, hd] local chunk
    v: jnp.ndarray,           # [b, lk, nkv, hd]
    axis_name: str,           # the sp mesh axis (call inside shard_map)
    chunk_offset: Optional[jnp.ndarray] = None,  # global start of this chunk
    slopes: Optional[jnp.ndarray] = None,        # [nh] ALiBi slopes
) -> jnp.ndarray:
    """Causal self-attention with sequence sharded over ``axis_name``.

    Device ``i`` owns tokens ``[i*lq, (i+1)*lq)`` (contiguous layout) unless
    ``chunk_offset`` overrides the global start.  K/V blocks rotate around
    the ring; after ``sp_size`` steps every device has attended its queries
    to every causally-visible key.  Returns [b, lq, nh, hd] in q.dtype.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, lq, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = _split_heads(q.astype(jnp.float32) * scale, nkv)
    q_off = (idx * lq) if chunk_offset is None else chunk_offset
    q_pos = q_off + jnp.arange(lq)

    o = jnp.zeros((b, nkv, g, lq, hd), jnp.float32)
    m = jnp.full((b, nkv, g, lq), _NEG, jnp.float32)
    l = jnp.zeros((b, nkv, g, lq), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    all_valid = jnp.ones((k.shape[1],), bool)

    def attend(o, m, l, kb, vb, kv_off):
        kv_pos = kv_off + jnp.arange(k.shape[1])
        scores = _block_scores(qf, kb.astype(jnp.float32))
        scores, valid = _bias_and_mask(scores, q_pos, kv_pos, all_valid,
                                       slopes)
        return _online_update(o, m, l, scores, valid,
                              vb.astype(jnp.float32))

    def step(s, carry):
        o, m, l, kb, vb, kv_off = carry
        # rotate first (blocks travel in their native dtype — half the ICI
        # bytes of an f32 ring for bf16 KV), then attend the arrived block.
        kb, vb, kv_off = jax.lax.ppermute((kb, vb, kv_off), axis_name, perm)
        o, m, l = attend(o, m, l, kb, vb, kv_off)
        return o, m, l, kb, vb, kv_off

    kv_off0 = (idx * k.shape[1]) if chunk_offset is None else chunk_offset
    kv_off0 = jnp.asarray(kv_off0, jnp.int32)
    # local block first, then n-1 rotate-attend steps: no wasted final hop.
    o, m, l = attend(o, m, l, k, v, kv_off0)
    carry = (o, m, l, k, v, kv_off0)
    o, m, l, *_ = jax.lax.fori_loop(0, n - 1, step, carry)
    out = o / jnp.maximum(l, 1e-30)[..., None]         # [b, nkv, g, lq, hd]
    out = out.transpose(0, 3, 1, 2, 4)                 # [b, lq, nkv, g, hd]
    return out.reshape(b, lq, nh, hd).astype(q.dtype)


def sp_decode_attention(
    q: jnp.ndarray,           # [b, lq, nh, hd] (replicated across sp ranks)
    k_shard: jnp.ndarray,     # [b, nkv, s_loc, hd] local cache shard
    v_shard: jnp.ndarray,     #   (head-major, see models.base.KVCache)
    kv_pos: jnp.ndarray,      # [s_loc] int32 global positions, -1 = empty
    q_positions: jnp.ndarray, # [b, lq] global positions of the queries
    axis_name: str,
    slopes: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Decode attention against a sequence-sharded KV cache.

    Every rank computes partial attention over its cache shard, then the
    partial softmax statistics are merged exactly across the ``sp`` axis:
    ``m* = pmax(m)``, ``l* = psum(l·e^{m-m*})``, ``o* = psum(o·e^{m-m*})/l*``.
    Only O(heads·hd) bytes cross the ICI per step — no KV movement.
    """
    b, lq, nh, hd = q.shape
    nkv = k_shard.shape[1]
    g = nh // nkv

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = _split_heads(q.astype(jnp.float32) * scale, nkv)
    kf = k_shard.astype(jnp.float32)
    vf = v_shard.astype(jnp.float32)

    scores = jnp.einsum("bqkgh,bksh->bkgqs", qf, kf)     # [b,nkv,g,lq,s]
    kv_valid = kv_pos >= 0
    # causal over global positions, per batch row
    causal = kv_pos[None, None, :] <= q_positions[:, :, None]   # [b, lq, s]
    valid = (causal & kv_valid[None, None, :])[:, None, None]   # [b,1,1,lq,s]
    if slopes is not None:
        dist = (q_positions[:, :, None] - kv_pos[None, None, :]
                ).astype(jnp.float32)                           # [b, lq, s]
        scores = scores + (-slopes.reshape(1, nkv, g, 1, 1)
                           * dist[:, None, None])
    scores = jnp.where(valid, scores, _NEG)

    m_loc = jnp.max(scores, axis=-1)                     # [b,nkv,g,lq]
    p = jnp.where(valid, jnp.exp(scores - m_loc[..., None]), 0.0)
    l_loc = jnp.sum(p, axis=-1)
    o_loc = jnp.einsum("bkgqs,bksh->bkgqh", p, vf)

    m_glob = jax.lax.pmax(m_loc, axis_name)
    alpha = jnp.exp(m_loc - m_glob)
    l_glob = jax.lax.psum(l_loc * alpha, axis_name)
    o_glob = jax.lax.psum(o_loc * alpha[..., None], axis_name)
    out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]  # [b,nkv,g,lq,hd]
    out = out.transpose(0, 3, 1, 2, 4)                    # [b,lq,nkv,g,hd]
    return out.reshape(b, lq, nh, hd).astype(q.dtype)
