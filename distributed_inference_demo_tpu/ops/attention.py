"""KV-cached multi-head / grouped-query attention as a pure function.

The reference has *no* KV cache — every decode step re-runs the module on a
1-token sequence with no memory of the prompt (``Communication.java:322-327``,
acknowledged "repetitive generation issue" ``BackgroundService.java:195``).
Here the cache is the contract: ``attention`` always reads K/V from the
caller-provided cache buffers after inserting the current chunk, so prefill
(chunk = prompt) and decode (chunk = 1 token) are the same code path with
static shapes — one compiled program each.

Masking uses position arithmetic instead of materialized [L, L] boolean
masks where possible so XLA can fuse it into the softmax.

Supports GQA (num_kv_heads < num_heads) by logical head-group broadcast, and
ALiBi bias for the bloom family.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (bloom family), shape [num_heads]."""
    import math
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]
    if math.log2(num_heads).is_integer():
        slopes = pow2_slopes(num_heads)
    else:
        closest = 2 ** math.floor(math.log2(num_heads))
        slopes = pow2_slopes(closest)
        extra = pow2_slopes(2 * closest)
        slopes += extra[0::2][: num_heads - closest]
    return jnp.asarray(slopes, jnp.float32)


def attention(
    q: jnp.ndarray,             # [batch, chunk, num_heads, head_dim]
    k_cache: jnp.ndarray,       # [batch, num_kv_heads, max_seq, head_dim]
    v_cache: jnp.ndarray,       # [batch, num_kv_heads, max_seq, head_dim]
    q_positions: jnp.ndarray,   # [batch, chunk] absolute positions of q tokens
    cache_len: jnp.ndarray,     # scalar int32: valid length of the cache
    slopes: Optional[jnp.ndarray] = None,  # [num_heads] ALiBi, or None
    window: int = 0,            # > 0: a query sees its last `window` keys
) -> jnp.ndarray:
    """Causal attention of the current chunk against the full cache; with
    ``window`` a query at position ``p`` sees keys ``p - window < j <= p``.

    Cache layout is head-major (see ``models.base.KVCache``).
    Returns [batch, chunk, num_heads, head_dim].
    """
    b, chunk, nh, hd = q.shape
    nkv = k_cache.shape[1]
    max_seq = k_cache.shape[2]
    groups = nh // nkv

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = q.astype(jnp.float32) * scale
    # [b, chunk, nkv, groups, hd]
    qf = qf.reshape(b, chunk, nkv, groups, hd)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)

    # scores: [b, nkv, groups, chunk, max_seq]
    scores = jnp.einsum("bqkgh,bksh->bkgqs", qf, kf)

    kv_pos = jnp.arange(max_seq)[None, None, :]                  # [1, 1, s]
    qpos = q_positions[:, :, None]                               # [b, q, 1]
    # causal + validity: a q token at position p attends to kv positions <= p
    # that are inside the filled cache region.
    valid = (kv_pos <= qpos) & (kv_pos < cache_len)              # [b, q, s]
    if window:
        valid = valid & (qpos - kv_pos < window)
    mask = valid[:, None, None, :, :]                            # [b,1,1,q,s]

    if slopes is not None:
        # ALiBi: bias = -slope * (qpos - kvpos); shape [b, nh, q, s]
        dist = (qpos - kv_pos).astype(jnp.float32)               # [b, q, s]
        bias = -slopes[None, :, None, None] * dist[:, None, :, :]
        scores = scores + bias.reshape(b, nkv, groups, chunk, max_seq)

    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksh->bqkgh", probs, vf)
    return out.reshape(b, chunk, nh, hd).astype(q.dtype)


def prepare_kv_chunk(
    k_new: jnp.ndarray,    # [batch, chunk, nkv, hd] (projection layout)
    v_new: jnp.ndarray,
    k_dtype,
    v_dtype,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Validate + cast a projection-layout K/V chunk for a cache write —
    the ONE entry every cache-write path goes through (the dense
    ``update_kv_cache`` below and the paged block write in
    ``ops.paged_attention.write_paged_kv``), so the write contract is
    stated and checked in one place:

    **Stale-slot invariant.**  A cache write may land garbage at any
    position >= the row's valid length (padded prefill tails, freed
    batching slots, speculative overshoot) PROVIDED every position is
    rewritten before any query attends it — causal masking
    (``kv_pos <= q_position``) plus contiguous advance makes that safe.
    Writers must never touch a position < the row's valid length: stored
    prefix K/V is immutable (the KV-cache manager's copy-on-write
    sharing, dense AND paged, relies on it).
    """
    assert k_new.ndim == 4 and k_new.shape == v_new.shape, (
        "KV chunk must be projection-layout [batch, chunk, nkv, hd]; got "
        f"{k_new.shape} / {v_new.shape}")
    return k_new.astype(k_dtype), v_new.astype(v_dtype)


def update_kv_cache(
    k_cache: jnp.ndarray,  # [batch, nkv, max_seq, hd] (head-major)
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,    # [batch, chunk, nkv, hd] (projection layout)
    v_new: jnp.ndarray,
    start: jnp.ndarray,    # scalar int32 insert offset
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Insert the chunk's K/V at position ``start`` of every head's plane.

    The chunk arrives in projection layout [b, chunk, nkv, hd] (as produced
    by the QKV matmuls) and is transposed to the cache's head-major layout
    here — a [b, chunk, nkv, hd]-sized shuffle, O(chunk), not O(max_seq).
    Write contract (stale-slot invariant): see :func:`prepare_kv_chunk`.
    """
    zeros = jnp.zeros((), jnp.int32)
    k_new, v_new = prepare_kv_chunk(k_new, v_new, k_cache.dtype,
                                    v_cache.dtype)
    k_new = k_new.transpose(0, 2, 1, 3)
    v_new = v_new.transpose(0, 2, 1, 3)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new, (zeros, zeros, start, zeros))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new, (zeros, zeros, start, zeros))
    return k_cache, v_cache
