"""Pallas TPU flash-attention kernel for the KV-cached decoder hot loop.

The reference's hot compute is an opaque ONNX ``Session.Run`` per module per
token (``cpp/inference.cpp:207-216``); here the hot op is written directly
for the TPU memory hierarchy: Q/K/V blocks stream HBM→VMEM, scores and the
online-softmax accumulator live in VMEM, and every matmul is shaped for the
MXU ([rows, hd] x [hd, block_k]).  One kernel covers both phases:

- **prefill**: q = the prompt chunk, cache holds the prompt's K/V;
- **decode**: q = one token (rows = GQA group size), same code path.

Layout trick for GQA: queries are regrouped to ``[b, nkv, chunk*g, hd]`` so
each grid program attends one kv-head's whole query group — K/V blocks are
loaded once per kv head (not once per q head), an (nh/nkv)× HBM-traffic
saving over a per-q-head loop, and the q-rows dimension is ``chunk*g`` which
keeps the MXU tiles tall even at decode (rows = g).

Causality is positional: q row ``r`` is the query at absolute position
``q_start + r//g``; kv column ``s`` is valid iff ``s < kv_len`` and
``s <= pos(r)``.  KV blocks entirely above the causal frontier are skipped
by bounding the inner loop, not masked — decode with a short cache does
O(kv_len) work regardless of ``max_seq``.

Numerics match ``ops.attention.attention`` (f32 softmax, same masking), so
the two are interchangeable; `attn_impl` hooks (models/decoder.py) select
the kernel on TPU and the jnp path elsewhere.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import update_kv_cache

_NEG = -1e30


def _live_kv_blocks(q_start, kv_len, row_blk_idx, rows_blk, groups, block_k):
    """Number of kv blocks below this row block's causal frontier (>= 1)."""
    max_pos = q_start + (row_blk_idx * rows_blk + rows_blk - 1) // groups
    upper = jnp.minimum(kv_len, max_pos + 1)
    return (upper + block_k - 1) // block_k


def _kernel(scalar_ref, q_ref, k_ref, v_ref, slopes_ref, o_ref,
            o_acc, m_acc, l_acc, *, block_k: int, groups: int,
            use_alibi: bool):
    """Grid (b, nkv, row_blocks, kv_blocks), kv innermost: one step streams
    one [block_k, hd] K/V block HBM→VMEM and folds it into the online-
    softmax accumulators held in VMEM scratch (which persists across the
    sequential grid on TPU).  KV blocks beyond a row block's causal frontier
    are neither fetched (index map clamps to the last live block — Mosaic
    skips the DMA when the block index repeats) nor computed (pl.when), so
    short-cache decode costs O(kv_len) HBM traffic, not O(max_seq).

    scalar_ref (SMEM, int32[2]): [q_start, kv_len].
    q_ref:      [1, 1, rows_blk, hd]   (rows = chunk * groups)
    k_ref/v_ref:[1, 1, block_k, hd]    (one streamed block of the kv plane)
    slopes_ref: [1, rows_blk, 1] f32   (per-row ALiBi slope, a sublane
                                        column — see flash_attention)
    o_ref:      [1, 1, rows_blk, hd]
    scratch: o_acc [rows_blk, hd] f32; m_acc/l_acc [rows_blk, 128] f32
    (lane-broadcast storage).
    """
    q_start = scalar_ref[0]
    kv_len = scalar_ref[1]
    rows_blk, hd = q_ref.shape[2], q_ref.shape[3]
    row_blk_idx = pl.program_id(2)
    ki = pl.program_id(3)
    num_ki = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    n_live = _live_kv_blocks(q_start, kv_len, row_blk_idx, rows_blk, groups,
                             block_k)

    @pl.when(ki < n_live)
    def _step():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        q = q * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
        row = (row_blk_idx * rows_blk
               + jax.lax.broadcasted_iota(jnp.int32, (rows_blk, 1), 0))
        q_pos = q_start + row // groups                   # [rows_blk, 1]

        k_blk = k_ref[0, 0, :, :]
        v_blk = v_ref[0, 0, :, :]
        s = jnp.dot(q, k_blk.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)   # [rows, bk]
        kv_pos = (ki * block_k
                  + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
        valid = (kv_pos <= q_pos) & (kv_pos < kv_len)     # [rows, bk]
        if use_alibi:
            slope_row = slopes_ref[0, :, :]               # [rows_blk, 1]
            s = s - slope_row * (q_pos - kv_pos).astype(jnp.float32)
        s = jnp.where(valid, s, _NEG)

        m = jnp.max(m_acc[:], axis=-1, keepdims=True)     # [rows, 1]
        l = jnp.max(l_acc[:], axis=-1, keepdims=True)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_acc[:] = o_acc[:] * alpha + jnp.dot(
            p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_acc[:] = jnp.broadcast_to(m_new, m_acc.shape)
        l_acc[:] = jnp.broadcast_to(l_new, l_acc.shape)

    @pl.when(ki == num_ki - 1)
    def _finalize():
        l = jnp.max(l_acc[:], axis=-1, keepdims=True)
        o_ref[0, 0, :, :] = (o_acc[:]
                             / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _pick_block(total: int, target: int) -> int:
    """Largest divisor of ``total`` that is <= target AND a multiple of 8.

    block_k is the sublane (second-to-minor) dimension of the streamed
    [block_k, hd] K/V tiles, so it must respect the TPU sublane granule of
    8 — an arbitrary divisor (e.g. 125 for total=1000) would hand Mosaic a
    misaligned tile.  Raises for totals not divisible by 8: pad max_seq to
    a multiple of 8 (the engine's KV capacity is caller-chosen) rather than
    silently running a misaligned kernel.
    """
    if total % 8:
        raise ValueError(
            f"flash attention requires max_seq divisible by 8, got {total}; "
            "pad the KV-cache capacity (engine max_seq) to a multiple of 8 "
            "or use the jnp attention backend")
    b = min(total, max(8, target - target % 8))
    while total % b or b % 8:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("block_k", "block_rows",
                                             "groups", "use_alibi",
                                             "interpret"))
def _flash_call(q_g, k_cache, v_cache, scalars, slopes, *, block_k,
                block_rows, groups, use_alibi, interpret):
    b, nkv, rows, hd = q_g.shape
    max_seq = k_cache.shape[2]
    grid = (b, nkv, rows // block_rows, max_seq // block_k)

    def kv_map(bb, h, r, ki, s):
        # clamp to the causal frontier: beyond-frontier grid steps re-fetch
        # the same block (no DMA) and skip compute (pl.when in the kernel).
        live = _live_kv_blocks(s[0], s[1], r, block_rows, groups, block_k)
        return (bb, h, jnp.minimum(ki, live - 1), 0)

    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, groups=groups,
                          use_alibi=use_alibi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_rows, hd),
                             lambda bb, h, r, ki, s: (bb, h, r, 0)),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_k, hd), kv_map),
                pl.BlockSpec((1, block_rows, 1),
                             lambda bb, h, r, ki, s: (h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_rows, hd),
                                   lambda bb, h, r, ki, s: (bb, h, r, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_rows, hd), jnp.float32),
                pltpu.VMEM((block_rows, 128), jnp.float32),
                pltpu.VMEM((block_rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nkv, rows, hd), q_g.dtype),
        interpret=interpret,
    )(scalars, q_g, k_cache, v_cache, slopes)


def flash_attention(
    q: jnp.ndarray,            # [b, chunk, nh, hd]
    k_cache: jnp.ndarray,      # [b, nkv, max_seq, hd] (head-major)
    v_cache: jnp.ndarray,
    q_start: jnp.ndarray,      # scalar int32: position of q[:, 0]
    kv_len: jnp.ndarray,       # scalar int32: valid cache length
    slopes: Optional[jnp.ndarray] = None,   # [nh] ALiBi slopes or None
    *,
    block_k: int = 512,
    block_rows_target: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in for ``ops.attention.attention`` with contiguous q positions
    (``q_positions = q_start + arange(chunk)`` — always true in the engine).

    Returns [b, chunk, nh, hd] in q.dtype.

    Default block sizes are tuned on TPU v5e (swept 128-512 x 256-2048 at
    chunk 2048): large kv blocks + tall row blocks keep the MXU fed and
    amortize the online-softmax bookkeeping — block_k=512/rows=2048 beat
    the old 128/512 defaults by ~1.3x and the jnp path at every
    prefill-sized chunk.
    """
    b, chunk, nh, hd = q.shape
    nkv, max_seq = k_cache.shape[1], k_cache.shape[2]
    g = nh // nkv

    # [b, chunk, nh, hd] -> [b, nkv, chunk*g, hd]: row r = (chunk r//g,
    # group member r%g); kv-head-major so each program loads K/V once.
    q_g = q.reshape(b, chunk, nkv, g, hd).transpose(0, 2, 1, 3, 4)
    q_g = q_g.reshape(b, nkv, chunk * g, hd)

    bk = _pick_block(max_seq, block_k)
    # Row blocks must hold whole query groups (so q_pos stays block-affine)
    # and satisfy the TPU sublane constraint: divisible by 8, or the whole
    # rows dimension.
    d = min(chunk, max(1, block_rows_target // g))
    while d > 1 and (chunk % d or (d * g) % 8):
        d -= 1
    br = d * g if (d * g) % 8 == 0 and chunk % d == 0 else chunk * g
    scalars = jnp.stack([jnp.asarray(q_start, jnp.int32),
                         jnp.asarray(kv_len, jnp.int32)])
    # per-row slope as a [br, 1] sublane COLUMN built out here: row r of
    # a block is group member r % g (blocks hold whole groups), so one
    # column serves every row block of a kv head.  Tiling the g-vector
    # inside the kernel is a lane->sublane relayout Mosaic refuses
    # ("Input offsets outside of the first tile", MHA at chunk 256).
    if slopes is None:
        slopes_g = jnp.zeros((nkv, br, 1), jnp.float32)  # zero: no bias
    else:
        slopes_g = jnp.tile(slopes.astype(jnp.float32).reshape(nkv, g),
                            (1, br // g))[:, :, None]

    out = _flash_call(q_g, k_cache, v_cache, scalars, slopes_g,
                      block_k=bk, block_rows=br, groups=g,
                      use_alibi=slopes is not None, interpret=interpret)
    out = out.reshape(b, nkv, chunk, g, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, chunk, nh, hd)


def make_flash_attn_impl(interpret: bool = False, min_chunk: int = 16):
    """Build an ``attn_impl`` hook (models/decoder.py): Pallas flash kernel
    for prefill-sized chunks, XLA-fused jnp attention for decode.

    Measured on TPU v5e (tinyllama shapes): flash prefill is ~2.3x the jnp
    path (no materialized [.., chunk, max_seq] score tensor), but decode
    (chunk=1, q rows = GQA group) is bandwidth-bound and XLA's fusion wins —
    so chunks below ``min_chunk`` take the jnp path.  ``chunk`` is static
    under jit, so the dispatch costs nothing.

    Assumes contiguous query positions (engine guarantee).
    """
    from .attention import attention

    def impl(q, k, v, k_cache, v_cache, positions, cache_start, slopes):
        k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v,
                                           cache_start)
        kv_len = cache_start + q.shape[1]
        if q.shape[1] >= min_chunk:
            out = flash_attention(q, k_cache, v_cache, cache_start, kv_len,
                                  slopes, interpret=interpret)
        else:
            out = attention(q, k_cache, v_cache, positions, kv_len, slopes)
        return out, k_cache, v_cache
    return impl
