"""Paged attention over LATENT pages (multi-head latent attention, MLA:
DeepSeek-V2, arXiv:2405.04434, section 2.1; the ``deepseek_v3`` family).

A token's cache is ONE row a layer: the normed latent ``c`` (``kv_lora_rank``
wide) and the roped key part ``k_pe`` that all heads share, side by side and
zero-padded to whole lanes (:func:`latent_page_width`: 512 + 64 -> 640).
The pool is one array ``[planes, N, 1, bt, width]``; no decompressed key or
value is ever stored.

Both read paths run in the ABSORBED form: the caller folds ``W_UK`` into the
query (``q_abs = [q_nope W_UK^T | q_pe]``, one ``width``-wide row a head;
the softmax ``scale`` multiplies the float32 scores), every head attends to the same page row, the
values are the page's first ``rank`` lanes, and ``W_UV`` is applied to the
output outside.  It is multi-query attention over one shared key:

- :func:`latent_gather_attention`: the XLA gather of the table's pages and
  a plain masked softmax, everywhere (CPU tests, the fallback);
- :func:`latent_paged_attention`: one Pallas kernel for both shapes.  Grid
  ``(rows of the batch, query tiles)``: a tile is ``tq`` chunk positions x
  all heads (``tq x nh`` query rows, a decode step is one tile of ``nh``
  rows), and walks the row's live pages up to the tile's own causal
  frontier through a VMEM ring, a GROUP of pages an iteration: ``G``
  ``[bt, width]`` pages land side by side in one slot (a DMA each) and
  fold into float32 online-softmax state as one block of keys, scores
  from ``[rows, width] x [width, G x bt]``, output from the same rows'
  first ``rank`` lanes, the state rescaled once.  ``G`` and the ring's
  depth are read off the call's shapes (:func:`latent_fold`): what an
  iteration costs beside its bytes (a chain of dependent steps, each
  waiting for the last, and in a tile of 1,024 rows a pass over 3 MiB
  of state) is paid once a group.
  The pool stays in HBM (``pl.ANY``) and only the pages walked move.
  The two jitted wrappers carry the names the trace readers know:
  ``_paged_call_latent`` (decode) and ``_paged_prefill_call_latent`` (a
  chunk over its cached context).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (PATH_DECODE_KERNEL, PATH_GATHER,
                              PATH_PREFILL_KERNEL, POOL_PLANE, WRITE_KERNEL,
                              WRITE_SCATTER, AttnPathRecord, _kernel_write,
                              _like, _pool, _write_group, over_parts,
                              parts_of, route_pool, streams_note)
from .stacked import LayerOf

_NEG = -1e30
_LANES = 128
# query rows one tile holds (chunk positions x heads): the float32
# accumulator [rows, rank] (2 MiB) and the scores [rows, G x bt] live in
# VMEM.  A slab call over 4k + 8k of context took 2.95 ms a layer at 512
# rows and 2.58 at 1,024 (my chip run, PR 44): a page is read once a tile
_TILE_ROWS = 1024
# what ONE fold iteration takes (:func:`latent_fold`): a group of pages,
# as many as these three allow.  Bytes of pages side by side in one slot
# of the ring (what holds a decode step's group); bytes of the float32
# scores [rows, G x bt] the iteration holds (what holds a slab tile's:
# 0.5 MiB a page at 1,024 rows); copies an iteration starts and waits
# for by hand, a DMA and a semaphore each.  us a page of 128 x 640 bf16
# by G = 1 / 2 / 4 / 8 (my chip runs, PR 55; 0.20 is the page's bytes at
# 819 GB/s): a decode step of 32 rows over ~67 pages 0.60 / 0.37 / 0.29
# / 0.28, and no ring depth moves G = 1; a two-segment slab over 4k + 8k
# (a page a tile) 3.26 / 2.63 / 2.52 / 2.54, while a prompt's first
# chunk, whose tiles fill no group, pays 188 / 184 / 219 / 376 us a call
_GROUP_BYTES = 640 << 10
_SCORES_BYTES = 2 << 20
_GROUP_PAGES = 8
# bytes of pages on their way while a group folds: the ring is the group
# folding and as many groups landing as hold these.  A decode step at
# G = 4: 0.31-0.34 us a page with one group landing, 0.27-0.30 with two
# (my chip runs, PR 55); a slab tile does not feel the depth
_FLIGHT_BYTES = 1280 << 10
_VMEM_LIMIT = 32 << 20
# the online-softmax state (running maximum, sum and output) between
# pages: float32.  A name of its own so that a parity tool can read the
# path against the next precision down (``tools/model_parity.py``)
_STATE_DTYPE = jnp.float32


def latent_page_width(rank: int, rope: int) -> int:
    """Lanes of one token's row in a latent page: ``rank + rope`` rounded
    up to whole 128-lane tiles (a DMA slices an HBM ref, and a matmul
    contracts, in whole lane tiles).  512 + 64 -> 640: 64 lanes of zeros,
    11 % on the 576 values a token holds."""
    return -(-(rank + rope) // _LANES) * _LANES


def _stack_of(pages):
    """``(stack [L, N, 1, bt, width], layer)`` of a pool operand."""
    if isinstance(pages, LayerOf):
        return pages.stack, jnp.asarray(pages.layer, jnp.int32)
    return pages[None], jnp.zeros((), jnp.int32)


def write_latent_pages(pages, new: jnp.ndarray, tables: jnp.ndarray,
                       positions: jnp.ndarray, *, form: str = WRITE_SCATTER,
                       interpret: bool = False):
    """The chunk's rows ``new`` [b, chunk, width] into their pages: token
    at position ``p`` of row ``b`` lands in page ``tables[b, p // bt]`` at
    offset ``p % bt``.  Sentinel entries and positions past the table drop
    (``ops.paged_attention.write_paged_kv``'s contract; the two forms are
    its two forms, over one array)."""
    P, li = _stack_of(pages)
    new = new.astype(P.dtype)
    if form == WRITE_KERNEL:
        (P,) = _kernel_write((P,), li, (new[:, :, None, :],),
                             tables.astype(jnp.int32),
                             positions[:, 0].astype(jnp.int32), interpret)
        return _like(pages, P)
    num_pages, bt, W = P.shape[1], P.shape[3], tables.shape[1]
    pidx = positions // bt
    page = jnp.take_along_axis(tables, jnp.minimum(pidx, W - 1), axis=1)
    page = jnp.where(pidx < W, page, num_pages)
    P = P.at[li, page, 0, positions % bt].set(new, mode="drop")
    return _like(pages, P)


def latent_attend_linear(q_abs: jnp.ndarray, lin: jnp.ndarray,
                         q_positions: jnp.ndarray, rank: int,
                         scale: float) -> jnp.ndarray:
    """Absorbed attention over a LINEAR latent cache: ``q_abs``
    [b, chunk, nh, width] against ``lin`` [b, S, width],
    row ``k`` of it position ``k``; causal by position; the values are
    the first ``rank`` lanes; ``[b, chunk, nh, rank]``, float32
    arithmetic."""
    lin = lin.astype(jnp.float32)
    s = scale * jnp.einsum("bqhw,bkw->bhqk", q_abs.astype(jnp.float32), lin,
                           precision=jax.lax.Precision.HIGHEST)
    ok = jnp.arange(lin.shape[1])[None, None, :] <= q_positions[:, :, None]
    p = jax.nn.softmax(jnp.where(ok[:, None], s, _NEG), axis=-1)
    out = jnp.einsum("bhqk,bkr->bqhr", p, lin[..., :rank],
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(q_abs.dtype)


def latent_dense_attn(q_abs, row, cache, positions, cache_start, rank: int,
                      scale: float):
    """The hook for a DENSE latent cache ``[b, 1, max_seq, width]`` (one
    layer's plane: the plain engines, scoring): insert the chunk's rows
    at ``cache_start``, attend to the cache."""
    cache = jax.lax.dynamic_update_slice(
        cache, row[:, None].astype(cache.dtype), (0, 0, cache_start, 0))
    return latent_attend_linear(q_abs, cache[:, 0], positions, rank,
                                scale), cache


def latent_gather_attention(q_abs: jnp.ndarray, pages, tables: jnp.ndarray,
                            q_positions: jnp.ndarray, rank: int,
                            scale: float) -> jnp.ndarray:
    """Pure-XLA path over pages: the table's pages gathered into a linear
    ``[b, W*bt, width]`` view, then :func:`latent_attend_linear`.  Reads
    ``(layer, page)`` of the stacked pool: only the table's pages move;
    sentinel entries clamp (the garbage is causally masked)."""
    P, li = _stack_of(pages)
    num_pages, bt = P.shape[1], P.shape[3]
    safe = jnp.clip(tables, 0, num_pages - 1)
    b, W = safe.shape
    lin = P[li, safe][:, :, 0].reshape(b, W * bt, P.shape[-1])
    return latent_attend_linear(q_abs, lin, q_positions, rank, scale)


def latent_fold(rows: int, block_tokens: int, width: int, itemsize: int,
                table_pages: int) -> tuple:
    """``(group, ring)`` of one compiled call, read off its shapes: the
    pages ONE fold iteration takes (side by side in a slot, one scores
    product, one maximum, one rescale of the state over all of them) and
    the slots of the ring (one folding, the rest landing; never more
    than the table fills).  ``rows`` is the tile's query rows
    (``tile_tokens x heads``).  Over 160 KiB pages a decode step of 32
    heads and a slab tile of 1,024 rows both fold 4 pages an iteration
    through 3 slots, the one held by a slot's bytes and the other by its
    scores."""
    page = block_tokens * width * itemsize
    group = max(1, min(_GROUP_BYTES // page,
                       _SCORES_BYTES // (rows * block_tokens * 4),
                       _GROUP_PAGES, table_pages))
    landing = min(-(-_FLIGHT_BYTES // (group * page)),
                  -(-table_pages // group))
    return group, 1 + landing


def _latent_kernel(tab_ref, start_ref, layer_ref, q_ref, pool_hbm, o_ref,
                   buf, sems, o_acc, m_acc, l_acc, *, block_tokens: int,
                   heads: int, tile_tokens: int, rank: int, group: int,
                   ring: int, scale: float):
    """Grid (b, query tiles).  Tile ``t`` of row ``b`` holds chunk
    positions ``[t * tq, (t + 1) * tq)`` x ``heads``: query row ``r`` is
    position ``start + t * tq + r // heads``.  It walks pages
    ``0 .. ceil((start + (t + 1) * tq) / bt)`` of ``tab_ref[b]`` (never
    past the table), ``group`` pages a fold: page ``j`` is copied, a DMA
    of its own, into part ``j % group`` of slot ``(j // group) % ring``
    while earlier groups fold.  A part of the last group that no page
    fills is zeroed before the fold reads it (its keys are behind the
    mask, and zero times whatever VMEM held would not be zero).  A row
    whose ``start`` is ``-chunk`` (no page: a freed slot) walks none and
    its output is zero."""
    b, t = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    num_pages, W = pool_hbm.shape[1], tab_ref.shape[1]
    bt, tq, G = block_tokens, tile_tokens, group
    rows = q_ref.shape[1]
    first = start_ref[b] + t * tq                 # the tile's first position
    n_live = jnp.clip((first + tq + bt - 1) // bt, 0, W)

    def part(gi, g):
        return buf.at[gi % ring, g * bt:(g + 1) * bt]

    def page_copy(gi, g):
        page = jnp.minimum(tab_ref[b, gi * G + g], num_pages - 1)
        return pltpu.make_async_copy(pool_hbm.at[layer, page, 0],
                                     part(gi, g), sems.at[gi % ring, g])

    def start_group(gi):
        for g in range(G):
            @pl.when(gi * G + g < n_live)
            def _start():
                page_copy(gi, g).start()

    for gi in range(ring - 1):
        start_group(gi)

    o_acc[...] = jnp.zeros_like(o_acc)
    m_acc[...] = jnp.full_like(m_acc, _NEG)
    l_acc[...] = jnp.zeros_like(l_acc)
    q = q_ref[0]                                        # [rows, width]
    q_pos = first + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // heads

    def fold(gi, carry):
        start_group(gi + ring - 1)
        for g in range(G):
            @pl.when(gi * G + g < n_live)
            def _landed():
                page_copy(gi, g).wait()

            if g:                       # page gi * G is live in every fold
                @pl.when(gi * G + g >= n_live)
                def _dead():
                    part(gi, g)[...] = jnp.zeros((bt, buf.shape[-1]),
                                                 buf.dtype)

        k_blk = buf[gi % ring]                          # [G * bt, width]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kv_pos = gi * (G * bt) + jax.lax.broadcasted_iota(
            jnp.int32, (1, G * bt), 1)
        valid = kv_pos <= q_pos                         # [rows, G * bt]
        s = jnp.where(valid, s, _NEG)
        m = m_acc[:, :1].astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = (l_acc[:, :1].astype(jnp.float32) * alpha
                 + jnp.sum(p, axis=-1, keepdims=True))
        v_blk = k_blk[:, :rank]
        if v_blk.dtype == jnp.float32:
            pv = jnp.dot(p, v_blk, preferred_element_type=jnp.float32)
        else:
            # the float32 weights as two bf16 terms (16 bits of mantissa
            # between them), each one MXU pass over the stored values; a
            # float32 matmul proper costs six.  (One bf16 term alone was
            # no faster in the slab's shape and 8 % in a decode step's:
            # my chip run, PR 44.)
            hi = p.astype(v_blk.dtype)
            lo = (p - hi.astype(jnp.float32)).astype(v_blk.dtype)
            pv = (jnp.dot(hi, v_blk, preferred_element_type=jnp.float32)
                  + jnp.dot(lo, v_blk, preferred_element_type=jnp.float32))
        o_acc[...] = (o_acc[...].astype(jnp.float32) * alpha
                      + pv).astype(o_acc.dtype)
        m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape).astype(m_acc.dtype)
        l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape).astype(l_acc.dtype)
        return carry

    jax.lax.fori_loop(0, (n_live + G - 1) // G, fold, 0)
    o_ref[0] = (o_acc[...].astype(jnp.float32)
                / jnp.maximum(l_acc[:, :1].astype(jnp.float32), 1e-30)
                ).astype(o_ref.dtype)


def _latent_call(q_rows, pool, layer, tables, starts, *, block_tokens, heads,
                 tile_tokens, rank, group, ring, scale, interpret,
                 state=jnp.float32):
    b, n_rows, width = q_rows.shape
    rows = tile_tokens * heads
    bt = block_tokens
    tile = lambda w: pl.BlockSpec((1, rows, w),
                                  lambda bb, t, *_: (bb, t, 0))
    return pl.pallas_call(
        functools.partial(_latent_kernel, block_tokens=bt, heads=heads,
                          tile_tokens=tile_tokens, rank=rank, group=group,
                          ring=ring, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_rows // rows),
            in_specs=[tile(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(rank),
            scratch_shapes=[
                pltpu.VMEM((ring, group * bt, width), pool.dtype),
                pltpu.SemaphoreType.DMA((ring, group)),
                pltpu.VMEM((rows, rank), state),
                pltpu.VMEM((rows, _LANES), state),
                pltpu.VMEM((rows, _LANES), state),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_rows, rank), q_rows.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tables, starts, layer, q_rows, pool)


_STATIC = ("block_tokens", "heads", "tile_tokens", "rank", "group", "ring",
           "scale", "interpret", "state")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _paged_call_latent(q_rows, pool, layer, tables, starts, **kw):
    """The decode call (one position a row): ``_paged_call...`` in a trace."""
    return _latent_call(q_rows, pool, layer, tables, starts, **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _paged_prefill_call_latent(q_rows, pool, layer, tables, starts, **kw):
    """The prefill call (a chunk over its cached context):
    ``_paged_prefill_call...`` in a trace."""
    return _latent_call(q_rows, pool, layer, tables, starts, **kw)


def latent_tile_tokens(chunk: int, heads: int) -> int:
    """Chunk positions one query tile holds: the most that divide the
    chunk and keep ``tq x heads`` rows within ``_TILE_ROWS``."""
    tq = max(1, min(chunk, _TILE_ROWS // heads))
    while chunk % tq:
        tq -= 1
    return tq


def _fold_of(pool, tables, chunk: int, heads: int) -> tuple:
    """:func:`latent_fold` of a call over ``pool`` [.., bt, width]."""
    return latent_fold(latent_tile_tokens(chunk, heads) * heads,
                       pool.shape[-2], pool.shape[-1], pool.dtype.itemsize,
                       tables.shape[1])


def latent_paged_attention(q_abs: jnp.ndarray, pages, tables: jnp.ndarray,
                           q_positions: jnp.ndarray, rank: int,
                           scale: float, *,
                           interpret: bool = False) -> jnp.ndarray:
    """The Pallas path: ``q_abs`` [b, chunk, nh, width] over the row's
    pages, positions CONTIGUOUS per row; ``[b, chunk, nh, rank]``.
    Numerics match :func:`latent_gather_attention` (float32 online
    softmax, same masking)."""
    b, chunk, nh, width = q_abs.shape
    P, li = _stack_of(pages)
    num_pages, bt = P.shape[1], P.shape[3]
    tables = tables.astype(jnp.int32)
    # a freed slot has no page, so it has no length (its table row is
    # sentineled and the caller discards its output)
    starts = jnp.where(tables[:, 0] >= num_pages, -chunk,
                       q_positions[:, 0].astype(jnp.int32))
    call = _paged_call_latent if chunk == 1 else _paged_prefill_call_latent
    tq = latent_tile_tokens(chunk, nh)
    group, ring = _fold_of(P, tables, chunk, nh)
    out = call(q_abs.reshape(b, chunk * nh, width), P, li.reshape(1), tables,
               starts, block_tokens=bt, heads=nh, tile_tokens=tq, rank=rank,
               group=group, ring=ring, scale=float(scale),
               interpret=interpret, state=_STATE_DTYPE)
    return out.reshape(b, chunk, nh, rank)


def route_latent_attention(backend: str, platform: str, pages, chunk: int,
                           heads: int):
    """``(path, why)`` for one traced call over latent pages: the rule of
    ``ops.paged_attention.route_paged_attention`` with this kernel's
    gates (a page of whole sublane tiles, a row of whole lane tiles, a
    tile of query rows that is whole sublane tiles)."""
    if backend == "xla":
        return PATH_GATHER, "backend=xla"
    if backend == "auto" and platform != "tpu":
        return PATH_GATHER, f"backend=auto on platform={platform}"
    pool = _pool(pages)
    bt, width = pool.shape[-2], pool.shape[-1]
    why = ""
    if bt % _write_group(pool.dtype):
        why = f"block_tokens={bt} is not whole sublane tiles"
    elif width % _LANES:
        why = f"a latent row of {width} lanes is not whole lane tiles"
    elif (latent_tile_tokens(chunk, heads) * heads) % 8:
        why = f"a query tile of {chunk} x {heads} rows is not whole tiles"
    if why:
        if backend == "pallas":
            raise ValueError(f"paged attention backend 'pallas' cannot "
                             f"take this shape: {why}")
        return PATH_GATHER, why
    return (PATH_DECODE_KERNEL if chunk == 1 else PATH_PREFILL_KERNEL), ""


def make_latent_attn_impl(rank: int, scale: float, backend: str = "auto",
                          interpret: bool = False,
                          record: Optional[AttnPathRecord] = None):
    """``(impl, bind)`` for a latent page pool: ``make_paged_attn_impl``'s
    seam.  ``impl(q_abs, row, pages, positions)`` writes the chunk's rows
    ``row`` [b, chunk, width] into their pages, attends ``q_abs``
    [b, chunk, nh, width] over them (scores times ``scale``) and returns ``(out [b, chunk, nh,
    rank], pages)``; ``impl.latent`` tells the decoder's block which
    signature the hook has, as ``stacked_cache`` tells its scan that the
    pool comes whole."""
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown paged attention backend {backend!r}; "
                         "expected 'auto', 'xla', or 'pallas'")
    bound = {}

    def bind(tables, program: str):
        bound["tables"] = tables
        bound["program"] = program

    def attend(tables, q_abs, row, positions, pages):
        assert isinstance(pages, LayerOf), "the pool comes stacked"
        chunk, nh = q_abs.shape[1], q_abs.shape[2]
        platform = jax.default_backend()
        path, why = route_latent_attention(backend, platform, pages, chunk,
                                           nh)
        # one head of ``width`` lanes: the pair pools' rule as it stands
        pool = route_pool(backend, platform, pages, chunk)
        if record is not None:
            record.note(bound["program"], chunk, path, why, pool,
                        fold_pages=None if path == PATH_GATHER else
                        _fold_of(_pool(pages), tables, chunk, nh)[0])
        whole = pages
        with jax.named_scope("mla_attend"):
            if pool == POOL_PLANE:
                pages = whole.sliced()
            pages = write_latent_pages(
                pages, row, tables, positions,
                form=WRITE_SCATTER if pool == POOL_PLANE else pool,
                interpret=interpret)
            if path == PATH_GATHER:
                out = latent_gather_attention(q_abs, pages, tables,
                                              positions, rank, scale)
            else:
                out = latent_paged_attention(q_abs, pages, tables,
                                             positions, rank, scale,
                                             interpret=interpret)
            if pool == POOL_PLANE:
                pages = LayerOf(whole.updated(pages), whole.layer)
        return out, pages

    def impl(q_abs, row, pages, positions):
        # (a pair of tables: a merged call, a part through each)
        return over_parts(bound["tables"], (q_abs, row, positions),
                          (pages,), attend)

    impl.stacked_cache = True
    impl.latent = True
    impl.parts = parts_of(bound)
    impl.note_streams = streams_note(record, bound)
    return impl, bind
