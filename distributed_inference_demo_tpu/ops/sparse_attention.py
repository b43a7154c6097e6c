"""Block-sparse attention over a page pool: pooled-key selection of blocks
(InfLLM-v2, as MiniCPM4 and MiniCPM-SALA's ``minicpm4`` layers have it).

A kv head's keys are pooled, the mean of ``kernel`` consecutive keys every
``stride`` tokens (kernel ``j`` covers tokens ``stride j .. stride j +
kernel - 1`` and CLOSES on its last one), and a query at position ``t``
past ``dense_len`` attends a subset of the BLOCKS of ``block`` tokens of
its context (docs/DESIGN.md section 32):

    p_a   = softmax_j(q_a . c_j * hd ** -0.5)     over the kernels closed by t,
                                                  float32, a query head a
    s_j   = sum_a p_a[j]                          the heads of one kv group
    score(b) = max(s_j : kernel j meets block b)
    kept  = the first ``init`` blocks, the blocks of the last ``local``
            tokens (``t // block - local / block + 1 .. t // block``) and the
            ``topk`` best-scoring of the others (ties to the lower block)
    out   = causal softmax over the tokens <= t of the kept blocks

A query under ``dense_len`` keeps every block of its context (the dense
rule, a query).  Nothing here has weights of its own.

Three pieces, in the order a layer call runs them after the chunk's keys
and values are in their pages:

* :func:`write_index`: the pooled keys of the kernels that close inside the
  chunk go to the INDEX PLANE beside the pool (``ModelConfig.index_shape``:
  a page's ``bt / stride`` rows lie under the page's id, so they are leased
  and freed with it); the up to ``kernel - 1`` keys before the chunk's first
  token are read back from the pool, so a kernel may straddle a chunk's
  edge or a page's.
* :func:`select_blocks`: the kept blocks a (query, kv head), a mask, from
  the row's index rows gathered through its table.  Plain XLA, float32
  scores, inside the program that attends.
* the fold: :func:`sparse_fold` on the chip (a Pallas call: grid (query
  tiles, kv heads); a step walks the list of blocks that ANY query of its
  tile keeps, copies each, ``[block, hd]`` of K and of V, from its page into
  a ring in VMEM, and folds it under the mask of the tile's queries that
  keep it: one bit a query in a word a (tile, block); a block no query of
  the tile keeps is never read), :func:`sparse_gather_attention` elsewhere.
  A decode row is a tile of one query, a prefill segment is cut into tiles
  of :func:`ops.paged_attention.sub_chunk` queries, as the dense prefill
  kernel's are; the two are one kernel under two jitted names,
  ``_paged_call_sparse`` and ``_paged_prefill_call_sparse``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (PATH_GATHER, WRITE_SCATTER, POOL_PLANE, _NEG,
                              _fold_page, _ring_depth, _state_bytes,
                              route_pool, sub_chunk, write_paged_kv)
from .quant import QuantizedKVPages
from .stacked import LayerOf

PATH_SPARSE_DECODE = "pallas_sparse_decode"
PATH_SPARSE_PREFILL = "pallas_sparse_prefill"


def blocks_kept(t, sizes: tuple) -> tuple:
    """``(live, kept, index rows)``: the blocks of its context the query at
    position ``t`` has, the blocks it folds and the pooled keys its
    selection scores, a kv head (host arithmetic for the scheduler's
    record, on an int or a numpy array of positions: nothing here reads
    the device)."""
    import numpy as np
    kernel, stride, block, topk, init, local, dense = sizes
    t = np.asarray(t)
    live = t // block + 1
    forced = np.minimum(live, init) + np.minimum(np.maximum(live - init, 0),
                                                 local // block)
    kept = np.where(t < dense, live, forced + np.minimum(topk, live - forced))
    rows = np.where(t + 1 >= kernel, (t + 1 - kernel) // stride + 1, 0)
    return live, kept, rows


def tile_queries(s: int, groups: int) -> int:
    """Queries a tile of the two Pallas calls: one for a token a row, else
    the most that divide a segment of ``s`` within the dense prefill
    kernel's own tile (:func:`ops.paged_attention.sub_chunk`) and a word's
    32 bits."""
    return max(n for n in range(1, min(32, sub_chunk(s, groups)) + 1)
               if s % n == 0)


# ------------------------------------------------------------ the index plane

def _keys_before(K, plane, tables, start, kernel: int):
    """The ``kernel`` keys before each row's position ``start`` ``[b]``, read
    back from the pool ``K`` ``[planes, pages, nkv, bt, hd]`` through
    ``tables`` ``[b, W]``: ``[b, kernel, nkv, hd]`` (the first is never
    needed: whole for the arithmetic; before a request's first token there
    is nothing: clamped, unread).  They lie in at most ``span`` pages, which
    are gathered WHOLE (a gather along the pool's leading axes moves pages
    as they lie; one that picks tokens inside pages would have the compiler
    lay the whole pool out again), then cut to the tokens."""
    num_pages, nkv, bt, hd = K.shape[1:]
    b, W = tables.shape
    span = -(-kernel // bt) + 1
    first = jnp.maximum(start - kernel, 0)
    cols = jnp.minimum((first // bt)[:, None] + jnp.arange(span), W - 1)
    pages = jnp.clip(jnp.take_along_axis(tables, cols, 1), 0, num_pages - 1)
    held = K[plane, pages]                      # [b, span, nkv, bt, hd]
    held = held.transpose(0, 1, 3, 2, 4).reshape(b, span * bt, nkv, hd)
    at = jnp.maximum(start[:, None] - kernel + jnp.arange(kernel), 0)
    at = jnp.clip(at - (first // bt * bt)[:, None], 0, span * bt - 1)
    return jnp.take_along_axis(held, at[:, :, None, None], axis=1)


def write_index(index: LayerOf, k_pages: LayerOf, k_new, tables, positions,
                sizes: tuple):
    """The pooled keys of every kernel that closes on a token of the chunk
    ``k_new`` ``[b, s, nkv, hd]`` at ``positions`` ``[b, s]`` (contiguous a
    row), written to ``index`` (``LayerOf`` the index plane ``[planes,
    pages x bt / stride, nkv x hd]``) through ``tables`` ``[b, W]``.  The
    chunk's keys are in ``k_pages`` already; the ``kernel - 1`` keys before
    its first token are read from there.  A kernel whose row lies under a
    sentinel entry is dropped; one that closes on a padded position writes
    what nobody reads before the token that really closes it writes it
    again.  Returns ``index'``."""
    kernel, stride = sizes[:2]
    K = k_pages.stack
    if isinstance(K, QuantizedKVPages):
        raise ValueError("a sparse kind pools its keys from bf16 pages: "
                         "int8 / int4 pages have no index plane")
    b, s, nkv, hd = k_new.shape
    num_pages, bt = K.shape[1], K.shape[3]
    W = tables.shape[1]
    rows_a_page = bt // stride
    plane = jnp.asarray(k_pages.layer, jnp.int32)
    start = positions[:, 0].astype(jnp.int32)
    f32 = jnp.float32
    prev = _keys_before(K, plane, tables, start, kernel)
    ext = jnp.concatenate([prev.astype(f32), k_new.astype(f32)], axis=1)
    # the first kernel that closes at or after ``start``, and as many as a
    # chunk can close
    M = s // stride + 1
    n = start - kernel + stride
    j = (jnp.where(n > 0, n // stride, 0)[:, None]
         + jnp.arange(M, dtype=jnp.int32))                      # [b, M]
    close = stride * j + kernel - 1
    ok = (close >= start[:, None]) & (close < start[:, None] + s)
    # position p of the request is ``ext[p - start + kernel]``
    at = jnp.clip((stride * j - start[:, None] + kernel)[:, :, None]
                  + jnp.arange(kernel), 0, kernel + s - 1)       # [b, M, kn]
    pooled = jnp.mean(ext[jnp.arange(b)[:, None, None], at],
                      axis=2)                                   # [b, M, nkv, hd]
    col = (stride * j) // bt
    page = jnp.take_along_axis(tables, jnp.minimum(col, W - 1), 1)
    # rows of the planes end to end: one scatter of rows along the leading
    # axis, no plane sliced out (a row outside them is dropped)
    planes, rows, width = index.stack.shape
    dest = jnp.where(ok & (col < W) & (page < num_pages),
                     plane * rows + page * rows_a_page + j % rows_a_page,
                     planes * rows)
    stack = index.stack.reshape(planes * rows, width).at[dest].set(
        pooled.reshape(b, M, width).astype(index.stack.dtype), mode="drop")
    return LayerOf(stack.reshape(planes, rows, width), index.layer)


# -------------------------------------------------------------- the selection

def _scores(q, c, t, sizes: tuple):
    """``s_j`` of the queries ``q`` ``[s, nkv, g, hd]`` at positions ``t``
    ``[s]`` over the row's pooled keys ``c`` ``[nkv, J, hd]``: the softmax a
    head over the kernels closed by ``t``, summed over the group, ``[s, nkv,
    J]`` float32, ``-inf`` at a kernel not closed.  Plain XLA."""
    kernel, stride = sizes[:2]
    hd = q.shape[-1]
    J = c.shape[1]
    f32 = jnp.float32
    scores = jnp.einsum("sngd,njd->sngj", q.astype(f32), c.astype(f32),
                        preferred_element_type=f32,
                        precision=(jax.lax.Precision.HIGHEST
                                   if q.dtype == f32 else None))
    scores = scores * hd ** -0.5
    closed = (stride * jnp.arange(J) + kernel - 1)[None, :] <= t[:, None]
    scores = jnp.where(closed[:, None, None, :], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(closed[:, None, None, :],
                  jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0)), 0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    sj = jnp.sum(p, axis=2)                                     # [s, nkv, J]
    return jnp.where(closed[:, None, :], sj, -jnp.inf)


def _choose(sj, t, sizes: tuple):
    """Kept blocks ``[s, nkv, J stride / block]`` bool from the kernels'
    scores ``sj`` ``[s, nkv, J]`` (``J`` whole blocks' worth) of queries at
    positions ``t`` ``[s]``."""
    kernel, stride, block, topk, init, local, dense = sizes
    s, nkv, J = sj.shape
    ratio = block // stride
    NB = J // ratio
    # a block's score: the best of the kernels that meet it, the ``ratio``
    # that start inside it and the ``kernel / stride - 1`` before those
    score = jnp.max(sj.reshape(s, nkv, NB, ratio), axis=-1)
    for d in range(1, kernel // stride):
        shifted = jnp.concatenate(
            [jnp.full((s, nkv, d), -jnp.inf), sj[..., :-d]], axis=-1)
        score = jnp.maximum(score, shifted[..., ::ratio])
    blk = jnp.arange(NB)[None, :]
    last = (t // block)[:, None]
    exists = blk <= last
    forced = exists & ((blk < init) | (blk > last - local // block))
    cand = (exists & ~forced)[:, None, :] & jnp.isfinite(score)
    k = min(topk, NB)
    # the k-th best score a (query, kv head), by bisection on the bits of
    # the scores (sums of probabilities: not negative, so their float32
    # bit patterns order as they do; a block that is no candidate reads
    # -1): 31 counts over the blocks, where a sort of them cost 6.4 ms a
    # layer a slab (my chip run, PR 69).  Where fewer than k candidates
    # exist it ends at 0 and every candidate is kept
    bits = jnp.where(cand, jax.lax.bitcast_convert_type(
        jnp.maximum(score, 0.0).astype(jnp.float32), jnp.int32), -1)

    def narrow(i, kth):
        probe = kth | jnp.left_shift(jnp.int32(1), 30 - i)
        enough = jnp.sum(bits >= probe, axis=-1, keepdims=True) >= k
        return jnp.where(enough, probe, kth)

    kth = jax.lax.fori_loop(0, 31, narrow,
                            jnp.zeros(bits.shape[:-1] + (1,), jnp.int32))
    above = bits > kth
    # ties at the k-th score go to the lower block ids
    tied = bits == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    keep = forced[:, None, :] | chosen
    return jnp.where((t < dense)[:, None, None], exists[:, None, :], keep)


def _select(q, c, t, sizes: tuple):
    """Kept blocks of the queries ``q`` ``[s, nkv, g, hd]`` at positions
    ``t`` ``[s]`` over the row's pooled keys ``c`` ``[nkv, J, hd]`` (``J``
    whole blocks' worth): ``[s, nkv, J stride / block]`` bool."""
    return _choose(_scores(q, c, t, sizes), t, sizes)


def _scores_kernel(start_ref, q_ref, c_ref, o_ref, *, kernel: int,
                   stride: int, tq: int, groups: int):
    """Grid (kv heads, query tiles): one step scores ONE tile of ``tq``
    queries of one kv head against the row's ``J`` pooled keys ``c_ref``
    ``[1, 1, J, hd]`` (the block stays while the tiles of one row follow
    one another).  ``q_ref`` ``[1, 1, rows, hd]``: row ``r tq + c`` is query
    ``c``'s head ``r`` of the group.  ``o_ref`` ``[1, 1, tq, J]``: the
    softmax a row over the kernels its query's position has closed, summed
    over the group's ``groups`` heads; ``-inf`` at a kernel not closed."""
    rows, hd = q_ref.shape[2:]
    J = c_ref.shape[2]
    s = jax.lax.dot_general(
        q_ref[0, 0], c_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (hd ** -0.5)      # [rows, J]
    t = start_ref[pl.program_id(1)] + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) % tq
    closes = (stride * jax.lax.broadcasted_iota(jnp.int32, (1, J), 1)
              + kernel - 1)
    closed = closes <= t
    s = jnp.where(closed, s, _NEG)
    e = jnp.where(closed, jnp.exp(s - jnp.max(s, axis=1, keepdims=True)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
    sj = p[0:tq]
    for r in range(1, groups):
        sj = sj + p[r * tq:(r + 1) * tq]
    o_ref[0, 0] = jnp.where(closed[0:tq], sj, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "tq",
                                             "groups", "tiles", "interpret"))
def _sparse_scores(starts, q_g, c, *, kernel, stride, tq, groups, tiles,
                   interpret):
    """The Pallas call (``_sparse_scores.<n>`` in a trace): ``q_g`` ``[R,
    nkv, rows, hd]``, ``c`` ``[R / tiles, nkv, J, hd]``, ``starts`` ``[R]``
    each tile's first position.  Returns ``[R, nkv, tq, J]`` float32."""
    R, nkv, rows, hd = q_g.shape
    J = c.shape[2]
    return pl.pallas_call(
        functools.partial(_scores_kernel, kernel=kernel, stride=stride,
                          tq=tq, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nkv, R),
            in_specs=[pl.BlockSpec((1, 1, rows, hd),
                                   lambda h, i, *_: (i, h, 0, 0)),
                      pl.BlockSpec((1, 1, J, hd),
                                   lambda h, i, *_: (i // tiles, h, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, tq, J),
                                   lambda h, i, *_: (i, h, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((R, nkv, tq, J), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, 20 * rows * J + (8 << 20))),
        interpret=interpret,
    )(starts, q_g, c)


def scores_on_kernel(q, c, positions, sizes: tuple, *, interpret: bool):
    """:func:`_scores` of every row at once through the Pallas call: ``q``
    ``[b, s, nkv, g, hd]``, ``c`` ``[b, nkv, J, hd]`` (``J`` whole lanes),
    ``positions`` ``[b, s]``.  Returns ``[b, s, nkv, J]``."""
    b, s, nkv, g, hd = q.shape
    tq = tile_queries(s, g)
    T = s // tq
    # row ``r tq + c`` of a tile's kv head is its query ``c``'s head ``r``
    q_g = q.reshape(b, T, tq, nkv, g, hd).transpose(0, 1, 3, 4, 2, 5)
    q_g = q_g.reshape(b * T, nkv, g * tq, hd)
    rows = max(8, -(-g * tq // 8) * 8)
    if rows > g * tq:
        q_g = jnp.pad(q_g, ((0, 0), (0, 0), (0, rows - g * tq), (0, 0)))
    out = _sparse_scores(
        positions[:, ::tq].reshape(-1).astype(jnp.int32), q_g, c,
        kernel=sizes[0], stride=sizes[1], tq=tq, groups=g, tiles=T,
        interpret=interpret)
    return out.reshape(b, T, nkv, tq, -1).transpose(0, 1, 3, 2, 4).reshape(
        b, s, nkv, -1)


def select_blocks(q, index: LayerOf, tables, positions, sizes: tuple,
                  nkv: int, block_tokens: int, *, kernel: bool = False,
                  interpret: bool = False):
    """The kept blocks of every query: ``q`` ``[b, s, nh, hd]`` at
    ``positions`` ``[b, s]``, each row over the index rows of its own
    table ``tables`` ``[b, W]``.  Returns ``[b, s, nkv, W bt / block]``
    bool.  With ``kernel`` the scores are the Pallas call's
    (:func:`scores_on_kernel`) for every row at once; else plain XLA, rows
    one after another where a row is a segment (the scores of one are ``s
    x nh x J`` float32), at once where it is a token."""
    b, s, nh, hd = q.shape
    planes, rows_a_plane, width = index.stack.shape
    ix = index.stack.reshape(planes * rows_a_plane, width)  # planes end to end
    rows_a_page = block_tokens // sizes[1]
    num_pages = rows_a_plane // rows_a_page
    first = jnp.asarray(index.layer, jnp.int32) * rows_a_plane
    q = q.reshape(b, s, nkv, nh // nkv, hd)

    def pooled(tab):
        """A row's pooled keys through its table: ``[nkv, J, hd]``."""
        rows = (first + jnp.clip(tab, 0, num_pages - 1)[:, None] * rows_a_page
                + jnp.arange(rows_a_page)).reshape(-1)
        return ix[rows].reshape(-1, nkv, hd).transpose(1, 0, 2)

    def one(row):
        q_r, tab, t = row
        t = t.astype(jnp.int32)
        return _choose(_scores(q_r, pooled(tab), t, sizes), t, sizes)

    with jax.named_scope("sparse_select"):
        if kernel:
            # the table padded to whole lanes of pooled keys (what stands
            # there lies past every position: no kernel of it has closed)
            J = tables.shape[1] * rows_a_page
            assert 128 % rows_a_page == 0, "a page's rows divide the lanes"
            tabs = jnp.pad(tables, ((0, 0), (0, -J % 128 // rows_a_page)),
                           mode="edge")
            sj = scores_on_kernel(q, jax.vmap(pooled)(tabs), positions, sizes,
                                  interpret=interpret)[..., :J]
            return jax.vmap(lambda sj, t: _choose(sj, t.astype(jnp.int32),
                                                  sizes))(sj, positions)
        if s == 1:
            return jax.vmap(one)((q, tables, positions))
        return jax.lax.map(one, (q, tables, positions))


def tile_entries(keep, tq: int, cap: int):
    """What the fold walks, from ``keep`` ``[b, s, nkv, NB]``: the queries
    cut into tiles of ``tq`` (``s % tq == 0``, ``tq <= 32``), and for each
    (tile, kv head) the blocks ANY of its queries keeps, ascending, with a
    word a block whose bit ``i`` says that the tile's query ``i`` keeps it.
    Returns ``(ids, words [b s / tq, nkv, cap] int32, counts [b s / tq,
    nkv] int32)``; ``cap`` bounds the list (all of ``NB`` for a tile of
    many queries)."""
    b, s, nkv, NB = keep.shape
    T = s // tq
    bits = jnp.left_shift(jnp.uint32(1), jnp.arange(tq, dtype=jnp.uint32))
    words = jnp.sum(
        keep.reshape(b * T, tq, nkv, NB).astype(jnp.uint32)
        * bits[None, :, None, None], axis=1, dtype=jnp.uint32)
    some = words != 0
    order = jnp.argsort(~some, axis=-1, stable=True)[..., :cap]
    words = jnp.take_along_axis(words, order, axis=-1)
    if cap > NB:        # (whole iterations of the fold: blocks nobody keeps)
        pad = ((0, 0), (0, 0), (0, cap - NB))
        order, words = jnp.pad(order, pad), jnp.pad(words, pad)
    return (order.astype(jnp.int32),
            jax.lax.bitcast_convert_type(words, jnp.int32),
            jnp.minimum(jnp.sum(some, axis=-1), cap).astype(jnp.int32))


# -------------------------------------------------------------------- the fold

def _sparse_kernel(tab_ref, start_ref, layer_ref, cnt_ref, ids_ref,
                   words_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                   o_acc, m_acc, l_acc, *, block_tokens: int, block: int,
                   groups: int, tiles: int, ring: int, cap: int, fold: int):
    """Grid (query tiles, kv heads): one step folds, for ONE tile of
    queries and ONE kv head, the ``cnt_ref[tile, head]`` blocks that any of
    the tile's queries keeps, ``fold`` of them an iteration.  Block
    ``ids_ref[.., e]`` of the row is tokens ``id block ..`` of page
    ``tables[row, id block // bt]``; its ``[block, hd]`` of K and of V
    (contiguous in the pool) are copied into their place in slot ``e //
    fold % ring`` of a VMEM ring while earlier blocks fold
    (:func:`ops.paged_attention._fold_page`, ``fold x block`` keys at once:
    the fold's own cost an iteration, its state read and written, is what a
    block a time was bound by, 1.0 us a block of 64 at 512 rows where the
    products take 0.09; my chip run, PR 69) under ``kv_pos <= q_pos`` and
    bit ``query`` of ``words_ref[.., e]``.  A list's last iteration may run
    past its count: the entries there are blocks no query keeps (word 0).
    The tile's row of the tables is ``tile // tiles``."""
    i, h = pl.program_id(0), pl.program_id(1)
    nkv = pl.num_programs(1)
    layer = layer_ref[0]
    num_pages = k_hbm.shape[1]
    rows, hd = q_ref.shape[2:]
    bt = block_tokens
    per_page = bt // block
    row = i // tiles
    base = (i * nkv + h) * cap
    n_iter = (cnt_ref[i * nkv + h] + fold - 1) // fold

    def copies(it):
        slot = it % ring
        out = []
        for g in range(fold):
            blk = ids_ref[base + it * fold + g]
            page = jnp.minimum(tab_ref[row, blk // per_page], num_pages - 1)
            at = pl.ds((blk % per_page) * block, block)
            to = pl.ds(g * block, block)
            out += [pltpu.make_async_copy(hbm.at[layer, page, h, at],
                                          buf.at[slot, to],
                                          sems.at[slot, n * fold + g])
                    for n, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                    (v_hbm, v_buf)))]
        return out

    for it in range(ring - 1):
        @pl.when(it < n_iter)
        def _prime():
            for c in copies(it):
                c.start()

    o_acc[...] = jnp.zeros_like(o_acc)
    m_acc[...] = jnp.full_like(m_acc, _NEG)
    l_acc[...] = jnp.zeros_like(l_acc)
    q = q_ref[0, 0].astype(jnp.float32) * (1.0 / jnp.sqrt(
        jnp.asarray(hd, jnp.float32)))
    query = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // groups
    q_pos = start_ref[i] + query
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, fold * block), 1)
    part, inside = lane // block, lane % block

    def body(it, carry):
        @pl.when(it + ring - 1 < n_iter)
        def _prefetch():
            for c in copies(it + ring - 1):
                c.start()

        for c in copies(it):
            c.wait()
        slot = it % ring
        kv_pos = inside
        keeps = jnp.zeros((rows, fold * block), jnp.bool_)
        for g in range(fold):
            e = base + it * fold + g
            kv_pos = kv_pos + jnp.where(part == g, ids_ref[e] * block, 0)
            bit = (jax.lax.shift_right_logical(
                jnp.full((rows, 1), words_ref[e], jnp.int32), query) & 1) == 1
            keeps = keeps | ((part == g) & bit)
        o, m, l = _fold_page(
            q, k_buf[slot].astype(jnp.float32),
            v_buf[slot].astype(jnp.float32),
            (o_acc[...], m_acc[...][:, :1], l_acc[...][:, :1]),
            kv_pos, q_pos, None, 0, keeps)
        o_acc[...] = o
        m_acc[...] = jnp.broadcast_to(m, m_acc.shape)
        l_acc[...] = jnp.broadcast_to(l, l_acc.shape)
        return carry

    jax.lax.fori_loop(0, n_iter, body, 0)
    o_ref[0, 0] = (o_acc[...] / jnp.maximum(l_acc[...][:, :1], 1e-30)
                   ).astype(o_ref.dtype)


def _sparse_call_body(q_g, k_pages, v_pages, layer, tables, starts, counts,
                      ids, words, *, block_tokens, block, groups, tiles,
                      interpret):
    """The Pallas call: ``q_g`` ``[R, nkv, rows, hd]`` (a tile's queries,
    ``groups`` rows each), the stacked pools as ``pl.ANY`` operands (only
    the blocks copied move), ``tables`` ``[R / tiles, W]``, ``starts``
    ``[R]`` each tile's first position, ``counts`` ``[R nkv]``, ``ids`` /
    ``words`` ``[R nkv cap]`` flat."""
    R, nkv, rows, hd = q_g.shape
    cap = ids.shape[0] // (R * nkv)
    fold = fold_blocks(block)
    assert cap % fold == 0, "a list holds whole iterations"
    ring = _ring_depth(fold * block * hd * k_pages.dtype.itemsize)
    tile = pl.BlockSpec((1, 1, rows, hd), lambda i, h, *_: (i, h, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((ring, fold * block, hd), k_pages.dtype)
    return pl.pallas_call(
        functools.partial(_sparse_kernel, block_tokens=block_tokens,
                          block=block, groups=groups, tiles=tiles,
                          ring=ring, cap=cap, fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(R, nkv),
            in_specs=[tile, pool, pool],
            out_specs=tile,
            scratch_shapes=[buf, buf,
                            pltpu.SemaphoreType.DMA((ring, 2 * fold)),
                            pltpu.VMEM((rows, hd), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q_g.shape, q_g.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                16 << 20, _state_bytes(rows, hd)
                + 4 * rows * hd * q_g.dtype.itemsize
                + 16 * rows * max(hd, fold * block) + (8 << 20))),
        interpret=interpret,
    )(tables, starts, layer, counts, ids, words, q_g, k_pages, v_pages)


def fold_blocks(block: int) -> int:
    """Blocks the fold takes an iteration: 256 keys' worth (four of 64)."""
    return max(1, min(8, 256 // block))


_STATIC = ("block_tokens", "block", "groups", "tiles", "interpret")


# the two jitted calls, named as the trace readers know them
@functools.partial(jax.jit, static_argnames=_STATIC)
def _paged_call_sparse(*args, **kw):
    return _sparse_call_body(*args, **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _paged_prefill_call_sparse(*args, **kw):
    return _sparse_call_body(*args, **kw)


def sparse_fold(q, k_pages: LayerOf, v_pages: LayerOf, tables, positions,
                keep, *, block: int, cap: int, interpret: bool = False):
    """The Pallas fold of ``q`` ``[b, s, nh, hd]`` over the kept blocks
    ``keep`` ``[b, s, nkv, NB]``: a token a row (``s == 1``) is a tile of
    one query under the decode call's name, a segment is cut into tiles of
    :func:`sub_chunk` queries under the prefill call's."""
    b, s, nh, hd = q.shape
    K, V = k_pages.stack, v_pages.stack
    nkv, bt = K.shape[2], K.shape[3]
    g = nh // nkv
    tq = tile_queries(s, g)
    T = s // tq
    rows_real = tq * g
    rows = max(8, -(-rows_real // 8) * 8)
    fold = fold_blocks(block)
    ids, words, counts = tile_entries(keep, tq, -(-cap // fold) * fold)
    # row ``c g + r`` of a tile's kv head ``h`` is its query ``c``'s q head
    # ``h g + r`` (as ``ops.paged_attention._query_tiles``)
    q_g = q.reshape(b, T, tq, nkv, g, hd).transpose(0, 1, 3, 2, 4, 5)
    q_g = q_g.reshape(b * T, nkv, rows_real, hd)
    if rows > rows_real:
        q_g = jnp.pad(q_g, ((0, 0), (0, 0), (0, rows - rows_real), (0, 0)))
    call = _paged_call_sparse if s == 1 else _paged_prefill_call_sparse
    out = call(q_g, K, V, jnp.reshape(k_pages.layer, (1,)).astype(jnp.int32),
               tables.astype(jnp.int32),
               positions[:, ::tq].reshape(-1).astype(jnp.int32),
               counts.reshape(-1), ids.reshape(-1), words.reshape(-1),
               block_tokens=bt, block=block, groups=g, tiles=T,
               interpret=interpret)
    out = out[:, :, :rows_real].reshape(b, T, nkv, tq, g, hd)
    return out.transpose(0, 1, 3, 2, 4, 5).reshape(b, s, nh, hd)


def sparse_gather_attention(q, k_pages: LayerOf, v_pages: LayerOf, tables,
                            positions, keep, *, block: int):
    """Pure XLA: each row's pages gathered into a linear view and one
    masked softmax a query (``keep`` spread over its blocks' tokens,
    ``<= t``), float32.  Where the kernel does not run (the CPU)."""
    K, V = k_pages.stack, v_pages.stack
    num_pages, nkv, bt, hd = K.shape[1:]
    b, s, nh, _ = q.shape
    li = jnp.asarray(k_pages.layer, jnp.int32)
    safe = jnp.clip(tables, 0, num_pages - 1)
    lin = lambda P: P[li, safe].transpose(0, 2, 1, 3, 4).reshape(  # noqa: E731
        b, nkv, -1, hd)
    k_lin, v_lin = lin(K), lin(V)
    T = k_lin.shape[2]
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if q.dtype == f32 else None
    qg = q.reshape(b, s, nkv, nh // nkv, hd)
    sc = jnp.einsum("bsngd,bntd->bsngt", qg, k_lin,
                    preferred_element_type=f32, precision=prec) * hd ** -0.5
    see = (jnp.repeat(keep, block, axis=-1)[..., :T]
           & (jnp.arange(T)[None, None, :] <= positions[:, :, None])[
               :, :, None, :])[:, :, :, None, :]
    sc = jnp.where(see, sc, _NEG)
    w = jnp.where(see, jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bsngt,bntd->bsngd", w.astype(v_lin.dtype), v_lin,
                     preferred_element_type=f32, precision=prec)
    return out.reshape(b, s, nh, hd).astype(q.dtype)


def route_sparse(backend: str, platform: str, k_pages, chunk: int,
                 block: int) -> tuple:
    """``(path, why)`` of one traced sparse attention call: the kernel on
    a TPU over bf16 pages of whole lanes and whole blocks, the gather
    elsewhere; ``backend`` as :func:`ops.paged_attention.
    route_paged_attention` reads it."""
    if backend == "xla":
        return PATH_GATHER, "backend=xla"
    if backend == "auto" and platform != "tpu":
        return PATH_GATHER, f"backend=auto on platform={platform}"
    K = k_pages.stack
    why = ""
    if isinstance(K, QuantizedKVPages):
        why = "quantized pages have no sparse kernel"
    elif K.shape[-1] % 128 or block % 8 or K.shape[-2] % block:
        why = (f"head {K.shape[-1]}, block {block}, page {K.shape[-2]}: "
               f"the kernel copies [block, head] of whole lanes")
    if why:
        if backend == "pallas":
            raise ValueError(f"sparse attention backend 'pallas' cannot "
                             f"take this shape: {why}")
        return PATH_GATHER, why
    return (PATH_SPARSE_DECODE if chunk == 1 else PATH_SPARSE_PREFILL), ""


def kept_counts(keep, positions, valid, dense: int):
    """What a call's selections kept, counted where they were made: ``[3]``
    int32, over the queries that hold a token (``valid`` ``[b, s]``) and
    the kv heads: the (query, kv head) pairs past ``dense`` (the ones that
    selected), the blocks those kept, and the blocks all kept (``keep``
    ``[b, s, nkv, NB]``: a query under ``dense`` keeps its context's)."""
    n = jnp.where(valid[:, :, None], jnp.sum(keep, axis=-1, dtype=jnp.int32),
                  0)                                            # [b, s, nkv]
    selects = (valid & (positions >= dense))[:, :, None]
    return jnp.stack([jnp.sum(selects, dtype=jnp.int32) * keep.shape[2],
                      jnp.sum(jnp.where(selects, n, 0)), jnp.sum(n)])


def sparse_attend(q, k, v, k_pages: LayerOf, v_pages: LayerOf,
                  index: LayerOf, positions, tables, kind, *, backend: str,
                  interpret: bool, note=None, valid=None):
    """One traced layer call of a sparse kind over ``tables``: write the
    chunk's keys and values, write the index rows its tokens close, select
    each query's blocks, fold them.  ``(out, k_pages', v_pages', index',
    counts)``, the last :func:`kept_counts` of the mask the fold was handed
    (``valid``: the queries that hold a token, all of them where None)."""
    sizes = kind.sparse_sizes
    block = sizes[2]
    chunk = q.shape[1]
    nkv = k.shape[2]
    path, why = route_sparse(backend, jax.default_backend(), k_pages, chunk,
                             block)
    pool = route_pool(backend, jax.default_backend(), k_pages, chunk)
    if pool == POOL_PLANE:      # (no served shape: the kernels' gate holds)
        pool = WRITE_SCATTER
    if note is not None:
        note(chunk, path, why, pool)
    with jax.named_scope("paged_attention"):
        k_pages, v_pages = write_paged_kv(k_pages, v_pages, k, v, tables,
                                          positions, form=pool,
                                          interpret=interpret)
    with jax.named_scope("sparse_index"):
        index = write_index(index, k_pages, k, tables, positions, sizes)
    keep = select_blocks(q, index, tables, positions, sizes, nkv,
                         k_pages.stack.shape[3], kernel=path != PATH_GATHER,
                         interpret=interpret)
    counts = kept_counts(
        keep, positions,
        jnp.ones(positions.shape, bool) if valid is None else valid,
        sizes[6])
    NB = keep.shape[-1]
    if chunk == 1:      # one query: its own list, or a dense row's blocks
        cap = min(NB, max(sizes[4] + sizes[5] // block + sizes[3],
                          -(-sizes[6] // block)))
    else:
        cap = NB
    with jax.named_scope("sparse_fold"):
        if path == PATH_GATHER:
            out = sparse_gather_attention(q, k_pages, v_pages, tables,
                                          positions, keep, block=block)
        else:
            out = sparse_fold(q, k_pages, v_pages, tables, positions, keep,
                              block=block, cap=cap, interpret=interpret)
    return out, k_pages, v_pages, index, counts
