"""Paged decode attention: per-sequence block tables over a device pool.

The PagedAttention memory model (vLLM, SOSP'23): instead of one dense
``[batch, nkv, max_seq, hd]`` cache row per sequence, K/V live in a
shared pool of fixed-size pages ``[num_pages, nkv, block_tokens, hd]``
(one pool per layer — the engines stack a leading layer axis) and each
sequence addresses its pages through a block table ``[batch, W]`` of
page ids.  Every function here takes a pool operand either as one
layer's plane or as ``LayerOf(stack, layer)`` (``ops.stacked``): the
stacked ``[L, N, H, bt, D]`` pool and the layer's index.  Both address
``(layer, page)`` in a stack (a plane is a stack of one), so the decoder's
layer scan hands over its carried pool whole, and where the hook
addresses it in place (:func:`route_pool`) no plane of it and no second
copy of the pool is made.  Two consequences the dense layout cannot give:

- HBM is reserved per page actually allocated, not ``batch x max_seq``
  worst-case rows;
- two sequences sharing a prefix share the SAME pages (the radix tree in
  ``runtime/kvcache`` hands out the ids) — a prefix hit is a block-table
  entry, not a copy of any kind.

Sentinel convention: a table entry ``>= num_pages`` means "no page
here".  Writes through a sentinel DROP (jax scatter ``mode="drop"`` —
this is how freed batching slots and fused-block overshoot are routed
to nowhere); reads CLAMP (the gathered garbage is causally masked, and
pool pages always hold finite values, so masked garbage contributes
exact zeros).

Interchangeable compute paths (same numerics as ``ops.attention``):

- :func:`paged_gather_attention` — pure XLA ``jnp.take`` gather of the
  table's pages into a linear view + the reference ``attention``.  Runs
  everywhere (``JAX_PLATFORMS=cpu`` tier-1 and interpret-mode tests
  exercise the same code path the TPU fallback uses).
- :func:`paged_flash_attention` — Pallas TPU decode kernel: grid
  ``(batch,)``, the pools stay in HBM and the block table and lengths
  ride scalar prefetch.  One grid step walks one row: a loop over the
  row's ``ceil(kv_len / block_tokens)`` LIVE pages copies page
  ``tables[b, j]`` (``[nkv, block_tokens, hd]``, all kv heads in one
  DMA) into a VMEM ring ahead of the fold, and folds it into
  online-softmax accumulators carried by the loop.  A call's time is
  ``batch`` grid steps plus the live pages — the table's width ``W`` is
  not in it, and a freed slot costs its grid step alone.
- :func:`paged_prefill_attention` — the Pallas TPU prefill kernel, the
  same walk for a chunk of queries: grid ``(rows of the call, blocks of
  kv heads)``, one step one QUERY TILE (a chunk, or a sub-chunk of one:
  :func:`sub_chunk`) over as many kv heads as its float32 state lets
  VMEM hold (:func:`_heads_a_step`).  The step loops over the tile's
  live pages, from the page of the first key its first query sees (0
  without a window) to its own causal frontier ``ceil((start + chunk) /
  bt)``, copying those heads of page ``tables[b, j]`` (``[heads, bt,
  hd]``, one DMA) through a VMEM ring and folding them, head by head,
  into float32 online-softmax state in scratch (:func:`_fold_page`).
  The table's width is not in a call's time, and a page the chunk cannot
  see costs nothing.

The gate both loops share (:func:`_page_loop_covers`): Mosaic (jax 0.9)
slices an HBM ref by hand only where its minor dimension fills the 128
lanes, so heads that are no multiple of 128 and int8 pages under 128
tokens keep the prefill GRID kernel (:func:`_paged_prefill_kernel`: grid
``(b, nkv, W)``, one page of the table a step through the BlockSpec
pipeline, the decode path of those shapes as a 1-token chunk).  It folds
with the same :func:`_fold_page`, so the two agree bit for bit; no served
configuration runs it.
"""

import functools
import types
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import attention, prepare_kv_chunk
from .quant import QuantizedKVPages, quantize_kv_like
from .stacked import LayerOf

_NEG = -1e30

# the dtype the kernels' online-softmax state (running maximum, sum and
# output) is ROUNDED to between pages: float32, the state's own, rounds
# nothing and traces nothing.  ``tools/model_parity.py
# --bf16-softmax-state`` sets bfloat16: the next precision down, which a
# long-context reading is held against
_STATE_DTYPE = jnp.float32


def _state(x):
    if _STATE_DTYPE == jnp.float32:
        return x
    return x.astype(_STATE_DTYPE).astype(jnp.float32)


# how a layer call reaches the pool (route_pool picks one): in place in
# the stack, by one of write_paged_kv's two forms, or through the layer's
# plane, sliced out and put back
WRITE_SCATTER = "scatter write"
WRITE_KERNEL = "kernel write"
POOL_PLANE = "plane"


def _pool(pages):
    """The pages themselves, a plane or the stack a ``LayerOf`` holds."""
    return pages.stack if isinstance(pages, LayerOf) else pages


def _stacked(k_pages, v_pages):
    """``(k_stack, v_stack, layer)`` of a pair of pool operands: a
    :class:`LayerOf` as it is, a plane ``[N, H, bt, D]`` as the stack of
    one layer it is (a reshape)."""
    if isinstance(k_pages, LayerOf):
        return (k_pages.stack, v_pages.stack,
                jnp.asarray(k_pages.layer, jnp.int32))
    one = lambda pages: jax.tree.map(lambda a: a[None], pages)
    return one(k_pages), one(v_pages), jnp.zeros((), jnp.int32)


def _like(pages, stack):
    """``stack`` handed back in the form ``pages`` came in."""
    if isinstance(pages, LayerOf):
        return LayerOf(stack, pages.layer)
    return jax.tree.map(lambda a: a[0], stack)


def write_paged_kv(
    k_pages,                # [num_pages, nkv, block_tokens, hd] or LayerOf
    v_pages,
    k_new: jnp.ndarray,     # [batch, chunk, nkv, hd] (projection layout)
    v_new: jnp.ndarray,
    tables: jnp.ndarray,    # [batch, W] int32 page ids (>= num_pages = none)
    positions: jnp.ndarray,  # [batch, chunk] absolute token positions
    *,
    form: str = WRITE_SCATTER,  # or WRITE_KERNEL: route_pool says which
    interpret: bool = False,
):
    """Scatter the chunk's K/V into its pages: token at position ``p`` of
    row ``b`` lands in page ``tables[b, p // bt]`` at offset ``p % bt``.

    Sentinel table entries route the write out of bounds, where scatter
    ``mode="drop"`` discards it — the paged twin of the dense layout's
    "stale writes land on the row's own dead columns".  A position PAST
    the table (``p // bt >= W`` — a padded prefill tail running off the
    end of a full-width table) is routed to the sentinel too: the naive
    ``take_along_axis`` would CLAMP the page index to the last table
    entry, and for a request whose table is fully populated that is a
    real page — the write would corrupt a live position ``p % bt`` deep
    into it.  Write contract (stale-slot invariant, shared with the
    dense path): :func:`ops.attention.prepare_kv_chunk`.

    The pools come back in the form they came in.  A ``LayerOf`` is
    written at ``(layer, page, :, off)`` of the stacked pool, in place in
    the scan's carry, and every other layer's pages are untouched.
    ``form`` picks between two forms of the same write: one scatter a
    leaf (``WRITE_SCATTER``, everywhere), and the Pallas write below
    (``WRITE_KERNEL``: :func:`_page_write_call`; plain pages on the chip,
    ``positions`` CONTIGUOUS per row as in
    :func:`paged_prefill_attention`, and no two rows writing into the
    same tile group: :func:`route_pool` holds the chunk to that), which
    exists because of what the scatter on the stack costs there.
    """
    K, V, li = _stacked(k_pages, v_pages)
    bt = K.shape[3]
    if form == WRITE_KERNEL:
        k_new, v_new = prepare_kv_chunk(k_new, v_new, K.dtype, V.dtype)
        K, V = _kernel_write((K, V), li, (k_new, v_new),
                             tables.astype(jnp.int32),
                             positions[:, 0].astype(jnp.int32), interpret)
        return _like(k_pages, K), _like(v_pages, V)
    if isinstance(K, QuantizedKVPages):
        # quantize ONCE at write time, per token over head_dim: the
        # scale/zero sidecar leaves take the exact same scatter index
        # (their trailing axis is a broadcast singleton).
        k_new, v_new = prepare_kv_chunk(k_new, v_new, jnp.float32,
                                        jnp.float32)
    else:
        k_new, v_new = prepare_kv_chunk(k_new, v_new, K.dtype, V.dtype)
    qk = quantize_kv_like(K, k_new)
    qv = quantize_kv_like(V, v_new)
    num_pages, W = K.shape[1], tables.shape[1]
    pidx = positions // bt                                       # [b, s]
    page = jnp.take_along_axis(tables, jnp.minimum(pidx, W - 1), axis=1)
    page = jnp.where(pidx < W, page, num_pages)  # past-table -> drop
    off = positions % bt                                         # [b, s]
    # advanced indices at dims (0, 1, 3) around the head slice: the
    # indexed result layout [b, s, nkv, hd] is exactly the projection
    # layout the chunk arrives in — no transpose.
    scatter = lambda p, c: p.at[li, page, :, off].set(c, mode="drop")
    return (_like(k_pages, jax.tree.map(scatter, K, qk)),
            _like(v_pages, jax.tree.map(scatter, V, qv)))


# ---------------------------------------------------------------------------
# Pallas TPU page write
#
# On the chip the scatter above asks the compiler for the pool in ANOTHER
# layout than the attention kernels read (tokens outside heads, so that a
# token's [nkv, hd] window is contiguous), and the compiler then copies
# the WHOLE pool from one layout to the other between the write and the
# kernel, every layer call (compiled ahead of time for a v5e, PR 31:
# `copy(...)` of `[L, N, H, bt, D]` inside the layer loop).  A custom call
# takes its operands in the default layout, so the write is one too: the
# pool is aliased in and out and only the tiles that hold the chunk's
# tokens move.


def _write_group(dtype) -> int:
    """Tokens in one sublane tile of a page (8 of 32 bits, 16 of 16): what
    the write kernel reads, merges and writes back, since a DMA moves
    whole tiles."""
    return 32 // jnp.dtype(dtype).itemsize


def _page_write_kernel(page_ref, row_ref, lo_ref, hi_ref, layer_ref, *refs,
                       units: int, group: int):
    """Grid (batches of ``units``,).  Unit ``n`` is one tile group of one
    row's chunk: rows ``[row, row + group)`` of page ``page_ref[n]``, all
    kv heads (``[nkv, group, hd]``, strided over the heads).  A batch
    reads its units' tiles out of the pool, takes rows ``[lo, hi)`` of
    each from the chunk (``*_new_ref``: the chunk's tokens laid out like
    the tiles), and writes the tiles back.  A unit with ``hi <= lo`` (a
    sentinel page, a position past the table, padding) moves nothing.

    ``refs``, for ``n`` pools (K and V; a latent pool is one): the ``n``
    chunks, the ``n`` pools (aliased to the outputs and not read as
    inputs), the ``n`` outputs, ``n`` tile buffers and the semaphores."""
    n = (len(refs) - 1) // 4
    new_refs, hbms, bufs = refs[:n], refs[2 * n:3 * n], refs[3 * n:4 * n]
    sems = refs[-1]
    first = pl.program_id(0) * units
    layer = layer_ref[0]
    streams = tuple(zip(hbms, bufs, new_refs))

    def copies(u, to_pool):
        row = pl.multiple_of(row_ref[first + u], group)
        out = []
        for i, (hbm, buf, _) in enumerate(streams):
            tile = hbm.at[layer, page_ref[first + u], :,
                          pl.ds(row, group), :]
            src, dst = (buf.at[u], tile) if to_pool else (tile, buf.at[u])
            out.append(pltpu.make_async_copy(src, dst, sems.at[i, u]))
        return out

    def each_live(fn):
        def body(u, carry):
            @pl.when(hi_ref[first + u] > lo_ref[first + u])
            def _live():
                fn(u)
            return carry
        jax.lax.fori_loop(0, units, body, 0)

    def merge(u):
        r = jax.lax.broadcasted_iota(jnp.int32, bufs[0].shape[1:], 1)
        mine = (r >= lo_ref[first + u]) & (r < hi_ref[first + u])
        for _, buf, new in streams:
            # selected in 32 bits (exact): a mask over packed rows is
            # not something Mosaic has to lower
            buf[u] = jnp.where(mine, new[u].astype(jnp.float32),
                               buf[u].astype(jnp.float32)).astype(buf.dtype)

    each_live(lambda u: [c.start() for c in copies(u, False)])
    each_live(lambda u: [c.wait() for c in copies(u, False)])
    each_live(merge)
    each_live(lambda u: [c.start() for c in copies(u, True)])
    each_live(lambda u: [c.wait() for c in copies(u, True)])


@functools.partial(jax.jit, static_argnames=("units", "interpret"))
def _page_write_call(page, row, lo, hi, layer, news, pools, *, units,
                     interpret):
    """The Pallas call: ``pools`` (a tuple: K and V, or one latent pool)
    ``[L, N, nkv, bt, hd]`` aliased to the outputs, ``news`` their chunks
    ``[U, nkv, group, hd]`` with ``U`` a multiple of ``units``."""
    n = len(pools)
    U, nkv, group, hd = news[0].shape
    new_spec = pl.BlockSpec((units, nkv, group, hd),
                            lambda i, *_: (i, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((units, nkv, group, hd), pools[0].dtype)
    return pl.pallas_call(
        functools.partial(_page_write_kernel, units=units, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(U // units,),
            in_specs=[new_spec] * n + [pool_spec] * n,
            out_specs=[pool_spec] * n,
            scratch_shapes=[buf] * n + [
                pltpu.SemaphoreType.DMA((n, units))],
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands count the five scalar arrays too
        input_output_aliases={5 + n + i: i for i in range(n)},
        interpret=interpret,
        name="kv_page_write",
    )(page, row, lo, hi, layer, *news, *pools)


# units of one batch: their tiles are in flight together
_WRITE_UNITS = 32


def _kernel_write(pools, li, news, tables, starts, interpret):
    """The chunk cut into the kernel's units, for each of ``pools`` (K and
    V, or one latent pool) and its chunk in ``news``; the written pools
    come back in a list.  Row ``b``'s tokens sit at
    positions ``starts[b] + arange(chunk)``; they touch at most ``n_g``
    tile groups, each inside one page (``bt % group == 0``).  A group is
    one row's alone (:func:`route_pool`): two units naming the same
    group would both read it before either wrote it back."""
    _, num_pages, nkv, bt, hd = pools[0].shape
    b, chunk = news[0].shape[:2]
    W = tables.shape[1]
    G = _write_group(pools[0].dtype)
    n_g = (chunk + 2 * G - 2) // G
    first = ((starts // G) * G)[:, None] + G * jnp.arange(n_g)  # [b, n_g]
    pidx = first // bt
    page = jnp.take_along_axis(tables, jnp.minimum(pidx, W - 1), axis=1)
    live = (pidx < W) & (page < num_pages)      # else the write drops
    lo = jnp.clip(starts[:, None] - first, 0, G)
    hi = jnp.where(live, jnp.clip(starts[:, None] + chunk - first, 0, G), lo)
    # token (group g, row r) of a row is chunk column first + r - start
    col = (first - starts[:, None])[:, :, None] + jnp.arange(G)
    col = jnp.clip(col, 0, chunk - 1).reshape(b, n_g * G)

    def tiles(x):                       # [b, chunk, nkv, hd] -> units
        x = jnp.take_along_axis(x, col[:, :, None, None], axis=1)
        x = x.reshape(b, n_g, G, nkv, hd).transpose(0, 1, 3, 2, 4)
        return x.reshape(b * n_g, nkv, G, hd)

    U = b * n_g
    batches = -(-U // _WRITE_UNITS)
    units = -(-U // batches)
    pad = batches * units - U
    flat = lambda a: jnp.pad(a.reshape(U).astype(jnp.int32), (0, pad))
    padded = lambda x: jnp.pad(x, ((0, pad), (0, 0), (0, 0), (0, 0)))
    return _page_write_call(
        flat(jnp.minimum(page, num_pages - 1)), flat(first % bt), flat(lo),
        flat(hi), li.reshape(1), tuple(padded(tiles(x)) for x in news),
        tuple(pools), units=units, interpret=interpret)


def route_pool(backend: str, platform: str, k_pages, chunk: int) -> str:
    """How a traced layer call reaches the pool: in place in the stack by
    one of :func:`write_paged_kv`'s forms, or through the layer's plane
    (``POOL_PLANE``).  A pure function of what the trace can see, like
    :func:`route_paged_attention`.

    Off the chip the stack is addressed in place: the Pallas write where
    "pallas" asks for the kernels and the write covers the shape, else the
    scatter (the kernels interpreted, or the gather, read ``(layer,
    page)``).

    On the chip the stack is addressed in place where the Pallas write
    covers the shape, and nowhere else: there the scatter asks for the
    stack in another layout than the kernels read, and a pool of narrow
    heads or of narrow pages lies in HBM in another layout than a custom
    call takes (the compiler picks the one that pads least), and either
    costs copies of the WHOLE pool, a layer call (compiled ahead of time,
    PR 31: ``tests/test_bring_up.py``).  Such a call slices its layer's
    plane out, runs on the plane, and puts it back, which is the program
    every pool had before PR 31.

    The Pallas write covers plain pages whose head fills the lanes and
    whose page holds whole tile groups, **at a chunk of one token or of
    whole tile groups**.  It reads, merges and writes back whole tile
    groups, a batch of them in flight at once, so no two rows of a call
    may write into the same group.  Rows of different requests never do
    (they write their own pages).  Rows of ONE request exist: the mixed
    slab packs an admission's sequential chunks as rows of one call, each
    starting where the last ended, and the first on a page boundary (a
    prefix hit is whole pages).  At a chunk of whole groups every row
    then starts on a group boundary; at any other chunk two rows share a
    group and the second write-back would restore the first's tokens to
    what they were.  A quantized pool's sidecar has a minor dimension of
    1, which a DMA cannot slice."""
    pool = _pool(k_pages)
    kernel = False
    if (backend != "xla" and not isinstance(pool, QuantizedKVPages)
            and pool.shape[-1] % 128 == 0):
        group = _write_group(pool.dtype)
        kernel = pool.shape[-2] % group == 0 and (chunk == 1
                                                  or chunk % group == 0)
    if platform == "tpu":
        return WRITE_KERNEL if kernel else POOL_PLANE
    return WRITE_KERNEL if kernel and backend == "pallas" else WRITE_SCATTER


def paged_gather_attention(
    q: jnp.ndarray,          # [batch, chunk, nh, hd]
    k_pages,                 # [num_pages, nkv, block_tokens, hd] or LayerOf
    v_pages,
    tables: jnp.ndarray,     # [batch, W] int32
    q_positions: jnp.ndarray,  # [batch, chunk]
    slopes: Optional[jnp.ndarray] = None,
    window: int = 0,
) -> jnp.ndarray:
    """Pure-XLA fallback: gather each row's pages into a linear
    ``[batch, nkv, W*bt, hd]`` view and run the reference ``attention``.

    Materializes the gathered view (a full cache copy per layer) — fine
    for CPU tests and small batches, which is exactly where it runs; the
    TPU path is the Pallas kernel.  Quantized pools gather the NARROW
    leaves through the table first, then dequantize the gathered view to
    f32 — the same per-element ``convert * scale (+ zero)`` the kernel
    runs in-register, so the two paths stay bit-exact.  The gather reads
    ``(layer, page)`` of the stacked pool: only the table's pages move."""
    K, V, li = _stacked(k_pages, v_pages)
    _, num_pages, nkv, bt, hd = K.shape
    safe = jnp.clip(tables, 0, num_pages - 1)
    gather = lambda p: p[li, safe]                # [b, W, nkv, bt, ·]
    b, W = safe.shape
    if isinstance(K, QuantizedKVPages):
        k_lin = jax.tree.map(gather, K).dequantize(jnp.float32)
        v_lin = jax.tree.map(gather, V).dequantize(jnp.float32)
    else:
        k_lin = gather(K)
        v_lin = gather(V)
    k_lin = k_lin.transpose(0, 2, 1, 3, 4).reshape(b, nkv, W * bt, hd)
    v_lin = v_lin.transpose(0, 2, 1, 3, 4).reshape(b, nkv, W * bt, hd)
    # (pages behind a window are masked, whatever their entries hold)
    return attention(q, k_lin, v_lin, q_positions,
                     jnp.asarray(W * bt, jnp.int32), slopes, window)


# ---------------------------------------------------------------------------
# Pallas TPU decode kernel


# bytes of K (and as many of V) a row keeps in flight while it folds:
# the page ring below is as deep as this buys, between 2 and 8 pages
_RING_BYTES = 1 << 20


def _ring_depth(page_bytes: int) -> int:
    return max(2, min(8, _RING_BYTES // page_bytes))


def _page_loop_covers(k_pages) -> bool:
    """Whether a kernel may copy this pool's pages by hand (the page loop
    of :func:`_paged_kernel` and :func:`_paged_prefill_loop_kernel`).
    Mosaic (jax 0.9) slices an HBM ref only where the ref's minor
    dimension fills the 128 lanes: a head that is a multiple of 128 and,
    for int8 pages, a scale sidecar ``[.., bt]`` of 128-token pages.
    Every other shape keeps the BlockSpec pipeline, whose grid has the
    table's width in it."""
    k_pages = _pool(k_pages)
    hd, bt = k_pages.shape[-1], k_pages.shape[-2]
    return not (hd % 128
                or (isinstance(k_pages, QuantizedKVPages) and bt % 128))


def _paged_kernel(tab_ref, len_ref, layer_ref, q_ref, *refs,
                  block_tokens: int, ring: int, use_alibi: bool,
                  quantized: bool, window: int = 0):
    """Grid (b,): one step walks ONE row's live pages, all kv heads at
    once.  The stacked pools stay in HBM; page ``tables[b, j]`` of layer
    ``layer_ref[0]`` (``[nkv, bt, hd]``, contiguous in the pool) is copied
    into slot ``j % ring`` of a
    VMEM ring while earlier pages fold into the online-softmax
    accumulators, which are loop carries.  The loop runs
    ``ceil(kv_len / bt)`` times (at most ``W``), so a row with no live
    page costs the grid step alone.  Rows of a head are the q-head group
    members of that kv head (decode chunk = 1), all at the same query
    position ``kv_len - 1``.

    tab_ref (SMEM int32 [b, W]): the block tables; len_ref (SMEM int32
    [b]): per-row valid lengths AFTER the current token's insert;
    layer_ref (SMEM int32 [1]): the layer of the stack.  With
    ``quantized`` the pools are int8 and each is followed by its f32
    scale sidecar ``[L, N, nkv, bt]``, copied page for page beside it:
    the dequant happens in-register right after the narrow DMA — HBM
    traffic stays 1 byte + 4/hd per element.

    ``window`` > 0 (a window kind of block): the row's one query sees
    keys ``kv_len - window <= j < kv_len``, and the loop visits only the
    pages that meet that range; the table's entries behind it may be
    sentinel (their pages went back to the pool)."""
    if quantized:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, slopes_ref, o_ref,
         k_buf, ks_buf, v_buf, vs_buf, sems) = refs
    else:
        k_hbm, v_hbm, slopes_ref, o_ref, k_buf, v_buf, sems = refs
    b = pl.program_id(0)
    layer = layer_ref[0]
    num_pages, W = k_hbm.shape[1], tab_ref.shape[1]
    _, nkv, rows, hd = q_ref.shape
    kv_len = len_ref[b]
    bt = block_tokens
    # a length past the table (a row that finished inside a fused block
    # keeps stepping) walks the table's W entries and no further
    n_live = jnp.minimum((kv_len + bt - 1) // bt, W)
    # the first page the loop visits: a Python 0 without a window, so the
    # trace is the one it was
    first = (jnp.minimum(jnp.maximum(kv_len - window, 0) // bt, n_live)
             if window else 0)

    def page_copies(j):
        # sentinel entries clamp in-range: the garbage is masked below
        page = jnp.minimum(tab_ref[b, j], num_pages - 1)
        slot = j % ring
        streams = [(k_hbm.at[layer, page], k_buf),
                   (v_hbm.at[layer, page], v_buf)]
        if quantized:
            streams += [(ks_hbm.at[layer, page], ks_buf),
                        (vs_hbm.at[layer, page], vs_buf)]
        return [pltpu.make_async_copy(src, buf.at[slot], sems.at[slot, i])
                for i, (src, buf) in enumerate(streams)]

    for j in range(ring - 1):
        @pl.when(first + j < n_live)
        def _prime():
            for c in page_copies(first + j):
                c.start()

    q = q_ref[0].astype(jnp.float32)                    # [nkv, rows, hd]
    q = q * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))

    def fold(j, carry):
        o, m, l = carry

        @pl.when(j + ring - 1 < n_live)
        def _prefetch():
            for c in page_copies(j + ring - 1):
                c.start()

        for c in page_copies(j):
            c.wait()
        slot = j % ring
        k_blk = k_buf[slot].astype(jnp.float32)         # [nkv, bt, hd]
        v_blk = v_buf[slot].astype(jnp.float32)
        if quantized:
            k_blk = k_blk * ks_buf[slot][:, :, None]    # * [nkv, bt, 1]
            v_blk = v_blk * vs_buf[slot][:, :, None]
        s = jnp.einsum("hrd,htd->hrt", q, k_blk,
                       preferred_element_type=jnp.float32)
        kv_pos = (j * bt
                  + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bt), 2))
        # every q row is the same decode position kv_len - 1, so the
        # causal bound and the validity bound coincide
        valid = jnp.broadcast_to(
            (kv_pos < kv_len) & (kv_pos >= kv_len - window) if window
            else kv_pos < kv_len, s.shape)
        if use_alibi:
            dist = ((kv_len - 1) - kv_pos).astype(jnp.float32)
            s = s - slopes_ref[:] * dist                # [nkv, rows, 1]
        s = jnp.where(valid, s, _NEG)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * alpha + jnp.einsum(
            "hrt,htd->hrd", p, v_blk, preferred_element_type=jnp.float32)
        return _state(o_new), _state(m_new), _state(l_new)

    o, _, l = jax.lax.fori_loop(
        first, n_live, fold,
        (jnp.zeros((nkv, rows, hd), jnp.float32),
         jnp.full((nkv, rows, 1), _NEG, jnp.float32),
         jnp.zeros((nkv, rows, 1), jnp.float32)))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_call_body(q_g, k_pages, v_pages, layer, tables, kv_lens, slopes,
                     *, block_tokens, use_alibi, interpret, window=0):
    """The Pallas call.  ``k_pages`` / ``v_pages`` are the STACKED pools
    ``[L, N, nkv, bt, hd]`` and ``layer`` [1] int32 picks the layer: the
    pools are ``pl.ANY`` operands, so nothing of them moves but the pages
    the kernel copies."""
    b, nkv, rows, hd = q_g.shape
    quantized = isinstance(k_pages, QuantizedKVPages)
    bt = block_tokens
    k_data = k_pages.data if quantized else k_pages
    page_bytes = nkv * bt * hd * k_data.dtype.itemsize
    ring = _ring_depth(page_bytes)

    row_spec = pl.BlockSpec((1, nkv, rows, hd),
                            lambda bb, tab, lens, lay: (bb, 0, 0, 0))
    slopes_spec = pl.BlockSpec((nkv, rows, 1),
                               lambda bb, tab, lens, lay: (0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    page_buf = pltpu.VMEM((ring, nkv, bt, hd), k_data.dtype)
    if quantized:
        # Mosaic slices an HBM ref only where its minor dimension fills
        # the lanes, which the pool's [.., bt, 1] sidecar does not: the
        # kernel takes it as [L, N, nkv, bt].  (On the chip a narrow pool
        # comes as one layer's plane, route_pool: that is a plane's
        # sidecar, as it always was, and never the stack's.)
        scale_buf = pltpu.VMEM((ring, nkv, bt), k_pages.scale.dtype)
        in_specs = [row_spec] + [pool_spec] * 4 + [slopes_spec]
        operands = (q_g, k_pages.data, k_pages.scale[..., 0],
                    v_pages.data, v_pages.scale[..., 0], slopes)
        buffers = [page_buf, scale_buf, page_buf, scale_buf]
    else:
        in_specs = [row_spec, pool_spec, pool_spec, slopes_spec]
        operands = (q_g, k_pages, v_pages, slopes)
        buffers = [page_buf, page_buf]

    return pl.pallas_call(
        functools.partial(_paged_kernel, block_tokens=bt, ring=ring,
                          use_alibi=use_alibi, quantized=quantized,
                          **({"window": window} if window else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((ring, len(buffers)))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nkv, rows, hd), q_g.dtype),
        # the two rings, and a page of K and of V widened to f32 twice
        # over (the fold's operands and its products), beside the
        # compiler's default 16 MiB where that is more
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            16 << 20,
            2 * ring * page_bytes + 16 * nkv * bt * hd + (4 << 20))),
        interpret=interpret,
    )(tables, kv_lens, layer, *operands)


# the two jitted calls, named as the trace readers know them: a window
# kind's calls carry their own name (``_paged_call_window.<n>``), and
# without a window the program is the one it was
@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "use_alibi",
                                    "interpret"))
def _paged_call(q_g, k_pages, v_pages, layer, tables, kv_lens, slopes, *,
                block_tokens, use_alibi, interpret):
    return _paged_call_body(q_g, k_pages, v_pages, layer, tables, kv_lens,
                            slopes, block_tokens=block_tokens,
                            use_alibi=use_alibi, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "use_alibi",
                                    "interpret", "window"))
def _paged_call_window(q_g, k_pages, v_pages, layer, tables, kv_lens,
                       slopes, *, block_tokens, use_alibi, interpret,
                       window):
    return _paged_call_body(q_g, k_pages, v_pages, layer, tables, kv_lens,
                            slopes, block_tokens=block_tokens,
                            use_alibi=use_alibi, interpret=interpret,
                            window=window)


# a model with a summarised cache (``ops.eva_attention``): the SAME two
# programs over the table of summary pages then window pages that its
# hook builds, under names of their own so that a trace reader can tell
# its calls from another model's
@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "use_alibi",
                                    "interpret"))
def _paged_call_eva(q_g, k_pages, v_pages, layer, tables, kv_lens, slopes,
                    *, block_tokens, use_alibi, interpret):
    return _paged_call_body(q_g, k_pages, v_pages, layer, tables, kv_lens,
                            slopes, block_tokens=block_tokens,
                            use_alibi=use_alibi, interpret=interpret)


def paged_flash_attention(
    q: jnp.ndarray,          # [batch, 1, nh, hd] — decode chunk only
    k_pages,                 # [num_pages, nkv, block_tokens, hd] or LayerOf
    v_pages,
    tables: jnp.ndarray,     # [batch, W] int32
    kv_lens: jnp.ndarray,    # [batch] int32 valid length incl. this token
    slopes: Optional[jnp.ndarray] = None,
    *,
    interpret: bool = False,
    window: int = 0,
    eva: bool = False,
) -> jnp.ndarray:
    """Pallas paged decode attention; numerics match
    :func:`paged_gather_attention` (f32 online softmax, same masking).

    Requires ``block_tokens % 8 == 0`` (the page's token axis is the
    sublane dimension of the streamed tiles) and a 1-token chunk; the
    caller falls back to the gather path otherwise.  Heads of a
    multiple of 128 take the page loop (:func:`_paged_kernel`; int8
    pages at a multiple of 128 tokens too); every other shape runs the
    prefill kernel as a 1-token chunk."""
    b, chunk, nh, hd = q.shape
    if chunk != 1:
        raise ValueError(f"paged_flash_attention is decode-only (chunk=1), "
                         f"got chunk={chunk}")
    K, V, li = _stacked(k_pages, v_pages)
    if isinstance(K, QuantizedKVPages) and K.bits != 8:
        # int4's nibble lane-interleave is Mosaic-hostile (an unpack in
        # the lane dimension per element); int4 is the CAPACITY config
        # and always takes the gather path — a deliberate gate, see
        # docs/DESIGN.md §17.
        raise ValueError("the Pallas kernel streams bf16 or int8 pages; "
                         "int4 KV takes the XLA gather path")
    _, num_pages, nkv, bt, _ = K.shape
    if bt % 8:
        raise ValueError(f"block_tokens must be a multiple of 8 for the "
                         f"Pallas kernel, got {bt}")
    if not _page_loop_covers(K):
        # a narrow head, or a scale sidecar of a narrow page, goes
        # through the prefill kernel's BlockSpec pipeline as a 1-token
        # chunk: the same fold, over a grid that still has the table's
        # width in it
        return paged_prefill_attention(
            q, k_pages, v_pages, tables, (kv_lens - 1)[:, None], slopes,
            interpret=interpret, window=window, eva=eva)
    g = nh // nkv
    rows = max(8, -(-g // 8) * 8)    # pad group rows to the sublane granule

    # [b, 1, nh, hd] -> [b, nkv, g, hd] (+ zero-pad rows): row r of head h
    # is q head h*g + r
    q_g = q.reshape(b, nkv, g, hd)
    if rows > g:
        q_g = jnp.pad(q_g, ((0, 0), (0, 0), (0, rows - g), (0, 0)))
    if slopes is None:
        slopes_g = jnp.zeros((nkv, rows, 1), jnp.float32)
    else:
        slopes_g = slopes.astype(jnp.float32).reshape(nkv, g, 1)
        slopes_g = jnp.pad(slopes_g, ((0, 0), (0, rows - g), (0, 0)))
    # a freed slot keeps its last length on the device and only its
    # table row is sentineled: it has no page, so it has no length (a
    # live row's first entry is always a real page, and the caller
    # discards a dead row's output)
    tables = tables.astype(jnp.int32)
    if window:
        # a window kind's first entries are sentinel once their pages
        # went back: a live row is told by the page of its last token
        last = jnp.take_along_axis(
            tables, jnp.clip((kv_lens.astype(jnp.int32) - 1) // bt, 0,
                             tables.shape[1] - 1)[:, None], axis=1)[:, 0]
        kv_lens = jnp.where(last >= num_pages, 0, kv_lens.astype(jnp.int32))
        call = functools.partial(_paged_call_window, window=window)
    else:
        kv_lens = jnp.where(tables[:, 0] >= num_pages, 0,
                            kv_lens.astype(jnp.int32))
        call = _paged_call_eva if eva else _paged_call

    out = call(q_g, K, V, li.reshape(1), tables, kv_lens, slopes_g,
               block_tokens=bt, use_alibi=slopes is not None,
               interpret=interpret)
    return out[:, :, :g, :].reshape(b, 1, nh, hd)


# ---------------------------------------------------------------------------
# Pallas TPU prefill kernels (docs/DESIGN.md §19): one fold, two ways to
# bring it its pages.  Where the kernel may copy pages by hand
# (:func:`_page_loop_covers`: every served shape) a grid step is one
# query tile and walks the tile's live pages; every other shape keeps a
# grid step a page of the table.


def _fold_page(q, k_blk, v_blk, state, kv_pos, q_pos, slope, window,
               keeps=None):
    """One page folded into one kv head's online-softmax ``state``
    ``(o [rows, hd], m [rows, 1], l [rows, 1])``, all float32: ``q``
    [rows, hd] scaled, ``k_blk`` / ``v_blk`` [bt, hd] dequantized,
    ``kv_pos`` [1, bt] and ``q_pos`` [rows, 1] the keys' and the rows'
    positions.  The causal bound is per ROW (``kv_pos <= q_pos``), not
    the single shared decode position: in-chunk keys were already written
    to the pages by ``write_paged_kv`` (write-before-attend inside the
    layer), so causality alone makes a query see exactly its prefix plus
    its own earlier in-chunk keys.  Under a ``window`` a row sees keys
    ``q_pos - window < j <= q_pos``.  Both prefill kernels fold with
    this, so they agree bit for bit on the pages both visit.  ``keeps``
    [rows, 1] bool (``ops.sparse_attention``): the rows that fold these
    keys at all."""
    o, m, l = state
    s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # [rows, bt]
    valid = kv_pos <= q_pos
    if window:
        valid = valid & (q_pos - kv_pos < window)
    if keeps is not None:
        valid = valid & keeps
    if slope is not None:
        s = s - slope * (q_pos - kv_pos).astype(jnp.float32)
    s = jnp.where(valid, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + jnp.dot(p, v_blk,
                                preferred_element_type=jnp.float32)
    return _state(o_new), _state(m_new), _state(l_new)


def _row_positions(start, rows: int, groups: int):
    """Query position of each row of a tile [rows, 1]: row ``r`` is chunk
    position ``r // g`` of q head ``h*g + r % g``.  Padding rows
    (``r >= chunk*g``) see a position past the segment; their garbage
    output is sliced away by the caller."""
    return start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // groups


def _walk_bounds(start, chunk: int, bt: int, width: int, window: int):
    """``(first, end)``: the pages ``[first, end)`` of its table that a
    query tile of ``chunk`` tokens at position ``start`` walks: up to its
    causal frontier ``ceil((start + chunk) / bt)`` and never past the
    table; under a ``window`` from the page of key ``start - window +
    1``, the first its first query sees (else a Python 0).  On traced
    scalars inside the kernel, on arrays of starts in a test."""
    end = jnp.minimum((start + chunk + bt - 1) // bt, width)
    if not window:
        return 0, end
    return jnp.minimum(jnp.maximum(start - window + 1, 0) // bt, end), end


def _paged_prefill_loop_kernel(tab_ref, start_ref, layer_ref, q_ref, *refs,
                               block_tokens: int, chunk: int, groups: int,
                               ring: int, use_alibi: bool, quantized: bool,
                               window: int = 0):
    """Grid (b, kv head blocks): one step is ONE query tile (a row of the
    call: a chunk or a sub-chunk of one segment) over ``heads`` kv heads,
    and walks the tile's live pages: from the page of the first key its
    first query sees (0 without a ``window``) to its own causal frontier
    ``ceil((start + chunk) / bt)``, never past the table.  The stacked
    pools stay in HBM; ``heads`` heads of page ``tables[b, j]`` of layer
    ``layer_ref[0]`` (``[heads, bt, hd]``, contiguous in the pool) are
    copied into slot ``j % ring`` of a VMEM ring while earlier pages
    fold, head by head (:func:`_fold_page`), into float32 state in VMEM
    scratch.  Entries of the table outside the walk are never read: a
    window kind's sentinel entries behind the window, the tail past the
    frontier.  A call's time is its tiles' grid steps plus the pages
    they walk; the table's width is not in it.

    tab_ref (SMEM int32 [b, W]): block tables; start_ref (SMEM int32
    [b]): the position of each tile's column 0; layer_ref (SMEM int32
    [1]): the layer of the stack.  With ``quantized`` the pools are int8
    and each is followed by its f32 scale sidecar ``[L, N, nkv, bt]``,
    copied page for page beside it."""
    if quantized:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, slopes_ref, o_ref,
         k_buf, ks_buf, v_buf, vs_buf, sems, o_acc, m_acc, l_acc) = refs
    else:
        (k_hbm, v_hbm, slopes_ref, o_ref,
         k_buf, v_buf, sems, o_acc, m_acc, l_acc) = refs
    b = pl.program_id(0)
    layer = layer_ref[0]
    num_pages, W = k_hbm.shape[1], tab_ref.shape[1]
    _, heads, rows, hd = q_ref.shape
    mine = pl.ds(pl.program_id(1) * heads, heads)
    start = start_ref[b]
    bt = block_tokens
    first, n_live = _walk_bounds(start, chunk, bt, W, window)

    def page_copies(j):
        # sentinel entries clamp in-range: the garbage is masked
        page = jnp.minimum(tab_ref[b, j], num_pages - 1)
        slot = j % ring
        streams = [(k_hbm, k_buf), (v_hbm, v_buf)]
        if quantized:
            streams += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]
        return [pltpu.make_async_copy(hbm.at[layer, page, mine],
                                      buf.at[slot], sems.at[slot, i])
                for i, (hbm, buf) in enumerate(streams)]

    for j in range(ring - 1):
        @pl.when(first + j < n_live)
        def _prime():
            for c in page_copies(first + j):
                c.start()

    o_acc[...] = jnp.zeros_like(o_acc)
    m_acc[...] = jnp.full_like(m_acc, _NEG)
    l_acc[...] = jnp.zeros_like(l_acc)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    q_pos = _row_positions(start, rows, groups)

    def fold(j, carry):
        @pl.when(j + ring - 1 < n_live)
        def _prefetch():
            for c in page_copies(j + ring - 1):
                c.start()

        for c in page_copies(j):
            c.wait()
        slot = j % ring
        kv_pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        if quantized:
            ks = ks_buf[slot][:, :, None]               # [heads, bt, 1]
            vs = vs_buf[slot][:, :, None]
        for h in range(heads):
            q = q_ref[0, h].astype(jnp.float32) * scale
            k_blk = k_buf[slot, h].astype(jnp.float32)  # [bt, hd]
            v_blk = v_buf[slot, h].astype(jnp.float32)
            if quantized:
                k_blk = k_blk * ks[h]
                v_blk = v_blk * vs[h]
            slope = slopes_ref[h, 0, :][:, None] if use_alibi else None
            o, m, l = _fold_page(
                q, k_blk, v_blk, (o_acc[h], m_acc[h][:, :1],
                                  l_acc[h][:, :1]),
                kv_pos, q_pos, slope, window)
            o_acc[h] = o
            m_acc[h] = jnp.broadcast_to(m, m_acc.shape[1:])
            l_acc[h] = jnp.broadcast_to(l, l_acc.shape[1:])
        return carry

    jax.lax.fori_loop(first, n_live, fold, 0)
    for h in range(heads):
        o_ref[0, h] = (o_acc[h] / jnp.maximum(l_acc[h][:, :1], 1e-30)
                       ).astype(o_ref.dtype)


# bytes of float32 online-softmax state one grid step of the loop holds:
# a step takes as many of a tile's kv heads as this buys, so that one
# copy moves them all (laguna's 8 x 384 rows whole, 16 of bloom's
# 32 x 256)
_PREFILL_STATE_BYTES = 6 << 20


def _state_bytes(rows: int, hd: int) -> int:
    """One kv head's state: the output ``[rows, hd]`` and the running
    maximum and sum, ``[rows, 128]`` each, in float32."""
    return rows * (hd + 2 * 128) * 4


def _heads_a_step(nkv: int, rows: int, hd: int) -> int:
    """The kv heads one grid step of the prefill loop folds: the largest
    divisor of ``nkv`` whose state fits ``_PREFILL_STATE_BYTES``."""
    fit = max(1, _PREFILL_STATE_BYTES // _state_bytes(rows, hd))
    return max(h for h in range(1, nkv + 1) if nkv % h == 0 and h <= fit)


def _paged_prefill_loop_call(q_g, k_pages, v_pages, layer, tables, starts,
                             slopes, *, block_tokens, chunk, groups,
                             use_alibi, interpret, window=0):
    """The page loop's Pallas call: the pools are ``pl.ANY`` operands, so
    nothing of them moves but the pages the kernel copies."""
    b, nkv, rows, hd = q_g.shape
    quantized = isinstance(k_pages, QuantizedKVPages)
    bt = block_tokens
    k_data = k_pages.data if quantized else k_pages
    heads = _heads_a_step(nkv, rows, hd)
    page_bytes = heads * bt * hd * k_data.dtype.itemsize
    ring = _ring_depth(page_bytes)

    tile_spec = pl.BlockSpec((1, heads, rows, hd),
                             lambda bb, hb, *_: (bb, hb, 0, 0))
    slopes_spec = pl.BlockSpec((heads, 1, rows),
                               lambda bb, hb, *_: (hb, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    page_buf = pltpu.VMEM((ring, heads, bt, hd), k_data.dtype)
    if quantized:
        # the sidecar as [L, N, nkv, bt]: :func:`_paged_call_body`
        scale_buf = pltpu.VMEM((ring, heads, bt), k_pages.scale.dtype)
        in_specs = [tile_spec] + [pool_spec] * 4 + [slopes_spec]
        operands = (q_g, k_pages.data, k_pages.scale[..., 0],
                    v_pages.data, v_pages.scale[..., 0], slopes)
        buffers = [page_buf, scale_buf, page_buf, scale_buf]
    else:
        in_specs = [tile_spec, pool_spec, pool_spec, slopes_spec]
        operands = (q_g, k_pages, v_pages, slopes)
        buffers = [page_buf, page_buf]
    tile_bytes = heads * rows * hd * q_g.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_paged_prefill_loop_kernel, block_tokens=bt,
                          chunk=chunk, groups=groups, ring=ring,
                          use_alibi=use_alibi, quantized=quantized,
                          **({"window": window} if window else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nkv // heads),
            in_specs=in_specs,
            out_specs=tile_spec,
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((ring, len(buffers))),
                pltpu.VMEM((heads, rows, hd), jnp.float32),
                pltpu.VMEM((heads, rows, 128), jnp.float32),
                pltpu.VMEM((heads, rows, 128), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nkv, rows, hd), q_g.dtype),
        # the state, the tile in and out (each buffered twice), the two
        # rings, and one head's fold in float32 (the tile, a page of K
        # and of V, scores and weights) with room to spare
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            16 << 20,
            heads * _state_bytes(rows, hd) + 4 * tile_bytes
            + 2 * ring * page_bytes
            + 16 * rows * max(hd, bt) + 16 * bt * hd + (8 << 20))),
        interpret=interpret,
    )(tables, starts, layer, *operands)


def window_tables(tables, starts, chunk: int, window: int, bt: int):
    """``(tables [b, n], page0 [b])``: each row's table cut to the pages a
    chunk at ``starts[b]`` can see under ``window`` (from the page of key
    ``starts[b] - window + 1`` to the page of the chunk's last token: at
    most ``n`` of them, a static count)."""
    n = min(tables.shape[1], -(-(chunk + window - 1) // bt) + 1)
    page0 = jnp.maximum(starts - window + 1, 0) // bt
    cols = jnp.minimum(page0[:, None] + jnp.arange(n), tables.shape[1] - 1)
    return jnp.take_along_axis(tables, cols, axis=1), page0.astype(jnp.int32)


def _paged_prefill_kernel(tab_ref, start_ref, layer_ref, q_ref, *refs,
                          block_tokens: int, chunk: int, groups: int,
                          use_alibi: bool, quantized: bool,
                          window: int = 0, page0_ref=None):
    """The grid kernel, for the shapes the page loop does not cover.
    Grid (b, nkv, W), page index innermost: each step folds one
    streamed [block_tokens, hd] page (:func:`_fold_page`) into
    online-softmax accumulators (VMEM scratch persists across the
    sequential grid).  Rows are (chunk position, q-head group member)
    pairs, so the whole C-token segment of one kv head folds each
    streamed page in ONE grid pass.  Steps past the live frontier move
    no page and skip the fold, but they are grid steps all the same.

    tab_ref (SMEM int32 [b, W]): block tables; start_ref (SMEM int32
    [b]): per-row segment start offsets (position of chunk column 0);
    layer_ref (SMEM int32 [1]): the layer of the stacked pool, read by
    the page index map alone.

    ``window`` > 0 (a window kind of block): ``tab_ref`` is the row's
    table from page ``page0_ref[b]`` on (the page that holds the first
    key the chunk's first query sees), the grid's last axis is as wide
    as a chunk and a window need, and a row's query sees keys
    ``q_pos - window < j <= q_pos``."""
    del layer_ref
    if quantized:
        (k_ref, ks_ref, v_ref, vs_ref, slopes_ref,
         o_ref, o_acc, m_acc, l_acc) = refs
    else:
        k_ref, v_ref, slopes_ref, o_ref, o_acc, m_acc, l_acc = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    num_j = pl.num_programs(2)
    rows, hd = q_ref.shape[2], q_ref.shape[3]
    start = start_ref[b]
    bt = block_tokens

    @pl.when(j == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    kv_len = start + chunk
    n_live = (kv_len + bt - 1) // bt
    jp = j      # the page's place in the row's whole table
    if window:
        n_live = n_live - page0_ref[b]
        jp = j + page0_ref[b]

    @pl.when(j < n_live)
    def _step():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        q = q * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        if quantized:
            k_blk = k_blk * ks_ref[0, 0, :, :]      # [bt, hd] * [bt, 1]
            v_blk = v_blk * vs_ref[0, 0, :, :]
        kv_pos = (jp * bt
                  + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1))
        slope = slopes_ref[0, 0, :][:, None] if use_alibi else None
        o, m, l = _fold_page(
            q, k_blk, v_blk,
            (o_acc[:], jnp.max(m_acc[:], axis=-1, keepdims=True),
             jnp.max(l_acc[:], axis=-1, keepdims=True)),
            kv_pos, _row_positions(start, rows, groups), slope, window)
        o_acc[:] = o
        m_acc[:] = jnp.broadcast_to(m, m_acc.shape)
        l_acc[:] = jnp.broadcast_to(l, l_acc.shape)

    @pl.when(j == num_j - 1)
    def _finalize():
        l = jnp.max(l_acc[:], axis=-1, keepdims=True)
        o_ref[0, 0, :, :] = (o_acc[:]
                             / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_prefill_kernel_window(tab_ref, start_ref, layer_ref, page0_ref,
                                 q_ref, *refs, **kw):
    """:func:`_paged_prefill_kernel` behind a fourth prefetched scalar."""
    _paged_prefill_kernel(tab_ref, start_ref, layer_ref, q_ref, *refs,
                          page0_ref=page0_ref, **kw)


def _paged_prefill_grid_call(q_g, k_pages, v_pages, layer, tables, starts,
                             slopes, *, block_tokens, chunk, groups,
                             use_alibi, interpret, window=0):
    """The grid kernel's Pallas call: the page index map returns
    ``(layer, page, head, 0, 0)``, so the pipeline copies the table's
    pages out of the stack and nothing else.  Under a ``window`` the
    table is first cut to the pages a chunk can see
    (:func:`window_tables`), so that the grid is as wide as those."""
    b, nkv, rows, hd = q_g.shape
    quantized = isinstance(k_pages, QuantizedKVPages)
    num_pages = k_pages.shape[1]
    bt = block_tokens
    prefetch = (tables, starts, layer)
    if window:
        tables, page0 = window_tables(tables, starts, chunk, window, bt)
        prefetch = (tables, starts, layer, page0)
    W = tables.shape[1]

    def page_map(bb, h, j, tab, starts_, lay, *first):
        # clamp to the segment's live frontier (start + chunk tokens):
        # beyond it the index repeats (no DMA, pl.when skips compute);
        # sentinel entries clamp in-range
        live = (starts_[bb] + chunk + bt - 1) // bt
        if first:       # a window's table starts at the row's page0
            live = live - first[0][bb]
        jj = jnp.minimum(j, jnp.maximum(live - 1, 0))
        page = jnp.minimum(tab[bb, jj], num_pages - 1)
        return (lay[0], page, h, 0, 0)

    q_spec = pl.BlockSpec((1, 1, rows, hd),
                          lambda bb, h, j, *_: (bb, h, 0, 0))
    slopes_spec = pl.BlockSpec((1, 1, rows),
                               lambda bb, h, j, *_: (h, 0, 0))
    page_spec = pl.BlockSpec((None, 1, 1, bt, hd), page_map)
    if quantized:
        scale_spec = pl.BlockSpec((None, 1, 1, bt, 1), page_map)
        in_specs = [q_spec, page_spec, scale_spec, page_spec,
                    scale_spec, slopes_spec]
        operands = (q_g, k_pages.data, k_pages.scale,
                    v_pages.data, v_pages.scale, slopes)
    else:
        in_specs = [q_spec, page_spec, page_spec, slopes_spec]
        operands = (q_g, k_pages, v_pages, slopes)

    kernel = functools.partial(
        _paged_prefill_kernel_window if window else _paged_prefill_kernel,
        block_tokens=bt, chunk=chunk, groups=groups, use_alibi=use_alibi,
        quantized=quantized, **({"window": window} if window else {}))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, nkv, W),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, hd), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nkv, rows, hd), q_g.dtype),
        interpret=interpret,
    )(*prefetch, *operands)


def _paged_prefill_call_body(q_g, k_pages, v_pages, layer, tables, starts,
                             slopes, **kw):
    """One of the two Pallas calls, by the pool's shape.  ``k_pages`` /
    ``v_pages`` are the STACKED pools ``[L, N, nkv, bt, hd]``, ``layer``
    [1] int32 picks the layer, ``tables`` [b, W] is each row's whole
    table."""
    call = (_paged_prefill_loop_call if _page_loop_covers(k_pages)
            else _paged_prefill_grid_call)
    return call(q_g, k_pages, v_pages, layer, tables, starts, slopes, **kw)


# the two jitted calls, named as the trace readers know them
# (``_paged_prefill_call.<n>``, a window kind's
# ``_paged_prefill_call_window.<n>``)
@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "chunk", "groups",
                                    "use_alibi", "interpret"))
def _paged_prefill_call(q_g, k_pages, v_pages, layer, tables, starts,
                        slopes, *, block_tokens, chunk, groups, use_alibi,
                        interpret):
    return _paged_prefill_call_body(
        q_g, k_pages, v_pages, layer, tables, starts, slopes,
        block_tokens=block_tokens, chunk=chunk, groups=groups,
        use_alibi=use_alibi, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "chunk", "groups",
                                    "use_alibi", "interpret", "window"))
def _paged_prefill_call_window(q_g, k_pages, v_pages, layer, tables, starts,
                               slopes, *, block_tokens, chunk, groups,
                               use_alibi, interpret, window):
    return _paged_prefill_call_body(
        q_g, k_pages, v_pages, layer, tables, starts, slopes,
        block_tokens=block_tokens, chunk=chunk, groups=groups,
        use_alibi=use_alibi, interpret=interpret, window=window)


@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "chunk", "groups",
                                    "use_alibi", "interpret"))
def _paged_prefill_call_eva(q_g, k_pages, v_pages, layer, tables, starts,
                            slopes, *, block_tokens, chunk, groups,
                            use_alibi, interpret):
    """:func:`_paged_prefill_call` under a summarised cache's name."""
    return _paged_prefill_call_body(
        q_g, k_pages, v_pages, layer, tables, starts, slopes,
        block_tokens=block_tokens, chunk=chunk, groups=groups,
        use_alibi=use_alibi, interpret=interpret)


# one kernel invocation's query rows = chunk * group; past this the
# f32 VMEM accumulators (rows x hd + 2 x rows x 128) crowd the page
# stream — larger chunks take the gather path
PREFILL_KERNEL_MAX_ROWS = 512


def _query_tiles(q, nkv: int, slopes):
    """``(q_g [b, nkv, rows, hd], slopes_g [nkv, 1, rows])``: a call's
    queries ``[b, chunk, nh, hd]`` as one tile of ``chunk x g`` rows a kv
    head (row ``c*g + r`` of kv head ``h`` is chunk position ``c`` of q
    head ``h*g + r``), zero-padded to whole sublane tiles, and each
    row's ALiBi slope (zeros without)."""
    b, chunk, nh, hd = q.shape
    g = nh // nkv
    rows_real = chunk * g
    rows = max(8, -(-rows_real // 8) * 8)
    q_g = q.reshape(b, chunk, nkv, g, hd).transpose(0, 2, 1, 3, 4)
    q_g = q_g.reshape(b, nkv, rows_real, hd)
    if rows > rows_real:
        q_g = jnp.pad(q_g, ((0, 0), (0, 0), (0, rows - rows_real),
                            (0, 0)))
    if slopes is None:
        return q_g, jnp.zeros((nkv, 1, rows), jnp.float32)
    # per-row slope = slopes[h*g + r % g]: the g-vector repeats once per
    # chunk position
    slopes_g = jnp.tile(slopes.astype(jnp.float32).reshape(nkv, 1, g),
                        (1, 1, chunk))
    return q_g, jnp.pad(slopes_g, ((0, 0), (0, 0), (0, rows - rows_real)))


def paged_prefill_attention(
    q: jnp.ndarray,          # [batch, chunk, nh, hd], chunk >= 1
    k_pages,                 # [num_pages, nkv, block_tokens, hd] or LayerOf
    v_pages,
    tables: jnp.ndarray,     # [batch, W] int32
    q_positions: jnp.ndarray,  # [batch, chunk]; CONTIGUOUS per row
    slopes: Optional[jnp.ndarray] = None,
    *,
    interpret: bool = False,
    window: int = 0,
    eva: bool = False,
) -> jnp.ndarray:
    """Pallas paged PREFILL attention: each row's chunk of queries
    attends causally over its own prior pages plus the in-chunk keys
    (already present — ``write_paged_kv`` runs before attention inside
    the layer).  Numerics match :func:`paged_gather_attention` (f32
    online softmax, same masking).

    Contract: ``q_positions[b] == q_positions[b, 0] + arange(chunk)``
    (every caller of the paged seam issues contiguous chunks); only the
    per-row start rides scalar prefetch, the rest is recovered from the
    static chunk length.  Same page-dtype gates as the decode kernel:
    bf16 or int8 pages, ``block_tokens % 8 == 0``; int4 takes the
    gather path."""
    b, chunk, nh, hd = q.shape
    K, V, li = _stacked(k_pages, v_pages)
    if isinstance(K, QuantizedKVPages) and K.bits != 8:
        raise ValueError("the Pallas kernel streams bf16 or int8 pages; "
                         "int4 KV takes the XLA gather path")
    _, num_pages, nkv, bt, _ = K.shape
    if bt % 8:
        raise ValueError(f"block_tokens must be a multiple of 8 for the "
                         f"Pallas kernel, got {bt}")
    g = nh // nkv
    q_g, slopes_g = _query_tiles(q, nkv, slopes)
    if q_g.shape[2] > PREFILL_KERNEL_MAX_ROWS:
        raise ValueError(
            f"prefill kernel rows {q_g.shape[2]} (chunk {chunk} x group "
            f"{g}) exceed {PREFILL_KERNEL_MAX_ROWS}; use the gather path")
    call = (functools.partial(_paged_prefill_call_window, window=window)
            if window else
            _paged_prefill_call_eva if eva else _paged_prefill_call)
    out = call(q_g, K, V, li.reshape(1), tables.astype(jnp.int32),
               q_positions[:, 0].astype(jnp.int32), slopes_g,
               block_tokens=bt, chunk=chunk, groups=g,
               use_alibi=slopes is not None, interpret=interpret)
    out = out[:, :, :chunk * g, :].reshape(b, nkv, chunk, g, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, chunk, nh, hd)


# ---------------------------------------------------------------------------
# the attn_impl seam (models/decoder.py hook)

PATH_DECODE_KERNEL = "pallas_decode"
PATH_PREFILL_KERNEL = "pallas_prefill"
PATH_GATHER = "gather"


class AttnPathRecord:
    """Which attention path each compiled program took, and why.

    The routing below is a Python decision made while a program is
    TRACED (chunk length, page dtype and page size are static), so a
    program that quietly compiled onto the XLA gather looks exactly
    like one on the kernels from outside.  Every engine owns one record,
    passes it down the seam, and serves :meth:`snapshot` under
    ``/stats["attention_paths"]``: ``{program: {"chunk=N": path}}`` with
    ``path`` one of ``pallas_decode`` / ``pallas_prefill`` /
    ``gather: <reason>``.

    How the call reached the pool (:func:`route_pool`) is recorded
    beside the path and served under ``/stats["pool_addressing"]`` in the
    same shape: ``kernel write`` and ``scatter write`` address ``(layer,
    page)`` in the carried pool and copy nothing of it; ``plane`` slices
    the layer's plane out and puts it back, so a program that fell back
    to planes shows there.

    A kernel over latent pages folds a group of pages an iteration, as
    many as its call's shapes allow (``ops.latent_attention.latent_fold``):
    the group is static in the compiled program, recorded here and served
    under ``/stats["fold_pages"]`` in the same shape (no entry for a call
    that gathers, none for a pool of key / value pairs)."""

    def __init__(self):
        self._paths: dict = {}
        self._addressing: dict = {}
        self._fold_pages: dict = {}

    def note(self, program: str, chunk: int, path: str, why: str,
             pool: Optional[str], fold_pages: Optional[int] = None) -> None:
        entry = path if not why else f"{path}: {why}"
        self._paths.setdefault(program, {})[f"chunk={chunk}"] = entry
        if pool is not None:    # an op beside the pools addresses none
            self._addressing.setdefault(program, {})[f"chunk={chunk}"] = pool
        if fold_pages is not None:
            self._fold_pages.setdefault(program, {})[
                f"chunk={chunk}"] = fold_pages

    @staticmethod
    def _copy(table: dict) -> dict:
        # tracing runs on the scheduler thread, /stats on an HTTP
        # thread: list() and dict() each copy in one step under the GIL
        return {prog: dict(chunks) for prog, chunks in list(table.items())}

    def snapshot(self) -> dict:
        return self._copy(self._paths)

    def addressing(self) -> dict:
        return self._copy(self._addressing)

    def fold_pages(self) -> dict:
        return self._copy(self._fold_pages)


def streams_note(record: Optional[AttnPathRecord], bound: dict):
    """A hook's ``note_streams(chunk, path, why)``: the path a model's
    residual-stream ops took (``ops.hyper_connection``, called by
    ``models.decoder._layer``), recorded as ``<program>/hc`` beside the
    attention's.  They choose kernel or plain path while the program is
    traced, as the attention does, and address no pool."""
    def note_streams(chunk: int, path: str, why: str) -> None:
        if record is not None:
            record.note(f"{bound['program']}/hc", chunk, path, why, None)

    return note_streams


def parts_of(bound: dict):
    """A hook's ``parts()``: the pair a merged call's rows divide by
    (:func:`split_rows`), or None where one table is bound."""
    def parts():
        tables = bound["tables"]
        return tables if isinstance(tables, tuple) else None

    return parts


def split_rows(tables, arrays):
    """The two parts of a MERGED call's rows (``mixed_step``'s slab pass
    that carries a decode step, docs/DESIGN.md section 19): ``arrays``
    ``[1, r x C + B, ...]`` hold a slab's ``r`` segments of ``C`` rows and
    then the ``B`` decoding rows, one token each, and ``tables`` is the
    pair of their tables (``[r, W]``, ``[B, W]``), or of anything else with
    one entry a segment and one a decoding row.  Returns each part's arrays
    in the layout a hook takes, ``[r, C, ...]`` then ``[B, 1, ...]``."""
    r, B = tables[0].shape[0], tables[1].shape[0]
    cut = arrays[0].shape[1] - B
    return (
        tuple(a[0, :cut].reshape((r, cut // r) + a.shape[2:])
              for a in arrays),
        tuple(a[0, cut:].reshape((B, 1) + a.shape[2:]) for a in arrays))


def join_rows(outs):
    """Parts' outputs ``[rows, chunk, ...]`` side by side again,
    ``[1, all rows, ...]``: the other half of :func:`split_rows`."""
    return jnp.concatenate(
        [o.reshape((1, -1) + o.shape[2:]) for o in outs], axis=1)


def over_parts(tables, rows, pages, call):
    """``call(table, *rows, *pages) -> (out, *pages)``: once where
    ``tables`` is one table; where it is a pair (a merged call,
    :func:`split_rows`) once a part, the slab's route and then the
    decoding rows', each through its own table and with the pages the
    part before left, the outputs side by side."""
    if not isinstance(tables, tuple):
        return call(tables, *rows, *pages)
    outs = []
    for tab, part in zip(tables, split_rows(tables, rows)):
        out, *pages = call(tab, *part, *pages)
        outs.append(out)
    return (join_rows(outs), *pages)


def route_paged_attention(backend: str, platform: str, k_pages,
                          chunk: int, groups: int):
    """``(path, why)`` for one traced attention call — the ONE routing
    rule, a pure function of what the trace can see.

    ``backend`` "xla" always gathers; "auto" takes a kernel on TPU when
    the kernel covers the shape and gathers otherwise (``why`` says
    which gate refused); "pallas" is an explicit request and RAISES
    where "auto" would have gathered for a shape reason — honor or
    reject, never a silent downgrade.  Gates: int4 pages never take the
    kernel (nibble unpack in the lane dimension); int8 pages need
    ``block_tokens % 32 == 0`` on real hardware (the int8 tile is 32
    sublanes; forced-"pallas" runs interpret and may use smaller
    pages); every page needs ``block_tokens % 8 == 0``; the prefill
    kernel holds ``chunk x group`` query rows in VMEM and stops at
    ``PREFILL_KERNEL_MAX_ROWS``."""
    if backend == "xla":
        return PATH_GATHER, "backend=xla"
    if backend == "auto" and platform != "tpu":
        return PATH_GATHER, f"backend=auto on platform={platform}"
    k_pages = _pool(k_pages)
    bt = k_pages.shape[-2]
    why = ""
    if isinstance(k_pages, QuantizedKVPages) and k_pages.bits != 8:
        why = f"int{k_pages.bits} pages have no kernel"
    elif bt % 8:
        why = f"block_tokens={bt} is not a multiple of 8"
    elif (isinstance(k_pages, QuantizedKVPages) and bt % 32
          and backend != "pallas"):
        why = (f"int8 pages need block_tokens % 32 == 0 on the chip, "
               f"got {bt}")
    elif chunk > 1 and -(-(chunk * groups) // 8) * 8 > PREFILL_KERNEL_MAX_ROWS:
        why = (f"chunk {chunk} x group {groups} = {chunk * groups} query "
               f"rows > PREFILL_KERNEL_MAX_ROWS={PREFILL_KERNEL_MAX_ROWS}")
    if why:
        if backend == "pallas":
            raise ValueError(f"paged attention backend 'pallas' cannot "
                             f"take this shape: {why}")
        return PATH_GATHER, why
    return (PATH_DECODE_KERNEL if chunk == 1 else PATH_PREFILL_KERNEL), ""


def sub_chunk(chunk: int, groups: int) -> int:
    """The largest divisor of ``chunk`` whose ``x groups`` query rows the
    prefill kernel holds (``PREFILL_KERNEL_MAX_ROWS``), a multiple of 8
    where there is one; ``chunk`` itself where it fits."""
    fits = [c for c in range(chunk, 0, -1) if chunk % c == 0
            and -(-(c * groups) // 8) * 8 <= PREFILL_KERNEL_MAX_ROWS]
    return next((c for c in fits if c % 8 == 0), fits[0])


def prefill_pages_walked(start: int, chunk: int, tile: int, bt: int,
                         width: int, window: int = 0) -> int:
    """The pages the prefill page loop walks for ONE segment of ``chunk``
    tokens at position ``start``, cut into query tiles of ``tile`` tokens
    (:func:`sub_chunk`; ``chunk`` itself where it is not cut): the loop
    bounds of :func:`_paged_prefill_loop_kernel` (and of the latent
    kernel, which has no window) in Python integers, for the scheduler's
    dispatch record.  Host arithmetic: nothing here reads the device."""
    pages = 0
    for s in range(start, start + chunk, tile):
        n_live = min(-(-(s + tile) // bt), width)
        first = min(max(s - window + 1, 0) // bt, n_live) if window else 0
        pages += n_live - first
    return pages


def make_paged_attn_impl(block_tokens: int, backend: str = "auto",
                         interpret: bool = False,
                         record: Optional[AttnPathRecord] = None,
                         state_cols: int = 0):
    """``(impl, bind)``: an attention hook for paged-layout caches plus
    the binder that hands it the block tables.

    The decoder's ``attn_impl`` signature has no table slot, so the
    caller's jitted program binds the traced table array immediately
    before invoking the forward — ``bind(tables, program)`` at the top
    of the traced body, then ``fwd(...)``; the impl reads the binding
    during tracing (the layer scan closes over it as a loop constant).
    ``program`` names the compiled program being traced; the path each
    of its attention calls takes lands in ``record`` under that name.
    ``tables`` may be a PAIR, a slab's and the decoding rows': the forward
    is then a merged call (:func:`split_rows`), and every hook made here
    runs the slab's rows through the first table and the decoding rows
    through the second, on the route each would take alone.

    ``backend``: "auto" (Pallas on TPU, XLA gather elsewhere), "xla", or
    "pallas" — the rule is :func:`route_paged_attention`.

    The hook is made for a page pool, and a pool is addressed in place:
    ``impl.stacked_cache`` tells the decoder's layer scan to hand it the
    carried stacks and the layer's index (``LayerOf``) instead of a
    layer's plane, and it hands the stacks back the same way.  It takes
    nothing else, and where :func:`route_pool` says ``plane`` it is the
    hook that slices the layer out and puts it back.

    ``state_cols`` (a model with a recurrent state a request,
    ``ModelConfig.state_planes``): the bound tables' last column is not a
    page but the request's ROW of the state pool (``impl.for_state``).
    """
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown paged attention backend {backend!r}; "
                         "expected 'auto', 'xla', or 'pallas'")
    bound = {}

    def bind(tables, program: str):
        bound["tables"] = tables
        bound["program"] = program

    def attend(q, k, v, k_pages, v_pages, positions, slopes, tables,
               program, window=0, split=False, eva=False):
        """Write the chunk, then attend: one traced attention call over
        ``tables``.  ``window`` > 0 (a window kind of block) bounds what a
        query sees.  ``split`` (the kinds of a period model, whose context
        no gathered view could hold): a chunk of more query rows than the
        prefill kernel holds is cut into sub-chunks, a row of the call
        each (the keys are written before any of them attends, so a
        sub-chunk is a chunk that starts later).  ``eva``: the kernels'
        calls under a summarised cache's names."""
        assert isinstance(k_pages, LayerOf), "the pool comes stacked"
        chunk = q.shape[1]
        groups = q.shape[2] // k.shape[2]
        sub = chunk
        if split and chunk > 1 and backend != "xla":
            sub = sub_chunk(chunk, groups)
        path, why = route_paged_attention(
            backend, jax.default_backend(), k_pages, sub, groups)
        pool = route_pool(backend, jax.default_backend(), k_pages, chunk)
        if record is not None:
            record.note(program, chunk, path, why, pool)
        whole_k, whole_v = k_pages, v_pages
        gather_kw = {"window": window} if window else {}
        kw = dict(gather_kw, eva=True) if eva else gather_kw
        # metadata only: a profiler capture keeps the scope with each op
        with jax.named_scope("paged_attention"):
            if pool == POOL_PLANE:
                k_pages, v_pages = whole_k.sliced(), whole_v.sliced()
            k_pages, v_pages = write_paged_kv(
                k_pages, v_pages, k, v, tables, positions,
                form=WRITE_SCATTER if pool == POOL_PLANE else pool,
                interpret=interpret)
            if path == PATH_DECODE_KERNEL:
                kv_lens = positions[:, -1] + 1
                out = paged_flash_attention(q, k_pages, v_pages, tables,
                                            kv_lens, slopes,
                                            interpret=interpret, **kw)
            elif path == PATH_PREFILL_KERNEL:
                if sub != chunk:
                    b, n = q.shape[0], chunk // sub
                    cut = lambda a: a.reshape((b * n, sub) + a.shape[2:])
                    out = paged_prefill_attention(
                        cut(q), k_pages, v_pages,
                        jnp.repeat(tables, n, axis=0), cut(positions),
                        slopes, interpret=interpret, **kw)
                    out = out.reshape(q.shape)
                else:
                    out = paged_prefill_attention(
                        q, k_pages, v_pages, tables, positions, slopes,
                        interpret=interpret, **kw)
            else:
                out = paged_gather_attention(q, k_pages, v_pages, tables,
                                             positions, slopes,
                                             **gather_kw)
            if pool == POOL_PLANE:
                k_pages = LayerOf(whole_k.updated(k_pages), whole_k.layer)
                v_pages = LayerOf(whole_v.updated(v_pages), whole_v.layer)
        return out, k_pages, v_pages

    def impl(q, k, v, k_pages, v_pages, positions, cache_start, slopes):
        program = bound["program"]
        return over_parts(
            bound["tables"], (q, k, v, positions), (k_pages, v_pages),
            lambda tab, q, k, v, pos, kp, vp: attend(
                q, k, v, kp, vp, pos, slopes, tab, program))

    def for_pool(pool: int, pools: int, window: int, name: str):
        """The hook of one KIND of block of a model with a cache spec a
        kind: the bound tables hold one table a pool side by side
        (``[rows, pools x W]``) and this kind reads pool ``pool``'s, under
        ``window``; its paths are recorded as ``<program>/<name>``."""

        def kind_impl(q, k, v, k_pages, v_pages, positions, cache_start,
                      slopes):
            program = f"{bound['program']}/{name}"

            def one(tables, q, k, v, pos, kp, vp):
                width = (tables.shape[1] - state_cols) // pools
                return attend(q, k, v, kp, vp, pos, slopes,
                              tables[:, pool * width:(pool + 1) * width],
                              program, window or 0, split=True)

            with jax.named_scope(f"attn_{name}"):
                return over_parts(bound["tables"], (q, k, v, positions),
                                  (k_pages, v_pages), one)

        kind_impl.stacked_cache = True
        return kind_impl

    def summarised(window: int, chunk: int, mu, phi):
        """The hook of a block with a SUMMARISED cache (EVA attention,
        ``ops.eva_attention``; docs/DESIGN.md section 26): the bound
        tables hold a row's summary pages then its window pages, and the
        hook builds from them and the chunk's positions the table the
        kernels walk, writes the chunk, attends, and pools what chunks
        the call completed into the pending summary page.  ``mu`` /
        ``phi``: this layer's learned pooling vectors, a kv head."""
        from .eva_attention import paged_eva_attention

        def eva_impl(q, k, v, k_pages, v_pages, positions, cache_start,
                     slopes):
            program = bound["program"]
            with jax.named_scope("attn_eva"):
                return over_parts(
                    bound["tables"], (q, k, v, positions),
                    (k_pages, v_pages),
                    lambda tab, q, k, v, pos, kp, vp: paged_eva_attention(
                        attend, q, k, v, kp, vp, pos, tab, program, window,
                        chunk, mu, phi, backend=backend,
                        interpret=interpret))

        eva_impl.stacked_cache = True
        return eva_impl

    def for_sparse(pool: int, pools: int, kind, name: str):
        """The hook of a block-sparse kind (``ops.sparse_attention``;
        docs/DESIGN.md section 32): its keys' cache comes as ``(pages,
        index plane, the rows that hold a token or None)`` and goes back as
        ``(pages, index plane, what the call's selections kept)``
        (``sparse_attention.kept_counts``, summed over a merged call's
        parts); it reads pool ``pool``'s table as :func:`for_pool`'s
        does."""
        from .sparse_attention import sparse_attend

        def sparse_impl(q, k, v, k_cache, v_pages, positions, cache_start,
                        slopes):
            program = f"{bound['program']}/{name}"
            note = None
            if record is not None:
                note = lambda chunk, path, why, to: record.note(  # noqa: E731
                    program, chunk, path, why, to)
            pages, index, valid = k_cache
            if valid is None:
                valid = jnp.ones(positions.shape, bool)

            def one(tables, q, k, v, pos, valid, kp, vp, ix, kept):
                width = (tables.shape[1] - state_cols) // pools
                out, kp, vp, ix, counts = sparse_attend(
                    q, k, v, kp, vp, ix, pos,
                    tables[:, pool * width:(pool + 1) * width], kind,
                    backend=backend, interpret=interpret, note=note,
                    valid=valid)
                return out, kp, vp, ix, kept + counts

            with jax.named_scope(f"attn_{name}"):
                out, kp, vp, ix, kept = over_parts(
                    bound["tables"], (q, k, v, positions, valid),
                    (pages, v_pages, index, jnp.zeros((3,), jnp.int32)), one)
            return out, (kp, ix, kept), vp

        sparse_impl.stacked_cache = True
        return sparse_impl

    def for_state(name: str):
        """The hook of a block whose cache is a recurrent state
        (``models.decoder._kda_mixer``, ``_ssd_mixer``): ``rows()`` is each
        bound row's row of the state pool, the tables' last column (a
        sentinel there
        is clamped onto the pool's last row, which is nobody's);
        ``note`` records the path its op took as ``<program>/<name>``."""
        def note(chunk: int, path: str, why: str) -> None:
            if record is not None:
                record.note(f"{bound['program']}/{name}", chunk, path, why,
                            "state row")

        # (a merged call's rows are a pair, as its tables are)
        return types.SimpleNamespace(
            rows=lambda: jax.tree.map(lambda t: t[:, -1], bound["tables"]),
            note=note, backend=backend, interpret=interpret)

    impl.for_pool = for_pool
    impl.for_sparse = for_sparse
    impl.for_state = for_state
    impl.parts = parts_of(bound)
    impl.note_streams = streams_note(record, bound)
    impl.summarised = summarised
    impl.stacked_cache = True
    return impl, bind
