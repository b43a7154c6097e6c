"""Paged decode attention: per-sequence block tables over a device pool.

The PagedAttention memory model (vLLM, SOSP'23): instead of one dense
``[batch, nkv, max_seq, hd]`` cache row per sequence, K/V live in a
shared pool of fixed-size pages ``[num_pages, nkv, block_tokens, hd]``
(one pool per layer — the engines stack a leading layer axis) and each
sequence addresses its pages through a block table ``[batch, W]`` of
page ids.  Two consequences the dense layout cannot give:

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

Two interchangeable compute paths (same numerics as ``ops.attention``):

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
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import attention, prepare_kv_chunk
from .quant import QuantizedKVPages, quantize_kv_like

_NEG = -1e30


def write_paged_kv(
    k_pages: jnp.ndarray,   # [num_pages, nkv, block_tokens, hd]
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,     # [batch, chunk, nkv, hd] (projection layout)
    v_new: jnp.ndarray,
    tables: jnp.ndarray,    # [batch, W] int32 page ids (>= num_pages = none)
    positions: jnp.ndarray  # [batch, chunk] absolute token positions
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
    """
    bt = k_pages.shape[2]
    if isinstance(k_pages, QuantizedKVPages):
        # quantize ONCE at write time, per token over head_dim: the
        # scale/zero sidecar leaves take the exact same scatter index
        # (their trailing axis is a broadcast singleton).
        k_new, v_new = prepare_kv_chunk(k_new, v_new, jnp.float32,
                                        jnp.float32)
    else:
        k_new, v_new = prepare_kv_chunk(k_new, v_new, k_pages.dtype,
                                        v_pages.dtype)
    qk = quantize_kv_like(k_pages, k_new)
    qv = quantize_kv_like(v_pages, v_new)
    num_pages, W = k_pages.shape[0], tables.shape[1]
    pidx = positions // bt                                       # [b, s]
    page = jnp.take_along_axis(tables, jnp.minimum(pidx, W - 1), axis=1)
    page = jnp.where(pidx < W, page, num_pages)  # past-table -> drop
    off = positions % bt                                         # [b, s]
    # advanced indices at dims (0, 2) around the head slice: the indexed
    # result layout [b, s, nkv, hd] is exactly the projection layout the
    # chunk arrives in — no transpose.
    scatter = lambda p, c: p.at[page, :, off].set(c, mode="drop")
    k_pages = jax.tree.map(scatter, k_pages, qk)
    v_pages = jax.tree.map(scatter, v_pages, qv)
    return k_pages, v_pages


def paged_gather_attention(
    q: jnp.ndarray,          # [batch, chunk, nh, hd]
    k_pages: jnp.ndarray,    # [num_pages, nkv, block_tokens, hd]
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,     # [batch, W] int32
    q_positions: jnp.ndarray,  # [batch, chunk]
    slopes: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Pure-XLA fallback: gather each row's pages into a linear
    ``[batch, nkv, W*bt, hd]`` view and run the reference ``attention``.

    Materializes the gathered view (a full cache copy per layer) — fine
    for CPU tests and small batches, which is exactly where it runs; the
    TPU path is the Pallas kernel.  Quantized pools gather the NARROW
    leaves through the table first, then dequantize the gathered view to
    f32 — the same per-element ``convert * scale (+ zero)`` the kernel
    runs in-register, so the two paths stay bit-exact."""
    num_pages, nkv, bt, hd = k_pages.shape
    safe = jnp.clip(tables, 0, num_pages - 1)
    gather = lambda p: jnp.take(p, safe, axis=0)  # [b, W, nkv, bt, ·]
    b, W = safe.shape
    if isinstance(k_pages, QuantizedKVPages):
        k_lin = jax.tree.map(gather, k_pages).dequantize(jnp.float32)
        v_lin = jax.tree.map(gather, v_pages).dequantize(jnp.float32)
    else:
        k_lin = gather(k_pages)
        v_lin = gather(v_pages)
    k_lin = k_lin.transpose(0, 2, 1, 3, 4).reshape(b, nkv, W * bt, hd)
    v_lin = v_lin.transpose(0, 2, 1, 3, 4).reshape(b, nkv, W * bt, hd)
    return attention(q, k_lin, v_lin, q_positions,
                     jnp.asarray(W * bt, jnp.int32), slopes)


# ---------------------------------------------------------------------------
# Pallas TPU decode kernel


# bytes of K (and as many of V) a row keeps in flight while it folds:
# the page ring below is as deep as this buys, between 2 and 8 pages
_RING_BYTES = 1 << 20


def _paged_kernel(tab_ref, len_ref, q_ref, *refs, block_tokens: int,
                  ring: int, use_alibi: bool, quantized: bool):
    """Grid (b,): one step walks ONE row's live pages, all kv heads at
    once.  The pools stay in HBM; page ``tables[b, j]`` (``[nkv, bt,
    hd]``, contiguous in the pool) is copied into slot ``j % ring`` of a
    VMEM ring while earlier pages fold into the online-softmax
    accumulators, which are loop carries.  The loop runs
    ``ceil(kv_len / bt)`` times (at most ``W``), so a row with no live
    page costs the grid step alone.  Rows of a head are the q-head group
    members of that kv head (decode chunk = 1), all at the same query
    position ``kv_len - 1``.

    tab_ref (SMEM int32 [b, W]): the block tables; len_ref (SMEM int32
    [b]): per-row valid lengths AFTER the current token's insert.  With
    ``quantized`` the pools are int8 and each is followed by its f32
    scale sidecar as ``[num_pages, nkv, bt]``, copied page for page
    beside it: the dequant happens in-register right after the narrow
    DMA — HBM traffic stays 1 byte + 4/hd per element."""
    if quantized:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, slopes_ref, o_ref,
         k_buf, ks_buf, v_buf, vs_buf, sems) = refs
        streams = ((k_hbm, k_buf), (ks_hbm, ks_buf),
                   (v_hbm, v_buf), (vs_hbm, vs_buf))
    else:
        k_hbm, v_hbm, slopes_ref, o_ref, k_buf, v_buf, sems = refs
        streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    num_pages, W = k_hbm.shape[0], tab_ref.shape[1]
    _, nkv, rows, hd = q_ref.shape
    kv_len = len_ref[b]
    bt = block_tokens
    # a length past the table (a row that finished inside a fused block
    # keeps stepping) walks the table's W entries and no further
    n_live = jnp.minimum((kv_len + bt - 1) // bt, W)

    def page_copies(j):
        # sentinel entries clamp in-range: the garbage is masked below
        page = jnp.minimum(tab_ref[b, j], num_pages - 1)
        slot = j % ring
        return [pltpu.make_async_copy(hbm.at[page], buf.at[slot],
                                      sems.at[slot, i])
                for i, (hbm, buf) in enumerate(streams)]

    for j in range(ring - 1):
        @pl.when(j < n_live)
        def _prime():
            for c in page_copies(j):
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
        valid = jnp.broadcast_to(kv_pos < kv_len, s.shape)
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
        return o_new, m_new, l_new

    o, _, l = jax.lax.fori_loop(
        0, n_live, fold,
        (jnp.zeros((nkv, rows, hd), jnp.float32),
         jnp.full((nkv, rows, 1), _NEG, jnp.float32),
         jnp.zeros((nkv, rows, 1), jnp.float32)))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "use_alibi",
                                    "interpret"))
def _paged_call(q_g, k_pages, v_pages, tables, kv_lens, slopes, *,
                block_tokens, use_alibi, interpret):
    b, nkv, rows, hd = q_g.shape
    quantized = isinstance(k_pages, QuantizedKVPages)
    bt = block_tokens
    k_data = k_pages.data if quantized else k_pages
    page_bytes = nkv * bt * hd * k_data.dtype.itemsize
    ring = max(2, min(8, _RING_BYTES // page_bytes))

    row_spec = pl.BlockSpec((1, nkv, rows, hd),
                            lambda bb, tab, lens: (bb, 0, 0, 0))
    slopes_spec = pl.BlockSpec((nkv, rows, 1),
                               lambda bb, tab, lens: (0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    page_buf = pltpu.VMEM((ring, nkv, bt, hd), k_data.dtype)
    if quantized:
        # Mosaic slices an HBM ref only where its minor dimension fills
        # the lanes, which the pool's [.., bt, 1] sidecar does not: the
        # kernel takes it as [num_pages, nkv, bt]
        scale_buf = pltpu.VMEM((ring, nkv, bt), k_pages.scale.dtype)
        in_specs = [row_spec] + [pool_spec] * 4 + [slopes_spec]
        operands = (tables, kv_lens, q_g,
                    k_pages.data, k_pages.scale[..., 0],
                    v_pages.data, v_pages.scale[..., 0], slopes)
        buffers = [page_buf, scale_buf, page_buf, scale_buf]
    else:
        in_specs = [row_spec, pool_spec, pool_spec, slopes_spec]
        operands = (tables, kv_lens, q_g, k_pages, v_pages, slopes)
        buffers = [page_buf, page_buf]

    return pl.pallas_call(
        functools.partial(_paged_kernel, block_tokens=bt, ring=ring,
                          use_alibi=use_alibi, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
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
    )(*operands)


def paged_flash_attention(
    q: jnp.ndarray,          # [batch, 1, nh, hd] — decode chunk only
    k_pages: jnp.ndarray,    # [num_pages, nkv, block_tokens, hd]
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,     # [batch, W] int32
    kv_lens: jnp.ndarray,    # [batch] int32 valid length incl. this token
    slopes: Optional[jnp.ndarray] = None,
    *,
    interpret: bool = False,
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
    if isinstance(k_pages, QuantizedKVPages) and k_pages.bits != 8:
        # int4's nibble lane-interleave is Mosaic-hostile (an unpack in
        # the lane dimension per element); int4 is the CAPACITY config
        # and always takes the gather path — a deliberate gate, see
        # docs/DESIGN.md §17.
        raise ValueError("the Pallas kernel streams bf16 or int8 pages; "
                         "int4 KV takes the XLA gather path")
    num_pages, nkv, bt, _ = k_pages.shape
    if bt % 8:
        raise ValueError(f"block_tokens must be a multiple of 8 for the "
                         f"Pallas kernel, got {bt}")
    if hd % 128 or (isinstance(k_pages, QuantizedKVPages) and bt % 128):
        # Mosaic copies a slice of an HBM ref only where the ref's minor
        # dimension fills the 128 lanes.  A narrower head, or a scale
        # sidecar of a narrower page, goes through the prefill kernel's
        # BlockSpec pipeline as a 1-token chunk: the same fold, over a
        # grid that still has the table's width in it
        return paged_prefill_attention(
            q, k_pages, v_pages, tables, (kv_lens - 1)[:, None], slopes,
            interpret=interpret)
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
    kv_lens = jnp.where(tables[:, 0] >= num_pages, 0,
                        kv_lens.astype(jnp.int32))

    out = _paged_call(q_g, k_pages, v_pages, tables, kv_lens, slopes_g,
                      block_tokens=bt, use_alibi=slopes is not None,
                      interpret=interpret)
    return out[:, :, :g, :].reshape(b, 1, nh, hd)


# ---------------------------------------------------------------------------
# Pallas TPU prefill kernel (docs/DESIGN.md §19)


def _paged_prefill_kernel(tab_ref, start_ref, q_ref, *refs,
                          block_tokens: int, chunk: int, groups: int,
                          use_alibi: bool, quantized: bool):
    """Grid (b, nkv, W), page index innermost: each step folds one
    streamed [block_tokens, hd] page into online-softmax accumulators
    (VMEM scratch persists across the sequential grid), the fold of
    :func:`_paged_kernel`.  Rows are (chunk position, q-head group
    member) pairs: row ``r`` is query position ``start + r // g`` of
    q head ``h*g + r % g``, so the whole C-token segment of one kv
    head folds each streamed page into the online-softmax accumulators
    in ONE grid pass.  The causal bound is per ROW (``kv_pos <=
    start + r // g``), not the single shared decode position — in-chunk
    keys were already written to the pages by ``write_paged_kv``
    (write-before-attend inside the layer), so causality alone makes a
    query see exactly its prefix plus its own earlier in-chunk keys.

    tab_ref (SMEM int32 [b, W]): block tables; start_ref (SMEM int32
    [b]): per-row segment start offsets (position of chunk column 0)."""
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
    g = groups

    @pl.when(j == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    kv_len = start + chunk
    n_live = (kv_len + bt - 1) // bt

    @pl.when(j < n_live)
    def _step():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        q = q * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        if quantized:
            k_blk = k_blk * ks_ref[0, 0, :, :]      # [bt, hd] * [bt, 1]
            v_blk = v_blk * vs_ref[0, 0, :, :]
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32)     # [rows, bt]
        kv_pos = (j * bt
                  + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1))
        # per-row query position: padding rows (r >= chunk*g) see a
        # position past the segment — their garbage output is sliced
        # away by the caller
        q_pos = (start
                 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // g)
        valid = kv_pos <= q_pos                             # [rows, bt]
        if use_alibi:
            slope = slopes_ref[0, 0, :][:, None]            # [rows, 1]
            dist = (q_pos - kv_pos).astype(jnp.float32)
            s = s - slope * dist
        s = jnp.where(valid, s, _NEG)

        m = jnp.max(m_acc[:], axis=-1, keepdims=True)       # [rows, 1]
        l = jnp.max(l_acc[:], axis=-1, keepdims=True)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_acc[:] = o_acc[:] * alpha + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        m_acc[:] = jnp.broadcast_to(m_new, m_acc.shape)
        l_acc[:] = jnp.broadcast_to(l_new, l_acc.shape)

    @pl.when(j == num_j - 1)
    def _finalize():
        l = jnp.max(l_acc[:], axis=-1, keepdims=True)
        o_ref[0, 0, :, :] = (o_acc[:]
                             / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_tokens", "chunk", "groups",
                                    "use_alibi", "interpret"))
def _paged_prefill_call(q_g, k_pages, v_pages, tables, starts, slopes, *,
                        block_tokens, chunk, groups, use_alibi,
                        interpret):
    b, nkv, rows, hd = q_g.shape
    quantized = isinstance(k_pages, QuantizedKVPages)
    num_pages = k_pages.shape[0]
    W = tables.shape[1]
    bt = block_tokens

    def page_map(bb, h, j, tab, starts_):
        # clamp to the segment's live frontier (start + chunk tokens):
        # beyond it the index repeats (no DMA, pl.when skips compute);
        # sentinel entries clamp in-range
        live = (starts_[bb] + chunk + bt - 1) // bt
        jj = jnp.minimum(j, jnp.maximum(live - 1, 0))
        page = jnp.minimum(tab[bb, jj], num_pages - 1)
        return (page, h, 0, 0)

    q_spec = pl.BlockSpec((1, 1, rows, hd),
                          lambda bb, h, j, tab, starts_: (bb, h, 0, 0))
    slopes_spec = pl.BlockSpec((1, 1, rows),
                               lambda bb, h, j, tab, starts_: (h, 0, 0))
    page_spec = pl.BlockSpec((1, 1, bt, hd), page_map)
    if quantized:
        scale_spec = pl.BlockSpec((1, 1, bt, 1), page_map)
        in_specs = [q_spec, page_spec, scale_spec, page_spec,
                    scale_spec, slopes_spec]
        operands = (tables, starts, q_g, k_pages.data, k_pages.scale,
                    v_pages.data, v_pages.scale, slopes)
    else:
        in_specs = [q_spec, page_spec, page_spec, slopes_spec]
        operands = (tables, starts, q_g, k_pages, v_pages, slopes)

    return pl.pallas_call(
        functools.partial(_paged_prefill_kernel, block_tokens=bt,
                          chunk=chunk, groups=groups,
                          use_alibi=use_alibi, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nkv, W),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, rows, hd),
                                   lambda bb, h, j, tab, starts_:
                                   (bb, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, hd), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nkv, rows, hd), q_g.dtype),
        interpret=interpret,
    )(*operands)


# one kernel invocation's query rows = chunk * group; past this the
# f32 VMEM accumulators (rows x hd + 2 x rows x 128) crowd the page
# stream — larger chunks take the gather path
PREFILL_KERNEL_MAX_ROWS = 512


def paged_prefill_attention(
    q: jnp.ndarray,          # [batch, chunk, nh, hd], chunk >= 1
    k_pages: jnp.ndarray,    # [num_pages, nkv, block_tokens, hd]
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,     # [batch, W] int32
    q_positions: jnp.ndarray,  # [batch, chunk]; CONTIGUOUS per row
    slopes: Optional[jnp.ndarray] = None,
    *,
    interpret: bool = False,
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
    if isinstance(k_pages, QuantizedKVPages) and k_pages.bits != 8:
        raise ValueError("the Pallas kernel streams bf16 or int8 pages; "
                         "int4 KV takes the XLA gather path")
    num_pages, nkv, bt, _ = k_pages.shape
    if bt % 8:
        raise ValueError(f"block_tokens must be a multiple of 8 for the "
                         f"Pallas kernel, got {bt}")
    g = nh // nkv
    rows_real = chunk * g
    rows = max(8, -(-rows_real // 8) * 8)
    if rows > PREFILL_KERNEL_MAX_ROWS:
        raise ValueError(
            f"prefill kernel rows {rows} (chunk {chunk} x group {g}) "
            f"exceed {PREFILL_KERNEL_MAX_ROWS}; use the gather path")

    # [b, chunk, nh, hd] -> [b, nkv, chunk*g, hd]: row c*g + r of kv
    # head h is chunk position c of q head h*g + r
    q_g = q.reshape(b, chunk, nkv, g, hd).transpose(0, 2, 1, 3, 4)
    q_g = q_g.reshape(b, nkv, rows_real, hd)
    if rows > rows_real:
        q_g = jnp.pad(q_g, ((0, 0), (0, 0), (0, rows - rows_real),
                            (0, 0)))
    if slopes is None:
        slopes_g = jnp.zeros((nkv, 1, rows), jnp.float32)
    else:
        # per-row slope = slopes[h*g + r % g]: the g-vector repeats
        # once per chunk position
        slopes_g = jnp.tile(
            slopes.astype(jnp.float32).reshape(nkv, 1, g),
            (1, 1, chunk))
        slopes_g = jnp.pad(slopes_g,
                           ((0, 0), (0, 0), (0, rows - rows_real)))

    out = _paged_prefill_call(
        q_g, k_pages, v_pages, tables.astype(jnp.int32),
        q_positions[:, 0].astype(jnp.int32), slopes_g,
        block_tokens=bt, chunk=chunk, groups=g,
        use_alibi=slopes is not None, interpret=interpret)
    out = out[:, :, :rows_real, :].reshape(b, nkv, chunk, g, hd)
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
    ``gather: <reason>``."""

    def __init__(self):
        self._paths: dict = {}

    def note(self, program: str, chunk: int, path: str, why: str) -> None:
        entry = path if not why else f"{path}: {why}"
        self._paths.setdefault(program, {})[f"chunk={chunk}"] = entry

    def snapshot(self) -> dict:
        # tracing runs on the scheduler thread, /stats on an HTTP
        # thread: list() and dict() each copy in one step under the GIL
        return {prog: dict(chunks)
                for prog, chunks in list(self._paths.items())}


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
    bt = k_pages.shape[2]
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


def make_paged_attn_impl(block_tokens: int, backend: str = "auto",
                         interpret: bool = False,
                         record: Optional[AttnPathRecord] = None):
    """``(impl, bind)``: an attention hook for paged-layout caches plus
    the binder that hands it the block tables.

    The decoder's ``attn_impl`` signature has no table slot, so the
    caller's jitted program binds the traced table array immediately
    before invoking the forward — ``bind(tables, program)`` at the top
    of the traced body, then ``fwd(...)``; the impl reads the binding
    during tracing (the layer scan closes over it as a loop constant).
    ``program`` names the compiled program being traced; the path each
    of its attention calls takes lands in ``record`` under that name.

    ``backend``: "auto" (Pallas on TPU, XLA gather elsewhere), "xla", or
    "pallas" — the rule is :func:`route_paged_attention`.
    """
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown paged attention backend {backend!r}; "
                         "expected 'auto', 'xla', or 'pallas'")
    bound = {}

    def bind(tables, program: str):
        bound["tables"] = tables
        bound["program"] = program

    def impl(q, k, v, k_pages, v_pages, positions, cache_start, slopes):
        tables = bound["tables"]
        chunk = q.shape[1]
        path, why = route_paged_attention(
            backend, jax.default_backend(), k_pages, chunk,
            q.shape[2] // k.shape[2])
        if record is not None:
            record.note(bound["program"], chunk, path, why)
        # metadata only: a profiler capture keeps the scope with each op
        with jax.named_scope("paged_attention"):
            k_pages, v_pages = write_paged_kv(k_pages, v_pages, k, v,
                                              tables, positions)
            if path == PATH_DECODE_KERNEL:
                kv_lens = positions[:, -1] + 1
                out = paged_flash_attention(q, k_pages, v_pages, tables,
                                            kv_lens, slopes,
                                            interpret=interpret)
            elif path == PATH_PREFILL_KERNEL:
                out = paged_prefill_attention(q, k_pages, v_pages, tables,
                                              positions, slopes,
                                              interpret=interpret)
            else:
                out = paged_gather_attention(q, k_pages, v_pages, tables,
                                             positions, slopes)
        return out, k_pages, v_pages

    return impl, bind
