"""Device-side copy programs for the block KV cache.

The manager (``paged.py``) is pure host bookkeeping over page ids; these
are the device ends of the seam the engines share — the row <-> pages
and cache <-> pages gathers and scatters (docs/DESIGN.md §11), all
device-to-device: neither a prefix hit nor a store crosses the host
boundary.

Kept separate from ``paged.py`` so the manager (and its tests) never
import jax.

Every program below is dtype-polymorphic (docs/DESIGN.md §17): a pool
tensor is either a plain array or a :class:`QuantizedKVPages` tree
whose leaves share the pool's leading ``[L, N, H, bt]`` axes, so one
tree-mapped gather/scatter serves both.  The quantize/dequantize always
happens HERE, at the row <-> pages seam — dense working rows stay
full-width, pages hold the narrow bytes + scale sidecar.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...ops.quant import (QuantizedKVPages, quantize_kv_like,
                          quantize_kv_pages)


def _gather_run(pool, idx):
    """``[L, n, H, bt, D]`` FULL-WIDTH block run for index row ``idx``
    into a pool's page axis — narrow leaves gather first (only the
    table's bytes move), then the gathered view dequantizes."""
    g = jax.tree.map(lambda p: jnp.take(p, idx, axis=1), pool)
    if isinstance(g, QuantizedKVPages):
        return g.dequantize(jnp.float32)
    return g


def _scatter_run(pool, run, table):
    """Scatter a full-width ``[L, n, H, bt, D]`` block run into the pool
    at ``table``'s ids (sentinels drop) — quantizing once, here, when
    the pool is narrow."""
    payload = quantize_kv_like(pool, run)
    return jax.tree.map(
        lambda p, b: p.at[:, table].set(b, mode="drop"), pool, payload)


@partial(jax.jit, donate_argnums=(0, 1))
def seed_cache_from_pages(ck, cv, pk, pv, table):
    """Gather a matched block run out of the page pool into a (fresh,
    donatable) engine cache's columns ``[0, n*bt)``: pages
    ``[L, N, H, bt, D]`` + table ``[n]`` of real page ids -> cache
    ``[L, 1, H, S, D]``.  The caller sets the cache's valid length to
    ``n*bt`` afterwards; columns past it stay zero and are overwritten
    by the suffix prefill before any query attends them (stale-slot
    invariant).  Device-to-device: a prefix hit moves zero bytes
    through the host.  Compiled per matched length."""
    L, N, H, bt, D = pk.shape
    n = table.shape[0]
    rk = _gather_run(pk, table)               # [L, n, H, bt, D]
    rv = _gather_run(pv, table)
    rk = rk.transpose(0, 2, 1, 3, 4).reshape(L, 1, H, n * bt, D)
    rv = rv.transpose(0, 2, 1, 3, 4).reshape(L, 1, H, n * bt, D)
    zero = jnp.zeros((), jnp.int32)
    idx = (zero, zero, zero, zero, zero)
    return (jax.lax.dynamic_update_slice(ck, rk.astype(ck.dtype), idx),
            jax.lax.dynamic_update_slice(cv, rv.astype(cv.dtype), idx))


@partial(jax.jit, donate_argnums=(0, 1))
def store_cache_to_pages(pk, pv, ck, cv, table, start):
    """Scatter an engine cache's full blocks ``[start, start + n)`` into
    the page pool at ``table``'s ids — the paged store: cache ``[L, 1,
    H, S, D]`` columns ``[start*bt, (start+n)*bt)`` land in pages
    ``table[0..n)`` in place on device, zero D2H.  ``start``
    (traced block offset) is the tail-only store seam: blocks the radix
    tree already covers are neither re-allocated nor re-written.  The
    cache is read, not donated — the caller keeps decoding against it;
    only the pool buffers rotate."""
    L, N, H, bt, D = pk.shape
    n = table.shape[0]
    run_k = jax.lax.dynamic_slice_in_dim(ck[:, 0], start * bt, n * bt,
                                         axis=2)
    run_v = jax.lax.dynamic_slice_in_dim(cv[:, 0], start * bt, n * bt,
                                         axis=2)
    rk = run_k.reshape(L, H, n, bt, D).transpose(0, 2, 1, 3, 4)
    rv = run_v.reshape(L, H, n, bt, D).transpose(0, 2, 1, 3, 4)
    return _scatter_run(pk, rk, table), _scatter_run(pv, rv, table)


@partial(jax.jit, donate_argnums=(0, 1))
def adopt_blocks_into_pages(pk, pv, k_blocks, v_blocks, table):
    """Scatter migrated block payloads ``[n, L, H, bt, D]`` into the
    page pool at ``table``'s ids — the disaggregation import seam
    (docs/DESIGN.md §15): a decode worker lands a complete migration's
    staged blocks in ONE device scatter, then the radix tree ADOPTS
    the pages (``store_shared``) and the joining request's block table
    references them.  The pool never round-trips through a dense row,
    so ``dwt_kvcache_h2d_bytes_total`` (the dense-seed counter) stays 0
    on the decode side by construction; the migration's own bytes are
    accounted as ``dwt_disagg_migrated_bytes_total``.

    Payloads may arrive quantized (a quantized prefill pool ships its
    narrow bytes + scale sidecar on the wire): matching leaves adopt
    VERBATIM — the decode pool holds bit-identical pages to the prefill
    side.  A full-width payload into a quantized pool quantizes here
    (the premigrated-join escape hatch for full-width exporters)."""
    def _adopt(pool, blocks):
        if (isinstance(pool, QuantizedKVPages)
                and not isinstance(blocks, QuantizedKVPages)):
            blocks = quantize_kv_pages(blocks.astype(jnp.float32),
                                       pool.bits)
        return jax.tree.map(
            lambda p, b: p.at[:, table].set(
                jnp.moveaxis(b, 0, 1).astype(p.dtype), mode="drop"),
            pool, blocks)

    return _adopt(pk, k_blocks), _adopt(pv, v_blocks)


@jax.jit
def export_blocks_from_pages(pk, pv, table):
    """Gather block payloads ``[n, L, H, bt, D]`` out of the page pool at
    ``table``'s (real) ids — the EXACT inverse of
    :func:`adopt_blocks_into_pages` and the live-migration export seam
    (docs/DESIGN.md §18): a decode replica snapshots a mid-flight
    request's pages in one device gather, ships them, and the target's
    adopt scatter lands bit-identical pages.

    Quantized pools gather their narrow leaves VERBATIM (no dequantize /
    re-quantize round trip) — the payload stays a
    :class:`QuantizedKVPages` tree with block-leading leaves, which the
    adopt side recognizes and writes back untouched.  The caller slices
    the table to the request's used blocks; the partial tail block ships
    as-is (its columns past the valid length hold garbage the stale-slot
    invariant already covers — decode rewrites them before any query
    attends)."""
    def _export(pool):
        return jax.tree.map(
            lambda p: jnp.moveaxis(jnp.take(p, table, axis=1), 0, 1),
            pool)

    return _export(pk), _export(pv)


@partial(jax.jit, donate_argnums=(0, 1))
def write_row_to_pages(pk, pv, row_k, row_v, table):
    """Scatter a prefilled dense row ``[L, 1, H, W*bt, D]`` into the page
    pool at ``table``'s ids — the paged store: blocks land in place on
    device, zero D2H.  Sentinel entries (>= N) DROP their block — the
    caller sentinels the matched-prefix slots (those pages are tree-owned
    and immutable) and the unallocated tail; everything written is a page
    this request owns.  Write contract: ops.attention.prepare_kv_chunk
    (blocks past the prompt length hold garbage until decode rewrites
    them — the stale-slot invariant, block-shaped)."""
    L, N, H, bt, D = pk.shape
    W = table.shape[0]
    rk = row_k[:, 0].reshape(L, H, W, bt, D).transpose(0, 2, 1, 3, 4)
    rv = row_v[:, 0].reshape(L, H, W, bt, D).transpose(0, 2, 1, 3, 4)
    return _scatter_run(pk, rk, table), _scatter_run(pv, rv, table)
