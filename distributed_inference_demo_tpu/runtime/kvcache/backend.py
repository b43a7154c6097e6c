"""The KV backend seam: ONE prefix-reuse surface over the page pool.

The seam is the two calls a single-request engine needs around its
prefill:

- ``seed(ids, cache) -> (start, cache)`` — write the longest cached
  prefix of the (batch-1) prompt into a fresh engine cache's leading
  columns; ``start`` is how many positions are now exact, so the engine
  prefills only the suffix.
- ``store(ids, cache) -> None`` — cache the prefilled prompt's full
  blocks for the next shared-prefix request.  Runs before the decode
  program donates the cache buffers.

:class:`PagedKVBackend` owns a DEVICE-resident page pool
  ``[L, N, H, bt, D]`` plus the §11 page-id
  :class:`~.paged.PagedKVCacheManager`: seeds gather pages into the
  cache on device and stores scatter cache blocks into freshly
  allocated pages on device — zero bytes cross the host boundary in
  either direction, and two prompts sharing a prefix share the very
  same pages in HBM (radix-tree dedup; the speculative engine's target
  prefills ride this, so a draft/verify request never duplicates an
  accepted prefix already paged in).

The single-request engines keep a contiguous *working* cache for the
one request in flight (its decode loop donates it); the standing
*pool* is what this class pages — the batching scheduler and the ring
stages page their own decode caches (docs/DESIGN.md §14).

Ownership: pages are tree-owned or free — a seed copies out of
tree pages under a short-lived pin, a store hands freshly written pages
to the tree (redundant ones are freed immediately), so after every
``seed``/``store`` the leak invariant ``used == tree.block_count``
holds with zero live leases.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .paged import (PagedKVCacheManager, apply_byte_budget,
                    resolve_kvcache_config)


class PagedKVBackend:
    """Device page pool behind the seam (docs/DESIGN.md §11/§14)."""

    def __init__(self, cfg, num_blocks: int, block_tokens: int,
                 dtype=None, kv_dtype=None,
                 kv_host_tier_bytes: Optional[int] = None,
                 kv_disk_tier_path: Optional[str] = None,
                 kv_disk_tier_bytes: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from ...ops.quant import alloc_kv_pages, resolve_kv_dtype
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        self.mgr = PagedKVCacheManager.for_model(
            cfg, num_blocks, block_tokens, dtype=dtype,
            kv_dtype=self.kv_dtype)
        self.block_tokens = self.mgr.block_tokens
        page_dtype = dtype if dtype is not None else cfg.dtype
        self._pk = alloc_kv_pages(
            (cfg.kv_planes, self.mgr.num_blocks, cfg.num_kv_heads,
             self.mgr.block_tokens, cfg.head_dim), self.kv_dtype,
            page_dtype)
        self._pv = jax.tree.map(jnp.zeros_like, self._pk)
        # tiered KV (docs/DESIGN.md §21): evicted tree leaves demote
        # into the host ring; seed() promotes a demoted continuation
        # back before its match.  Arg over env (resolve_tier_config),
        # so single-request engines inherit DWT_KV_HOST_TIER_BYTES with
        # zero per-engine plumbing — the §17 kv_dtype pattern.
        from .tiered import (TieredKVStore, make_demote_hook,
                             resolve_tier_config)
        tier_host, tier_path, tier_disk = resolve_tier_config(
            kv_host_tier_bytes, kv_disk_tier_path, kv_disk_tier_bytes)
        self.tier = None
        if tier_host > 0:
            self.tier = TieredKVStore(tier_host, self.block_tokens,
                                      disk_path=tier_path,
                                      disk_bytes=tier_disk)
            self.mgr.tier = self.tier
            self.mgr.demote_hook = make_demote_hook(
                self.tier, lambda: (self._pk, self._pv))

    def seed(self, ids, cache):
        """Match + device gather out of the pool into the fresh cache —
        zero H2D on the device-tier path (this class never moves bytes
        through the host; ``dwt_kvcache_h2d_bytes_total`` counts only
        §21 tier promotions, re-staged here before the match so a
        demoted prefix still seeds).  The pin is released right after
        the gather dispatch: device ops execute in dispatch order, so a
        later store/evict can never overwrite the pages before the
        gather reads them."""
        import jax.numpy as jnp

        from ...models.base import KVCache
        from .device import seed_cache_from_pages
        if ids.shape[0] != 1:
            return 0, cache
        if self.tier is not None:
            from .tiered import promote_prefix
            self._pk, self._pv, _ = promote_prefix(
                self.mgr, self.tier, self._pk, self._pv,
                np.asarray(ids[0]))
        lease = self.mgr.match(np.asarray(ids[0]))
        if lease is None:
            return 0, cache
        m = lease.tokens
        ck, cv = seed_cache_from_pages(
            cache.keys, cache.values, self._pk, self._pv,
            jnp.asarray(lease.block_ids, jnp.int32))
        lease.release()
        return m, KVCache(ck, cv, jnp.int32(m))

    def store(self, ids, cache) -> None:
        """Allocate pages for the prompt's MISSING tail blocks, scatter
        the matching cache columns into them on device (zero D2H), and
        hand them to the radix tree.  Blocks the tree already covers
        (``peek``) allocate and write nothing — a warm store must not
        evict hot prefixes to stage pages the tree would immediately
        decline.  ``peek`` is capped below the prompt length and an
        eviction can race the coverage read, so the tree-side contract
        (``store_shared`` with None placeholders) stops insertion at
        any block the caller brought no page for — caching less is
        always correct; genuinely redundant tail pages are declined and
        freed here."""
        import jax.numpy as jnp

        from .device import store_cache_to_pages
        if ids.shape[0] != 1:
            return
        prompt = np.asarray(ids[0])
        n_blocks = len(prompt) // self.mgr.block_tokens
        if n_blocks < 1:
            return
        covered = self.mgr.peek(prompt) // self.mgr.block_tokens
        missing = n_blocks - covered         # >= 1: peek caps at len-1
        block_ids = self.mgr.alloc(missing)
        if block_ids is None:
            return      # every evictable page pinned: caching less is fine
        self._pk, self._pv = store_cache_to_pages(
            self._pk, self._pv, cache.keys, cache.values,
            jnp.asarray(block_ids, jnp.int32), jnp.int32(covered))
        adopted, lease = self.mgr.store_shared(
            prompt, [None] * covered + list(block_ids))
        if lease is not None:
            # nothing outlives this call that references the pages (the
            # engine decodes against its own cache copy) — release now
            lease.release()
        declined = set(block_ids) - set(adopted)
        if declined:
            self.mgr.free(sorted(declined))

    @property
    def stats(self) -> dict:
        return self.mgr.stats

    def snapshot(self) -> dict:
        return self.mgr.snapshot()

    def debug_state(self) -> dict:
        return self.mgr.debug_state()

    def reset_stats(self) -> None:
        self.mgr.reset_stats()

    def close(self) -> None:
        """Drop the host/disk tier with the pool it shadows — demoted
        entries reference a page layout a successor backend may not
        share, so they die here rather than resurrect wrong."""
        if self.tier is not None:
            self.mgr.demote_hook = None
            self.mgr.tier = None
            self.tier.close()
            self.tier = None


def make_kv_backend(cfg, kv_cache_blocks: Optional[int],
                    kv_block_tokens: Optional[int], *,
                    dtype=None, kv_dtype=None, default_blocks: int = 0,
                    kv_host_tier_bytes: Optional[int] = None,
                    kv_disk_tier_path: Optional[str] = None,
                    kv_disk_tier_bytes: Optional[int] = None):
    """The one constructor every engine calls: resolve the block-count /
    block-tokens knobs (CLI over env over ``default_blocks``) and build
    the backend — or None when the pool is off (0 blocks, or a
    ``DWT_KVCACHE_BYTES`` ceiling below one block: a knob documented as
    a ceiling must never crash engine construction).

    ``kv_dtype`` (arg over ``DWT_KV_DTYPE`` over bf16) selects the page
    WIDTH; every engine behind this seam inherits it with no per-engine
    plumbing.  Mutually exclusive with a ``dtype`` storage cast: the
    cast rescales the same full-width layout, quantization replaces it."""
    from ...ops.quant import kv_token_head_bytes, resolve_kv_dtype
    kv_dtype = resolve_kv_dtype(kv_dtype)
    if kv_dtype != "bf16" and dtype is not None:
        raise ValueError(
            f"kv_dtype={kv_dtype!r} quantizes the page pool and cannot "
            "compose with a kv_cache_dtype storage cast "
            f"({np.dtype(dtype).name}); drop one of the two knobs")
    n_blocks, block_tokens = resolve_kvcache_config(
        kv_cache_blocks, kv_block_tokens, default_blocks=default_blocks)
    if n_blocks < 1:
        return None
    # the byte budget admits blocks at their ACTUAL page width (narrow
    # data + scale sidecar), not the full-width itemsize — one shared
    # owner with PagedKVCacheManager so admission and accounting agree
    dtype_ = dtype if dtype is not None else cfg.dtype
    block_bytes = (2 * int(cfg.kv_planes) * int(cfg.num_kv_heads)
                   * int(block_tokens)
                   * kv_token_head_bytes(int(cfg.head_dim), kv_dtype,
                                         dtype_))
    if apply_byte_budget(n_blocks, block_bytes) < 1:
        return None
    return PagedKVBackend(cfg, n_blocks, block_tokens, dtype=dtype,
                          kv_dtype=kv_dtype,
                          kv_host_tier_bytes=kv_host_tier_bytes,
                          kv_disk_tier_path=kv_disk_tier_path,
                          kv_disk_tier_bytes=kv_disk_tier_bytes)
