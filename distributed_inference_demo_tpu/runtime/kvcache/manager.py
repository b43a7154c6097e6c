"""The KV cache manager: the ONE prefix-reuse path for every engine.

Ties the host block pool (``pool.py``) and the block radix tree
(``radix.py``) into the surface the engines consume:

- ``match(prompt) -> KVLease | None`` — longest-partial-prefix lookup.
  A lease pins the matched nodes against eviction (refcount) until the
  caller has copied the blocks out (``gather`` + ``release``, or the
  ``with`` form).  Matched length is whole blocks, capped at
  ``len(prompt) - 1`` so the caller's suffix forward is never empty.
- ``store(prompt, keys, values, row)`` — slice a freshly prefilled
  device cache row into full blocks and insert them (one D2H copy for
  the missing tail; already-cached blocks are recognized, not
  re-copied).  Stores happen at PREFILL time — the next request sharing
  the prefix hits even while this one is still decoding.
- ``peek(prompt)`` — match length without stats, leases, or LRU touch
  (scheduler classification, e.g. batching's ``_needs_stream``).

Eviction is LRU over unpinned leaves, triggered by allocation pressure:
``store`` evicts just enough to place the new blocks and gives up (still
correct, smaller cache) when every leaf is leased.  The byte budget is
the pool's preallocated capacity — there is nothing to account drift
against.

Reuse is EXACT by construction: blocks are keyed by the exact token ids
they cover, and causal attention makes a prefix's K/V independent of
any suffix — a primed generation is token-identical to a cold one
(pinned by tests/test_kvcache.py and the engine exactness tests).

Config knobs (CLI flags override env, 0 disables):
``DWT_KVCACHE_BLOCKS`` (pool size, blocks), ``DWT_KVCACHE_BLOCK_TOKENS``
(granularity, default 16), ``DWT_KVCACHE_BYTES`` (cap: shrinks BLOCKS
to fit when set).
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

from ...telemetry._env import env_int
from ...telemetry.flightrecorder import get_flight_recorder
from .pool import KVBlockPool
from .radix import RadixTree

DEFAULT_BLOCK_TOKENS = 16


def resolve_kvcache_config(num_blocks: Optional[int] = None,
                           block_tokens: Optional[int] = None,
                           default_blocks: int = 0):
    """(num_blocks, block_tokens) from explicit args over env knobs over
    ``default_blocks`` (each engine's own default — the batching
    scheduler defaults ON, the single-request engines default OFF).
    ``None`` means "not specified"; 0 blocks disables the subsystem."""
    if num_blocks is None:
        num_blocks = env_int("DWT_KVCACHE_BLOCKS", default_blocks)
    if block_tokens is None:
        block_tokens = env_int("DWT_KVCACHE_BLOCK_TOKENS",
                               DEFAULT_BLOCK_TOKENS)
    return num_blocks, block_tokens


def apply_byte_budget(num_blocks: int, block_bytes: int) -> int:
    """Shrink ``num_blocks`` to the DWT_KVCACHE_BYTES cap (0 = uncapped).
    Never rounds up — the env cap is a ceiling, not a target."""
    budget = env_int("DWT_KVCACHE_BYTES", 0)
    if budget > 0 and block_bytes > 0:
        num_blocks = min(num_blocks, budget // block_bytes)
    return num_blocks


class KVLease:
    """A pinned prefix match: ``tokens`` positions of reusable KV.

    The pin (a refcount on the deepest matched radix node) guarantees
    eviction cannot free the matched blocks before the caller copies
    them out; stored blocks are never mutated, so the copy the caller
    takes is the copy-on-write snapshot.  Release promptly — an
    unreleased lease shrinks what eviction may reclaim."""

    def __init__(self, mgr: "KVCacheManager", node, block_ids: List[int],
                 tokens: int):
        self._mgr = mgr
        self._node = node
        self.block_ids = block_ids
        self.tokens = tokens
        self._released = False

    def gather(self):
        """Host ``[L, H, tokens, D]`` K/V run for the matched blocks.
        The copy-out is destined for a device cache row, so the bytes
        count toward ``h2d_bytes`` (the dense layout's per-hit H2D cost
        the paged layout exists to delete)."""
        if self._released:
            raise RuntimeError("gather on a released lease")
        k, v = self._mgr.pool.gather(self.block_ids)
        k, v = k[:, :, :self.tokens], v[:, :, :self.tokens]
        with self._mgr._lock:
            self._mgr.stats["h2d_bytes"] += k.nbytes + v.nbytes
        return k, v

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._mgr._release(self._node)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


class KVCacheManager:
    """Block-level KV cache with radix-tree prefix sharing."""

    def __init__(self, num_layers: int, num_kv_heads: int, head_dim: int,
                 num_blocks: int, block_tokens: int, dtype):
        num_layers = int(num_layers)
        bt = int(block_tokens)
        block_bytes = (2 * num_layers * int(num_kv_heads) * bt
                       * int(head_dim) * np.dtype(dtype).itemsize)
        num_blocks = apply_byte_budget(int(num_blocks), block_bytes)
        if num_blocks < 1:
            raise ValueError(
                "KVCacheManager needs >= 1 block (0 means: don't build "
                "a manager at all)")
        self.block_tokens = bt
        self.pool = KVBlockPool(num_blocks, num_layers, num_kv_heads,
                                bt, head_dim, dtype)
        self.tree = RadixTree()
        # serializes tree/pool mutation: engines on scheduler threads and
        # /metrics scrapes on HTTP threads share one manager
        self._lock = threading.Lock()
        # content mutation epoch: memoized classifications (e.g.
        # batching's _needs_stream) revalidate against it
        self.epoch = 0
        self.stats = {"hits": 0, "misses": 0, "partial_hit_tokens": 0,
                      "stores": 0, "stored_blocks": 0,
                      "evicted_blocks": 0, "h2d_bytes": 0}
        self._flight = get_flight_recorder()

    @classmethod
    def for_model(cls, cfg, num_blocks: int, block_tokens: int,
                  dtype=None) -> Optional["KVCacheManager"]:
        """Build from a ModelConfig (+ optional reduced cache dtype —
        blocks store whatever the engine's KV cache holds, so a hit
        round-trips the exact on-device bytes).  Returns None when the
        DWT_KVCACHE_BYTES ceiling leaves room for less than one block:
        for the engines that means "cache off", and an env knob
        documented as a ceiling must never crash serve startup."""
        dtype = dtype if dtype is not None else cfg.dtype
        block_bytes = (2 * int(cfg.kv_planes) * int(cfg.num_kv_heads)
                       * int(block_tokens) * int(cfg.head_dim)
                       * np.dtype(dtype).itemsize)
        if apply_byte_budget(int(num_blocks), block_bytes) < 1:
            return None
        return cls(cfg.kv_planes, cfg.num_kv_heads, cfg.head_dim,
                   num_blocks, block_tokens, dtype)

    # ------------------------------------------------------------------

    def _block_keys(self, prompt, n_blocks: int):
        bt = self.block_tokens
        return [tuple(int(t) for t in prompt[i * bt:(i + 1) * bt])
                for i in range(n_blocks)]

    def match(self, prompt) -> Optional[KVLease]:
        """Longest cached block-prefix of ``prompt`` (capped at
        ``len(prompt) - 1`` tokens), as a pinned lease, or None."""
        prompt = np.asarray(prompt).reshape(-1)
        max_blocks = (len(prompt) - 1) // self.block_tokens
        if max_blocks < 1:
            # too short to ever reuse a whole block: not a lookup at all
            return None
        with self._lock:
            ids, node = self.tree.match(
                self._block_keys(prompt, max_blocks))
            if not ids:
                self.stats["misses"] += 1
                return None
            self.tree.acquire(node)
            tokens = len(ids) * self.block_tokens
            self.stats["hits"] += 1
            self.stats["partial_hit_tokens"] += tokens
        self._flight.record("kvcache_hit", tokens=tokens,
                            blocks=len(ids), prompt_len=len(prompt))
        return KVLease(self, node, ids, tokens)

    def peek(self, prompt) -> int:
        """Matched token count with no stats, lease, or LRU touch (the
        same walk as ``match`` — RadixTree.match is the one owner)."""
        prompt = np.asarray(prompt).reshape(-1)
        max_blocks = (len(prompt) - 1) // self.block_tokens
        if max_blocks < 1:
            return 0
        with self._lock:
            ids, _node = self.tree.match(
                self._block_keys(prompt, max_blocks), touch=False)
            return len(ids) * self.block_tokens

    def _release(self, node) -> None:
        with self._lock:
            self.tree.release(node)

    # ------------------------------------------------------------------

    def store(self, prompt, keys_dev, values_dev, row: int = 0) -> int:
        """Cache every full block of ``prompt`` from a prefilled device
        cache ``[L, B, H, S, D]`` (row ``row``); returns blocks added.

        Only the MISSING tail is copied device→host (one slice per
        store); blocks already in the tree are recognized by key.  Under
        pool pressure, LRU leaves are evicted to make room; if eviction
        cannot free enough (all leased), the tail is simply not cached.
        """
        prompt = np.asarray(prompt).reshape(-1)
        bt = self.block_tokens
        n_blocks = len(prompt) // bt
        if n_blocks < 1:
            return 0
        keys = self._block_keys(prompt, n_blocks)
        with self._lock:
            existing_ids, _ = self.tree.match(keys)
            n_existing = len(existing_ids)
        if n_existing >= n_blocks:
            return 0
        # The D2H copy runs OUTSIDE the lock: it forces a device sync
        # (possibly MBs of K/V), and a /metrics scrape's snapshot() or a
        # sibling engine's match() must not stall behind it.  ONE slice
        # for the whole missing tail, then split into blocks
        # ([L, H, n*bt, D] -> per-block [L, H, bt, D]).
        lo, hi = n_existing * bt, n_blocks * bt
        k_tail = np.asarray(keys_dev[:, row, :, lo:hi, :])
        v_tail = np.asarray(values_dev[:, row, :, lo:hi, :])
        with self._lock:
            evicted = 0

            def alloc(j):
                nonlocal evicted
                if j < n_existing:
                    # a concurrent eviction removed blocks we classified
                    # as existing (and did not copy): skip this store —
                    # caching less is always correct
                    return None
                bid = self.pool.alloc()
                while bid is None:
                    freed = self.tree.evict_lru_leaf()
                    if not freed:
                        return None          # everything left is leased
                    self.pool.free(freed)
                    evicted += len(freed)
                    bid = self.pool.alloc()
                o = (j - n_existing) * bt
                self.pool.write(bid, k_tail[:, :, o:o + bt],
                                v_tail[:, :, o:o + bt])
                return bid

            # insert re-walks under the lock, so blocks another store
            # added meanwhile are recognized (alloc only runs for what
            # is still missing, always at offsets we actually copied)
            _, added = self.tree.insert(keys, alloc)
            self.epoch += 1
            self.stats["stores"] += 1
            self.stats["stored_blocks"] += added
            if evicted:
                self.stats["evicted_blocks"] += evicted
        if evicted:
            self._flight.record("kvcache_evict", blocks=evicted)
        if added:
            self._flight.record("kvcache_admit", blocks=added,
                                tokens=added * bt,
                                prompt_len=len(prompt))
        return added

    # ------------------------------------------------------------------

    def reset_stats(self) -> None:
        with self._lock:
            for k in self.stats:
                self.stats[k] = 0

    def snapshot(self) -> dict:
        """Counters + occupancy for ``/stats`` and the ``dwt_kvcache_*``
        catalog bridge."""
        with self._lock:
            return dict(self.stats,
                        layout="dense",
                        block_tokens=self.block_tokens,
                        blocks_total=self.pool.num_blocks,
                        blocks_used=self.pool.used_blocks,
                        resident_bytes=self.pool.resident_bytes,
                        device_resident_bytes=0,   # host pool: see paged.py
                        capacity_bytes=self.pool.capacity_bytes,
                        nodes=self.tree.node_count - 1)   # excl. root

    def debug_state(self) -> dict:
        """``GET /debugz`` fragment: occupancy + the LRU picture (a few
        coldest evictable leaves), bounded and read-only."""
        snap = self.snapshot()
        with self._lock:
            leaves = sorted(self.tree.evictable_leaves(),
                            key=lambda n: n.last_use)[:8]
            snap["lru_leaves"] = [
                {"blocks": len(n.blocks), "last_use": n.last_use}
                for n in leaves]
            snap["leased_nodes"] = sum(
                1 for n in self._iter_nodes() if n.refs > 0)
        return snap

    def _iter_nodes(self):
        stack = [self.tree.root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            yield node
