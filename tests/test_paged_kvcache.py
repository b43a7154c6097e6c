"""PagedKVCacheManager (runtime/kvcache/paged.py): id-only bookkeeping
for the device page pool — allocation/eviction under pressure, lease
pinning, copy-free store adoption, and the accounting invariants the
block-leak engine tests rely on."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_inference_demo_tpu.runtime.kvcache import (
    PagedKVCacheManager)


def mgr(blocks=16, bt=4):
    return PagedKVCacheManager(num_layers=2, num_kv_heads=2, head_dim=4,
                               num_blocks=blocks, block_tokens=bt,
                               dtype=np.float32)


def test_alloc_free_accounting():
    m = mgr(8)
    ids = m.alloc(5)
    assert len(ids) == 5 and len(set(ids)) == 5
    assert m.used_blocks == 5 and m.free_blocks == 3
    m.free(ids[:2])
    assert m.used_blocks == 3
    with pytest.raises(RuntimeError):
        m.free(list(range(8)))    # over capacity = double free


def test_alloc_exhausted_returns_none_keeps_state():
    m = mgr(4)
    ids = m.alloc(4)
    assert m.alloc(1) is None     # nothing evictable: all request-owned
    assert m.used_blocks == 4
    m.free(ids)
    assert m.used_blocks == 0


def test_store_adopts_only_missing_blocks_and_match_hits():
    m = mgr(16, bt=4)
    prompt = np.arange(12)        # 3 full blocks
    mine = m.alloc(3)
    adopted, lease = m.store_shared(prompt, mine)
    assert list(adopted) == mine  # empty tree: everything adopted
    assert lease is not None and m.tree.block_count == 3

    # same prompt from a second request: nothing new to adopt
    theirs = m.alloc(3)
    adopted2, lease2 = m.store_shared(prompt, theirs)
    assert list(adopted2) == []
    # match returns the shared ids (capped below the prompt length)
    hit = m.match(np.arange(13))
    assert hit is not None and hit.block_ids == mine
    assert hit.tokens == 12
    hit.release()
    lease.release()
    lease2.release()
    m.free(theirs)                # not adopted: still request-owned
    assert m.used_blocks == m.tree.block_count == 3


def test_eviction_respects_lease_pins():
    m = mgr(6, bt=4)
    a = m.alloc(2)
    m.store_shared(np.arange(8), a)[1].release()          # tree: blocks 0-1
    b = m.alloc(2)
    lease_b = m.store_shared(np.arange(100, 108), b)[1]   # tree: pinned
    assert m.used_blocks == 4
    # pool has 2 free; asking for 4 must evict the UNPINNED leaf only
    got = m.alloc(4)
    assert got is not None
    assert m.stats["evicted_blocks"] == 2
    # the pinned node survived
    assert m.peek(np.arange(100, 109)) == 8
    lease_b.release()
    m.free(got)


def test_match_caps_below_prompt_len_and_counts():
    m = mgr(8, bt=4)
    ids = m.alloc(2)
    m.store_shared(np.arange(8), ids)[1].release()
    assert m.match(np.arange(4)) is None       # would cover whole prompt
    assert m.stats["misses"] == 0              # not even a lookup
    assert m.match(np.arange(200, 206)) is None  # real lookup, no match
    assert m.stats["misses"] == 1
    hit = m.match(np.arange(8))                # capped at 1 block
    assert hit.tokens == 4
    hit.release()
    snap = m.snapshot()
    assert snap["h2d_bytes"] == 0              # structural: no data here
    assert snap["device_resident_bytes"] == 2 * m.block_bytes
    assert snap["blocks_used"] == 2


def test_epoch_bumps_on_store_and_evict():
    m = mgr(4, bt=4)
    e0 = m.epoch
    ids = m.alloc(1)
    m.store_shared(np.arange(4, dtype=np.int64) + 50, ids)[1].release()
    assert m.epoch > e0
    e1 = m.epoch
    m.alloc(4)                                  # forces eviction
    assert m.epoch > e1


def test_infeasible_alloc_does_not_flush_the_cache():
    """Feasibility is checked before eviction: an admission that can
    never be satisfied must not evict a single tree leaf on its way to
    None (a pending request would otherwise flush the whole prefix
    cache once per scheduler retry)."""
    m = mgr(4, bt=4)
    ids = m.alloc(2)
    m.store_shared(np.arange(8), ids)[1].release()
    assert m.tree.block_count == 2 and m.free_blocks == 2
    assert m.alloc(5) is None                  # > pool: infeasible
    assert m.tree.block_count == 2             # nothing evicted
    assert m.stats["evicted_blocks"] == 0
    # pinned blocks are not reclaimable either
    hold = m.match(np.arange(9))
    assert m.alloc(3) is None                  # 2 free + 0 reclaimable
    assert m.tree.block_count == 2
    hold.release()
    got = m.alloc(3)                           # now feasible: evicts
    assert got is not None and m.stats["evicted_blocks"] == 2
    m.free(got)


def test_catalog_bridges_tree_share_vs_all_owners():
    """dwt_kvcache_used_blocks (tree share) and
    dwt_kvcache_blocks_in_use (all owners) must come from different
    snapshot keys on the paged layout — their gap is the §11 runbook's
    page-leak signal."""
    from distributed_inference_demo_tpu.telemetry import catalog
    m = mgr(8, bt=4)
    ids = m.alloc(2)
    lease = m.store_shared(np.arange(8), ids)[1]
    private = m.alloc(3)                       # in-flight request pages
    catalog.update_kvcache_series(m.snapshot())

    def val(metric):
        [(_, _, v)] = list(metric.samples())
        return v

    assert val(catalog.KVCACHE_USED_BLOCKS) == 2
    assert val(catalog.KVCACHE_BLOCKS_IN_USE) == 5
    assert val(catalog.KVCACHE_DEVICE_RESIDENT_BYTES) == 5 * m.block_bytes
    lease.release()
    m.free(private)
