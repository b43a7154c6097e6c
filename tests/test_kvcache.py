"""Block-level KV cache (runtime/kvcache): radix-tree properties against
a brute-force reference, refcount/CoW + eviction invariants, byte
accounting, and cold-vs-primed EXACTNESS through the single-request
engines (ISSUE 3 acceptance: cached-vs-cold generations are
token-identical; eviction honors live leases).

The tree/pool/manager tests run host-only (numpy in, numpy out — no jax
below the manager); the exactness tests drive real engines on tiny
models.
"""

import numpy as np
import pytest

from distributed_inference_demo_tpu.runtime.kvcache import (
    KVBlockPool, KVCacheManager, RadixTree)

# ---------------------------------------------------------------------------
# radix tree vs brute-force reference


def _keys(tokens, bt):
    return [tuple(tokens[i * bt:(i + 1) * bt])
            for i in range(len(tokens) // bt)]


class BruteForce:
    """Reference model: a bag of stored block-key sequences; the longest
    common block-prefix over the bag is the ground truth for match."""

    def __init__(self):
        self.seqs = []

    def insert(self, keys):
        self.seqs.append(list(keys))

    def match_len(self, keys):
        best = 0
        for seq in self.seqs:
            n = 0
            while (n < len(seq) and n < len(keys)
                   and seq[n] == keys[n]):
                n += 1
            best = max(best, n)
        return best


@pytest.mark.quick
def test_radix_match_equals_bruteforce_on_random_workload():
    rng = np.random.default_rng(0)
    bt = 4
    tree, ref = RadixTree(), BruteForce()
    next_id = [0]

    def alloc(_):
        next_id[0] += 1
        return next_id[0] - 1

    for step in range(400):
        tokens = rng.integers(0, 5, size=rng.integers(0, 40)).tolist()
        keys = _keys(tokens, bt)
        if rng.random() < 0.5:
            tree.insert(keys, alloc)
            ref.insert(keys)
        else:
            ids, _node = tree.match(keys)
            assert len(ids) == ref.match_len(keys), (step, tokens)
        tree.check()


def test_radix_match_returns_blocks_in_insert_order():
    tree = RadixTree()
    keys = [(1, 2), (3, 4), (5, 6)]
    tree.insert(keys, lambda j: 10 + j)
    ids, node = tree.match(keys)
    assert ids == [10, 11, 12]
    # partial lookup stops mid-edge, no split needed
    ids2, _ = tree.match(keys[:2])
    assert ids2 == [10, 11]
    # divergent insert splits; shared blocks keep their identity
    keys_b = [(1, 2), (3, 4), (7, 8)]
    tree.insert(keys_b, lambda j: 20 + j)
    ids3, _ = tree.match(keys_b)
    assert ids3 == [10, 11, 22]
    tree.check()


def test_radix_eviction_respects_leases_and_lru():
    tree = RadixTree()
    tree.insert([(1,), (2,)], lambda j: j)          # blocks 0, 1
    tree.insert([(1,), (9,)], lambda j: 10 + j)     # splits; block 11
    # pin the (9,) leaf via a match lease
    ids, node = tree.match([(1,), (9,)])
    tree.acquire(node)
    # LRU order now favors the (2,) leaf; the pinned leaf must survive
    # even when evict is called repeatedly
    freed = tree.evict_lru_leaf()
    assert freed == [1]                              # the (2,) tail
    assert tree.evict_lru_leaf() == []               # (9,) pinned, (1,)
    tree.check()                                     # has a child
    tree.release(node)
    freed2 = tree.evict_lru_leaf()
    assert 11 in freed2                              # now evictable
    tree.check()


def test_radix_release_without_acquire_raises():
    tree = RadixTree()
    tree.insert([(1,)], lambda j: j)
    _, node = tree.match([(1,)])
    with pytest.raises(RuntimeError, match="release"):
        tree.release(node)


# ---------------------------------------------------------------------------
# pool accounting


def test_pool_alloc_free_accounting_balances():
    pool = KVBlockPool(4, num_layers=2, num_kv_heads=2, block_tokens=2,
                       head_dim=3, dtype=np.float32)
    assert pool.resident_bytes == 0
    ids = [pool.alloc() for _ in range(4)]
    assert pool.alloc() is None                      # exhausted
    assert pool.used_blocks == 4
    assert pool.resident_bytes == pool.capacity_bytes
    pool.free(ids)
    assert pool.free_blocks == 4 and pool.resident_bytes == 0
    with pytest.raises(ValueError):
        pool.free([99])


def test_pool_gather_roundtrips_block_data():
    pool = KVBlockPool(3, num_layers=1, num_kv_heads=2, block_tokens=2,
                       head_dim=4, dtype=np.float32)
    rng = np.random.default_rng(1)
    a, b = pool.alloc(), pool.alloc()
    ka = rng.normal(size=(1, 2, 2, 4)).astype(np.float32)
    kb = rng.normal(size=(1, 2, 2, 4)).astype(np.float32)
    pool.write(a, ka, ka + 1)
    pool.write(b, kb, kb + 1)
    k, v = pool.gather([a, b])
    assert k.shape == (1, 2, 4, 4)                   # [L, H, n*bt, D]
    np.testing.assert_array_equal(k[:, :, :2], ka)
    np.testing.assert_array_equal(k[:, :, 2:], kb)
    np.testing.assert_array_equal(v[:, :, 2:], kb + 1)


# ---------------------------------------------------------------------------
# manager: lease/CoW/eviction invariants (host-only; numpy "device" rows)


def _mgr(num_blocks=8, bt=4, L=2, H=2, D=4):
    return KVCacheManager(L, H, D, num_blocks=num_blocks,
                          block_tokens=bt, dtype=np.float32)


def _row(rng, L=2, H=2, D=4, S=64):
    return (rng.normal(size=(L, 1, H, S, D)).astype(np.float32),
            rng.normal(size=(L, 1, H, S, D)).astype(np.float32))


def test_manager_match_caps_below_prompt_and_roundtrips_data():
    rng = np.random.default_rng(2)
    mgr = _mgr()
    k, v = _row(rng)
    prompt = np.arange(12)                           # 3 whole blocks
    assert mgr.match(prompt) is None                 # cold: miss
    mgr.store(prompt, k, v)
    lease = mgr.match(prompt)                        # exact repeat
    assert lease.tokens == 8                         # capped below plen
    pk, pv = lease.gather()
    np.testing.assert_array_equal(pk, k[:, 0, :, :8])
    np.testing.assert_array_equal(pv, v[:, 0, :, :8])
    lease.release()
    longer = np.concatenate([np.arange(12), [7, 7, 7, 7, 7]])
    lease2 = mgr.match(longer)                       # mid-prompt hit
    assert lease2.tokens == 12
    lease2.release()
    assert mgr.peek(longer) == 12                    # peek = match, no stats
    assert mgr.stats["hits"] == 2 and mgr.stats["misses"] == 1


def test_manager_store_skips_existing_blocks():
    rng = np.random.default_rng(3)
    mgr = _mgr()
    k, v = _row(rng)
    mgr.store(np.arange(8), k, v)                    # 2 blocks
    added = mgr.store(np.concatenate([np.arange(8), [50, 51, 52, 53]]),
                      k, v)
    assert added == 1                                # only the new tail
    assert mgr.snapshot()["blocks_used"] == 3


def test_manager_eviction_honors_live_leases():
    """ISSUE 3 acceptance: eviction honors live leases — a pinned match
    survives arbitrary pool pressure and still gathers the exact bytes
    it matched; releasing makes it reclaimable."""
    rng = np.random.default_rng(4)
    mgr = _mgr(num_blocks=4, bt=4)
    k, v = _row(rng)
    prompt = np.arange(8)                            # 2 blocks
    mgr.store(prompt, k, v)
    lease = mgr.match(np.concatenate([prompt, [9]]))
    assert lease.tokens == 8
    # flood the pool: every new store needs blocks the leased entry holds
    for i in range(6):
        nk, nv = _row(rng)
        mgr.store(rng.integers(100, 200, size=12), nk, nv)
        snap = mgr.snapshot()
        assert snap["blocks_used"] <= 4
    # the leased blocks were never reclaimed: the gather still matches
    pk, pv = lease.gather()
    np.testing.assert_array_equal(pk, k[:, 0, :, :8])
    lease.release()
    # released: pressure can now reclaim them
    for i in range(4):
        mgr.store(rng.integers(200, 300, size=16), *_row(rng))
    assert mgr.peek(np.concatenate([prompt, [9]])) in (0, 4, 8)


def test_manager_accounting_balances_to_zero_after_drain():
    """Byte accounting: evicting everything returns every block to the
    pool and resident bytes to exactly zero."""
    rng = np.random.default_rng(5)
    mgr = _mgr(num_blocks=8, bt=4)
    for _ in range(5):
        mgr.store(rng.integers(0, 50, size=rng.integers(4, 20)),
                  *_row(rng))
        mgr.tree.check()
    # drain: evict until nothing is left (no leases outstanding)
    while True:
        freed = mgr.tree.evict_lru_leaf()
        if not freed:
            break
        mgr.pool.free(freed)
    snap = mgr.snapshot()
    assert snap["blocks_used"] == 0
    assert snap["resident_bytes"] == 0
    assert snap["nodes"] == 0
    assert mgr.pool.free_blocks == mgr.pool.num_blocks
    mgr.tree.check()


def test_manager_random_workload_invariants():
    """Property sweep over random match/store/evict interleavings with
    live leases: the pool never over-commits, leased gathers always
    return the bytes that were stored, accounting never drifts."""
    rng = np.random.default_rng(6)
    mgr = _mgr(num_blocks=6, bt=2)
    stored = {}                                      # tuple(prompt) -> row
    leases = []
    for step in range(300):
        op = rng.random()
        prompt = rng.integers(0, 4, size=rng.integers(2, 14))
        if op < 0.45:
            k, v = _row(rng)
            mgr.store(prompt, k, v)
            stored[tuple(int(t) for t in prompt)] = (k, v)
        elif op < 0.8:
            lease = mgr.match(prompt)
            if lease is not None and len(leases) < 3:
                leases.append(lease)
            elif lease is not None:
                lease.release()
        elif leases:
            leases.pop(rng.integers(len(leases))).release()
        mgr.tree.check()
        snap = mgr.snapshot()
        assert snap["blocks_used"] <= 6
        assert (snap["blocks_used"] * mgr.pool.block_bytes
                == snap["resident_bytes"])
        assert mgr.pool.free_blocks + snap["blocks_used"] == 6
    for lease in leases:
        lease.release()


def test_env_knobs_and_byte_budget(monkeypatch):
    from distributed_inference_demo_tpu.runtime.kvcache import (
        resolve_kvcache_config)
    monkeypatch.setenv("DWT_KVCACHE_BLOCKS", "12")
    monkeypatch.setenv("DWT_KVCACHE_BLOCK_TOKENS", "8")
    assert resolve_kvcache_config(None, None) == (12, 8)
    assert resolve_kvcache_config(3, 2) == (3, 2)    # explicit wins
    monkeypatch.delenv("DWT_KVCACHE_BLOCKS")
    assert resolve_kvcache_config(None, 4, default_blocks=64) == (64, 4)
    # DWT_KVCACHE_BYTES shrinks the pool to fit
    mgr_free = _mgr(num_blocks=8, bt=4)
    monkeypatch.setenv("DWT_KVCACHE_BYTES",
                       str(3 * mgr_free.pool.block_bytes))
    mgr_capped = _mgr(num_blocks=8, bt=4)
    assert mgr_capped.pool.num_blocks == 3
    # a ceiling below ONE block disables the cache (for_model -> None)
    # instead of crashing engine construction — the knob is a ceiling
    import types
    cfg = types.SimpleNamespace(kv_planes=2, num_kv_heads=2, head_dim=4,
                                dtype=np.float32)
    monkeypatch.setenv("DWT_KVCACHE_BYTES", "1")
    assert KVCacheManager.for_model(cfg, 8, 4) is None
    monkeypatch.delenv("DWT_KVCACHE_BYTES")
    assert KVCacheManager.for_model(cfg, 8, 4) is not None


# ---------------------------------------------------------------------------
# engine exactness: cold vs primed token identity (ISSUE 3 acceptance)


@pytest.fixture(scope="module")
def tiny():
    import jax
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    cfg = get_model_config("llama-test")
    return cfg, init_full_params(jax.random.PRNGKey(0), cfg)


GREEDY_KW = {}


def _greedy():
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    return SamplingParams(greedy=True)


# the unchunked variant is a redundant-coverage twin of
# tests/test_kv_backend.py's plain-engine layout-parity test (which
# runs cold + primed on the same path); the chunked variant is the
# unique coverage and stays in the fast lane
@pytest.mark.parametrize("chunk", [
    pytest.param(None, marks=pytest.mark.slow), 8])
def test_engine_primed_vs_cold_exactness(tiny, chunk):
    """InferenceEngine path: generating the same prompt (shared prefix +
    fresh suffix) on a COLD engine and on one PRIMED with the prefix is
    token-identical under greedy sampling, blocking and streaming."""
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    cfg, params = tiny
    cold = InferenceEngine(cfg, params, max_seq=96, sampling=_greedy(),
                           prefill_chunk=chunk)
    primed = InferenceEngine(cfg, params, max_seq=96, sampling=_greedy(),
                             prefill_chunk=chunk, kv_cache_blocks=32,
                             kv_block_tokens=4)
    shared = list(range(2, 22))                     # 20 tokens = 5 blocks
    prompt = np.asarray([shared + [51, 52, 53]])
    primed.generate(np.asarray([shared + [90]]), 4)  # prime the cache
    want = cold.generate(prompt, 10).tokens
    got = primed.generate(prompt, 10).tokens
    np.testing.assert_array_equal(got, want)
    assert primed.kv_cache.stats["hits"] == 1
    assert primed.kv_cache.stats["partial_hit_tokens"] == 20
    # streaming twin
    streamed = np.concatenate(
        list(primed.generate_stream(prompt, 10)))
    np.testing.assert_array_equal(streamed, want[0])


def test_engine_near_capacity_suffix_single_dispatch(tiny):
    """The cap<C seeded-suffix branch of run_chunked_prefill: a prefix
    hit within one chunk of max_seq still decodes exactly."""
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    cfg, params = tiny
    cold = InferenceEngine(cfg, params, max_seq=32, sampling=_greedy(),
                           prefill_chunk=8)
    primed = InferenceEngine(cfg, params, max_seq=32, sampling=_greedy(),
                             prefill_chunk=8, kv_cache_blocks=32,
                             kv_block_tokens=4)
    base = list(range(1, 29))                       # 28 tokens
    prompt = np.asarray([base[:28] + [3, 4]])       # 30 tokens, suffix 2
    primed.generate(np.asarray([base]), 2)
    want = cold.generate(prompt, 2).tokens
    got = primed.generate(prompt, 2).tokens
    np.testing.assert_array_equal(got, want)
    assert primed.kv_cache.stats["hits"] == 1
    assert primed.kv_cache.stats["partial_hit_tokens"] == 28


@pytest.mark.slow
def test_speculative_target_primed_vs_cold_exactness(tiny):
    """SpeculativeEngine path: target-side block reuse keeps greedy
    output bit-identical to the cold plain engine.  Slow lane: the
    quick lane keeps two spec-pool reps — test_kv_backend's
    page-sharing ownership test (primed == cold equality) and
    test_kv_quant's speculative cold-oracle/primed-floor test."""
    import jax
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    from distributed_inference_demo_tpu.runtime import (InferenceEngine,
                                                        SpeculativeEngine)
    cfg, params = tiny
    dcfg = get_model_config("llama-test-int8")
    dparams = init_full_params(jax.random.PRNGKey(0), dcfg, quantize=True)
    cold = InferenceEngine(cfg, params, max_seq=96, sampling=_greedy())
    spec = SpeculativeEngine(cfg, params, dcfg, dparams, max_seq=96,
                             sampling=_greedy(), num_draft=3,
                             kv_cache_blocks=32, kv_block_tokens=4)
    shared = list(range(3, 23))                     # 20 tokens
    prompt = np.asarray([shared + [61, 62, 63]])
    spec.generate(np.asarray([shared + [90]]), 4)   # prime (target side)
    want = cold.generate(prompt, 10).tokens
    got, _stats = spec.generate(prompt, 10)
    np.testing.assert_array_equal(got.tokens, want)
    assert spec.kv_cache.stats["hits"] == 1


def test_engine_scrape_and_debugz_fragments(tiny):
    """The plain engine exposes its cache on /metrics (scrape_stats) and
    /debugz (debug_state) without growing a /stats surface."""
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    from distributed_inference_demo_tpu.telemetry import catalog
    cfg, params = tiny
    eng = InferenceEngine(cfg, params, max_seq=64, sampling=_greedy(),
                          kv_cache_blocks=8, kv_block_tokens=4)
    prompt = np.asarray([list(range(1, 13))])
    eng.generate(prompt, 4)
    eng.generate(prompt, 4)
    assert eng.kv_cache.stats["hits"] == 1
    text = catalog.scrape(eng)
    assert "dwt_kvcache_hits_total 1" in text
    # the deprecated dwt_batching_prefix_* aliases are REMOVED (PR 3
    # kept them one release; tools/check_metrics_names.py guards the
    # tombstone)
    assert "dwt_batching_prefix_cache_hits_total" not in text
    dbg = eng.debug_state()["kvcache"]
    assert dbg["blocks_used"] > 0 and "lru_leaves" in dbg
    assert not hasattr(eng, "stats")
