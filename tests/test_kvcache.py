"""Block-level KV cache (runtime/kvcache): radix-tree and page-id manager
properties against brute-force references, lease + eviction
invariants, page accounting, the backend's store / seed round trip, and
cold-vs-primed EXACTNESS through the single-request engines (ISSUE 3
acceptance: cached-vs-cold generations are token-identical; eviction
honors live leases).

The tree and manager tests run host-only (no jax below the manager);
the backend and exactness tests drive the device programs and real
engines on tiny models.
"""

import numpy as np
import pytest

from distributed_inference_demo_tpu.runtime.kvcache import (
    PagedKVCacheManager, RadixTree)

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
# the page-id manager against a brute-force dictionary of prefixes


def _mgr(num_blocks=8, bt=4, L=2, H=2, D=4):
    return PagedKVCacheManager(L, H, D, num_blocks=num_blocks,
                               block_tokens=bt, dtype=np.float32)


class PrefixModel:
    """Reference for the manager: every stored block-prefix and the page
    that holds its last block; the pages requests hold for themselves;
    the leases alive.  ``check`` is what must hold after any operation."""

    def __init__(self, mgr):
        self.mgr, self.bt = mgr, mgr.block_tokens
        self.pages = {}              # tuple of block keys -> page id
        self.private = []            # pages a request owns
        self.leases = []

    def took(self, ids):
        """``ids`` came out of ``alloc``: what the tree lost to make
        room is what now lies in the free list or in ``ids``."""
        gone = set(self.mgr._free) | set(ids)
        self.pages = {k: v for k, v in self.pages.items() if v not in gone}

    def longest(self, prompt):
        keys = _keys([int(t) for t in prompt], self.bt)
        keys = keys[:(len(prompt) - 1) // self.bt]      # the cap
        n = 0
        while n < len(keys) and tuple(keys[:n + 1]) in self.pages:
            n += 1
        return [self.pages[tuple(keys[:j + 1])] for j in range(n)]

    def check(self):
        m = self.mgr
        m.tree.check()
        tree = list(self.pages.values())
        assert m.tree.block_count == len(tree)
        assert m.used_blocks == len(tree) + len(self.private)
        held = tree + self.private + list(m._free)
        assert len(held) == len(set(held)) == m.num_blocks
        # a stored prefix's own prefixes are stored (eviction takes
        # leaves), and a lease's pages are the tree's still
        for keys in self.pages:
            assert len(keys) == 1 or keys[:-1] in self.pages
        for lease in self.leases:
            assert set(lease.block_ids) <= set(tree)
        snap = m.snapshot()
        assert snap["blocks_used"] == m.used_blocks
        assert snap["device_resident_bytes"] == m.used_blocks * m.block_bytes


def _random_workload(seed, steps=300):
    rng = np.random.default_rng(seed)
    mgr = _mgr(num_blocks=7, bt=2)
    ref = PrefixModel(mgr)
    for _ in range(steps):
        op = rng.random()
        prompt = rng.integers(0, 3, size=rng.integers(2, 14))
        if op < 0.4:                                    # store
            n = len(prompt) // mgr.block_tokens
            ids = mgr.alloc(n)
            if ids is not None:
                ref.took(ids)
                adopted, lease = mgr.store_shared(prompt, ids)
                keys = _keys([int(t) for t in prompt], mgr.block_tokens)
                # the missing tail alone is adopted, in order
                have = sum(tuple(keys[:j + 1]) in ref.pages
                           for j in range(n))
                assert list(adopted) == ids[have:]
                for j, page in zip(range(have, n), adopted):
                    ref.pages[tuple(keys[:j + 1])] = page
                mgr.free(ids[:have])                    # declined
                ref.leases.append(lease)
        elif op < 0.7:                                  # match
            want = ref.longest(prompt)
            assert mgr.peek(prompt) == len(want) * mgr.block_tokens
            lease = mgr.match(prompt)
            assert (lease.block_ids if lease else []) == want
            if lease is not None:
                assert lease.tokens == len(want) * mgr.block_tokens
                ref.leases.append(lease)
        elif op < 0.85 and ref.leases:                  # release
            ref.leases.pop(rng.integers(len(ref.leases))).release()
        elif op < 0.95:                                 # a request's pages
            ids = mgr.alloc(int(rng.integers(1, 5)))
            if ids is not None:
                ref.took(ids)
                ref.private += ids
        elif ref.private:                               # it completes
            mgr.free(ref.private)
            ref.private = []
        while len(ref.leases) > 3:
            ref.leases.pop(0).release()
        ref.check()
    return mgr, ref


@pytest.mark.parametrize("seed", [6, 7, 8])
def test_manager_random_workload_against_prefix_dictionary(seed):
    """Random store / match / release / evict interleavings with live
    leases: after every operation the used pages are the tree's and the
    requests', no page id is held twice, a match is the longest stored
    prefix capped below the prompt and names the pages that hold it, and
    a leased prefix outlives any pool pressure."""
    mgr, ref = _random_workload(seed)
    assert mgr.stats["evicted_blocks"] > 0 and mgr.stats["hits"] > 0
    assert mgr.stats["stored_blocks"] > mgr.num_blocks


def test_manager_accounting_returns_to_zero_after_drain():
    """Every lease released, every request's pages freed and the tree
    drained: every page is free again and nothing is resident."""
    mgr, ref = _random_workload(9, steps=120)
    for lease in ref.leases:
        lease.release()
    mgr.free(ref.private)
    ids = mgr.alloc(mgr.num_blocks)         # evicts all that is left
    assert ids is not None and sorted(ids) == list(range(mgr.num_blocks))
    assert mgr.tree.block_count == 0 and mgr.tree.node_count == 1
    mgr.free(ids)
    snap = mgr.snapshot()
    assert snap["blocks_used"] == snap["tree_blocks"] == snap["nodes"] == 0
    assert snap["device_resident_bytes"] == 0
    assert mgr.free_blocks == mgr.num_blocks
    assert mgr.debug_state()["leased_nodes"] == 0
    mgr.tree.check()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_backend_store_then_seed_roundtrips_the_shared_prefix(kv_dtype):
    """``PagedKVBackend``: what ``store`` scattered into pages is what
    ``seed`` gathers out of them for a prompt sharing the prefix, bit
    for bit: full-width pages give back the stored keys and values,
    int8 pages what their codes and scales (the stored rows' per-token
    quantization) dequantize to."""
    import jax
    import jax.numpy as jnp
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.base import KVCache
    from distributed_inference_demo_tpu.ops.quant import quantize_kv_pages
    from distributed_inference_demo_tpu.runtime.kvcache import (
        make_kv_backend)
    cfg = get_model_config("llama-test")
    be = make_kv_backend(cfg, 8, 4, kv_dtype=kv_dtype)
    fresh = KVCache.create(cfg, cfg.num_layers, 1, 32)
    kk, kv = jax.random.split(jax.random.PRNGKey(3))
    stored = KVCache(
        jax.random.normal(kk, fresh.keys.shape, fresh.keys.dtype),
        jax.random.normal(kv, fresh.values.shape, fresh.values.dtype),
        jnp.int32(14))
    prompt = np.arange(1, 15)[None]                     # 3 blocks + 2
    be.store(prompt, stored)
    assert be.mgr.tree.block_count == be.mgr.used_blocks == 3
    longer = np.concatenate([prompt[0, :12], [90, 91, 92]])[None]
    m, seeded = be.seed(longer, fresh)
    assert m == 12 and int(seeded.length) == 12
    pages = jnp.asarray(be.mgr.tree.match(
        _keys(list(range(1, 13)), 4), touch=False)[0])

    def rows(x):        # [L, n, H, bt, D] pages as a cache's columns
        L, n, H, bt, D = x.shape
        return np.asarray(x.transpose(0, 2, 1, 3, 4).reshape(
            L, 1, H, n * bt, D))

    for got, put, pool in ((seeded.keys, stored.keys, be._pk),
                           (seeded.values, stored.values, be._pv)):
        want = np.asarray(put[:, :, :, :m])
        if kv_dtype == "int8":
            # the pages hold the stored rows' codes, a scale a token,
            # and the seed is what those pages dequantize to
            held = jax.tree.map(lambda p: p[:, pages], pool)
            ref = quantize_kv_pages(put[:, :, :, :m], 8)
            np.testing.assert_array_equal(rows(held.data),
                                          np.asarray(ref.data))
            np.testing.assert_allclose(rows(held.scale),
                                       np.asarray(ref.scale), rtol=1e-6)
            want = rows(held.dequantize(jnp.float32).astype(put.dtype))
        np.testing.assert_array_equal(np.asarray(got[:, :, :, :m]), want)
        assert not np.asarray(got[:, :, :, m:]).any()   # untouched
    snap = be.snapshot()
    assert snap["h2d_bytes"] == 0 and snap["page_dtype"] == kv_dtype
    assert be.mgr.debug_state()["leased_nodes"] == 0


def test_env_knobs_and_byte_budget(monkeypatch, tiny):
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    from distributed_inference_demo_tpu.runtime.kvcache import (
        make_kv_backend, resolve_kvcache_config)
    monkeypatch.setenv("DWT_KVCACHE_BLOCKS", "12")
    monkeypatch.setenv("DWT_KVCACHE_BLOCK_TOKENS", "8")
    assert resolve_kvcache_config(None, None) == (12, 8)
    assert resolve_kvcache_config(3, 2) == (3, 2)    # explicit wins
    monkeypatch.delenv("DWT_KVCACHE_BLOCKS")
    assert resolve_kvcache_config(None, 4, default_blocks=64) == (64, 4)
    # DWT_KVCACHE_BYTES shrinks the pool to fit: the manager's count,
    # and the pool the backend allocates for it
    cfg, params = tiny
    page = _mgr(num_blocks=8, bt=4).block_bytes
    model_page = make_kv_backend(cfg, 8, 4).mgr.block_bytes
    monkeypatch.setenv("DWT_KVCACHE_BYTES", str(3 * page))
    assert _mgr(num_blocks=8, bt=4).num_blocks == 3
    monkeypatch.setenv("DWT_KVCACHE_BYTES", str(3 * model_page))
    capped = make_kv_backend(cfg, 8, 4)
    assert capped.mgr.num_blocks == capped._pk.shape[1] == 3
    # a ceiling below ONE page disables reuse (no backend) instead of
    # crashing engine construction — the knob is a ceiling
    monkeypatch.setenv("DWT_KVCACHE_BYTES", "1")
    assert make_kv_backend(cfg, 8, 4) is None
    eng = InferenceEngine(cfg, params, max_seq=32, kv_cache_blocks=8,
                          kv_block_tokens=4)
    assert eng.kv_cache is None
    monkeypatch.delenv("DWT_KVCACHE_BYTES")
    assert make_kv_backend(cfg, 8, 4).mgr.num_blocks == 8


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
