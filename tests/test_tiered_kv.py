"""Tiered KV (runtime/kvcache/tiered.py, docs/DESIGN.md §21): the
host-RAM/disk capacity tier below the device page pool.

Three layers, cheapest first:

- pure unit tests over the TieredKVStore ring (demote/take roundtrips
  bit-identical across {bf16, int8, int4} leaf layouts, LRU budget
  spill/drop, digest publishing, the check() accounting invariants) —
  no jax;
- manager-level promotion seam (promote_prefix over a real paged pool:
  alloc-pressure skip, take-race skip, honest h2d accounting);
- engine-level end-to-end: eviction demotes, a re-submitted prefix
  promotes, greedy tokens stay bit-identical to the cold run, and the
  three-tier leak invariant (device used == tree blocks, tier ledger
  exact) closes on finish/cancel/close.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_inference_demo_tpu.runtime.kvcache import (  # noqa: E402
    TieredKVStore, resolve_tier_config)
from distributed_inference_demo_tpu.runtime.kvcache.tiered import (  # noqa: E402
    chain_digests)

BT = 4


def _keys(tokens):
    toks = list(tokens)
    return [tuple(toks[i * BT:(i + 1) * BT])
            for i in range(len(toks) // BT)]


def _payload(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    shape = (n, 2, 2, BT, 8)                    # [n, L, H, bt, D]
    k = rng.standard_normal(shape).astype(dtype)
    v = rng.standard_normal(shape).astype(dtype)
    return k, v


def _quant_payload(n, bits, seed=0):
    from distributed_inference_demo_tpu.ops.quant import QuantizedKVPages
    rng = np.random.default_rng(seed)
    d = 8 // 2 if bits == 4 else 8
    dt = np.uint8 if bits == 4 else np.int8
    shape = (n, 2, 2, BT, d)

    def one():
        data = rng.integers(0, 255, shape).astype(dt)
        scale = rng.standard_normal((n, 2, 2, BT, 1)).astype(np.float32)
        zero = (rng.standard_normal((n, 2, 2, BT, 1)).astype(np.float32)
                if bits == 4 else None)
        return QuantizedKVPages(data, scale, zero, bits)

    return one(), one()


def _assert_blocks_equal(a, b):
    from distributed_inference_demo_tpu.ops.quant import QuantizedKVPages
    if isinstance(a, QuantizedKVPages):
        assert isinstance(b, QuantizedKVPages) and a.bits == b.bits
        np.testing.assert_array_equal(np.asarray(a.data),
                                      np.asarray(b.data))
        np.testing.assert_array_equal(np.asarray(a.scale),
                                      np.asarray(b.scale))
        if a.zero is not None:
            np.testing.assert_array_equal(np.asarray(a.zero),
                                          np.asarray(b.zero))
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# unit: the store itself (no jax)
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_resolve_tier_config_args_env_and_rejection(monkeypatch):
    monkeypatch.delenv("DWT_KV_HOST_TIER_BYTES", raising=False)
    monkeypatch.delenv("DWT_KV_DISK_TIER_PATH", raising=False)
    monkeypatch.delenv("DWT_KV_DISK_TIER_BYTES", raising=False)
    assert resolve_tier_config() == (0, None, 0)
    monkeypatch.setenv("DWT_KV_HOST_TIER_BYTES", "4096")
    assert resolve_tier_config() == (4096, None, 0)
    # explicit arg wins over env (the §17 funnel)
    assert resolve_tier_config(host_bytes=8192) == (8192, None, 0)
    # a disk path without a byte budget is no segment
    assert resolve_tier_config(8192, "/tmp/x", 0) == (8192, None, 0)
    assert resolve_tier_config(8192, "/tmp/x", 1 << 20) == (
        8192, "/tmp/x", 1 << 20)
    # the disk tier sits BELOW the host ring: host off + disk on is a
    # config error, loudly
    with pytest.raises(ValueError, match="BELOW the host ring"):
        resolve_tier_config(0, "/tmp/x", 1 << 20)


@pytest.mark.quick
def test_demote_take_roundtrip_host_bit_identity():
    t = TieredKVStore(1 << 20, BT)
    toks = list(range(3 * BT))
    k, v = _payload(3)
    assert t.demote(_keys(toks), k, v) == 3
    snap = t.snapshot()
    assert snap["host_blocks"] == 3 and snap["disk_blocks"] == 0
    assert snap["host_resident_bytes"] == 6 * k[0].nbytes
    # match walks from the device-covered start, capped below len
    run = t.match(np.asarray(toks + [99]), 0)
    assert len(run) == 3
    kb, vb, nbytes, n = t.take(run)
    assert n == 3 and nbytes == 6 * k[0].nbytes
    _assert_blocks_equal(kb, k)
    _assert_blocks_equal(vb, v)
    # move semantics: the entries are gone
    assert t.match(np.asarray(toks + [99]), 0) == []
    assert t.snapshot()["host_blocks"] == 0
    assert t.host_resident_bytes == 0
    assert t.stats["host_hits"] == 3
    t.check()


@pytest.mark.quick
@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_leaves_roundtrip_verbatim(bits, tmp_path):
    """int8/int4 payloads (data + scale [+ zero]) survive demote/take
    VERBATIM — through the host ring AND through a disk spill — so a
    promoted page is bit-identical to the page that was evicted (no
    dequant round trip anywhere in the tier)."""
    k, v = _quant_payload(2, bits)
    toks = list(range(2 * BT))
    for disk in (False, True):
        kw = ({"disk_path": str(tmp_path / f"seg{bits}{disk}.kv"),
               "disk_bytes": 1 << 20} if disk else {})
        entry_bytes = (k.data[0].nbytes + k.scale[0].nbytes
                       + (k.zero[0].nbytes if k.zero is not None else 0))
        # host budget of ONE entry pair forces a spill when disk is on
        budget = (2 * entry_bytes + 1) if disk else (1 << 20)
        t = TieredKVStore(budget, BT, **kw)
        assert t.demote(_keys(toks), k, v) == 2
        if disk:
            assert t.snapshot()["disk_blocks"] >= 1
            assert t.stats["spilled_blocks"] >= 1
        t.check()
        kb, vb, _, n = t.take(t.match(np.asarray(toks + [9]), 0))
        assert n == 2
        _assert_blocks_equal(kb, k)
        _assert_blocks_equal(vb, v)
        t.check()
        t.close()


@pytest.mark.quick
def test_lru_budget_drops_oldest_without_disk():
    k, v = _payload(1)
    entry = 2 * k[0].nbytes
    t = TieredKVStore(2 * entry, BT)            # room for exactly 2
    for i in range(4):
        toks = list(range(100 * i, 100 * i + BT))
        ki, vi = _payload(1, seed=i)
        t.demote(_keys(toks), ki, vi)
    snap = t.snapshot()
    assert snap["host_blocks"] == 2
    assert t.stats["dropped_blocks"] == 2
    # the SURVIVORS are the newest two
    assert t.match(np.asarray(list(range(300, 304)) + [0]), 0)
    assert not t.match(np.asarray(list(range(0, 4)) + [0]), 0)
    t.check()


@pytest.mark.quick
def test_disk_overflow_drops_oldest_and_recycles_slots(tmp_path):
    k, v = _payload(1)
    entry = 2 * k[0].nbytes
    t = TieredKVStore(entry, BT,
                      disk_path=str(tmp_path / "seg.kv"),
                      disk_bytes=2 * entry)
    for i in range(5):                          # 1 host + 2 disk fit
        toks = list(range(100 * i, 100 * i + BT))
        ki, vi = _payload(1, seed=i)
        t.demote(_keys(toks), ki, vi)
    snap = t.snapshot()
    assert snap["host_blocks"] == 1 and snap["disk_blocks"] == 2
    assert t.stats["dropped_blocks"] == 2
    t.check()
    # a disk take frees its slot for the next spill
    run = t.match(np.asarray(list(range(200, 204)) + [0]), 0)
    assert run and t.take(run)[3] == 1
    assert t.stats["disk_hits"] == 1
    t.check()
    t.close()


@pytest.mark.quick
def test_digest_is_truncated_hex_and_capped():
    t = TieredKVStore(1 << 24, BT, digest_cap=3)
    for i in range(5):
        toks = list(range(10 * i, 10 * i + BT))
        ki, vi = _payload(1, seed=i)
        t.demote(_keys(toks), ki, vi)
    d = t.digest()
    assert d["block_tokens"] == BT
    assert len(d["digests"]) == 3               # newest-first cap
    assert all(len(x) == 16 and int(x, 16) >= 0 for x in d["digests"])
    # byte-compatible with chain_digests + the router's truncation
    newest = _keys(list(range(40, 44)))
    assert chain_digests(newest)[0].hex()[:16] == d["digests"][-1]


@pytest.mark.quick
def test_match_respects_start_and_stops_at_holes():
    t = TieredKVStore(1 << 24, BT)
    toks = list(range(4 * BT))
    k, v = _payload(4)
    t.demote(_keys(toks), k, v)
    # start past the end of coverage
    assert t.match(np.asarray(toks + [7]), 4) == []
    # start inside the run: only the continuation comes back
    assert len(t.match(np.asarray(toks + [7]), 2)) == 2
    # a hole stops the run: drop block 1, then match from 0 sees just
    # block 0
    dg = chain_digests(_keys(toks))
    with t._lock:
        t._drop_locked(dg[1])
    assert len(t.match(np.asarray(toks + [7]), 0)) == 1


# ---------------------------------------------------------------------------
# manager-level: the promotion seam over a real paged pool
# ---------------------------------------------------------------------------

def _paged_pool(num_blocks=8, bt=BT):
    import jax
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.runtime.kvcache import (
        PagedKVCacheManager)
    mgr = PagedKVCacheManager(num_layers=2, num_kv_heads=2, head_dim=8,
                              num_blocks=num_blocks, block_tokens=bt,
                              dtype=np.float32)
    pk = jnp.zeros((2, num_blocks, 2, bt, 8), jnp.float32)
    pv = jax.tree.map(jnp.zeros_like, pk)
    return mgr, pk, pv


@pytest.mark.quick
def test_promote_prefix_restores_tree_and_counts_h2d():
    from distributed_inference_demo_tpu.runtime.kvcache import (
        promote_prefix)
    mgr, pk, pv = _paged_pool()
    tier = TieredKVStore(1 << 24, BT)
    mgr.tier = tier
    toks = list(range(50, 50 + 3 * BT))
    k, v = _payload(3)
    tier.demote(_keys(toks), k, v)
    prompt = np.asarray(toks + [1])
    assert mgr.peek(prompt) == 0
    pk, pv, promoted = promote_prefix(mgr, tier, pk, pv, prompt)
    assert promoted == 3 * BT
    # the promoted blocks are ordinary tree state now: match hits, the
    # tier is empty, and the h2d really happened
    assert mgr.peek(prompt) == 3 * BT
    hit = mgr.match(prompt)
    assert hit is not None and hit.tokens == 3 * BT
    hit.release()
    assert mgr.used_blocks == mgr.tree.block_count == 3
    snap = mgr.snapshot()
    assert snap["h2d_bytes"] == tier.stats["promoted_bytes"] > 0
    assert snap["tier"]["promoted_blocks"] == 3
    assert snap["tier"]["host_blocks"] == 0
    # and the promoted page BYTES are the demoted ones, verbatim
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.runtime.kvcache.device import (
        export_blocks_from_pages)
    ids = mgr.match(prompt)
    kb, _ = export_blocks_from_pages(
        pk, pv, jnp.asarray(ids.block_ids, jnp.int32))
    _assert_blocks_equal(kb, k)
    ids.release()
    tier.check()


@pytest.mark.quick
def test_promote_skips_on_alloc_pressure_and_take_race():
    from distributed_inference_demo_tpu.runtime.kvcache import (
        promote_prefix)
    mgr, pk, pv = _paged_pool(num_blocks=4)
    tier = TieredKVStore(1 << 24, BT)
    toks = list(range(3 * BT))
    k, v = _payload(3)
    tier.demote(_keys(toks), k, v)
    # every page request-owned: alloc is infeasible -> promote skips,
    # nothing leaks, the tier keeps its entries for the next chance
    held = mgr.alloc(3)
    pk, pv, promoted = promote_prefix(mgr, tier, pk, pv,
                                      np.asarray(toks + [1]))
    assert promoted == 0 and tier.snapshot()["host_blocks"] == 3
    assert mgr.used_blocks == 3
    mgr.free(held)
    # take-race: the entries vanish between match and take (a second
    # engine thread, in production) -> ids freed, no leak, no crash
    real_take = tier.take
    tier.take = lambda run: None
    pk, pv, promoted = promote_prefix(mgr, tier, pk, pv,
                                      np.asarray(toks + [1]))
    assert promoted == 0 and mgr.used_blocks == 0
    tier.take = real_take
    tier.check()


@pytest.mark.quick
def test_manager_eviction_demotes_through_hook():
    """The full eviction->demotion seam at manager level: stored pages
    whose leaf gets LRU-evicted land in the tier, keyed so the SAME
    prompt matches them back, with the payload bytes the pages held."""
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.runtime.kvcache import (
        make_demote_hook)
    from distributed_inference_demo_tpu.runtime.kvcache.device import (
        adopt_blocks_into_pages)
    mgr, pk, pv = _paged_pool(num_blocks=4)
    tier = TieredKVStore(1 << 24, BT)
    state = {}
    mgr.tier = tier
    mgr.demote_hook = make_demote_hook(tier,
                                       lambda: (state["pk"], state["pv"]))
    # store prompt A's 2 blocks with known payload
    toks_a = list(range(2 * BT))
    k, v = _payload(2, seed=3)
    ids = mgr.alloc(2)
    pk, pv = adopt_blocks_into_pages(
        pk, pv, jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(np.asarray(ids, np.int32)))
    state["pk"], state["pv"] = pk, pv
    _, lease = mgr.store_shared(np.asarray(toks_a), ids)
    lease.release()
    # demand forces eviction of A's leaf -> the hook demotes it
    got = mgr.alloc(4)
    assert got is not None and mgr.stats["evicted_blocks"] == 2
    assert tier.stats["demoted_blocks"] == 2
    assert tier.stats["demote_errors"] == 0
    run = tier.match(np.asarray(toks_a + [9]), 0)
    kb, vb, _, n = tier.take(run)
    assert n == 2
    _assert_blocks_equal(kb, k)
    _assert_blocks_equal(vb, v)
    mgr.free(got)
    tier.check()


# ---------------------------------------------------------------------------
# engine-level: end-to-end demote -> promote with bit-identity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def params():
    import jax

    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    return init_full_params(jax.random.PRNGKey(0),
                            get_model_config("llama-test"))


def _engine(params, **kw):
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    kw.setdefault("max_seq", 96)
    kw.setdefault("sampling", SamplingParams(greedy=True))
    return InferenceEngine(get_model_config("llama-test"), params, **kw)


PROMPT_A = np.asarray([list(range(2, 22)) + [51, 52, 53]])   # 5 blocks
PROMPT_B = np.asarray([list(range(60, 80)) + [1, 2, 3]])


def test_engine_evict_demotes_resubmit_promotes_bit_identical(
        params, monkeypatch):
    """The §21 headline at engine level: a pool too small for two
    working sets demotes the first prompt's blocks on eviction; its
    re-run promotes them back (h2d counted, tier hit counted) and the
    greedy tokens match the cold run bit-for-bit."""
    monkeypatch.setenv("DWT_KV_HOST_TIER_BYTES", str(1 << 22))
    # 7 blocks x 4 tokens: A stores 5, B's store evicts some of A
    eng = _engine(params, kv_cache_blocks=7, kv_block_tokens=4)
    tier = eng.kv_cache.tier
    assert tier is not None
    cold = eng.generate(PROMPT_A, 8)
    eng.generate(PROMPT_B, 8)                    # evicts -> demotes
    assert tier.stats["demoted_blocks"] > 0
    assert tier.stats["demote_errors"] == 0
    promoted = eng.generate(PROMPT_A, 8)
    np.testing.assert_array_equal(cold.tokens, promoted.tokens)
    snap = eng.kv_cache.snapshot()
    assert tier.stats["promoted_blocks"] > 0
    assert snap["h2d_bytes"] == tier.stats["promoted_bytes"] > 0
    assert snap["tier"]["host_hits"] > 0
    # three-tier leak close: device pages tree-owned, tier ledger exact
    mgr = eng.kv_cache.mgr
    assert mgr.used_blocks == mgr.tree.block_count
    assert eng.kv_cache.debug_state()["leased_nodes"] == 0
    tier.check()
    # close drops the tier with the pool it shadows
    eng.kv_cache.close()
    assert eng.kv_cache.tier is None and mgr.demote_hook is None


def test_batching_engine_tier_end_to_end(params):
    """ContinuousBatchingEngine with explicit tier kwargs: oversubscribed
    admissions demote + promote across requests, tokens stay exact,
    /stats carries the tier fragment + digest for the gateway, the HBM
    ledger gains (and on close loses) the host_tier owner, and the
    three-tier leak invariant closes after every request."""
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.telemetry import profiling
    oracle = _engine(params)
    prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(5)]
    eng = ContinuousBatchingEngine(
        get_model_config("llama-test"), params, max_seq=64, max_batch=4,
        sampling=SamplingParams(greedy=True), prompt_buckets=(16,),
        kv_cache_blocks=8, kv_block_tokens=4,
        kv_host_tier_bytes=1 << 22)
    with eng:
        tier = eng._kv_tier
        assert tier is not None and eng.kv_cache.tier is tier
        reqs = [eng.submit(p, 18) for p in prompts]
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(
                r.wait(timeout=300),
                oracle.generate(np.asarray(p)[None, :], 18).tokens[0])
        # oversubscription (4 slots x 2 blocks > 8 pool blocks after
        # stores) demoted at least one evicted leaf
        assert tier.stats["demoted_blocks"] > 0
        assert tier.stats["demote_errors"] == 0
        # re-submit the first prompt: its demoted prefix promotes back
        r = eng.submit(prompts[0], 18)
        np.testing.assert_array_equal(
            r.wait(timeout=300),
            oracle.generate(np.asarray(prompts[0])[None, :],
                            18).tokens[0])
        snap = eng.stats()["kvcache"]
        assert "tier" in snap and "digest" in snap["tier"]
        assert all(len(d) == 16 for d in snap["tier"]["digest"])
        if tier.stats["promoted_blocks"]:
            assert snap["h2d_bytes"] > 0
        mgr = eng.kv_cache
        assert mgr.used_blocks == mgr.tree.block_count
        tier.check()
        assert "host_tier" in profiling.get_hbm_watermarks().watermarks()
    # close(): tier dies with the engine, ledger owner retired
    assert "host_tier" not in profiling.get_hbm_watermarks().watermarks()


def test_tier_fragment_bridges_to_catalog():
    from distributed_inference_demo_tpu.telemetry import catalog
    t = TieredKVStore(1 << 20, BT)
    k, v = _payload(2)
    t.demote(_keys(list(range(2 * BT))), k, v)
    frag = t.snapshot()
    catalog.update_kvcache_tier_series(frag)

    def val(metric, **labels):
        for _, lab, v in metric.samples():
            if all(dict(lab).get(k) == w for k, w in labels.items()):
                return v
        raise AssertionError(f"no sample {labels}")

    assert val(catalog.KVCACHE_TIER_RESIDENT_BLOCKS, tier="host") == 2
    assert val(catalog.KVCACHE_TIER_RESIDENT_BYTES,
               tier="host") == t.host_resident_bytes
    assert val(catalog.KVCACHE_TIER_DEMOTED_BLOCKS) == 2


# ---------------------------------------------------------------------------
# tools/fleet_top.py --kv: the per-replica tier-occupancy section


def _fleet_top():
    import importlib.util
    path = (Path(__file__).resolve().parents[1] / "tools"
            / "fleet_top.py")
    spec = importlib.util.spec_from_file_location("fleet_top", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_top_kv_section_crash_safe_without_tier_series():
    """A fleet with tiering off (or pre-§21 replicas) exports no
    dwt_kvcache_tier_* series: the --kv section renders its placeholder
    line instead of crashing — same contract as --profile."""
    ft = _fleet_top()
    samples = ft.parse_metrics(
        'dwt_slo_requests_total{tenant="a",replica="r0"} 3\n'
        'dwt_gateway_fleet_scrape_age_seconds{replica="r0"} 0.5\n')
    rows = ft.kv_tier_rows(samples)
    assert rows == []
    page = ft.render_kv(rows)
    assert "no dwt_kvcache_tier_* series exported" in page


def test_fleet_top_kv_rows_from_federated_series():
    ft = _fleet_top()
    text = "\n".join([
        'dwt_kvcache_tier_resident_blocks{tier="host",replica="r0"} 6',
        'dwt_kvcache_tier_resident_bytes{tier="host",replica="r0"} 6144',
        'dwt_kvcache_tier_capacity_bytes{tier="host",replica="r0"} 8192',
        'dwt_kvcache_tier_hits_total{tier="host",replica="r0"} 11',
        'dwt_kvcache_tier_resident_blocks{tier="disk",replica="r0"} 2',
        'dwt_kvcache_tier_resident_bytes{tier="disk",replica="r0"} 2048',
        'dwt_kvcache_tier_capacity_bytes{tier="disk",replica="r0"} 4096',
        'dwt_kvcache_tier_hits_total{tier="disk",replica="r0"} 3',
        'dwt_kvcache_tier_demoted_blocks_total{replica="r0"} 9',
        'dwt_kvcache_tier_promoted_blocks_total{replica="r0"} 7',
        'dwt_kvcache_tier_spilled_blocks_total{replica="r0"} 2',
        'dwt_kvcache_tier_dropped_blocks_total{replica="r0"} 0',
        'dwt_kvcache_tier_resident_blocks{tier="host",replica="r1"} 0',
        'dwt_kvcache_tier_resident_bytes{tier="host",replica="r1"} 0',
        'dwt_kvcache_tier_capacity_bytes{tier="host",replica="r1"} 8192',
    ])
    rows = ft.kv_tier_rows(ft.parse_metrics(text))
    assert [r["replica"] for r in rows] == ["r0", "r1"]
    r0 = rows[0]
    assert r0["tiers"]["host"] == {"blocks": 6.0, "bytes": 6144.0,
                                   "cap": 8192.0, "hits": 11.0}
    assert r0["tiers"]["disk"]["bytes"] == 2048.0
    assert (r0["demoted"], r0["promoted"],
            r0["spilled"], r0["dropped"]) == (9.0, 7.0, 2.0, 0.0)
    page = ft.render_kv(rows)
    assert "r0" in page and "host" in page and "disk" in page
    assert "75.0%" in page            # 6144 / 8192
    # the empty-but-capacitied r1 host ring renders 0% — not a NaN crash
    assert "r1" in page and "0.0%" in page
