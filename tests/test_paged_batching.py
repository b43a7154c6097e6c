"""Paged KV layout on the ContinuousBatchingEngine (docs/DESIGN.md §11).

The acceptance oracle is the same one the dense engine answers to:
greedy tokens must be bit-identical to a lone InferenceEngine run —
cold AND radix-primed — because the paged layout is a memory
architecture, never a semantics change.  On top of parity: the
block-leak invariant (after every request finishes, cancels, or fails,
the only allocated pages are the radix tree's), zero H2D on primed
admissions, and — since the scheduler went paged-NATIVE (docs/DESIGN.md
§14) — the speculative slot proposers riding the pool and the loud
rejection of the deleted dense batch cache.

Runs on CPU through the XLA-gather fallback — the same code path the
TPU kernel's auto-dispatch falls back to, so tier-1 exercises the
production control flow end to end.
"""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

CFG = get_model_config("llama-test")
GREEDY = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def oracle(params):
    return InferenceEngine(CFG, params, max_seq=96, sampling=GREEDY)


def expected(oracle, prompt, n):
    return oracle.generate(np.asarray(prompt)[None, :], n).tokens[0]


def paged_engine(params, **kw):
    kw.setdefault("max_seq", 96)
    kw.setdefault("max_batch", 4)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("prompt_buckets", (16,))
    kw.setdefault("kv_block_tokens", 8)
    return ContinuousBatchingEngine(CFG, params, **kw)


def assert_no_leak(eng):
    """All pages either free or radix-tree-owned: nothing leaked by a
    completed/cancelled/failed request, and no lease pin outlives its
    request (leased_nodes counts live pins)."""
    mgr = eng.kv_cache
    assert mgr.used_blocks == mgr.tree.block_count, (
        mgr.used_blocks, mgr.tree.block_count)
    assert mgr.debug_state()["leased_nodes"] == 0


@pytest.mark.slow
def test_cold_parity_concurrent_requests(params, oracle):
    prompts = [[3, 14, 15], [9, 2, 6, 5, 3, 5], [1], [7, 7, 7, 7]]
    ns = [10, 14, 8, 12]
    with paged_engine(params) as eng:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, ns)]
        for p, n, r in zip(prompts, ns, reqs):
            np.testing.assert_array_equal(r.wait(timeout=300),
                                          expected(oracle, p, n))
        assert_no_leak(eng)


@pytest.mark.slow
def test_primed_parity_and_zero_h2d(params, oracle):
    """Radix-primed admission: the second request block-table-references
    the first one's pages — identical greedy tokens, h2d_bytes == 0
    (the paged path never gathers block bytes through the host)."""
    shared = list(np.arange(16) + 40)        # two whole 8-token blocks
    pa, pb = shared + [1, 2, 3], shared + [4, 5, 6]
    with paged_engine(params) as eng:
        ra = eng.submit(pa, 10)
        np.testing.assert_array_equal(ra.wait(timeout=300),
                                      expected(oracle, pa, 10))
        rb = eng.submit(pb, 10)
        np.testing.assert_array_equal(rb.wait(timeout=300),
                                      expected(oracle, pb, 10))
        snap = eng.kv_cache.snapshot()
        assert snap["hits"] >= 1
        assert snap["partial_hit_tokens"] >= 16
        assert snap["h2d_bytes"] == 0
        assert snap["device_resident_bytes"] > 0
        assert_no_leak(eng)


def test_oversubscribed_pool_requeues_and_completes(params, oracle):
    """More demand than pages: admissions wait for completions to free
    pages (the paged twin of waiting for a slot) and still come out
    exact.  4 slots x 3 blocks/request > 8 pool blocks."""
    prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(6)]
    with paged_engine(params, max_seq=64, kv_cache_blocks=8) as eng:
        reqs = [eng.submit(p, 18) for p in prompts]
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(r.wait(timeout=300),
                                          expected(oracle, p, 18))
        assert_no_leak(eng)


def test_cancel_and_close_free_blocks(params):
    with paged_engine(params, max_batch=2) as eng:
        r = eng.submit([5, 4, 3, 2], 60)
        deadline = time.monotonic() + 240
        while len(r.tokens) < 3:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        r.cancel()
        r.wait(timeout=120)
        deadline = time.monotonic() + 30
        while eng.kv_cache.used_blocks != eng.kv_cache.tree.block_count:
            assert time.monotonic() < deadline, "cancel leaked pages"
            time.sleep(0.02)
        assert_no_leak(eng)
        # a request failed at submit-time validation must not leak either
        with pytest.raises(ValueError):
            eng.submit([], 4)
        assert_no_leak(eng)


def test_failed_request_frees_blocks(params):
    """A request the scheduler fails mid-flight (close() drain) releases
    its pages like a completed one."""
    eng = paged_engine(params, max_batch=1)
    slow = eng.submit([9, 9, 9], 80)
    queued = eng.submit([8, 8, 8], 80)     # waits for the only slot
    while len(slow.tokens) < 2:
        time.sleep(0.01)
    eng.close()                            # drains: fails in-flight+queued
    with pytest.raises(RuntimeError):
        queued.wait(timeout=60)
    assert_no_leak(eng)


def test_submit_rejects_request_larger_than_pool(params):
    with paged_engine(params, max_batch=1, kv_cache_blocks=2) as eng:
        with pytest.raises(ValueError, match="paged pool"):
            eng.submit(list(range(1, 30)), 30)


@pytest.mark.slow
def test_paged_speculative_slot_modes_and_leak(params, oracle):
    """The §11 rejection matrix is DISSOLVED (docs/DESIGN.md §14): the
    speculative slot proposers run on the page pool — prompt-lookup
    verifies through the frozen tables, the draft model additionally
    reserves (and drains) its own scratch page pool — with greedy
    parity against the plain engine and zero leaked pages."""
    with paged_engine(params, max_batch=2, prompt_lookup=True,
                      num_draft=3) as eng:
        p = [5, 4, 3, 2, 5, 4, 3]
        np.testing.assert_array_equal(eng.submit(p, 9).wait(timeout=300),
                                      expected(oracle, p, 9))
        assert_no_leak(eng)
    cfg8 = get_model_config("llama-test-int8")
    params8 = init_full_params(jax.random.PRNGKey(0), cfg8,
                               quantize=True)
    with paged_engine(params, max_batch=2, draft_cfg=cfg8,
                      draft_params=params8, num_draft=3) as eng:
        p = [5, 4, 3, 2]
        np.testing.assert_array_equal(eng.submit(p, 9).wait(timeout=300),
                                      expected(oracle, p, 9))
        assert_no_leak(eng)
        # the draft half of the leak invariant: scratch pages drained
        assert eng._dmgr.used_blocks == 0


def test_decode_block_fused_parity(params, oracle):
    """Fused multi-step decode over the paged cache: tables frozen for
    the block, finished rows' overshoot writes drop via sentinels."""
    ps = [[5, 4, 3, 2], [8, 8, 1]]
    with paged_engine(params, max_batch=2, decode_block=4) as eng:
        reqs = [eng.submit(p, 13) for p in ps]
        for p, r in zip(ps, reqs):
            np.testing.assert_array_equal(r.wait(timeout=300),
                                          expected(oracle, p, 13))
        assert_no_leak(eng)


@pytest.mark.slow
def test_chunked_admission_parity(params, oracle):
    """prefill_chunk composes with paged: chunks stream into the dense
    temp row, the finished row scatters into this request's own pages."""
    long_p = list(np.arange(40) % 50 + 1)
    with paged_engine(params, max_batch=2, prompt_buckets=(16, 64),
                      prefill_chunk=16) as eng:
        r = eng.submit(long_p, 10)
        np.testing.assert_array_equal(r.wait(timeout=300),
                                      expected(oracle, long_p, 10))
        assert eng.chunk_stats["chunks"] >= 1
        assert_no_leak(eng)
