"""The universal-paged KV contract (docs/DESIGN.md §14).

A KV cache is a pool of pages behind one backend seam; what this file
pins about it:

- determinism: cold vs radix-primed runs agree bit-for-bit (a prefix
  hit is a memory optimization, never a semantics change) — greedy in
  tier-1, sampled + fused streaming on the slow lane;
- the zero-copy claim: ``h2d_bytes == 0`` after primed runs (hits are
  device gathers, never host round-trips);
- the page-leak invariant after every request: ``used ==
  tree.block_count`` with zero live leases (pages are tree-owned or
  free, nothing dangles);
- speculative page-sharing ownership (two requests sharing a prefix
  reference the SAME pages in HBM);
- the ring-stage per-stage pool frees every page on ``free(rid)``;
- the sp backend has no pool to report: its cache is per-request
  scratch inside the sharded program.

The paged-primed coverage for the batching scheduler, chunked prefill,
``stream_block`` fusion, and the speculative slot modes lives in
tests/test_paged_batching.py, tests/test_kvcache.py, and
tests/test_device_loop.py.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.base import StageSpec
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import (InferenceEngine,
                                                    SpeculativeEngine)

CFG = get_model_config("llama-test")
GREEDY = SamplingParams(greedy=True)
SAMPLED = SamplingParams(temperature=0.7, top_k=7)
POOL = dict(kv_cache_blocks=32, kv_block_tokens=4)
SHARED = list(range(2, 22))                  # 20 tokens = 5 blocks
PROMPT = np.asarray([SHARED + [51, 52, 53]])


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def assert_drained(backend):
    """Paged leak invariant: every page is tree-owned or free, and no
    lease pin outlives its request."""
    mgr = backend.mgr
    assert mgr.used_blocks == mgr.tree.block_count
    assert backend.debug_state()["leased_nodes"] == 0


def cold_and_primed(eng):
    """(cold, primed) results for one engine; asserts the primed run
    hit the radix tree, moved zero bytes through the host, and the
    pool drained."""
    prime = np.asarray([SHARED + [90]])
    cold = eng.generate(PROMPT, 8)
    eng.generate(prime, 4)                   # prime the radix tree
    primed = eng.generate(PROMPT, 8)
    snap = eng.kv_cache.snapshot()
    assert snap["hits"] >= 1
    assert snap["h2d_bytes"] == 0
    assert_drained(eng.kv_cache)
    return cold, primed


_GREEDY_REF = []


def greedy_reference(params):
    """The plain-engine greedy token reference, built at most once per
    process (an engine build costs seconds; several tests pin against
    the same stream)."""
    if not _GREEDY_REF:
        _GREEDY_REF.append(InferenceEngine(
            CFG, params, max_seq=96, sampling=GREEDY,
            **POOL).generate(PROMPT, 8).tokens)
    return _GREEDY_REF[0]


# tier-1 budget: tests/test_kvcache.py::test_engine_primed_vs_cold_
# exactness[8] keeps the quick-lane cold/primed rep on this seam
@pytest.mark.slow
def test_plain_engine_paged_cold_primed_greedy(params):
    """InferenceEngine: a radix-primed greedy run agrees bit-for-bit
    with the cold run and with the shared reference (the tier-1
    prefix-hit oracle; sampled + fused streaming ride the slow
    lane)."""
    cold, primed = cold_and_primed(InferenceEngine(
        CFG, params, max_seq=96, sampling=GREEDY, **POOL))
    np.testing.assert_array_equal(cold.tokens, primed.tokens)
    np.testing.assert_array_equal(cold.tokens, greedy_reference(params))


@pytest.mark.slow
def test_plain_engine_paged_sampled_and_fused(params):
    """The rest of the plain-engine matrix: seeded SAMPLED runs stay
    deterministic across a prefix hit, and fused streaming
    (stream_block > 1) over a primed pool matches the greedy
    reference."""
    cold, primed = cold_and_primed(InferenceEngine(
        CFG, params, max_seq=96, sampling=SAMPLED, **POOL))
    np.testing.assert_array_equal(cold.tokens, primed.tokens)
    # the device loop's K-token blocks ride the seeded-suffix path too
    fused = InferenceEngine(CFG, params, max_seq=96, sampling=GREEDY,
                            stream_block=4, **POOL)
    fused.generate(np.asarray([SHARED + [90]]), 4)       # prime
    streamed = np.concatenate(list(fused.generate_stream(PROMPT, 8)))
    np.testing.assert_array_equal(streamed, greedy_reference(params)[0])
    assert fused.kv_cache.stats["hits"] >= 1
    assert_drained(fused.kv_cache)


# tier-1 budget: the mixed-dispatch spec tests assert draft-pool
# ownership (used==0 idle) every run and are the quick-lane reps
@pytest.mark.slow
def test_speculative_page_sharing_ownership(params):
    """Speculative target prefills SHARE prefix pages: the second
    request sharing a prompt prefix adds no new pages for it (the radix
    tree declines duplicates and the request references the same pages
    in HBM), h2d stays 0, and completion drains to tree-only
    ownership."""
    cfg8 = get_model_config("llama-test-int8")
    params8 = init_full_params(jax.random.PRNGKey(0), cfg8,
                               quantize=True)
    spec = SpeculativeEngine(CFG, params, cfg8, params8, max_seq=96,
                             sampling=GREEDY, num_draft=3, **POOL)
    r1, _ = spec.generate(PROMPT, 8)
    snap1 = spec.kv_cache.snapshot()
    r2, _ = spec.generate(PROMPT, 8)
    snap2 = spec.kv_cache.snapshot()
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    # no duplicate pages for the accepted prefix: the re-run stored
    # nothing new and the pool grew by zero blocks
    assert snap2["stored_blocks"] == snap1["stored_blocks"]
    assert snap2["blocks_used"] == snap1["blocks_used"]
    assert snap2["hits"] >= 1 and snap2["h2d_bytes"] == 0
    assert_drained(spec.kv_cache)


@pytest.mark.quick
def test_ring_stage_runtime_paged(params):
    """The ring-stage path: a loopback single-stage StageRuntime
    decodes the same greedy tokens for two rids sharing one prompt
    (prefill chunk + fused-tail steps are deterministic over the
    per-stage page pool), and ``free(rid)`` returns every page."""
    from distributed_inference_demo_tpu.runtime.distributed import (
        StageRuntime)
    spec = StageSpec(0, 1, 0, CFG.num_layers)
    prompt = PROMPT.astype(np.int32)
    rt = StageRuntime(CFG, spec, params, max_seq=64, sampling=GREEDY)
    toks = {}
    for rid in (7, 8):
        out = []
        tok = rt.run_chunk_sample(rid, 0, prompt)
        out.append(tok.copy())
        for step in range(1, 6):
            tok = rt.run_chunk_sample(rid, step, tok[:, None])
            out.append(tok.copy())
        toks[rid] = np.stack(out, axis=1)
    np.testing.assert_array_equal(toks[7], toks[8])
    held = sum(1 for v in rt._tables[7].flat if v != rt._sentinel)
    assert held == -(-int(rt._rid_len[7]) // rt._bt)
    free_before = len(rt._pool_free)
    rt.free(7)
    assert len(rt._pool_free) == free_before + held
    rt.free(8)
    assert not rt._tables


def test_ring_stage_pool_gets_every_page_back(params):
    """Interleaved rids over one stage pool (two chunked prompts, then
    their decode steps in turn): ``free`` hands one request's pages
    back, ``reset_caches`` (a reshard, a restart) everybody else's, and
    the pool then holds every page id once."""
    from distributed_inference_demo_tpu.runtime.distributed import (
        StageRuntime)
    spec = StageSpec(0, 1, 0, CFG.num_layers)
    rt = StageRuntime(CFG, spec, params, max_seq=64, sampling=GREEDY)
    n_pages = rt._sentinel
    assert sorted(rt._pool_free) == list(range(n_pages))
    prompt = PROMPT.astype(np.int32)
    half = prompt.shape[1] // 2
    toks = {}
    for rid in (1, 2, 3):
        rt.run_chunk(rid, prompt[:, :half])
    for rid in (3, 1, 2):
        toks[rid] = rt.run_chunk_sample(rid, 0, prompt[:, half:])
    for step in range(1, 4):
        for rid in (2, 3, 1):
            toks[rid] = rt.run_chunk_sample(rid, step, toks[rid][:, None])
    np.testing.assert_array_equal(toks[1], toks[2])
    np.testing.assert_array_equal(toks[1], toks[3])
    held = {rid: [int(v) for v in rt._tables[rid].flat
                  if v != rt._sentinel] for rid in (1, 2, 3)}
    assert all(len(h) == -(-(prompt.shape[1] + 3) // rt._bt)
               for h in held.values())
    assert len(rt._pool_free) == n_pages - sum(map(len, held.values()))
    rt.free(2)
    assert 2 not in rt._tables and set(held[2]) <= set(rt._pool_free)
    rt.free(2)                              # an `end` sent twice
    rt.reset_caches()
    assert not rt._tables and not rt._rid_len and not rt._rid_blocks
    assert sorted(rt._pool_free) == list(range(n_pages))


def test_sp_backend_stats_hold_no_page_pool(params):
    """The sp backend's cache is per-request sequence-sharded scratch
    (documented in runtime/sp_backend.py): it builds with no KV option
    and its /stats name no pool."""
    from distributed_inference_demo_tpu.parallel.mesh import local_sp_mesh
    from distributed_inference_demo_tpu.runtime.sp_backend import (
        SequenceParallelBackend)
    mesh = local_sp_mesh(2)
    be = SequenceParallelBackend(CFG, params, mesh, max_seq=64)
    st = be.stats()
    assert st["mode"] == "sequence_parallel" and st["sp"] == 2
    assert "kv_cache" not in st and not hasattr(be, "kv_cache")
