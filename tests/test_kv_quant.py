"""Quantized KV pages (docs/DESIGN.md §17): int8 / packed int4 as
first-class page widths behind the kvcache seam.

The contract under test, layer by layer:

- ops: quantize→dequantize error is bounded by the per-token scale,
  re-quantizing a dequantized page is BIT-IDEMPOTENT (the invariant
  that lets a prefix-hit export re-quantize without drift), and the
  paged gather path over a quantized pool equals the dense reference
  over the pool's dequantized linearization bit-for-bit;
- kernel: the int8 Pallas kernel (interpret mode on CPU) matches the
  XLA gather fallback to f32 tolerance; int4 is deliberately gated off
  the kernel (nibble unpack is Mosaic-hostile) and says so loudly;
- seams: the byte budget admits blocks at their ACTUAL narrow width
  (satellite: the old full-width math undercounted capacity 2-4x),
  ``kv_dtype`` refuses to compose with the ``kv_cache_dtype`` storage
  cast, and snapshots/telemetry surface the page width;
- engines: greedy decode through quantized pools stays within pinned
  per-dtype agreement of the bf16 reference — cold runs on the plain
  engine are IDENTICAL (the prefix pool is untouched), primed runs are
  bounded; the batching scheduler decodes directly against quantized
  pages;
- disagg: a quantized migration payload adopts into the decode pool
  BIT-IDENTICALLY (narrow bytes + scale sidecar over the wire, verbatim
  scatter on adopt).
"""

import sys
import threading
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.attention import attention
from distributed_inference_demo_tpu.ops.paged_attention import (
    paged_flash_attention, paged_gather_attention, write_paged_kv)
from distributed_inference_demo_tpu.ops.quant import (
    KV_DTYPES, QuantizedKVPages, alloc_kv_pages, kv_scale_token_head_bytes,
    kv_token_head_bytes, quantize_kv_pages, resolve_kv_dtype)
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

CFG = get_model_config("llama-test")
GREEDY = SamplingParams(greedy=True)

# empirically pinned greedy token-agreement floors for the tiny random
# llama-test model (primed plain engine / batching decode vs bf16) —
# regressions in the quantization math show up as drops below these
AGREEMENT_FLOOR = {"int8": 0.9, "int4": 0.6}


def _bits(kv_dtype):
    return 4 if kv_dtype == "int4" else 8


def _agreement(got, want):
    got, want = np.asarray(got).ravel(), np.asarray(want).ravel()
    n = min(len(got), len(want))
    return float((got[:n] == want[:n]).mean()) if n else 1.0


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def oracle(params):
    eng = InferenceEngine(CFG, params, max_seq=96, sampling=GREEDY)

    def run(prompt, n):
        return eng.generate(np.asarray(prompt, np.int32)[None], n).tokens[0]
    return run


# ---------------------------------------------------------------------------
# ops: quantize / dequantize / paged paths


@pytest.mark.quick
@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_roundtrip_error_bounded_by_scale(kv_dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 2, 8, 16)) * 3, jnp.float32)
    q = quantize_kv_pages(x, _bits(kv_dtype))
    err = np.abs(np.asarray(q.dequantize()) - np.asarray(x))
    # per-token bound: half a quantization step (+ float slack)
    bound = np.asarray(q.scale) * 0.5 + 1e-5
    assert (err <= bound).all(), float((err - bound).max())
    assert q.shape == x.shape and q.ndim == x.ndim
    assert q.nbytes < x.nbytes


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_requantize_is_bit_idempotent(kv_dtype):
    """quantize(dequantize(q)) == q bitwise — the property that makes a
    prefix-hit re-export (disagg seed → export) drift-free."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 2, 8, 16)), jnp.float32)
    q = quantize_kv_pages(x, _bits(kv_dtype))
    q2 = quantize_kv_pages(q.dequantize(), _bits(kv_dtype))
    np.testing.assert_array_equal(np.asarray(q.data), np.asarray(q2.data))
    np.testing.assert_allclose(np.asarray(q.scale), np.asarray(q2.scale),
                               rtol=1e-6)


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_gather_matches_dense_on_dequantized(kv_dtype):
    """Paged attention over quantized pages == dense attention over the
    pool's dequantized linearization, bit-for-bit (the gather dequants
    then runs the exact same elementwise program)."""
    rng = np.random.default_rng(2)
    nkv, nh, hd, bt, W = 2, 4, 16, 8, 4
    lens = [5, 8, 17]
    b = len(lens)
    N = sum(-(-l // bt) for l in lens) + 2
    pk = quantize_kv_pages(
        jnp.asarray(rng.standard_normal((N, nkv, bt, hd)), jnp.float32),
        _bits(kv_dtype))
    pv = quantize_kv_pages(
        jnp.asarray(rng.standard_normal((N, nkv, bt, hd)), jnp.float32),
        _bits(kv_dtype))
    tables = np.full((b, W), N + 7, np.int32)
    nxt = 0
    for i, l in enumerate(lens):
        for j in range(-(-l // bt)):
            tables[i, j] = nxt
            nxt += 1
    tables = jnp.asarray(tables)
    q = jnp.asarray(rng.standard_normal((b, 1, nh, hd)), jnp.float32)
    qpos = jnp.asarray([l - 1 for l in lens], jnp.int32)[:, None]

    dk, dv = np.asarray(pk.dequantize()), np.asarray(pv.dequantize())
    k_lin = np.zeros((b, nkv, W * bt, hd), np.float32)
    v_lin = np.zeros_like(k_lin)
    tt = np.asarray(tables)
    for i in range(b):
        for j in range(W):
            if tt[i, j] < N:
                k_lin[i, :, j * bt:(j + 1) * bt] = dk[tt[i, j]]
                v_lin[i, :, j * bt:(j + 1) * bt] = dv[tt[i, j]]
    ref = attention(q, jnp.asarray(k_lin), jnp.asarray(v_lin), qpos,
                    jnp.int32(W * bt), None)
    got = paged_gather_attention(q, pk, pv, tables, qpos, None)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    # the int8 kernel (interpret) against the gather oracle; int4 is
    # gated off the kernel and must say so
    kv_lens = jnp.asarray(lens, jnp.int32)
    if kv_dtype == "int8":
        out = paged_flash_attention(q, pk, pv, tables, kv_lens, None,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)
    else:
        with pytest.raises(ValueError, match="int4"):
            paged_flash_attention(q, pk, pv, tables, kv_lens, None,
                                  interpret=True)


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_write_quantizes_at_the_page_boundary(kv_dtype):
    """write_paged_kv into a quantized pool quantizes ONCE, landing the
    same bytes a direct quantize of the chunk produces, at the right
    page/offset; sentinel writes still vanish."""
    rng = np.random.default_rng(3)
    nkv, hd, bt, W = 2, 16, 8, 3
    N, b = 6, 2
    pk = alloc_kv_pages((N, nkv, bt, hd), kv_dtype, jnp.float32)
    pv = jax.tree.map(jnp.zeros_like, pk)
    tables = jnp.asarray([[0, 1, 2], [3, 4, N + 7]], jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((b, 1, nkv, hd)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((b, 1, nkv, hd)), jnp.float32)
    pos = jnp.asarray([[9], [3]], jnp.int32)
    pk2, pv2 = write_paged_kv(pk, pv, k_new, v_new, tables, pos)
    qk = quantize_kv_pages(k_new, _bits(kv_dtype))
    # row 0 lands page 1 offset 1; row 1 page 3 offset 3
    np.testing.assert_array_equal(np.asarray(pk2.data)[1, :, 1],
                                  np.asarray(qk.data)[0, 0])
    np.testing.assert_array_equal(np.asarray(pk2.scale)[1, :, 1],
                                  np.asarray(qk.scale)[0, 0])
    np.testing.assert_array_equal(np.asarray(pk2.data)[3, :, 3],
                                  np.asarray(qk.data)[1, 0])
    # a sentinel table entry drops the write: no page changed for a
    # row routed entirely through the sentinel
    all_sent = jnp.full_like(tables, N + 7)
    pk3, pv3 = write_paged_kv(pk2, pv2, k_new, v_new, all_sent, pos)
    np.testing.assert_array_equal(np.asarray(pk3.data),
                                  np.asarray(pk2.data))
    np.testing.assert_array_equal(np.asarray(pv3.scale),
                                  np.asarray(pv2.scale))


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_stacked_write_into_narrow_pages_is_the_planes_write(kv_dtype,
                                                             layer):
    """The pool addressed in place: a write at ``(layer, page, :, off)``
    of a stacked narrow pool quantizes once and lands, data and sidecars
    on the same index, exactly what the per-plane write lands in that
    layer's plane; every other layer's leaves are bit-identical; a
    sentinel row and a position past the table drop."""
    from distributed_inference_demo_tpu.ops.stacked import LayerOf
    rng = np.random.default_rng(5)
    L, N, nkv, hd, bt, W, b, chunk = 3, 7, 2, 16, 8, 3, 3, 3
    full = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((L, N, nkv, bt, hd)), jnp.float32)
    K = quantize_kv_pages(full(), _bits(kv_dtype))
    V = quantize_kv_pages(full(), _bits(kv_dtype))
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5], [N + 7] * 3], jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((b, chunk, nkv, hd)),
                        jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((b, chunk, nkv, hd)),
                        jnp.float32)
    # row 0 crosses a page boundary, row 1 runs off its table, row 2 is
    # a freed slot
    pos = (jnp.asarray([6, W * bt - 1, 2], jnp.int32)[:, None]
           + jnp.arange(chunk, dtype=jnp.int32))
    li = jnp.int32(layer)
    k2, v2 = write_paged_kv(LayerOf(K, li), LayerOf(V, li), k_new, v_new,
                            tables, pos)
    assert isinstance(k2, LayerOf) and isinstance(v2, LayerOf)
    plane = lambda t, l: jax.tree.map(lambda a: a[l], t)  # noqa: E731
    want_k, want_v = write_paged_kv(plane(K, layer), plane(V, layer),
                                    k_new, v_new, tables, pos)
    for got, want, before in ((k2.stack, want_k, K), (v2.stack, want_v, V)):
        assert isinstance(got, QuantizedKVPages) and got.bits == before.bits
        for l in range(L):
            ref = want if l == layer else plane(before, l)
            for g, w in zip(jax.tree.leaves(plane(got, l)),
                            jax.tree.leaves(ref), strict=True):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the bytes are a direct quantize of the chunk: row 0's second token
    # is position 7, page 0 offset 7; its third is page 1 offset 0
    qk = quantize_kv_pages(k_new, _bits(kv_dtype))
    np.testing.assert_array_equal(np.asarray(k2.stack.data)[layer, 0, :, 7],
                                  np.asarray(qk.data)[0, 1])
    np.testing.assert_array_equal(np.asarray(k2.stack.scale)[layer, 1, :, 0],
                                  np.asarray(qk.scale)[0, 2])
    # page 6 is in nobody's table, pages 3-4 are row 1's and its one
    # in-table token went to page 5
    for untouched in (3, 4, 6):
        np.testing.assert_array_equal(
            np.asarray(k2.stack.data)[layer, untouched],
            np.asarray(K.data)[layer, untouched])


@pytest.mark.quick
def test_byte_owners_and_resolver(monkeypatch):
    """kv_token_head_bytes is the ONE owner of page-width math: narrow
    data + scale sidecar, ~2x / ~4x under bf16 at real head dims."""
    bf16 = kv_token_head_bytes(128, "bf16", jnp.bfloat16)
    i8 = kv_token_head_bytes(128, "int8", jnp.bfloat16)
    i4 = kv_token_head_bytes(128, "int4", jnp.bfloat16)
    assert (bf16, i8, i4) == (256, 128 + 4, 64 + 8)
    assert [kv_scale_token_head_bytes(d) for d in KV_DTYPES] == [0, 4, 8]
    with pytest.raises(ValueError):
        kv_token_head_bytes(128, "int2", jnp.bfloat16)
    with pytest.raises(ValueError):
        quantize_kv_pages(jnp.zeros((2, 3)), 4)  # odd head_dim unpackable

    assert resolve_kv_dtype("int8") == "int8"
    monkeypatch.setenv("DWT_KV_DTYPE", "int4")
    assert resolve_kv_dtype(None) == "int4"
    assert resolve_kv_dtype("bf16") == "bf16"  # arg wins over env
    monkeypatch.setenv("DWT_KV_DTYPE", "fp7")
    with pytest.raises(ValueError, match="fp7"):
        resolve_kv_dtype(None)


# ---------------------------------------------------------------------------
# seams: byte budget, exclusivity, snapshot/telemetry


def test_byte_budget_admits_more_narrow_blocks(monkeypatch):
    """The make_kv_backend byte ceiling counts blocks at their ACTUAL
    width: at a fixed DWT_KVCACHE_BYTES budget an int8 pool holds ~2x
    the bf16 block count, int4 ~4x (the satellite fix: the old math
    priced every width at the full itemsize)."""
    from distributed_inference_demo_tpu.runtime.kvcache import (
        make_kv_backend)
    bf16_block = (2 * CFG.num_layers * CFG.num_kv_heads * 8
                  * kv_token_head_bytes(CFG.head_dim, "bf16", CFG.dtype))
    monkeypatch.setenv("DWT_KVCACHE_BYTES", str(4 * bf16_block))
    n = {}
    for d in KV_DTYPES:
        be = make_kv_backend(CFG, 64, 8, kv_dtype=d)
        n[d] = be.mgr.num_blocks
        assert be.kv_dtype == d
    assert n["bf16"] == 4
    assert n["int8"] > n["bf16"]
    assert n["int4"] > n["int8"]


def test_kv_dtype_refuses_storage_cast(params):
    from distributed_inference_demo_tpu.runtime.kvcache import (
        make_kv_backend)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        make_kv_backend(CFG, 8, 8, dtype=jnp.dtype("float16"),
                        kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ContinuousBatchingEngine(CFG, params, max_seq=64, max_batch=1,
                                 kv_cache_dtype="float16",
                                 kv_dtype="int8")


def test_snapshot_and_metrics_surface_page_dtype():
    from distributed_inference_demo_tpu.runtime.kvcache import (
        PagedKVCacheManager)
    from distributed_inference_demo_tpu.telemetry import catalog
    from distributed_inference_demo_tpu.telemetry.metrics import REGISTRY
    mgr = PagedKVCacheManager.for_model(CFG, 8, 8, kv_dtype="int4")
    snap = mgr.snapshot()
    assert snap["page_dtype"] == "int4"
    assert snap["quant_scale_bytes"] == 0          # idle pool
    ids = mgr.alloc(3)
    snap = mgr.snapshot()
    assert snap["quant_scale_bytes"] == 3 * mgr.scale_block_bytes > 0
    assert snap["page_dtype"] in dict(mgr.debug_state()).values()
    catalog.update_kvcache_series(snap)
    text = REGISTRY.render()
    assert 'dwt_kvcache_page_dtype_info{dtype="int4"} 1' in text
    assert "dwt_kvcache_quant_scale_bytes" in text
    mgr.free(ids)


# ---------------------------------------------------------------------------
# engines: greedy parity, cold and primed


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_plain_engine_cold_identical_primed_bounded(params, oracle,
                                                    kv_dtype):
    """Plain engine + quantized prefix pool: a COLD run never touches
    the pool, so its greedy tokens are IDENTICAL to bf16; the primed
    re-run decodes from dequantized pages and must stay within the
    pinned per-dtype agreement floor while actually hitting the radix
    tree (scales ride the block table through adoption)."""
    prompt = list((np.arange(19) % 29 + 2).astype(int))
    eng = InferenceEngine(CFG, params, max_seq=96, sampling=GREEDY,
                          kv_cache_blocks=16, kv_block_tokens=8,
                          kv_dtype=kv_dtype)
    want = oracle(prompt, 12)
    cold = eng.generate(np.asarray(prompt, np.int32)[None], 12).tokens[0]
    np.testing.assert_array_equal(cold, want)
    snap = eng.kv_cache.snapshot()
    assert snap["page_dtype"] == kv_dtype
    assert snap["stored_blocks"] >= 2
    primed = eng.generate(np.asarray(prompt, np.int32)[None],
                          12).tokens[0]
    assert eng.kv_cache.snapshot()["hits"] >= 1
    agr = _agreement(primed, want)
    assert agr >= AGREEMENT_FLOOR[kv_dtype], (kv_dtype, agr, primed, want)


@pytest.fixture(scope="module")
def int8_batching(params):
    with ContinuousBatchingEngine(
            CFG, params, max_seq=96, max_batch=2, sampling=GREEDY,
            prompt_buckets=(16,), kv_block_tokens=8,
            kv_dtype="int8") as eng:
        yield eng


def test_batching_decodes_against_quantized_pages(int8_batching, oracle):
    """The scheduler's decode step reads K/V straight out of int8 pages
    (no dense shadow): greedy agreement with the bf16 reference stays
    above the pinned floor for every concurrent request, and the pool
    leak invariant holds with sidecars in play."""
    eng = int8_batching
    prompts = [[3, 14, 15, 9, 2, 6], [1, 7, 7, 21]]
    reqs = [eng.submit(p, 12) for p in prompts]
    for p, r in zip(prompts, reqs):
        agr = _agreement(r.wait(timeout=300), oracle(p, 12))
        assert agr >= AGREEMENT_FLOOR["int8"], (p, agr)
    mgr = eng.kv_cache
    assert mgr.used_blocks == mgr.tree.block_count
    assert mgr.snapshot()["page_dtype"] == "int8"


@pytest.mark.slow
def test_speculative_decodes_against_quantized_pages(params, oracle):
    """The speculative path inherits the quantized pool through the
    same make_kv_backend seam: a COLD greedy run never reads the pool
    (draft-verify exactness keeps it bit-identical to the plain bf16
    oracle), and the primed re-run seeds from dequantized int8 pages
    while holding the pinned agreement floor with real radix hits."""
    from distributed_inference_demo_tpu.runtime.speculative import (
        SpeculativeEngine)
    cfg8 = get_model_config("llama-test-int8")
    params8 = init_full_params(jax.random.PRNGKey(0), cfg8,
                               quantize=True)
    spec = SpeculativeEngine(CFG, params, cfg8, params8, max_seq=96,
                             sampling=GREEDY, num_draft=3,
                             kv_cache_blocks=16, kv_block_tokens=8,
                             kv_dtype="int8")
    prompt = list((np.arange(17) % 23 + 2).astype(int))
    want = oracle(prompt, 12)
    r1, _ = spec.generate(np.asarray(prompt, np.int32)[None], 12)
    np.testing.assert_array_equal(r1.tokens[0], want)
    assert spec.kv_cache.snapshot()["page_dtype"] == "int8"
    r2, _ = spec.generate(np.asarray(prompt, np.int32)[None], 12)
    assert spec.kv_cache.snapshot()["hits"] >= 1
    agr = _agreement(r2.tokens[0], want)
    assert agr >= AGREEMENT_FLOOR["int8"], (agr, r2.tokens, want)


def test_disagg_quantized_pages_adopt_bit_identically(params,
                                                      int8_batching):
    """The §15 join with int8 pages: blocks quantized ONCE at the
    prefill worker's export adopt into the decode pool VERBATIM — the
    decode-side page bytes and scale sidecars equal the exported
    payload exactly, zero H2D, and the joined request completes."""
    from distributed_inference_demo_tpu.comm.transport import (
        LoopbackNetwork, LoopbackTransport)
    from distributed_inference_demo_tpu.models.base import KVCache
    from distributed_inference_demo_tpu.runtime.disagg import PrefillWorker

    eng = int8_batching
    bt = eng.kv_cache.block_tokens
    net = LoopbackNetwork()
    pw = PrefillWorker(CFG, params, LoopbackTransport("pq", net),
                       max_seq=96, prefill_chunk=8, kv_block_tokens=bt,
                       kv_dtype="int8")
    assert pw.kv_cache.kv_dtype == "int8"
    prompt = (np.arange(33) % 43 + 2).astype(np.int32)
    n_mig = (len(prompt) - 1) // bt
    row = KVCache.create(CFG, CFG.num_layers, 1, 96)
    cache = KVCache(row.keys, row.values, jnp.int32(0))
    pos = 0
    while pos < n_mig * bt:
        step = min(8, n_mig * bt - pos)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :step] = prompt[pos:pos + step]
        cache = pw._chunk_mid(pw.params, jnp.asarray(chunk), cache,
                              jnp.int32(pos))
        pos += step
    k, v = pw._export_blocks(cache.keys, cache.values, 0, n_mig)
    assert isinstance(k, QuantizedKVPages) and k.bits == 8

    req = eng.submit_premigrated(prompt, 6, k, v)
    out = req.wait(timeout=300)
    assert len(out) == 6
    snap = eng.kv_cache.snapshot()
    assert snap["h2d_bytes"] == 0

    # the adopted prefix pages hold EXACTLY the exported bytes
    lease = eng.kv_cache.match(prompt)
    assert lease is not None and lease.tokens >= n_mig * bt - bt
    ids = list(lease.block_ids)[:n_mig]
    pool_k, pool_v = eng._pk, eng._pv
    for i, b in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(pool_k.data)[:, b],
                                      np.asarray(k.data)[i])
        np.testing.assert_array_equal(np.asarray(pool_k.scale)[:, b],
                                      np.asarray(k.scale)[i])
        np.testing.assert_array_equal(np.asarray(pool_v.data)[:, b],
                                      np.asarray(v.data)[i])
    lease.release()

    # a width mismatch is refused loudly, never silently dequantized
    with pytest.raises(ValueError, match="matching quantized pool"):
        from distributed_inference_demo_tpu.ops.quant import (
            quantize_kv_pages as qkp)
        bad_k = qkp(jnp.asarray(np.asarray(k.data, np.float32)
                                [..., : CFG.head_dim]), 4)
        eng.submit_premigrated(prompt, 4, bad_k, bad_k)


def test_page_frame_carries_quantized_leaves():
    """Wire format: quantized frames tag kv_dtype and carry the flat
    leaf list; bf16 frames keep the pre-§17 two-tensor format (byte
    compatibility with older senders)."""
    from distributed_inference_demo_tpu.runtime.disagg import (
        _page_frame, _parse_meta_frame)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 3, 2, 4, 6)), jnp.float32)
    qk = jax.tree.map(np.asarray, quantize_kv_pages(x, 4))
    qv = jax.tree.map(np.asarray, quantize_kv_pages(-x, 4))
    meta, tensors, _ = _parse_meta_frame(_page_frame(qk, qv, 7))
    assert meta == {"first_block": 7, "n_blocks": 2, "kv_dtype": "int4"}
    assert len(tensors) == 6
    np.testing.assert_array_equal(tensors[0], qk.data)
    np.testing.assert_array_equal(tensors[1], qk.scale)
    np.testing.assert_array_equal(tensors[2], qk.zero)
    np.testing.assert_array_equal(tensors[3], qv.data)
    meta2, t2, _ = _parse_meta_frame(
        _page_frame(np.asarray(x), np.asarray(-x), 0))
    assert meta2 == {"first_block": 0, "n_blocks": 2}
    assert len(t2) == 2
