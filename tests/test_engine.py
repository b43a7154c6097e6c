"""Engine tests: fused-scan vs streamed decode parity, capacity guard."""

import jax
import numpy as np
import pytest

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine


@pytest.fixture(scope="module")
def engine():
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(cfg, params, max_seq=64,
                           sampling=SamplingParams(greedy=True))


def test_generate_shapes_and_throughput(engine):
    prompt = np.arange(8).reshape(2, 4)
    res = engine.generate(prompt, max_new_tokens=10)
    assert res.tokens.shape == (2, 10)
    assert res.tokens.dtype == np.int32
    assert np.isfinite(res.tokens_per_second)


@pytest.mark.quick
def test_stream_matches_fused_scan(engine):
    """The streaming path must produce the same tokens as the fused scan
    (both greedy, same seed)."""
    prompt = np.asarray([[3, 14, 15, 92, 65]])
    fused = engine.generate(prompt, max_new_tokens=8, seed=7).tokens
    streamed = np.stack(list(engine.generate_stream(prompt, 8, seed=7)), 1)
    np.testing.assert_array_equal(fused, streamed)


@pytest.mark.parametrize("plen", [
    pytest.param(7, marks=pytest.mark.slow), 8,
    pytest.param(9, marks=pytest.mark.slow), 17])
def test_chunked_prefill_matches_whole(engine, plen):
    """Chunked prefill (C=8) must produce the same greedy tokens as
    whole-prompt prefill for every remainder shape: plen < C, == C,
    == C+1, and spanning 3 chunks."""
    cfg = engine.cfg
    chunked = InferenceEngine(cfg, engine.params, max_seq=64,
                              sampling=SamplingParams(greedy=True),
                              prefill_chunk=8)
    prompt = (np.arange(2 * plen).reshape(2, plen) % 199).astype(np.int32)
    want = engine.generate(prompt, 10).tokens
    got = chunked.generate(prompt, 10).tokens
    np.testing.assert_array_equal(want, got)


def test_chunked_prefill_stream_and_classify(engine):
    cfg = engine.cfg
    chunked = InferenceEngine(cfg, engine.params, max_seq=64,
                              sampling=SamplingParams(greedy=True),
                              prefill_chunk=4)
    prompt = np.asarray([[3, 14, 15, 92, 65, 35, 89, 79, 3]])
    fused = chunked.generate(prompt, 6).tokens
    streamed = np.stack(list(chunked.generate_stream(prompt, 6)), 1)
    np.testing.assert_array_equal(fused, streamed)
    labels = engine.classify(prompt, [5, 9])
    labels_chunked = chunked.classify(prompt, [5, 9])
    np.testing.assert_array_equal(labels, labels_chunked)


def test_prefill_chunk_validation(engine):
    with pytest.raises(ValueError, match="prefill_chunk"):
        InferenceEngine(engine.cfg, engine.params, max_seq=64,
                        prefill_chunk=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        InferenceEngine(engine.cfg, engine.params, max_seq=64,
                        prefill_chunk=65)


def test_chunked_prefill_padded_past_capacity(engine):
    """Regression: prompt whose chunk-padded length exceeds max_seq.
    The final chunk must left-shift (aligned last window) instead of
    letting dynamic_update_slice clamp into — and corrupt — valid KV.
    max_seq=30, C=8, plen=26: padding would want slot 31."""
    cfg = engine.cfg
    whole = InferenceEngine(cfg, engine.params, max_seq=30,
                            sampling=SamplingParams(greedy=True))
    chunked = InferenceEngine(cfg, engine.params, max_seq=30,
                              sampling=SamplingParams(greedy=True),
                              prefill_chunk=8)
    prompt = (np.arange(2 * 26).reshape(2, 26) % 199).astype(np.int32)
    want = whole.generate(prompt, 4).tokens
    got = chunked.generate(prompt, 4).tokens
    np.testing.assert_array_equal(want, got)


def test_tp_mesh_engine_matches_single(engine):
    """InferenceEngine(mesh=tp2) greedy output must equal the single-chip
    engine's — BASELINE config #3 (TP serving) as an engine surface."""
    from distributed_inference_demo_tpu.parallel import MeshConfig, make_mesh
    from distributed_inference_demo_tpu.runtime.engine import (
        shard_engine_params)

    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    params = shard_engine_params(engine.params, engine.cfg, mesh)
    tp_engine = InferenceEngine(engine.cfg, params, max_seq=64,
                                sampling=SamplingParams(greedy=True),
                                mesh=mesh)
    prompt = np.asarray([[3, 14, 15, 92], [7, 6, 5, 4]])
    want = engine.generate(prompt, 10).tokens
    got = tp_engine.generate(prompt, 10).tokens
    np.testing.assert_array_equal(want, got)
    # streaming and logprobs ride the same fwd seam
    streamed = np.stack(list(tp_engine.generate_stream(prompt, 6)), 1)
    np.testing.assert_array_equal(want[:, :6], streamed)
    lp = tp_engine.generate(prompt, 4, logprobs=True)
    assert lp.logprobs.shape == (2, 4) and (lp.logprobs <= 0).all()


@pytest.fixture
def flash_interpret(monkeypatch):
    """attn_backend="flash" builds the Pallas kernel in INTERPRET mode:
    the engines offer no way to serve from the interpreter, so the tests
    that need the kernel on a CPU swap the factory the engine calls."""
    from distributed_inference_demo_tpu.ops.flash_attention import (
        make_flash_attn_impl)
    from distributed_inference_demo_tpu.runtime import engine as engine_mod
    monkeypatch.setattr(engine_mod, "make_flash_attn_impl",
                        lambda: make_flash_attn_impl(interpret=True))


def test_tp_mesh_runs_flash_kernel_per_shard(engine, flash_interpret):
    """Under a tp mesh the flash kernel runs INSIDE each shard on its
    nkv / tp local kv heads (it used to be refused there, unexercised):
    greedy output equals the single-chip jnp engine's.  The 16-token
    prompt is a prefill-sized chunk, so the kernel is on the path."""
    from distributed_inference_demo_tpu.parallel import MeshConfig, make_mesh
    from distributed_inference_demo_tpu.runtime.engine import (
        shard_engine_params)

    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    params = shard_engine_params(engine.params, engine.cfg, mesh)
    tp_flash = InferenceEngine(engine.cfg, params, max_seq=64, mesh=mesh,
                               sampling=SamplingParams(greedy=True),
                               attn_backend="flash")
    assert tp_flash.attn_backend == "flash"
    prompt = np.random.RandomState(1).randint(0, engine.cfg.vocab_size,
                                              (2, 16))
    np.testing.assert_array_equal(engine.generate(prompt, 6).tokens,
                                  tp_flash.generate(prompt, 6).tokens)


def test_fp8_kv_cache_under_tp_mesh(engine):
    """kv_cache_dtype composes with a tp mesh: the insert cast and read
    upcast run inside the shard, so tp-sharded fp8 decode must equal
    single-device fp8 decode bit-exactly."""
    import jax.numpy as jnp
    from distributed_inference_demo_tpu.parallel import MeshConfig, make_mesh
    from distributed_inference_demo_tpu.runtime.engine import (
        shard_engine_params)

    single = InferenceEngine(engine.cfg, engine.params, max_seq=64,
                             sampling=SamplingParams(greedy=True),
                             kv_cache_dtype="float8_e4m3fn")
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    params = shard_engine_params(engine.params, engine.cfg, mesh)
    tp_fp8 = InferenceEngine(engine.cfg, params, max_seq=64,
                             sampling=SamplingParams(greedy=True),
                             kv_cache_dtype="float8_e4m3fn", mesh=mesh)
    assert tp_fp8.new_cache(2).keys.dtype == jnp.float8_e4m3fn
    prompt = np.asarray([[3, 14, 15, 92], [7, 6, 5, 4]])
    np.testing.assert_array_equal(single.generate(prompt, 10).tokens,
                                  tp_fp8.generate(prompt, 10).tokens)


def test_logprobs(engine):
    """logprobs=True returns the raw log-softmax of each emitted token:
    negative, and for greedy decoding equal to the max log-softmax (which
    we cross-check by re-scoring the sequence)."""
    import jax.numpy as jnp
    from distributed_inference_demo_tpu.models.base import KVCache, StageSpec
    from distributed_inference_demo_tpu.models.decoder import stage_forward

    prompt = np.asarray([[3, 14, 15, 92], [7, 6, 5, 4]])
    res = engine.generate(prompt, 6, logprobs=True)
    assert res.logprobs is not None and res.logprobs.shape == (2, 6)
    assert (res.logprobs <= 0).all()
    # tokens unchanged by the flag
    base = engine.generate(prompt, 6)
    np.testing.assert_array_equal(base.tokens, res.tokens)
    assert base.logprobs is None
    # re-score: logprob of token t must match log_softmax at its position
    full = np.concatenate([prompt, res.tokens], axis=1)
    cache = KVCache.create(engine.cfg, engine.cfg.num_layers, 2,
                           full.shape[1])
    pos = jnp.broadcast_to(jnp.arange(full.shape[1]), full.shape)
    logits, _ = stage_forward(engine.params, engine.cfg,
                              StageSpec(0, 1, 0, engine.cfg.num_layers),
                              jnp.asarray(full), cache, pos)
    lsm = np.asarray(jax.nn.log_softmax(
        np.asarray(logits, np.float32), axis=-1))
    plen = prompt.shape[1]
    for b in range(2):
        for t in range(6):
            want = lsm[b, plen + t - 1, res.tokens[b, t]]
            np.testing.assert_allclose(res.logprobs[b, t], want, atol=5e-4)


@pytest.mark.slow
def test_eos_padding_in_fused_scan(engine):
    """Once a row emits eos_id, the fused scan pads its remaining steps
    with eos (mirrors the streaming path's early stop, row-wise)."""
    prompt = np.asarray([[3, 14, 15, 92]])
    first = engine.generate(prompt, 1).tokens[0, 0]
    eos_engine = InferenceEngine(engine.cfg, engine.params, max_seq=64,
                                 sampling=SamplingParams(greedy=True),
                                 eos_id=int(first))
    toks = eos_engine.generate(prompt, 8).tokens[0]
    assert (toks == int(first)).all()
    # and a non-eos run is unaffected by the flag
    other = InferenceEngine(engine.cfg, engine.params, max_seq=64,
                            sampling=SamplingParams(greedy=True),
                            eos_id=999999 % engine.cfg.vocab_size)
    base = engine.generate(prompt, 8).tokens
    if not (base == 999999 % engine.cfg.vocab_size).any():
        np.testing.assert_array_equal(other.generate(prompt, 8).tokens,
                                      base)


def test_eos_stream_matches_fused_scan_batch2(engine):
    """With eos_id set and batch > 1, the streamed and fused paths must
    still emit identical tokens (finished rows pad with eos in both)."""
    prompt = np.asarray([[3, 14, 15, 92], [8, 1, 9, 2]])
    first_row0 = int(engine.generate(prompt, 1).tokens[0, 0])
    eng = InferenceEngine(engine.cfg, engine.params, max_seq=64,
                          sampling=SamplingParams(greedy=True),
                          eos_id=first_row0)
    fused = eng.generate(prompt, 8).tokens
    streamed = np.stack(list(eng.generate_stream(prompt, 8, seed=0)), 1)
    np.testing.assert_array_equal(fused[:, :streamed.shape[1]], streamed)
    assert (fused[0] == first_row0).all()


def test_capacity_guard(engine):
    prompt = np.zeros((1, 60), np.int64)
    with pytest.raises(ValueError, match="exceeds KV-cache capacity"):
        engine.generate(prompt, max_new_tokens=10)


def test_eos_early_stop():
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, max_seq=64,
                          sampling=SamplingParams(greedy=True))
    prompt = np.asarray([[1, 2, 3]])
    # find what greedy emits first, then declare it EOS: stream must stop at 1
    first = next(iter(eng.generate_stream(prompt, 4, seed=0)))
    eng.eos_id = int(first[0])
    toks = list(eng.generate_stream(prompt, 8, seed=0))
    assert len(toks) == 1


def test_attn_backend_flash_parity(flash_interpret):
    """Engine-level wiring of the Pallas attention backend: the 'flash'
    engine (interpreted here) must generate identical tokens to 'jnp'."""
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    prompt = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16))
    toks = {}
    for backend in ("jnp", "flash"):
        eng = InferenceEngine(cfg, params, max_seq=32,
                              sampling=SamplingParams(greedy=True),
                              attn_backend=backend)
        toks[backend] = eng.generate(prompt, 8, seed=0).tokens
    np.testing.assert_array_equal(toks["jnp"], toks["flash"])


def test_flash_accepts_misaligned_max_seq(flash_interpret):
    """A max_seq that is NOT a multiple of 8 must still work on the flash
    backend: the engine pads the cache BUFFER to the sublane granule
    (models/base.pad_cache_capacity) while check_capacity keeps enforcing
    the caller's bound.  Regression: a speculative bench leg once died
    with 'flash attention requires max_seq divisible by 8, got 197'."""
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    prompt = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 11))
    toks = {}
    for backend in ("jnp", "flash"):
        eng = InferenceEngine(cfg, params, max_seq=27,
                              sampling=SamplingParams(greedy=True),
                              attn_backend=backend)
        assert eng.new_cache(2).max_seq == 32     # padded buffer
        toks[backend] = eng.generate(prompt, 8, seed=0).tokens
        with pytest.raises(ValueError, match="exceeds KV-cache capacity"):
            eng.generate(prompt, 17, seed=0)      # 11+17 > 27 still rejected
    np.testing.assert_array_equal(toks["jnp"], toks["flash"])


def test_chunked_prefill_misaligned_max_seq(engine):
    """Chunked prefill x non-multiple-of-8 max_seq: the left-shifted final
    chunk must WRITE at the shifted offset explicitly.  With the buffer
    padded past max_seq (27 -> 32) the old implicit dynamic_update_slice
    clamp lands at 32-8=24 instead of start=19, scattering the last
    chunk's K/V to the wrong columns — this pins the explicit
    length=start rewind in _run_prefill (engine.py)."""
    cfg = engine.cfg
    whole = InferenceEngine(cfg, engine.params, max_seq=27,
                            sampling=SamplingParams(greedy=True))
    chunked = InferenceEngine(cfg, engine.params, max_seq=27,
                              sampling=SamplingParams(greedy=True),
                              prefill_chunk=8)
    prompt = (np.arange(2 * 25).reshape(2, 25) % 199).astype(np.int32)
    want = whole.generate(prompt, 2).tokens
    got = chunked.generate(prompt, 2).tokens
    np.testing.assert_array_equal(want, got)


def test_attn_backend_rejects_unknown():
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="attn_backend"):
        InferenceEngine(cfg, params, attn_backend="pallas")


def test_fp8_kv_cache():
    """Opt-in reduced-precision cache: half the cache bytes, f32 attention
    math on upcast values, logits that track the full-precision cache."""
    import jax.numpy as jnp

    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    full = InferenceEngine(cfg, params, max_seq=64,
                           sampling=SamplingParams(greedy=True))
    fp8 = InferenceEngine(cfg, params, max_seq=64,
                          sampling=SamplingParams(greedy=True),
                          kv_cache_dtype="float8_e4m3fn")
    cache = fp8.new_cache(2)
    assert cache.keys.dtype == jnp.float8_e4m3fn
    assert cache.keys.nbytes * 4 == full.new_cache(2).keys.nbytes  # vs f32

    prompt = np.asarray(
        np.random.RandomState(11).randint(0, cfg.vocab_size, (2, 8)),
        np.int32)
    l_full, _ = full._prefill(full.params, prompt, full.new_cache(2))
    l_fp8, _ = fp8._prefill(fp8.params, prompt, fp8.new_cache(2))
    a, b = np.asarray(l_full, np.float64), np.asarray(l_fp8, np.float64)
    # prefill logits stay directionally faithful (cosine per row)
    cos = (a * b).sum(-1) / (np.linalg.norm(a, axis=-1)
                             * np.linalg.norm(b, axis=-1))
    assert (cos > 0.98).all(), cos

    res = fp8.generate(prompt, 8)
    assert res.tokens.shape == (2, 8)
    assert ((res.tokens >= 0) & (res.tokens < cfg.vocab_size)).all()


def test_fp8_kv_cache_rejects_explicit_flash():
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="incompatible"):
        InferenceEngine(cfg, params, max_seq=64, attn_backend="flash",
                        kv_cache_dtype="float8_e4m3fn")


def test_eos_stream_logprobs_match_fused(engine):
    """(token, logprob) pairs from the stream must match the fused scan
    even on eos-padded rows (mask-then-score order is shared)."""
    prompt = np.asarray([[3, 14, 15, 92], [8, 1, 9, 2]])
    first_row0 = int(engine.generate(prompt, 1).tokens[0, 0])
    eng = InferenceEngine(engine.cfg, engine.params, max_seq=64,
                          sampling=SamplingParams(greedy=True),
                          eos_id=first_row0)
    fused = eng.generate(prompt, 6, logprobs=True)
    pairs = list(eng.generate_stream(prompt, 6, logprobs=True))
    toks = np.stack([t for t, _ in pairs], 1)
    lps = np.stack([l for _, l in pairs], 1)
    n = toks.shape[1]
    np.testing.assert_array_equal(fused.tokens[:, :n], toks)
    np.testing.assert_allclose(fused.logprobs[:, :n], lps, atol=1e-5)
