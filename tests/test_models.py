"""Model-core tests: decoding correctness properties that the reference
demonstrably lacks (no KV cache — SURVEY.md §2.7) plus stage-split parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models import (
    KVCache, get_model_config, StageSpec)
from distributed_inference_demo_tpu.models.base import (
    slice_stage, split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (
    init_full_params, stage_forward)
from distributed_inference_demo_tpu.ops.sampling import (
    SamplingParams, sample_logits)


FAMILIES = ["llama-test", "bloom-test", "mixtral-test"]


def _full_spec(cfg):
    return StageSpec(0, 1, 0, cfg.num_layers)


@pytest.mark.parametrize("name", FAMILIES)
@pytest.mark.quick
def test_forward_shapes(name):
    cfg = get_model_config(name)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    spec = _full_spec(cfg)
    ids = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) % cfg.vocab_size
    cache = KVCache.create(cfg, cfg.num_layers, batch=2, max_seq=32)
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    logits, cache2 = stage_forward(params, cfg, spec, ids, cache, pos)
    assert logits.shape == (2, 6, cfg.vocab_size)
    assert int(cache2.length) == 6
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.quick
@pytest.mark.parametrize("at", [(4, 0), 3, (9, -1)],
                         ids=["a-row-each", "one-for-all", "clamped"])
@pytest.mark.parametrize("name", ["llama-test", "bloom-test", "ouro-test",
                                  "olmoe-test"])
def test_logits_at_one_position_are_that_row_of_all_positions(name, at):
    """``logits_at`` (PR 48): the head over ONE position a row gives the
    row that the head over every position gives there, ``[b, 1, V]`` of
    ``[b, s, V]``, for a dense model, a tied head behind a layer norm
    (bloom), a looped model (ouro: ``T > 1``, the last pass closed with
    the final norm already, so the gather comes after the loop) and a
    model with experts under ``valid`` (the gather is after the last
    layer: the rows routed and the cache are the same).  An index is one
    a row or one for all, and read as ``dynamic_index_in_dim`` read it:
    a negative one counts from the end, then it is clamped into the
    chunk."""
    cfg = get_model_config(name)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    # a norm left out, or applied to the wrong rows, moves the logits
    params.final_norm["w"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), params.final_norm["w"].shape)
    b, s = 2, 6
    ids = (jnp.arange(b * s, dtype=jnp.int32).reshape(b, s) * 7 + 3) % 256
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    kw = {}
    if cfg.num_experts:
        kw = dict(moe_stats=True,
                  valid=jnp.arange(s)[None, :] < jnp.asarray([[s], [5]]))

    def run(logits_at):
        cache = KVCache.create(cfg, cfg.num_layers, batch=b, max_seq=16)
        return stage_forward(params, cfg, _full_spec(cfg), ids, cache, pos,
                             logits_at=logits_at, **kw)

    whole, one = run(None), run(jnp.asarray(at, jnp.int32))
    assert whole[0].shape == (b, s, cfg.vocab_size)
    assert one[0].shape == (b, 1, cfg.vocab_size)
    rows = np.broadcast_to(np.asarray(at), (b,))
    rows = np.clip(np.where(rows < 0, rows + s, rows), 0, s - 1)
    np.testing.assert_allclose(
        np.asarray(one[0][:, 0]), np.asarray(whole[0])[np.arange(b), rows],
        rtol=1e-5, atol=1e-6)
    # ... and nothing before the head knows which rows it was asked for
    for x, y in zip(jax.tree.leaves(whole[1:]), jax.tree.leaves(one[1:])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(AssertionError, match="index along s"):
        run(True)       # the flag this argument replaced


@pytest.mark.parametrize("name", [
    "llama-test", "bloom-test",
    # MoE twin — slow lane: the cache layout is llama's; the routed
    # part is pinned quick by test_expert EP parity + hf_parity decode
    pytest.param("mixtral-test", marks=pytest.mark.slow),
])
def test_kv_cache_decode_matches_full_prefill(name):
    """Prefill(N) then decode 1-by-1 must equal prefill(N+k) logits.

    This is THE property the reference loses by feeding only the last token
    with no cache (Communication.java:322-327)."""
    cfg = get_model_config(name)
    params = init_full_params(jax.random.PRNGKey(1), cfg)
    spec = _full_spec(cfg)
    total = 10
    ids = (jax.random.randint(jax.random.PRNGKey(2), (1, total), 0,
                              cfg.vocab_size)).astype(jnp.int32)

    # one-shot full forward
    cache_a = KVCache.create(cfg, cfg.num_layers, 1, max_seq=32)
    pos = jnp.arange(total)[None, :]
    full_logits, _ = stage_forward(params, cfg, spec, ids, cache_a, pos)

    # prefill 6, then 4 single-token decode steps
    cache_b = KVCache.create(cfg, cfg.num_layers, 1, max_seq=32)
    out, cache_b = stage_forward(params, cfg, spec, ids[:, :6], cache_b,
                                 jnp.arange(6)[None, :])
    step_logits = [out]
    for t in range(6, total):
        out, cache_b = stage_forward(
            params, cfg, spec, ids[:, t:t + 1], cache_b,
            jnp.asarray([[t]], jnp.int32))
        step_logits.append(out)
    stepped = jnp.concatenate(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(full_logits, np.float32),
                               np.asarray(stepped, np.float32),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", FAMILIES)
def test_stage_split_matches_monolithic(name):
    """Running layer ranges across 2 'pipeline stages' must reproduce the
    single-stage logits exactly (the inter-stage seam is lossless)."""
    cfg = get_model_config(name)
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.arange(8, dtype=jnp.int32).reshape(1, 8) % cfg.vocab_size
    pos = jnp.arange(8)[None, :]

    mono, _ = stage_forward(params, cfg, _full_spec(cfg), ids,
                            KVCache.create(cfg, cfg.num_layers, 1, 32), pos)

    specs = split_layer_ranges(cfg.num_layers, 2)
    x = ids
    for spec in specs:
        sp = slice_stage(params, cfg, spec)
        cache = KVCache.create(cfg, spec.num_layers, 1, 32)
        x, _ = stage_forward(sp, cfg, spec, x, cache, pos)
    np.testing.assert_allclose(np.asarray(mono, np.float32),
                               np.asarray(x, np.float32), rtol=1e-5, atol=1e-5)


def test_split_layer_ranges_weighted():
    specs = split_layer_ranges(10, 3)
    assert sum(s.num_layers for s in specs) == 10
    assert all(s.num_layers >= 3 for s in specs)  # even-ish split
    assert specs[0].layer_start == 0 and specs[-1].layer_end == 10
    # weighted: heavy front layers -> smaller first range
    specs_w = split_layer_ranges(10, 2, weights=[4] * 2 + [1] * 8)
    assert specs_w[0].num_layers < specs_w[1].num_layers
    # heavy tail: the heavy layer must not drag everything into stage 0
    specs_t = split_layer_ranges(5, 2, weights=[1, 1, 1, 1, 100])
    assert all(s.num_layers >= 1 for s in specs_t)
    assert specs_t[1].layer_start == 4  # heavy layer isolated
    # more stages than layers is an error, not empty stages
    with pytest.raises(ValueError):
        split_layer_ranges(3, 5)


def test_int8_quantization():
    """-int8 catalog names produce genuinely quantized weights whose logits
    track the fp ones (reference parity: data/Data.kt int8 variants)."""
    from distributed_inference_demo_tpu.models.loader import load_or_init
    from distributed_inference_demo_tpu.ops.quant import QuantizedArray

    cfg = get_model_config("llama-test")
    cfg_q = cfg.replace(quantization="int8")
    assert get_model_config("bloom560m-int8").quantization == "int8"

    params = load_or_init("llama-test", cfg)
    params_q = load_or_init("llama-test", cfg_q)
    assert isinstance(params_q.layers["wq"], QuantizedArray)
    assert params_q.layers["wq"].q.dtype.name == "int8"
    # int8 stack is ~4x smaller than the f32 test weights
    assert params_q.layers["wq"].nbytes < params.layers["wq"].nbytes / 2

    ids = jnp.arange(6, dtype=jnp.int32)[None, :] % cfg.vocab_size
    pos = jnp.arange(6)[None, :]
    spec = _full_spec(cfg)
    # approximation property: quantizing THE SAME float tree must track its
    # logits.  (The -int8 random-init path above draws per-layer keys — a
    # different weight stream by design, bounded-memory init — so it can't
    # be compared against the float init value for value.)
    from distributed_inference_demo_tpu.ops.quant import maybe_quantize
    params_same_q = maybe_quantize(params, cfg_q)
    lf, _ = stage_forward(params, cfg, spec, ids,
                          KVCache.create(cfg, cfg.num_layers, 1, 32), pos)
    lq, _ = stage_forward(params_same_q, cfg_q, spec, ids,
                          KVCache.create(cfg, cfg.num_layers, 1, 32), pos)
    # quantized logits approximate fp logits (same argmax on most positions)
    agree = (np.argmax(np.asarray(lf), -1) == np.argmax(np.asarray(lq), -1))
    assert agree.mean() >= 0.5
    # and the int8-init path itself must produce finite, usable logits
    li, _ = stage_forward(params_q, cfg_q, spec, ids,
                          KVCache.create(cfg, cfg.num_layers, 1, 32), pos)
    assert np.isfinite(np.asarray(li, np.float32)).all()
    # quantized stage slicing works (QuantizedArray is a pytree)
    sp = slice_stage(params_q, cfg_q, split_layer_ranges(cfg.num_layers, 2)[0])
    assert sp.layers["wq"].q.shape[0] == split_layer_ranges(cfg.num_layers, 2)[0].num_layers


def test_sampling_modes():
    rng = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]] * 4)
    greedy = sample_logits(logits, rng, SamplingParams(greedy=True))
    assert (np.asarray(greedy) == 1).all()
    # top_k=1 == greedy regardless of rng
    topk1 = sample_logits(logits, rng, SamplingParams(top_k=1, temperature=0.9))
    assert (np.asarray(topk1) == 1).all()
    # top_k=2 never samples outside {1, 2}
    for seed in range(5):
        s = sample_logits(logits, jax.random.PRNGKey(seed),
                          SamplingParams(top_k=2, temperature=1.0))
        assert set(np.asarray(s).tolist()) <= {1, 2}
    # top_p tiny -> only the argmax survives
    topp = sample_logits(logits, rng, SamplingParams(top_k=0, top_p=0.1))
    assert (np.asarray(topp) == 1).all()


def test_topk_vals_idx_matches_lax_topk():
    from distributed_inference_demo_tpu.ops.sampling import topk_vals_idx
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 257).astype(np.float32))
    # plant duplicates to exercise the tie rule
    x = x.at[:, 11].set(x[:, 3])
    want_v, want_i = jax.lax.top_k(x, 7)
    got_v, got_i = topk_vals_idx(x, 7)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_topk_boundary_ties_exactly_k():
    """Logits tying AT the k-th boundary: both the filter and the fused
    draw must keep exactly k first-occurrence tokens — a value-threshold
    filter would keep the tied extra and diverge from the fused draw's
    distribution (the speculative accept/resample contract)."""
    from distributed_inference_demo_tpu.ops.sampling import filtered_logits
    params = SamplingParams(temperature=1.0, top_k=2)
    logits = jnp.asarray([[5.0, 3.0, 3.0, 1.0]])
    f = np.asarray(filtered_logits(logits, params))[0]
    assert np.isfinite(f).sum() == 2         # exactly k survive
    assert np.isfinite(f[[0, 1]]).all()      # first occurrence of the tie
    for s in range(50):
        tok = int(sample_logits(logits, jax.random.PRNGKey(s), params)[0])
        assert tok in (0, 1)


@pytest.mark.slow
def test_topk_fused_draw_matches_filtered_distribution():
    """The [b, k] candidate draw must follow the SAME distribution as a
    categorical over softmax(filtered_logits) — the contract speculative
    decoding's accept/resample rule depends on.  Compare empirical
    frequencies over many seeds against the exact probabilities."""
    from distributed_inference_demo_tpu.ops.sampling import filtered_logits
    params = SamplingParams(temperature=0.7, top_k=3)
    logits = jnp.asarray([[0.0, 2.0, 1.0, -1.0, 1.5]])
    p_exact = np.asarray(
        jax.nn.softmax(filtered_logits(logits, params), axis=-1))[0]
    draws = np.asarray([
        int(sample_logits(logits, jax.random.PRNGKey(s), params)[0])
        for s in range(4000)])
    freq = np.bincount(draws, minlength=5) / draws.size
    # zero-probability tokens must never appear; kept tokens within 3 sigma
    assert freq[p_exact == 0].sum() == 0
    for tok in np.nonzero(p_exact)[0]:
        sigma = np.sqrt(p_exact[tok] * (1 - p_exact[tok]) / draws.size)
        assert abs(freq[tok] - p_exact[tok]) < 3 * sigma + 1e-9, (
            tok, freq[tok], p_exact[tok])


def test_min_p_filter_and_fused_draw_agree():
    """min-p keeps tokens with prob >= min_p * max_prob on the scaled
    distribution; the full-vocab filter and the fused small-k draw must
    produce the same candidate set."""
    from distributed_inference_demo_tpu.ops.sampling import filtered_logits
    logits = jnp.asarray([[0.0, 5.0, 4.9, 1.0, -3.0]])
    # temp 1.0: threshold = 5 + ln(0.5) ~= 4.31 -> only tokens 1, 2 survive
    params = SamplingParams(temperature=1.0, top_k=0, min_p=0.5)
    f = np.asarray(filtered_logits(logits, params))[0]
    assert np.isfinite(f[[1, 2]]).all()
    assert not np.isfinite(f[[0, 3, 4]]).any()
    # fused small-k path (top_k set): identical candidate set
    pk = SamplingParams(temperature=1.0, top_k=4, min_p=0.5)
    f2 = np.asarray(filtered_logits(logits, pk))[0]
    assert np.isfinite(f2[[1, 2]]).all()
    assert not np.isfinite(f2[[0, 3, 4]]).any()
    for s in range(30):
        tok = int(sample_logits(logits, jax.random.PRNGKey(s), pk)[0])
        assert tok in (1, 2)
    # min_p=1.0 degenerates to argmax-only regardless of rng
    only_max = SamplingParams(temperature=1.0, top_k=0, min_p=1.0)
    for s in range(5):
        assert int(sample_logits(logits, jax.random.PRNGKey(s),
                                 only_max)[0]) == 1


def test_min_p_range_validated():
    with pytest.raises(ValueError, match="min_p"):
        SamplingParams(min_p=1.5)
    with pytest.raises(ValueError, match="min_p"):
        SamplingParams(min_p=-0.1)
