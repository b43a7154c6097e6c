"""Sequence/context parallelism: ring attention + sp-sharded-cache decode.

Validates the long-context path (absent in the reference, SURVEY.md §5.7) on
the virtual 8-device CPU mesh: blockwise ring attention must match dense
causal attention exactly (same math, different schedule), and full
sequence-parallel generation must match single-device generation token for
token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_inference_demo_tpu.models import (
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models.decoder import (
    init_full_params, stage_forward)
from distributed_inference_demo_tpu.ops.attention import (
    alibi_slopes, attention)
from distributed_inference_demo_tpu.ops.ring_attention import (
    ring_self_attention, sp_decode_attention)
from distributed_inference_demo_tpu.parallel import MeshConfig, make_mesh
from distributed_inference_demo_tpu.parallel.sequence import (
    make_sp_generate_fn)


SP = 4


@pytest.fixture(scope="module")
def sp_mesh(devices):
    return make_mesh(MeshConfig(sp=SP), devices[:SP])


def _dense_causal(q, k, v, slopes=None):
    """Reference: ops.attention with cache == the full sequence (the cache
    layout is head-major [b, nkv, S, hd], so transpose the fresh K/V)."""
    L = q.shape[1]
    q_pos = jnp.broadcast_to(jnp.arange(L), (q.shape[0], L))
    return attention(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                     q_pos, jnp.asarray(L, jnp.int32), slopes)


@pytest.mark.parametrize("alibi", [
    False, pytest.param(True, marks=pytest.mark.slow)])
def test_ring_self_attention_matches_dense(sp_mesh, alibi):
    b, L, nh, nkv, hd = 2, 32, 4, 2 if not alibi else 4, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, L, nh, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, L, nkv, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, L, nkv, hd), jnp.float32)
    slopes = alibi_slopes(nh) if alibi else None

    expected = _dense_causal(q, k, v, slopes)

    ring = jax.shard_map(
        lambda q, k, v: ring_self_attention(q, k, v, "sp", slopes=slopes),
        mesh=sp_mesh, in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False)
    got = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_sp_decode_attention_matches_dense(sp_mesh):
    """Decode vs a cache whose 20 valid positions are spread over 4 shards."""
    b, nh, nkv, hd = 2, 4, 2, 8
    s_loc, valid_per_rank = 8, 5
    L = SP * valid_per_rank                      # 20 filled positions
    rng = np.random.RandomState(1)
    k_dense = jnp.asarray(rng.randn(b, L, nkv, hd), jnp.float32)
    v_dense = jnp.asarray(rng.randn(b, L, nkv, hd), jnp.float32)
    q = jnp.asarray(rng.randn(b, 1, nh, hd), jnp.float32)
    q_pos = jnp.full((b, 1), L, jnp.int32)       # new token at position L

    expected = attention(q, k_dense.transpose(0, 2, 1, 3),
                         v_dense.transpose(0, 2, 1, 3), q_pos,
                         jnp.asarray(L, jnp.int32), None)

    # scatter the dense cache into the sharded head-major layout: rank r
    # slots [0,5) hold positions [r*5, r*5+5), slots [5,8) are empty (-1).
    k_shard = np.zeros((b, nkv, SP * s_loc, hd), np.float32)
    v_shard = np.zeros_like(k_shard)
    kv_pos = np.full((SP * s_loc,), -1, np.int32)
    for r in range(SP):
        for j in range(valid_per_rank):
            slot, pos = r * s_loc + j, r * valid_per_rank + j
            k_shard[:, :, slot] = np.asarray(k_dense[:, pos])
            v_shard[:, :, slot] = np.asarray(v_dense[:, pos])
            kv_pos[slot] = pos

    dec = jax.shard_map(
        lambda q, k, v, kp: sp_decode_attention(q, k, v, kp, q_pos, "sp"),
        mesh=sp_mesh,
        in_specs=(P(), P(None, None, "sp"), P(None, None, "sp"), P("sp")),
        out_specs=P(), check_vma=False)
    got = dec(q, jnp.asarray(k_shard), jnp.asarray(v_shard),
              jnp.asarray(kv_pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def _single_device_greedy(cfg, params, prompt, num_new, max_seq):
    """Token-for-token reference: plain cached generation, argmax."""
    b, plen = prompt.shape
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    cache = KVCache.create(cfg, cfg.num_layers, b, max_seq)
    pos = jnp.broadcast_to(jnp.arange(plen), (b, plen))
    logits, cache = stage_forward(params, cfg, spec, jnp.asarray(prompt),
                                  cache, pos)
    toks = [jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)]
    for i in range(num_new - 1):
        p = jnp.full((b, 1), plen + i, jnp.int32)
        logits, cache = stage_forward(params, cfg, spec, toks[-1][:, None],
                                      cache, p)
        toks.append(jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32))
    return np.stack([np.asarray(t) for t in toks], axis=1)


# tier-1 budget: the op-level ring/decode parity tests above and the
# sp_backend [ring] e2e keep the quick-lane reps; whole-generate
# parity rides the slow lane
@pytest.mark.parametrize("model", [
    pytest.param("llama-test", marks=pytest.mark.slow),
    pytest.param("bloom-test", marks=pytest.mark.slow),
])
def test_sp_generate_matches_single_device(sp_mesh, model):
    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    b, plen, num_new, max_seq = 2, 16, 8, 32
    prompt = np.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (b, plen)),
        np.int32)

    expected = _single_device_greedy(cfg, params, prompt, num_new, max_seq)

    gen = make_sp_generate_fn(cfg, sp_mesh, max_seq=max_seq,
                              num_new_tokens=num_new)
    got = gen(params, jnp.asarray(prompt), jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(got), expected)


def test_sp_generate_rejects_bad_shapes(sp_mesh):
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    gen = make_sp_generate_fn(cfg, sp_mesh, max_seq=32, num_new_tokens=4)
    with pytest.raises(ValueError, match="not divisible"):
        gen(params, jnp.zeros((1, 18), jnp.int32), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="max_seq"):
        gen(params, jnp.zeros((1, 32), jnp.int32), jax.random.PRNGKey(0))


@pytest.mark.slow
def test_sp_generate_fp8_cache_matches_fp8_engine(sp_mesh):
    """Reduced-precision sequence-sharded cache: greedy output matches a
    single-device engine storing its cache in the same dtype (attention
    reads what the cache stores, on both sides).  Slow lane: the cross
    of two quick-covered dimensions (sp greedy parity rep + fp8 cache
    reps in test_kvcache/engine)."""
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine

    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    b, plen, num_new, max_seq = 2, 16, 8, 32
    prompt = np.asarray(
        np.random.RandomState(11).randint(0, cfg.vocab_size, (b, plen)),
        np.int32)
    want = InferenceEngine(
        cfg, params, max_seq=max_seq, sampling=SamplingParams(greedy=True),
        kv_cache_dtype="float8_e4m3fn").generate(prompt, num_new).tokens

    gen = make_sp_generate_fn(cfg, sp_mesh, max_seq=max_seq,
                              num_new_tokens=num_new,
                              kv_cache_dtype="float8_e4m3fn")
    got = gen(params, jnp.asarray(prompt), jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(got), want)
