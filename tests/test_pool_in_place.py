"""The page pool is addressed in place (PERF.md section 6, PR 31).

The layer scan hands the paged hook its carried ``[L, N, H, bt, D]`` pool
and the layer's index (``ops.stacked.LayerOf``); the KV write, the gather
and both kernels address ``(layer, page)`` in it.  Held here, on the CPU:

- structure: the traced programs over the pool make no value of a plane's
  shape, and nothing of the pool's shape but the pool itself as it is
  carried, written in place and returned.  (What the TPU's compiler does
  with it is ``tests/test_bring_up.py`` and ``tools/aot_mixed_step.py``.)
- tokens: the same greedy tokens as the dense-cache engine, on one
  device and over a ``tp`` mesh of four, where each shard addresses its
  own ``[L, N, H / tp, bt, D]``.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

GREEDY = SamplingParams(greedy=True)
# tiny dense (GQA, rope), tiny bloom-like (MHA, ALiBi, LayerNorm), tiny
# expert model (the routed layer beside the pool in the same scan)
MODELS = ["qwen2-test", "bloom-test", "olmoe-test"]
B, CHUNK, BLOCK = 3, 8, 2

# what may have the pool's shape: the pool carried through loops and
# calls, and the scatter that writes it in place (on the chip the Pallas
# write, aliased in and out)
CARRIES_THE_POOL = {"scatter", "while", "scan", "cond", "jit", "pjit",
                    "closed_call", "core_call", "remat", "checkpoint",
                    "custom_jvp_call", "custom_vjp_call", "pallas_call"}


def _engine(model, **kw):
    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    return cfg, params, ContinuousBatchingEngine(
        cfg, params, max_seq=64, max_batch=B, sampling=GREEDY,
        decode_block=BLOCK, prefill_chunk=CHUNK, mixed_token_budget=16,
        kv_cache_blocks=11, kv_block_tokens=8, **kw)


def _programs(eng, params):
    """``{name: (jitted program, abstract arguments)}`` of the three
    programs over the pool that the issue names; ``mixed_step`` in both
    its variants (PR 33: a dispatch that packed nothing has no slab)."""
    S = jax.ShapeDtypeStruct
    i32, u32 = jnp.int32, jnp.uint32
    W = eng._table_width
    pool = (params, eng._pk, eng._pv)
    row = (S((B, W), i32), S((B,), i32), S((B,), i32), S((B,), jnp.bool_),
           S((2,), u32), S((), i32), S((B,), i32))
    return {
        "mixed_step": (eng._mixed_step.inner, (
            *pool, tuple(S(x.shape, x.dtype) for x in eng._slab_of(
                eng._blank_segments(), eng._mixed_seg_cap)), *row, BLOCK)),
        "mixed_step, nothing packed": (eng._mixed_step.inner, (
            *pool, None, *row, BLOCK)),
        "paged_multi_step": (eng._paged_multi_step.inner,
                             (*pool, *row, BLOCK)),
        "paged_prefill": (eng._paged_prefill.inner, (
            *pool, S((1, 16), i32), S((1, W), i32), S((), i32), S((), i32),
            S((2,), u32))),
    }


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("program", [
    "mixed_step", "mixed_step, nothing packed", "paged_multi_step",
    "paged_prefill"])
@pytest.mark.parametrize("model", MODELS)
def test_no_plane_and_no_second_pool_in_the_traced_program(model, program):
    cfg, params, eng = _engine(model)
    try:
        fn, args = _programs(eng, params)[program]
        static = tuple(i for i, a in enumerate(args)
                       if isinstance(a, (int, bool)))
        jaxpr = jax.make_jaxpr(fn, static_argnums=static)(*args)
        pool = tuple(eng._pk.shape)
        assert pool == (cfg.num_layers, 11, cfg.num_kv_heads, 8,
                        cfg.head_dim)
        writes = 0
        for eqn in _equations(jaxpr.jaxpr):
            for out in eqn.outvars:
                shape = tuple(getattr(out.aval, "shape", ()))
                name = eqn.primitive.name
                assert shape != pool[1:], (
                    f"{program}: {name} makes a layer's plane {shape}")
                if shape == pool:
                    assert name in CARRIES_THE_POOL, (
                        f"{program}: {name} makes a second pool {shape}")
                    writes += name == "scatter"
        # K and V, once a traced layer body, and in the merged body of a
        # mixed_step that packed a segment twice (the slab's rows, then
        # the decoding rows that ride its pass), beside the decode loop's
        assert writes == (6 if program == "mixed_step" else 2)
        addressing = eng.attn_paths.addressing()[program.split(",")[0]]
        assert addressing and all(
            how == "scatter write" for how in addressing.values())
    finally:
        eng.close()


PROMPTS = [[3, 14, 15, 92, 65, 35, 89, 79, 32, 38, 46], [2, 71, 82],
           [1, 61, 80, 33, 98, 87, 49, 89, 48, 20, 13, 17, 9, 4, 5, 6, 7]]


def _served(eng, n=9):
    reqs = [eng.submit(p, n) for p in PROMPTS]
    return [np.asarray(r.wait(timeout=300)) for r in reqs]


@pytest.mark.parametrize("model", ["bloom-test", "olmoe-test"])
def test_tp4_over_the_stacked_pool_emits_one_devices_tokens(model):
    """``--tp 4`` through ``make_paged_forward_seam`` (four of the eight
    virtual CPU devices): each shard writes and reads ``(layer, page)`` of
    its own quarter of the heads; greedy tokens equal one device's, which
    equal the dense-cache engine's."""
    from distributed_inference_demo_tpu.parallel.mesh import local_tp_mesh
    from distributed_inference_demo_tpu.runtime.engine import (
        shard_engine_params)
    cfg, params, one = _engine(model)
    try:
        want = _served(one)
        stats = one.stats()
    finally:
        one.close()
    assert stats["pool_addressing"]["mixed_step"] == {
        f"chunk={CHUNK}": "scatter write", "chunk=1": "scatter write"}
    assert set(stats["attention_paths"]) == set(stats["pool_addressing"])
    oracle = InferenceEngine(cfg, params, max_seq=64, sampling=GREEDY)
    for p, w in zip(PROMPTS, want):
        np.testing.assert_array_equal(
            w, oracle.generate(np.asarray(p)[None, :], 9).tokens[0])
    mesh = local_tp_mesh(4)
    cfg, _, four = _engine(model, mesh=mesh)
    try:
        four.params = shard_engine_params(params, cfg, mesh)
        got = _served(four)
        assert tuple(four._pk.sharding.shard_shape(four._pk.shape)) == (
            cfg.num_layers, 11, cfg.num_kv_heads // 4, 8, cfg.head_dim)
    finally:
        four.close()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
