"""Expert parallelism (MoE over the ``ep`` mesh axis).

The GShard-style capacity dispatch (``decoder._moe_mlp_ep``) and its
shard_map entry point (``parallel.expert.make_ep_stage_fn``) must:

- reproduce the dense ``_moe_mlp`` bit-for-tolerance when capacity is
  generous (no token dropped);
- drop exactly the over-capacity tokens (zero MoE contribution) when the
  capacity factor is small — GShard semantics, not an error;
- run the whole mixtral stage (prefill + decode) E-sliced over ``ep``.

Reference analog: per-device module placement (``server.py:893-905``);
the reference itself has no MoE or EP at all (SURVEY.md §2.7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_inference_demo_tpu.models import (
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models.decoder import (
    _moe_mlp, _moe_mlp_ep, init_full_params, stage_forward)
from distributed_inference_demo_tpu.parallel import MeshConfig, make_mesh
from distributed_inference_demo_tpu.parallel.expert import make_ep_stage_fn


def _layer_moe_params(rng, cfg):
    """One layer's MoE weights (no stacked-L axis), float32."""
    E, H, I = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    ks = jax.random.split(rng, 4)
    s = H ** -0.5
    return {
        "router": jax.random.normal(ks[0], (H, E), jnp.float32) * s,
        "w_gate": jax.random.normal(ks[1], (E, H, I), jnp.float32) * s,
        "w_up": jax.random.normal(ks[2], (E, H, I), jnp.float32) * s,
        "w_down": jax.random.normal(ks[3], (E, I, H), jnp.float32)
                  * I ** -0.5,
    }


def _run_ep_mlp(cfg, lp, x, mesh):
    specs = {"router": P(), "w_gate": P("ep", None, None),
             "w_up": P("ep", None, None), "w_down": P("ep", None, None)}
    fn = jax.shard_map(
        lambda lp_, x_: _moe_mlp_ep(cfg, lp_, x_, "ep"),
        mesh=mesh, in_specs=(specs, P("ep")), out_specs=P("ep"),
        check_vma=False)
    return fn(lp, x)


def test_ep_dispatch_matches_dense(devices):
    """Generous capacity: all_to_all dispatch == dense batched experts."""
    cfg = get_model_config("mixtral-test").replace(moe_capacity_factor=4.0)
    lp = _layer_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, cfg.hidden_size),
                          jnp.float32)
    dense = _moe_mlp(cfg, lp, x)
    mesh = make_mesh(MeshConfig(ep=2), devices)
    with mesh:
        ep = _run_ep_mlp(cfg, lp, x, mesh)
    np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_ep_capacity_drop(devices):
    """factor < 1: tokens beyond each expert's capacity get exactly zero
    MoE output (GShard drop), earlier tokens are untouched."""
    cfg = get_model_config("mixtral-test").replace(moe_capacity_factor=0.5)
    lp = _layer_moe_params(jax.random.PRNGKey(0), cfg)
    # force every token onto experts 0 and 1: capacity per expert is
    # C = ceil(T*k/E * 0.5) with T tokens per rank, all landing on 2 of
    # the 4 experts -> tokens with in-rank index >= C are dropped.
    E = cfg.num_experts
    router = jnp.zeros((cfg.hidden_size, E), jnp.float32)
    router = router.at[:, 0].set(1.0).at[:, 1].set(0.5)
    lp = dict(lp, router=router)

    b, s = 2, 8
    # positive activations => positive sum(x) => router logits rank
    # expert0 > expert1 > rest for EVERY token (the router is linear, so a
    # negative-sum token would otherwise flip the ranking)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1),
                                  (b, s, cfg.hidden_size), jnp.float32)) + 0.1
    T = (b // 2) * s                       # tokens per rank at ep=2
    C = int(np.ceil(T * cfg.experts_per_token / E * 0.5))
    assert C < T                           # the test must actually drop

    mesh = make_mesh(MeshConfig(ep=2), devices)
    with mesh:
        y = np.asarray(_run_ep_mlp(cfg, lp, x, mesh))
    dense = np.asarray(_moe_mlp(cfg, lp, x))

    y = y.reshape(2, T, -1)                # [rank, token-in-rank, H]
    dense = dense.reshape(2, T, -1)
    np.testing.assert_allclose(y[:, :C], dense[:, :C], rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(y[:, C:], np.zeros_like(y[:, C:]))


@pytest.mark.parametrize("quant", [
    False,
    # int8 twin — slow lane: int4 is the odd packed path and stays
    # quick; int8 expert dequant shares its code shape with int4
    pytest.param("int8", marks=pytest.mark.slow),
    "int4",
])
def test_ep_stage_prefill_decode_parity(quant, devices):
    """Whole mixtral stage E-sliced over ep=2: prefill logits match the
    single-device forward; one decode step on the sharded cache works.
    int8 AND packed int4 expert stacks slice over ep (the E axis is
    orthogonal to int4's packed input axis)."""
    name = "mixtral-test" + (f"-{quant}" if quant else "")
    cfg = get_model_config(name).replace(moe_capacity_factor=8.0)
    params = init_full_params(jax.random.PRNGKey(0), cfg, quantize=quant)
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    b, plen = 2, 8
    ids = (jnp.arange(b * plen, dtype=jnp.int32).reshape(b, plen)
           % cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(plen), (b, plen))

    ref, _ = stage_forward(params, cfg, spec, ids,
                           KVCache.create(cfg, cfg.num_layers, b, 32), pos)

    mesh = make_mesh(MeshConfig(ep=2), devices)
    with mesh:
        fn = make_ep_stage_fn(cfg, spec, mesh, params)
        out, cache = fn(params, ids,
                        KVCache.create(cfg, cfg.num_layers, b, 32), pos)
        nxt = jnp.argmax(out[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        out2, cache = fn(params, nxt, cache, jnp.full((b, 1), plen))
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(out, np.float32),
                               rtol=3e-4, atol=3e-4)
    assert int(cache.length) == plen + 1
    assert np.isfinite(np.asarray(out2, np.float32)).all()


def test_ep_rejects_bad_configs(devices):
    mesh = make_mesh(MeshConfig(ep=2), devices)
    dense_cfg = get_model_config("llama-test")
    with pytest.raises(ValueError, match="MoE"):
        make_ep_stage_fn(dense_cfg, StageSpec(0, 1, 0, 4), mesh,
                         init_full_params(jax.random.PRNGKey(0), dense_cfg))
    moe_cfg = get_model_config("mixtral-test").replace(num_experts=3)
    with pytest.raises(ValueError, match="divisible"):
        make_ep_stage_fn(moe_cfg, StageSpec(0, 1, 0, 2), mesh,
                         init_full_params(jax.random.PRNGKey(1), moe_cfg))
