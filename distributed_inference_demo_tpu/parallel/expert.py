"""Expert-parallel MoE stage execution over the ``ep`` mesh axis.

BASELINE.json config #4: "Mixtral-8x7B MoE: per-expert shard placement,
router on the server, experts as TPU clients" — done the TPU way (the
reference's closest concept is per-device module placement,
``/root/reference/server.py:893-905``): expert weights live E-sliced over
``ep`` (each chip holds ``E/ep`` experts), tokens are data-parallel over
the same axis, and ``decoder._moe_mlp_ep`` routes tokens to expert owners
with GShard-style capacity dispatch + ``all_to_all`` (PAPERS.md: GShard).

Everything that is not an expert weight — attention, norms, router,
embed/head — runs data-parallel over ``ep`` with replicated weights, so
the only cross-chip traffic is the two all_to_alls per MoE layer.
"""

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.base import KVCache, ModelConfig, StageParams, StageSpec
from ..models.decoder import stage_forward
from ..ops.quant import QuantizedArray, QuantizedArray4
from .sharding import quant4_specs, quant_scale_spec

# expert stacks [L, E, H, I]: shard E over ep; everything else replicated
_EP_LAYER_SPECS = {
    "w_gate": P(None, "ep", None, None),
    "w_up": P(None, "ep", None, None),
    "w_down": P(None, "ep", None, None),
}

# tokens are data-parallel over ep: batch-shard the cache
# [layers, batch, nkv, seq, hd]
_CACHE_SPEC = KVCache(keys=P(None, "ep", None, None, None),
                      values=P(None, "ep", None, None, None),
                      length=P())


def _ep_param_specs(params: StageParams) -> StageParams:
    def map_layers(layers):
        out = {}
        for k, v in layers.items():
            spec = _EP_LAYER_SPECS.get(k, P())
            if isinstance(v, QuantizedArray):
                out[k] = QuantizedArray(q=spec, scale=quant_scale_spec(spec))
            elif isinstance(v, QuantizedArray4):
                # ep slices the EXPERT axis; int4 packing lives on the
                # input axis (-2), so the two compose (quant4_specs
                # rejects only tp, which cuts the packed axis itself)
                out[k] = quant4_specs(v, spec)
            else:
                out[k] = spec
        return out

    rep = lambda d: None if d is None else {k: P() for k in d}
    return StageParams(layers=map_layers(params.layers),
                       embed=rep(params.embed),
                       final_norm=rep(params.final_norm),
                       lm_head=rep(params.lm_head))


def make_ep_stage_fn(cfg: ModelConfig, spec: StageSpec, mesh: Mesh,
                     params_template: StageParams):
    """Jitted fn(params, inputs, cache, positions) -> (out, cache) with
    expert weights E-sliced over ``ep`` and the batch data-parallel over it.

    Requires ``cfg.num_experts % ep == 0`` and ``batch % ep == 0``.
    Outputs come back batch-sharded (matching the inputs); the caller sees
    globally-shaped arrays either way.
    """
    ep = mesh.shape["ep"]
    if cfg.num_experts == 0:
        raise ValueError("expert parallelism needs a MoE config "
                         "(num_experts > 0)")
    if cfg.num_experts % ep:
        raise ValueError(
            f"num_experts={cfg.num_experts} not divisible by ep={ep}")

    p_specs = _ep_param_specs(params_template)
    data = P("ep")  # batch axis of ids/hidden/positions/logits

    def body(p, i, c, pos):
        return stage_forward(p, cfg, spec, i, c, pos, ep_axis="ep")

    def fn(params, inputs, cache, positions):
        if inputs.shape[0] % ep:
            raise ValueError(
                f"batch={inputs.shape[0]} not divisible by ep={ep} "
                "(tokens are data-parallel over the ep axis)")
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, data, _CACHE_SPEC, data),
            out_specs=(data, _CACHE_SPEC),
            check_vma=False,
        )(params, inputs, cache, positions)

    return jax.jit(fn, donate_argnums=(2,))
