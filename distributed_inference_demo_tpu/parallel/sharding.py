"""Sharding rules: where every parameter and activation lives on the mesh.

GSPMD path: annotate params with NamedSharding and let XLA insert the
collectives (all-gather for column-parallel outputs, reduce-scatter for
row-parallel) — the "pick a mesh, annotate, let XLA do the rest" recipe.
The manual shard_map pipeline (parallel/pipeline.py) slices the same layout.

Megatron-style TP layout:
- wq/wk/wv  [L, H, heads*hd]   -> shard last axis over tp (column parallel)
- wo        [L, heads*hd, H]   -> shard first non-L axis over tp (row parallel)
- w_gate/up [L, H, I]          -> column parallel
- w_down    [L, I, H]          -> row parallel
- MoE experts [L, E, H, I]     -> shard E over tp (expert parallelism: a
                                  rank's grouped matmul runs its local
                                  experts' groups, outputs psum)
- embed [V, H] / lm_head [H, V]-> shard V over tp (vocab parallel); logits
                                  all-gather only at the sampling boundary
- KV cache [Ls, B, S, nkv, hd] -> batch over dp, kv heads over tp, seq over sp
"""

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.base import ModelConfig, StageParams
from ..ops.quant import QuantizedArray, QuantizedArray4


# per-key PartitionSpec for the stacked layer dict; None entries = replicated
_LAYER_SPECS = {
    "attn_norm_w": P(),
    "attn_norm_b": P(),
    "mlp_norm_w": P(),
    "mlp_norm_b": P(),
    "wq": P(None, None, "tp"),
    "wk": P(None, None, "tp"),
    "wv": P(None, None, "tp"),
    "bq": P(None, "tp"),
    "bk": P(None, "tp"),
    "bv": P(None, "tp"),
    # olmoe's q/k RMSNorm weights span the projection's columns and are
    # sliced with them (decoder._whole_row_rms_norm psums the squares)
    "q_norm_w": P(None, "tp"),
    "k_norm_w": P(None, "tp"),
    "wo": P(None, "tp", None),
    "bo": P(),
    "w_gate": P(None, None, "tp"),
    "w_up": P(None, None, "tp"),
    "b_up": P(None, "tp"),
    "w_down": P(None, "tp", None),
    "b_down": P(),
    "router": P(),
}

# MoE expert stacks carry an extra E axis at position 1: shard experts.
_MOE_SPECS = {
    "w_gate": P(None, "tp", None, None),
    "w_up": P(None, "tp", None, None),
    "w_down": P(None, "tp", None, None),
}


def layer_spec(key: str, cfg: ModelConfig, pp_shard: bool = False) -> P:
    """PartitionSpec for one stacked-layer weight.  ``pp_shard`` additionally
    splits the leading layer axis over pp (SPMD pipeline layout)."""
    if cfg.num_experts > 0 and key in _MOE_SPECS:
        spec = _MOE_SPECS[key]
    else:
        spec = _LAYER_SPECS.get(key, P())
    if pp_shard:
        spec = P("pp", *spec[1:]) if len(spec) > 0 else P("pp")
    return spec


def _embed_specs(cfg: ModelConfig) -> dict:
    # vocab-parallel embedding: the gather masks out-of-shard ids and psums.
    specs = {"tokens": P("tp", None)}
    if cfg.family == "bloom":
        specs["norm_w"] = P()
        specs["norm_b"] = P()
    return specs


def quant4_specs(v: QuantizedArray4, spec: P):
    """Spec tree for a packed-int4 weight given its dense spec.

    Nibble packing only changes SIZES along the input axis, so ``q``
    inherits the dense spec unchanged; the group-wise scale inserts a
    broadcast axis before the output axis (shape ``(..., in/g, 1,
    out)``) and its group axis stays replicated.  Slicing the input or
    output axes themselves (tp) would cut through nibble pairs and
    group boundaries — callers must reject tp before calling."""
    if any(s == "tp" for s in spec):
        raise ValueError(
            "int4 (nibble-packed) weights do not compose with tp meshes "
            "yet — tensor-parallel slicing would cut through the packed "
            "input axis; use int8 for tensor-parallel serving")
    scale = P(*spec[:-2], None, None, spec[-1]) if len(spec) >= 2 else P()
    return QuantizedArray4(q=spec, scale=scale, group=v.group)


def quant_scale_spec(q_spec: P) -> P:
    """Scale spec matching ``quantize_array``'s layout.

    The scale has the q array's shape with the input axis (-2) collapsed
    to 1, so it inherits every axis of the q array's sharding but that
    one (a row-parallel ``wo`` shards its input axis and its scales stay
    whole; an expert stack shards E and its scales shard with it).
    """
    if len(q_spec) < 2:
        return q_spec
    return P(*q_spec[:-2], None, q_spec[-1])


def stage_param_spec_tree(params: StageParams, cfg: ModelConfig, *,
                          pp_shard: bool = False, use_tp: bool = True,
                          vocab_parallel_embed: bool = False) -> StageParams:
    """Raw PartitionSpec tree for a params tree — the single source of truth
    shared by the GSPMD path (wrapped in NamedSharding below) and the manual
    shard_map paths (pipeline.py / tensor.py in_specs).

    ``use_tp=False`` strips tp from layer specs (pipeline-only meshes);
    ``vocab_parallel_embed`` shards the token table over tp (GSPMD path) vs
    replicating it (manual paths, which gather by id locally).
    """
    def strip_tp(spec):
        return P(*(s if s == "pp" else None for s in spec))

    def map_layers(layers):
        out = {}
        for k, v in layers.items():
            spec = layer_spec(k, cfg, pp_shard)
            if not use_tp:
                spec = strip_tp(spec)
            if isinstance(v, QuantizedArray):
                out[k] = QuantizedArray(q=spec, scale=quant_scale_spec(spec))
            elif isinstance(v, QuantizedArray4):
                out[k] = quant4_specs(v, spec)
            else:
                out[k] = spec
        return out

    embed = None
    if params.embed is not None:
        if vocab_parallel_embed and use_tp:
            embed = {k: s for k, s in _embed_specs(cfg).items()
                     if k in params.embed}
        else:
            embed = {k: P() for k in params.embed}
    final_norm = None
    if params.final_norm is not None:
        final_norm = {k: P() for k in params.final_norm}
    lm_head = None
    if params.lm_head is not None:
        lm_head = {k: (P(None, "tp") if use_tp else P())
                   for k in params.lm_head}
    return StageParams(layers=map_layers(params.layers), embed=embed,
                       final_norm=final_norm, lm_head=lm_head)


def param_shardings(params: StageParams, cfg: ModelConfig, mesh: Mesh,
                    pp_shard: bool = False) -> StageParams:
    """Alias for :func:`stage_param_shardings` (full model == stage 0 of 1)."""
    return stage_param_shardings(params, cfg, mesh, pp_shard)


def stage_param_shardings(params: StageParams, cfg: ModelConfig, mesh: Mesh,
                          pp_shard: bool = False,
                          vocab_parallel_embed: bool = True) -> StageParams:
    """NamedShardings matching an actual params tree (GSPMD placement)."""
    specs = stage_param_spec_tree(
        params, cfg, pp_shard=pp_shard,
        vocab_parallel_embed=vocab_parallel_embed)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(params: StageParams, cfg: ModelConfig, mesh: Mesh,
                 pp_shard: bool = False,
                 vocab_parallel_embed: bool = True) -> StageParams:
    """Place a host-resident params tree onto the mesh."""
    shardings = stage_param_shardings(params, cfg, mesh, pp_shard,
                                      vocab_parallel_embed)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), params, shardings)


def cache_shardings(mesh: Mesh, shard_heads: bool = True,
                    shard_seq: bool = False):
    """NamedShardings for KVCache (keys/values/length).

    [layers, batch, kv_heads, seq, head_dim] (head-major): batch over dp,
    kv heads over tp (requires num_kv_heads % tp == 0), seq over sp for
    long-context.
    """
    from ..models.base import KVCache
    kv = P(None, "dp", "tp" if shard_heads else None,
           "sp" if shard_seq else None, None)
    return KVCache(keys=NamedSharding(mesh, kv),
                   values=NamedSharding(mesh, kv),
                   length=NamedSharding(mesh, P()))
