"""Analytic per-layer cost model: FLOPs, parameter bytes, activation bytes.

The reference's (missing) ``ModelCard.prepare_optimization_info`` computed
per-module FLOPs / memory / output-size maps for the planner
(``server.py:834-835``, SURVEY.md §2.2).  Here the same quantities come from
the architecture description (ModelConfig) analytically — no probe model or
ONNX export needed, and the numbers are exact for the decoder math we run.

Conventions:
- FLOPs are per generated token (decode step, batch 1, KV-cached attention
  over ``ctx`` cached positions).  Multiply by batch for batched decode;
  prefill FLOPs are per prompt token with ``ctx`` ≈ seq/2 on average.
- Bytes are weight-resident bytes (what must fit in device memory, before
  the 0.7 headroom factor the reference applies, ``server.py:860-862``).
- Activation bytes are what crosses a pipeline cut after the layer
  (hidden-state row per token), i.e. the wire payload between stages.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

from ..models.base import ModelConfig


@dataclass(frozen=True)
class LayerCost:
    flops: float           # per-token decode FLOPs
    param_bytes: int       # resident weight bytes
    act_bytes: int         # activation bytes crossing a cut after this layer
    kv_bytes_per_tok: int  # KV-cache growth per token (resident, per layer)


@dataclass(frozen=True)
class ModelCostProfile:
    """Costs for embedding, each decoder layer, and the head."""

    embed: LayerCost
    layers: List[LayerCost]
    head: LayerCost
    dtype_bytes: int

    @property
    def total_param_bytes(self) -> int:
        return (self.embed.param_bytes + self.head.param_bytes
                + sum(c.param_bytes for c in self.layers))

    @property
    def total_flops(self) -> float:
        return (self.embed.flops + self.head.flops
                + sum(c.flops for c in self.layers))


def _dtype_bytes(cfg: ModelConfig) -> float:
    if cfg.quantization == "int8":
        return 1
    if cfg.quantization == "int4":
        # nibble-packed weights + f32 group scales
        # (ops/quant.DEFAULT_INT4_GROUP) — mis-costing int4 at float
        # width would make the planner reject placements that fit
        from ..ops.quant import DEFAULT_INT4_GROUP
        return 0.5 + 4.0 / DEFAULT_INT4_GROUP
    return {"float32": 4, "bfloat16": 2, "float16": 2}.get(cfg.dtype_name, 2)


def model_cost_profile(cfg: ModelConfig, ctx: int = 1024) -> ModelCostProfile:
    """Cost profile at a representative KV context length ``ctx``."""
    h = cfg.hidden_size
    inter = cfg.intermediate_size
    kvh = cfg.num_kv_heads
    hd = cfg.head_dim
    wb = _dtype_bytes(cfg)
    act = 2 * h  # bf16 hidden row on the wire per token

    # attention weights: q (h * nh*hd), k,v (h * kvh*hd each),
    # o (nh*hd * h) — nh*hd != h when head_dim is decoupled (gemma)
    qo = h * cfg.num_heads * hd
    attn_params = qo + 2 * h * kvh * hd + qo
    # mlp weights: 2 matrices for bloom's dense GELU MLP, 3 for every
    # gated family (llama/qwen2/gemma SwiGLU-or-GeGLU, mixtral / olmoe
    # experts) — mirrors decoder._mlp's branch; an MoE layer adds its
    # router (H x E, never quantized) and pays k experts a token, which is
    # what the routed layer (decoder._moe_routed) computes.  What one
    # decode step READS is more: every expert some row touched
    gated = cfg.family != "bloom"
    mlp_params_dense = (3 if gated else 2) * h * inter
    if cfg.num_experts > 0:
        mlp_params = cfg.num_experts * mlp_params_dense + h * cfg.num_experts
        # only experts_per_token experts run per token
        mlp_flops = 2 * cfg.experts_per_token * mlp_params_dense \
            + 2 * h * cfg.num_experts
    else:
        mlp_params = mlp_params_dense
        mlp_flops = 2 * mlp_params_dense
    norm_params = 2 * h * (2 if cfg.attn_layernorm else 1)
    if cfg.qk_norm:  # olmoe: RMSNorm weights over the q and k projections
        norm_params += (cfg.num_heads + kvh) * hd

    # decode-step attention FLOPs: projections + scores/values over ctx
    attn_flops = 2 * attn_params + 2 * 2 * cfg.num_heads * hd * ctx

    layer = LayerCost(
        flops=float(attn_flops + mlp_flops),
        param_bytes=(attn_params + mlp_params + norm_params) * wb,
        act_bytes=act,
        kv_bytes_per_tok=2 * kvh * hd * 2,   # k+v, bf16
    )
    embed = LayerCost(
        flops=0.0,  # gather
        param_bytes=cfg.vocab_size * h * wb,
        act_bytes=act,
        kv_bytes_per_tok=0,
    )
    head = LayerCost(
        flops=float(2 * h * cfg.vocab_size),
        param_bytes=(0 if cfg.tie_embeddings else cfg.vocab_size * h * wb)
        + h * wb,
        act_bytes=4,  # a sampled token id
        kv_bytes_per_tok=0,
    )
    return ModelCostProfile(embed=embed, layers=[layer] * cfg.num_layers,
                            head=head, dtype_bytes=wb)
