"""One unified decoder implementation for every supported model family.

Instead of per-family ONNX exports (reference ``util.model_card.ModelCard``,
inferred at SURVEY.md §2.2), a single pure ``stage_forward`` covers:

- **llama family** (TinyLlama-1.1B, Llama-3-8B): RMSNorm, RoPE, GQA, SwiGLU.
- **bloom family** (bloom560m..7b1, reference ``data/Data.kt:19-33``):
  LayerNorm+bias, ALiBi, fused dense MLP with GELU.
- **mixtral family** (Mixtral-8x7B): llama blocks with top-k routed MoE MLP.
- **olmoe family** (OLMoE-1B-7B): the same routed MLP with the router's
  probabilities kept as they are, and RMSNorm over the q and k projections.
- **ouro family** (Ouro-2.6B): llama blocks that also norm each sublayer's
  output (``sandwich_norm``), the whole stack run ``ut_steps`` times a
  token with the final norm after every pass and K/V planes of each pass's
  own.
- **evabyte family** (EvaByte-6.5B): llama blocks whose RMSNorm gain is
  ``1 + w``, a float32 residual stream and float32 logits, and EVA
  attention: the exact keys of the query's own window and a learned-pooled
  summary a chunk of every earlier window, in one softmax
  (``ops.eva_attention``); the head holds ``num_pred_heads`` heads and the
  served path reads the first.
- **deepseek_v3 family** (kanana-2-30b-a3b): multi-head latent attention
  (a token's cache is one latent row a layer, read in absorbed form:
  ``ops.latent_attention``), leading dense blocks before the repeated
  expert blocks, a sigmoid router with a selection bias and a scale, and
  shared experts beside the routed ones.
- **xing4_0 family** (Xing4.0-29B-A4B): deepseek_v3's blocks with a
  low-rank query (``q_lora_rank``: ``wq_a``, a norm, ``wq``), YaRN on the
  latent head's rope lanes with the softmax scale that goes with it, and
  a changed residual path: ``hc_streams`` residual streams a token, read,
  written and mixed by three learned maps a sublayer
  (``ops.hyper_connection``); the embedding replicates, the final norm
  reads the streams' sum.

- **nemotron_h family** (Nemotron-3-Nano-30B-A3B): blocks of ONE sublayer
  each (``BlockKind.mlp`` / ``attn == "none"``; docs/DESIGN.md section
  31): a Mamba-2 mixer whose B, C and gated norm go by GROUPS of heads, a
  NoPE GQA attention, or the experts, which are of two matrices,
  ``down(relu(up h) ** 2)`` (``mlp_act == "relu2"``), beside a shared one.

The per-stage forward is a single ``lax.scan`` over stacked layer weights —
XLA compiles one loop body reused across layers, keeping compile time flat in
depth and the MXU saturated.  The KV cache threads through the scan as
per-layer xs/ys so each layer updates its slice functionally.
"""

import contextlib
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import alibi_slopes, attention, update_kv_cache
from ..ops.grouped_matmul import grouped_matmul
from ..ops.quant import dense
from ..ops.stacked import LayerOf
from ..ops.norms import layer_norm, rms_norm
from ..ops.eva_attention import eva_dense_attn
from ..ops import hyper_connection as hc_ops
from ..ops import kda as kda_ops
from ..ops import ssd as ssd_ops
from ..ops.latent_attention import latent_dense_attn
from ..ops.paged_attention import join_rows, split_rows
from ..ops.rope import (apply_rope, apply_rope_interleaved,
                        apply_rope_kind)
from .base import (KVCache, ModelConfig, StageParams, StageSpec,
                   require_one_kind, require_one_stream,
                   require_single_pass, require_token_rows)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("shape", "dtype"))
def _dense_init_jit(rng, scale, shape, dtype):
    # f32 sampling + scale + convert fuse into one XLA kernel under jit:
    # only the target-dtype output is ever materialized in HBM.
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def _dense_init(rng, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else float(fan_in) ** -0.5
    return _dense_init_jit(rng, jnp.float32(scale), tuple(shape),
                           jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("shape", "dtype", "mode"))
def _init_quantized_layer(rng, scale, shape, dtype, mode="int8"):
    from ..ops.quant import quantize_array, quantize_array4
    w = _dense_init_jit(rng, scale, shape, dtype)
    if mode == "int4":
        qa = quantize_array4(w)
        return qa.q, qa.scale
    qa = quantize_array(w)
    return qa.q, qa.scale


def _init_quantized(rng, shape, dtype, scale=None, mode="int8"):
    """Init + quantize (int8 or int4) one layer slice at a time.

    Peak HBM stays at the accumulating quantized footprint plus ONE
    layer's float transient — never the full tensor at float width.
    This is what lets an int8 Llama-3-8B be random-initialized on a
    16 GB chip whose bf16 variant would not fit (the reference ships
    pre-quantized exports instead, ``data/Data.kt:19-33``); int4 halves
    the footprint again.
    """
    from ..ops.quant import QuantizedArray, QuantizedArray4
    L = shape[0]
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = jnp.float32(scale if scale is not None else float(fan_in) ** -0.5)
    keys = jax.random.split(rng, L)
    qs, scales = [], []
    for i in range(L):
        q, s = _init_quantized_layer(keys[i], scale, tuple(shape[1:]),
                                     jnp.dtype(dtype), mode)
        qs.append(q)
        scales.append(s)
    if mode == "int4":
        from ..ops.quant import int4_group_for
        return QuantizedArray4(q=jnp.stack(qs), scale=jnp.stack(scales),
                               group=int4_group_for(shape[-2]))
    return QuantizedArray(q=jnp.stack(qs), scale=jnp.stack(scales))


def init_layer_params(rng: jax.Array, cfg: ModelConfig, num_layers: int,
                      quantize=False) -> dict:
    """Stacked per-layer weights, leading dim = num_layers.

    With ``quantize`` (True = "int8", or an explicit "int8"/"int4"
    mode), each big matmul operand is generated and quantized
    layer-by-layer (``_init_quantized``), so peak memory stays near the
    quantized footprint instead of materializing the whole tensor at
    the float dtype first — this is what lets an int8 8B model be
    random-initialized on a chip the bf16 variant would not fit on
    (int4 halves it again).
    """
    H, nh, nkv, hd = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    I, L = cfg.intermediate_size, num_layers
    dt = cfg.dtype

    mode = "int8" if quantize is True else quantize
    big = (partial(_init_quantized, mode=mode) if mode else _dense_init)

    keys = jax.random.split(rng, 16)
    kind = cfg.block_kind
    has_mlp = kind is None or kind.mlp
    if kind is not None and kind.attn == "none":
        p = {"mlp_norm_w": jnp.ones((L, H), dt)}    # no mixer, no cache
    elif kind is not None and kind.attn == "kda":
        # a gated delta-rule block (``_kda_mixer``): q, k and v of every
        # head (no grouped keys), the short convolution's taps, the two
        # low-rank gates (inner width = the head's), beta, a head's own
        # output norm.  ``A_log`` a head and ``dt_bias`` a channel are
        # seeded as the published initialiser does (A uniform in [1, 16],
        # dt log-uniform in [1e-3, 0.1] through the inverse softplus); the
        # gate's bias at N(0, 0.1), not zero, so that a path that dropped
        # it cannot pass for one that has it
        D, ks = nh * hd, jax.random.split(keys[14], 8)
        step = jnp.exp(jax.random.uniform(ks[0], (L, D), jnp.float32,
                                          jnp.log(1e-3), jnp.log(0.1)))
        p = {
            "attn_norm_w": jnp.ones((L, H), cfg.dtype),
            "wq": big(keys[0], (L, H, D), cfg.dtype),
            "wk": big(keys[1], (L, H, D), cfg.dtype),
            "wv": big(keys[2], (L, H, D), cfg.dtype),
            "conv_w": _dense_init(ks[1], (L, kind.conv, 3 * D), cfg.dtype),
            "wf_dn": _dense_init(ks[2], (L, H, hd), cfg.dtype),
            "wf_up": _dense_init(ks[3], (L, hd, D), cfg.dtype),
            "A_log": jnp.log(jax.random.uniform(ks[4], (L, nh), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "wb": _dense_init(ks[5], (L, H, nh), cfg.dtype),
            "wg_dn": _dense_init(ks[6], (L, H, hd), cfg.dtype),
            "wg_up": _dense_init(ks[7], (L, hd, D), cfg.dtype),
            "bg": _dense_init(keys[15], (L, D), cfg.dtype, scale=0.1),
            "o_norm_w": jnp.ones((L, hd), cfg.dtype),
            "wo": big(keys[3], (L, D, H), cfg.dtype),
            "mlp_norm_w": jnp.ones((L, H), cfg.dtype),
        }
    elif kind is not None and kind.attn == "ssd":
        # a Mamba-2 block (``_ssd_mixer``): ONE in-projection ``z | x B C |
        # dt``, the convolution's taps AND bias over the ``x B C``
        # channels, a head's ``A_log``, ``D`` and ``dt_bias``, the gated
        # norm over all of ``d_inner``, the out-projection as ``wo``.
        # Seeded as the mamba_ssm initialiser does (A uniform in [1, 16],
        # dt log-uniform in [1e-3, 0.1] through the inverse softplus, D at
        # 1); the convolution's bias at N(0, 0.1), not zero, so that a
        # path that dropped it cannot pass for one that has it
        sh, P = kind.state_heads, kind.state_head_dim
        D, GN = sh * P, kind.groups * kind.state_size
        ks = jax.random.split(keys[14], 4)
        step = jnp.exp(jax.random.uniform(ks[0], (L, sh), jnp.float32,
                                          jnp.log(1e-3), jnp.log(0.1)))
        p = {
            "attn_norm_w": jnp.ones((L, H), dt),
            "w_in": big(keys[0], (L, H, 2 * D + 2 * GN + sh), dt),
            "conv_w": _dense_init(ks[1], (L, kind.conv, D + 2 * GN), dt),
            "conv_b": _dense_init(ks[2], (L, D + 2 * GN), dt, scale=0.1),
            "A_log": jnp.log(jax.random.uniform(ks[3], (L, sh), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((L, sh), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssd_norm_w": jnp.ones((L, D), dt),
            "wo": big(keys[3], (L, D, H), dt),
            "mlp_norm_w": jnp.ones((L, H), dt),
        }
    elif kind is not None and kind.attn == "lightning":
        # a Lightning linear-attention block (``_lightning_mixer``): q, k
        # and v of every head (no grouped keys), a head's own output norm
        # with a weight a channel; the decay is a constant a head and no
        # leaf (``ops.ssd.lightning_log_decay``); the gate's ``wg`` below
        D = nh * hd
        p = {
            "attn_norm_w": jnp.ones((L, H), dt),
            "wq": big(keys[0], (L, H, D), dt),
            "wk": big(keys[1], (L, H, D), dt),
            "wv": big(keys[2], (L, H, D), dt),
            "o_norm_w": jnp.ones((L, D), dt),
            "wo": big(keys[3], (L, D, H), dt),
            "mlp_norm_w": jnp.ones((L, H), dt),
        }
    elif cfg.latent_kv:
        # deepseek_v3: q in one matrix, or with ``q_lora_rank`` in two
        # around a norm (``wq_a`` -> ``q_a_norm_w`` -> ``wq``); the latent
        # and the shared rope key from ``wkv_a``, and ``kv_b`` kept as its
        # two halves a head, laid out for the absorbed form: ``w_uk[i]`` =
        # W_UK_i^T (q_nope_i -> latent), ``w_uv[i]`` = W_UV_i (latent ->
        # v_i).  Seeded at fan-in ** -0.5 like every other matrix
        dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
        rq = cfg.q_lora_rank
        # Under a softmax scale with a factor of its own (YaRN's mscale
        # ** 2, ``attn_scale``) the query's matrix is seeded at 1 /
        # attn_scale of the fan-in scale: the seeded scores then spread as
        # they do without the factor (std ~1).  At the fan-in scale they
        # spread twice as wide at xing4.0's 2.005, the softmax is that
        # much sharper, and the bf16 rounding of the absorbed query and of
        # the cached latent moves a log-probability twice as far: 0.127-
        # 0.158 against the float32 reference where kanana reads 0.07,
        # 0.069 with the factor taken out of both sides, and 0.121 with a
        # float32 stream, which is not where it comes from (on the chip,
        # 2 + 5 blocks; my chip runs, PR 60).  A trained checkpoint's
        # scores have the factor trained in and bring their own weights
        q_scale = (float(rq or H) ** -0.5 / cfg.attn_scale
                   if cfg.attn_scale != 1.0 else None)
        p = {
            "attn_norm_w": jnp.ones((L, H), dt),
            "wq": big(keys[0], (L, rq or H, nh * (dn + dr)), dt,
                      scale=q_scale),
            "wkv_a": _dense_init(keys[1], (L, H, r + dr), dt),
            "kv_norm_w": jnp.ones((L, r), dt),
            "w_uk": _dense_init(keys[2], (L, nh, dn, r), dt, scale=r ** -0.5),
            "w_uv": _dense_init(keys[8], (L, nh, r, dv), dt),
            "wo": big(keys[3], (L, nh * dv, H), dt),
            "mlp_norm_w": jnp.ones((L, H), dt),
        }
        if rq:
            p["wq_a"] = _dense_init(jax.random.fold_in(keys[0], 1),
                                    (L, H, rq), dt)
            p["q_a_norm_w"] = jnp.ones((L, rq), dt)
    else:
        p = {
            "attn_norm_w": jnp.ones((L, H), dt),
            "wq": big(keys[0], (L, H, nh * hd), dt),
            "wk": big(keys[1], (L, H, nkv * hd), dt),
            "wv": big(keys[2], (L, H, nkv * hd), dt),
            "wo": big(keys[3], (L, nh * hd, H), dt),
            "mlp_norm_w": jnp.ones((L, H), dt),
        }
    if not has_mlp:     # a block of the mixer alone: one norm, no MLP leaf
        del p["mlp_norm_w"]
    if kind is not None and kind.gate == "per-head":
        # one scalar a head from the block's normed input (``_kv_attention``)
        p["wg"] = _dense_init(keys[13], (L, H, nh), dt)
    if kind is not None and kind.gate == "elementwise":
        p["wg"] = big(keys[13], (L, H, nh * hd), dt)    # one a channel
    if cfg.norm_unit_offset:
        # the stored weight is the gain's OFFSET (the gain is 1 + w).
        # Seeded at N(0, 0.1) and not at the published zero, so that a
        # norm that forgot the offset cannot pass for one that has it
        for j, leaf in enumerate(("attn_norm_w", "mlp_norm_w")):
            if leaf in p:
                p[leaf] = _dense_init(keys[14 + j], (L, H), dt, scale=0.1)
    if cfg.summary_kv:
        # EVA's two learned pooling vectors a kv head, as published:
        # clip(N(0, 1), +-1) x head_dim ** -0.5
        k_mu, k_phi = jax.random.split(jax.random.fold_in(rng, 17))
        vec = lambda k: (jnp.clip(jax.random.normal(
            k, (L, nkv, hd), jnp.float32), -1.0, 1.0)
            * hd ** -0.5).astype(dt)
        p["adaptive_mu_k"], p["adaptive_phi"] = vec(k_mu), vec(k_phi)
    if cfg.hc_streams:
        # the three maps of each sublayer (``ops.hyper_connection``), all
        # float32.  ``phi`` at N(0, 1 / nH): a token's 2n + n^2 raw
        # coefficients are N(0, 1).  ``alpha`` (0.5, 0.5, 0.25) and ``b``
        # N(0, 0.5) for the read and write maps, 1.5 I + N(0, 0.25) for the
        # mixing map: on the seeded model h_pre and h_post then spread
        # over ~0.4 from token to token and the doubly-stochastic map
        # stands ~0.15 an entry from the uniform map and ~0.2 from the
        # identity, so a path that left a map out, or held it constant,
        # cannot pass for one that has it; and its second singular value
        # (~0.5) lets 20 Sinkhorn steps reach 1e-6 where one leaves a
        # column 5 % (at worst 15-20 %) from 1
        n, maps = cfg.hc_streams, cfg.hc_maps
        sd = jnp.asarray([0.5] * (2 * n) + [0.25] * (n * n), jnp.float32)
        mean = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                                1.5 * jnp.eye(n).reshape(-1)])
        for j, sub in enumerate(("attn", "mlp")):
            k_phi, k_b = jax.random.split(jax.random.fold_in(rng, 23 + j))
            p[f"hc_{sub}_phi"] = _dense_init(
                k_phi, (L, maps, n * H), jnp.float32,
                scale=float(n * H) ** -0.5)
            p[f"hc_{sub}_alpha"] = jnp.tile(
                jnp.asarray([0.5, 0.5, 0.25], jnp.float32), (L, 1))
            p[f"hc_{sub}_b"] = mean + sd * jax.random.normal(
                k_b, (L, maps), jnp.float32)
    if cfg.attn_layernorm:  # bloom: LayerNorm has bias; linears have bias
        p["attn_norm_b"] = jnp.zeros((L, H), dt)
        p["mlp_norm_b"] = jnp.zeros((L, H), dt)
        p["bo"] = jnp.zeros((L, H), dt)
    if cfg.attn_layernorm or cfg.attn_qkv_bias:  # + qwen2: qkv-only bias
        p["bq"] = jnp.zeros((L, nh * hd), dt)
        p["bk"] = jnp.zeros((L, nkv * hd), dt)
        p["bv"] = jnp.zeros((L, nkv * hd), dt)
    if cfg.qk_norm:  # olmoe: RMSNorm over the whole q / k projection
        p["q_norm_w"] = jnp.ones((L, nh * hd), dt)
        p["k_norm_w"] = jnp.ones((L, nkv * hd), dt)
    if kind is not None and kind.qk_norm:
        # RMSNorm a HEAD, one weight ``[head_dim]`` for all heads.  Seeded
        # at 1 + N(0, 0.1), not at one, so that a path that dropped the
        # weight cannot pass for one that has it
        for j, leaf in enumerate(("q_norm_w", "k_norm_w")):
            p[leaf] = 1 + _dense_init(jax.random.fold_in(keys[15], 31 + j),
                                      (L, hd), dt, scale=0.1)
    if cfg.sandwich_norm:
        # ouro: RMSNorm on each sublayer's output.  Seeded at (2 L)^-1/2
        # and not at one: a pass's 2 L normed outputs then sum to about
        # the size of the stream that entered it.  At one they bury it
        # (the stream grows tenfold a pass at L = 48) and the seeded
        # loop amplifies any difference in its input about 2.5 times a
        # pass (bf16 rounding read against float32, on the chip, after
        # 1 / 2 / 3 / 4 passes: 0.086 / 0.089 / 0.218 / 0.579, PR 34),
        # which no model trained to refine one state over its passes
        # does.  A loaded checkpoint brings its own.
        gain = (2 * cfg.num_layers) ** -0.5
        p["attn_post_norm_w"] = jnp.full((L, H), gain, dt)
        p["mlp_post_norm_w"] = jnp.full((L, H), gain, dt)
    # experts of two matrices, ``down(relu(up h) ** 2)``: no gate projection
    gated = cfg.mlp_act != "relu2"
    if not has_mlp:
        return p
    if cfg.num_experts > 0:  # mixtral / olmoe MoE
        # the router scores every expert; the stacks hold this chip's
        # share of them (``experts_held``: all, for every model but one
        # cut to a deployment's share)
        E = cfg.experts_here
        p["router"] = _dense_init(keys[4], (L, H, cfg.num_experts), dt)
        if gated:
            p["w_gate"] = big(keys[5], (L, E, H, I), dt)
            p["w_up"] = big(keys[6], (L, E, H, I), dt)
        else:
            # stored TRANSPOSED, ``[I, H]`` an expert: the chip lays the
            # lane-filling dimension minor, and ``I`` need not be whole
            # lanes (``ops.grouped_matmul``'s ``transposed``)
            p["w_up_t"] = _dense_init(keys[6], (L, E, I, H), dt,
                                      scale=H ** -0.5)
        # Routed down projections under a sigmoid router (deepseek_v3)
        # are seeded at 1/32 of the fan-in scale.  There a token's k
        # weights are renormalised to sum to ``routed_scaling_factor``:
        # 0.41 an expert at kanana's 6 and 2.448, where olmoe's softmax
        # over 64 gives about 1/64.  Seeded experts are unrelated random
        # functions, so where the k-th and (k+1)-th scores nearly tie (a
        # few per cent of (token, layer) pairs: the spacing of 128
        # Gaussian order statistics against bf16 matmul noise) the served
        # path and the float32 reference swap a sixth of the routed sum
        # for another.  At the fan-in scale that read 0.03-0.66 on 16
        # canary tokens and a mean of 1.07 over the vocabulary against
        # olmoe's 0.056 (on the chip, lead + 7 layers; my chip run, PR
        # 44), and no precision of the ROUTER mends it: the noise is in
        # the rows it reads.  A trained checkpoint's routing is decisive
        # and brings its own weights; at 1/32 one seeded expert's share
        # of the stream (0.013) is what it is in olmoe's cell.  The same
        # holds under a softmax router whose k weights are renormalised
        # and scaled up (a period model's top-10 at 2.5: a quarter of the
        # routed sum an expert), and for a chip's share of a renormalised
        # top-k (granite's top-10 of 72, half of them held).
        # Experts of two matrices (nemotron_h) take 1/16, not 1/32: an
        # ``E`` block there is the experts and nothing else, and at 1/32
        # the held routed sum of one block lies under bfloat16's noise on
        # the stream (PERF.md section 7, PR 62 f: granite's last block's
        # experts moved no observable of the reply).  At 1/8 the last
        # block's held sum moved the emitted tokens' log-probabilities by
        # 0.033 in the mean against a sound 0.013-0.015, but a swapped
        # expert moved single tokens by 0.10-0.12 (3 of 396 positions past
        # 0.09, on the chip, my chip runs, PR 66), which the harness's 0.1
        # on a canary's sixteen tokens would meet once in some dozens of
        # runs; 1/16 halves both.
        p["w_down"] = big(keys[7], (L, E, I, H), dt,
                          scale=(I ** -0.5 / 16 if not gated
                                 else I ** -0.5 / 32
                                 if cfg.router_scoring == "sigmoid"
                                 or cfg.routed_scaling_factor > 1.0
                                 or (cfg.norm_topk_prob
                                     and cfg.experts_held)
                                 else None))
        if cfg.router_bias:
            # non-zero, so that choosing by score + bias and weighing by
            # the score differ; float32 like the scores it is added to
            # (over every expert the router scores, held here or not).
            # Experts of two matrices (nemotron_h) take N(0, 0.02): the
            # published bias is there to BALANCE the router, and at 0.1,
            # against a spread of ~0.1 among a token's best scores, the
            # seeded one sent a step's 192 held rows to 59-62 % of the
            # held experts where an even router touches 95 % (my chip
            # runs, PR 66); at 0.02 it still changes most of one choice in
            # six a token and leaves 93 % touched
            p["router_bias"] = (0.1 if gated else 0.02) * jax.random.normal(
                keys[9], (L, cfg.num_experts), jnp.float32)
        if cfg.num_shared_experts > 0:
            Is = cfg.num_shared_experts * I
            if gated:
                p["ws_gate"] = big(keys[10], (L, H, Is), dt)
            p["ws_up"] = big(keys[11], (L, H, Is), dt)
            p["ws_down"] = big(keys[12], (L, Is, H), dt)
    elif cfg.family == "bloom":  # dense 4H GELU MLP with bias
        p["w_up"] = big(keys[5], (L, H, I), dt)
        p["b_up"] = jnp.zeros((L, I), dt)
        p["w_down"] = big(keys[7], (L, I, H), dt)
        p["b_down"] = jnp.zeros((L, H), dt)
    else:  # llama SwiGLU
        p["w_gate"] = big(keys[5], (L, H, I), dt)
        p["w_up"] = big(keys[6], (L, H, I), dt)
        p["w_down"] = big(keys[7], (L, I, H), dt)
    return p


def lead_block_config(cfg: ModelConfig) -> ModelConfig:
    """The configuration of a LEADING dense block: the model's attention,
    and a dense SwiGLU of width ``lead_intermediate_size`` where the
    repeated stack has its experts."""
    if cfg.lead_kind is not None:       # a period model's leading kind
        cfg = cfg.of_kind(cfg.lead_kind)
    return cfg.replace(num_experts=0, num_shared_experts=0,
                       router_bias=False, experts_held=(),
                       intermediate_size=cfg.lead_intermediate_size)


def init_full_params(rng: jax.Array, cfg: ModelConfig,
                     quantize=False) -> StageParams:
    """Random-init full model as a single StageParams (stage 0 of 1).

    ``quantize=True`` resolves to the config's own quantization mode
    (int8 or int4), so ``get_model_config("x-int4")`` + ``quantize=True``
    does the right thing without every caller re-deriving the mode."""
    if quantize is True and cfg.quantization in ("int8", "int4"):
        quantize = cfg.quantization
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    lead = None
    if cfg.lead_dense_layers > 0:
        lead = init_layer_params(
            jax.random.fold_in(k_layers, 1), lead_block_config(cfg),
            cfg.lead_dense_layers, quantize=quantize)
    dt = cfg.dtype
    embed = {"tokens": _dense_init(k_emb, (cfg.vocab_size, cfg.hidden_size), dt,
                                   scale=0.02)}
    if cfg.family == "bloom":  # bloom applies LayerNorm right after embedding
        embed["norm_w"] = jnp.ones((cfg.hidden_size,), dt)
        embed["norm_b"] = jnp.zeros((cfg.hidden_size,), dt)
    final_norm = {"w": jnp.ones((cfg.hidden_size,), dt)}
    if cfg.norm_unit_offset:    # the gain's offset (``init_layer_params``)
        final_norm["w"] = _dense_init(jax.random.fold_in(k_head, 1),
                                      (cfg.hidden_size,), dt, scale=0.1)
    if cfg.attn_layernorm:
        final_norm["b"] = jnp.zeros((cfg.hidden_size,), dt)
    if cfg.tie_embeddings:
        lm_head = {}  # reuse embed["tokens"]
    else:
        # ``num_pred_heads`` heads side by side, the next token's first
        lm_head = {"w": _dense_init(
            k_head, (cfg.hidden_size,
                     cfg.num_pred_heads * cfg.vocab_size), dt)}
    if cfg.period:
        layers = init_period_params(k_layers, cfg, quantize=quantize)
    else:
        layers = init_layer_params(k_layers, cfg, cfg.num_layers,
                                   quantize=quantize)
    return StageParams(layers=layers, embed=embed, final_norm=final_norm,
                       lm_head=lm_head, lead=lead)


def init_period_params(rng: jax.Array, cfg: ModelConfig,
                       quantize=False) -> dict:
    """A period model's repeated stack: ONE stack a kind of block, every
    leaf of it named ``<leaf>.<kind name>`` (``ModelConfig.kinds``) and
    shaped ``[repeats, blocks of the kind in a period, ...]``.  The
    leading axis of every leaf is the repeat of the period, so a slice of
    the tree along it is whole periods (what a scan, a stage and the
    benchmark's reference index)."""
    R = cfg.num_layers
    out = {}
    for i, (name, kind, places) in enumerate(cfg.kinds):
        n = len(places)
        stack = init_layer_params(jax.random.fold_in(rng, 2 + i),
                                  cfg.of_kind(kind), R * n,
                                  quantize=quantize)
        # (a leaf leaves ``stack`` as it is reshaped: the reshape is a new
        # buffer, and a kind's stacks held twice do not fit the chip where
        # the kind is most of the model, granite's nine ssd blocks)
        for leaf in list(stack):
            out[f"{leaf}.{name}"] = jax.tree.map(
                lambda x: x.reshape((R, n) + x.shape[1:]), stack.pop(leaf))
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def embed_tokens(params: StageParams, cfg: ModelConfig,
                 ids: jnp.ndarray) -> jnp.ndarray:
    """Token ids -> [b, s, H] through the full embedding pipeline (table
    lookup + bloom's embedding LayerNorm).  The single source shared by the
    ids path of ``stage_forward`` and multimodal prefix construction."""
    x = params.embed["tokens"][ids]
    if cfg.embed_scale:
        # gemma scales embeddings by sqrt(H), with the normalizer cast to
        # the activation dtype FIRST (HF semantics — the rounding is part
        # of the checkpoint's numerics)
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    if cfg.embedding_multiplier != 1.0:     # granite
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if "norm_w" in params.embed:  # bloom embedding LayerNorm
        x = layer_norm(x, params.embed["norm_w"], params.embed["norm_b"],
                       cfg.norm_eps)
    return x


def hc_sinkhorn_probe(params: StageParams, cfg: ModelConfig,
                      ids: jnp.ndarray) -> jnp.ndarray:
    """Largest ``|row or column sum - 1|`` of the first block's attention
    map over the embedded tokens ``ids`` ``[T]`` (a model with
    ``hc_streams``): the same ``hc_pre`` and iteration count as the served
    blocks, the kernel where ``T`` rows take it.  ~1e-6 says the Sinkhorn
    iterations ran; the engine's ``/stats.hc.sinkhorn_residual_max`` and
    ``tools/model_parity.py`` read it."""
    n = cfg.hc_streams
    first = params.lead if cfg.lead_dense_layers else params.layers
    x = embed_tokens(params, cfg, ids[None])[0]
    if cfg.fp32_residual:
        x = x.astype(jnp.float32)
    _, coef = hc_ops.hc_pre(
        hc_ops.expand(x, n), first["hc_attn_phi"][0],
        first["hc_attn_alpha"][0], first["hc_attn_b"][0], **cfg.hc_args)
    return hc_ops.sinkhorn_residual(coef, ids.shape[0], n)


def _mlp(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
         tp_axis: Optional[str] = None,
         ep_axis: Optional[str] = None,
         valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """MLP block.  Under manual TP (``tp_axis`` set inside shard_map),
    w_gate/w_up arrive column-sliced and w_down row-sliced: the partial
    products are summed with an explicit psum (Megatron layout); biases are
    added once, after the reduction.  ``ep_axis`` selects the expert-
    parallel all_to_all dispatch path for MoE layers; ``valid`` reaches
    the routed experts only (:func:`_moe_routed`)."""
    if cfg.num_experts > 0:
        if ep_axis is not None:
            if cfg.mlp_act == "relu2":
                raise ValueError(
                    "the capacity-slot expert-parallel path is written for "
                    "gated experts of three matrices; experts of two "
                    "(mlp_act relu2) are served dropless on one chip")
            return _moe_mlp_ep(cfg, lp, x, ep_axis)
        return _moe_mlp(cfg, lp, x, tp_axis, valid)
    if cfg.family == "bloom":
        # under manual TP, b_up arrives column-sliced (P(None, "tp")) to
        # match w_up's local columns, so a plain add is correct either way.
        h = dense(x, lp["w_up"], "bsh,hi->bsi") + lp["b_up"]
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        out = dense(h, lp["w_down"], "bsi,ih->bsh")
        if tp_axis is not None:
            out = jax.lax.psum(out, tp_axis)
        return out + lp["b_down"]
    gate = dense(x, lp["w_gate"], "bsh,hi->bsi")
    up = dense(x, lp["w_up"], "bsh,hi->bsi")
    gate = gate.astype(jnp.float32)
    act = (jax.nn.gelu(gate, approximate=True)
           if cfg.mlp_act == "gelu_tanh" else jax.nn.silu(gate))
    h = (act * up.astype(jnp.float32)).astype(x.dtype)
    out = dense(h, lp["w_down"], "bsi,ih->bsh")
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return out


_EXPERT_STACKS = ("w_gate", "w_up", "w_up_t", "w_down")


def _router_logits(h: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """The router's matmul in float32 at ``HIGHEST`` on float32-cast rows
    (:func:`_route` says why); a function of its own so that a parity
    tool can swap in a careless one (``tools/model_parity.py``)."""
    return jnp.einsum("th,he->te", h.astype(jnp.float32),
                      w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _route(cfg: ModelConfig, lp: dict, h: jnp.ndarray):
    """The router: ``(weights [T, k] float32, experts [T, k] int32)`` for
    rows ``h`` [T, H].

    Matmul, softmax and top-k all in float32 on float32-cast rows (the
    router leaf is never quantized): where the k-th and (k+1)-th
    probabilities nearly tie, a bf16 router picks another expert than the
    model's, an error that does not shrink with the width of the rest.
    The k largest of ``softmax(h Wr)`` over ALL experts; renormalised to
    sum to 1 iff ``cfg.norm_topk_prob`` (mixtral: the same arithmetic as
    its "top-k of the logits, then softmax over the k"), else kept as they
    are (olmoe: they sum to less than 1).  ``router_scoring`` "sigmoid"
    (deepseek_v3) scores each expert by the sigmoid of its logit, in the
    same precision: there a flipped expert carries about a sixth of the
    routed sum, not olmoe's 1/64."""
    with jax.named_scope("moe_route"):
        logits = _router_logits(h, lp["router"])
        if cfg.router_scoring == "sigmoid":
            # deepseek_v3 ``noaux_tc``: each expert's score is the
            # sigmoid of its logit; the k are CHOSEN by score + stored
            # bias and WEIGHED by the score alone, renormalised with the
            # source's 1e-20 and scaled.  (Its group step, the best
            # ``topk_group`` of ``n_group`` groups, selects everything
            # at n_group = 1 and is not written.)
            scores = jax.nn.sigmoid(logits)
            choice = (scores + lp["router_bias"].astype(jnp.float32)
                      if cfg.router_bias else scores)
            _, experts = jax.lax.top_k(choice, cfg.experts_per_token)
            weights = jnp.take_along_axis(scores, experts, axis=-1)
            if cfg.norm_topk_prob:
                weights = weights / (jnp.sum(weights, axis=-1,
                                             keepdims=True) + 1e-20)
            weights = weights * cfg.routed_scaling_factor
            return weights, experts.astype(jnp.int32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, cfg.experts_per_token)
        if cfg.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        if cfg.routed_scaling_factor != 1.0:
            weights = weights * cfg.routed_scaling_factor
    return weights, experts.astype(jnp.int32)


def _relu2(up: jnp.ndarray) -> jnp.ndarray:
    """``relu(up) ** 2`` in float32, back in ``up``'s dtype: the
    activation of an expert of two matrices (nemotron_h's ``relu2``)."""
    return jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(up.dtype)


def _combine(out: jnp.ndarray, order: jnp.ndarray, weights: jnp.ndarray,
             written: jnp.ndarray) -> jnp.ndarray:
    """A token's ``k`` expert rows back from expert order, weighted and
    summed: ``y[t] = sum_j float32(out[inv[j, t]]) x weights[t, j]``
    ([T, H] float32) for ``out`` [k T, H] as the down projection wrote it,
    ``order`` the sort of the k-major rows (``_moe_routed``) and
    ``weights`` [T, k] float32.

    The rows cross HBM once, in ``out``'s dtype: the gather by the
    inverse permutation gives ``[k, T, H]`` (a split of the major axis),
    and one fusion over it masks, widens, weights and sums over ``k``, the
    major axis, products and sum in float32.  Rows at ``written`` and past
    it in expert order (all ``k T`` where every row lies in a group) are
    rows no group held; they are masked by ``where`` on the row's place,
    never by a zero weight: 0 x what the kernel never wrote may be NaN.
    The mask comes before the widening (the two commute exactly): behind
    it, or with no mask between the gather and the widening, the compiler
    leaves the widening a pass of its own, a float32 ``[k T, H]`` through
    HBM."""
    T, k = weights.shape
    inv = jnp.argsort(order).reshape(k, T)
    back = out[inv]                                       # [k, T, H]
    back = jnp.where((inv < written)[:, :, None], back, 0)
    return jnp.sum(back.astype(jnp.float32) * weights.T[:, :, None], axis=0)


def _moe_routed(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
                tp_axis: Optional[str] = None,
                valid: Optional[jnp.ndarray] = None):
    """The routed expert layer, dropless: ``(y [b, s, H], rows [E] int32)``
    with ``rows[e]`` the token-expert rows routed to expert ``e``.

    ``[b, s, H]`` is flattened to ``T`` tokens and each token's ``k``
    (token, expert) rows are sorted by expert: ``T k`` rows in ragged
    groups, one grouped matmul a projection
    (``ops.grouped_matmul``: a Pallas call on the chip that reads a
    touched expert's int8 matrix once and never widens a stack in HBM,
    ``ragged_dot`` elsewhere), silu(gate) x up (or, for experts of two
    matrices, ``mlp_act == "relu2"``: ``relu(up) ** 2`` and no gate call),
    the down projection, each row times its router weight, and a token's
    ``k`` rows summed in float32.  Shapes are static and the group sizes
    are data, so an
    expert may take every row or none, and no row of a token is dropped.

    The ``T k`` rows are laid k-major before the sort (row ``j T + t`` is
    token ``t``'s ``j``-th expert), so the rows come back from expert
    order as ``[k, T, H]`` by ONE gather of what the down projection
    wrote, in its dtype, and the sum over ``k`` runs over the major axis:
    ``k`` is never a tiled axis, and no float32 ``[T k, H]`` crosses HBM
    (:func:`_combine`).

    ``valid`` ``[b, s]`` bool (the mixed dispatch: a slot that decodes,
    a slab position that holds a prompt token) says which rows hold a
    token.  The router still runs on every row; a row that holds none
    takes the expert id ``E``, which sorts behind every group and counts
    in none (``rows``, the group sizes), so the grouped matmuls read no
    expert's matrix for it, and its output is zero.  A valid row's
    arithmetic is what it is without the mask.  ``None``: every row holds
    a token, and the program is the one traced without the argument.

    Under ``tp_axis`` the expert stacks arrive E-sliced (expert
    parallelism over ``tp``): this rank's groups are its local experts,
    the other ranks' rows sort behind them into no group (with the rows
    that hold no token, the same device), and the partial sums meet in
    the ``psum``."""
    b, s, H = x.shape
    T, k, E = b * s, cfg.experts_per_token, cfg.num_experts
    gated = cfg.mlp_act != "relu2"
    xt = x.reshape(T, H)
    weights, experts = _route(cfg, lp, xt)
    with jax.named_scope("moe_experts"):
        # k-major: row j T + t is token t's j-th expert
        flat = experts.T.reshape(k * T)
        if valid is not None:
            flat = jnp.where(jnp.tile(valid.reshape(T), k), flat, E)
        rows = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
        sizes = rows
        if cfg.experts_held:
            # this chip's share of a block's experts (``experts_held``):
            # the branch below without its ``psum``.  Rows routed to the
            # experts of the other chips enter no group, and what those
            # experts would add is left out; ``rows`` counts the held
            # experts' rows alone
            if tp_axis is not None:
                raise ValueError(
                    "a chip's share of the experts (experts_held) and "
                    "tensor parallelism both cut the expert stacks: "
                    "serve the share on one chip")
            e_local, e0 = cfg.experts_held
            mine = (flat >= e0) & (flat < e0 + e_local)
            flat = jnp.where(mine, flat - e0, e_local)
            rows = sizes = rows[e0:e0 + e_local]
        if tp_axis is not None:
            e_local = lp["w_down"].shape[0]  # quantized, LayerOf: .shape
            e0 = jax.lax.axis_index(tp_axis) * e_local
            mine = (flat >= e0) & (flat < e0 + e_local)
            flat = jnp.where(mine, flat - e0, e_local)
            sizes = jax.lax.dynamic_slice_in_dim(rows, e0, e_local)
        order = jnp.argsort(flat, stable=True)
        token = order % T
        xs = xt[token]                                    # [k T, H]
        # ``routed``: the T k rows spread over all E experts, whatever
        # share of them is here (the row tile follows the rows a group)
        if gated:
            gate = grouped_matmul(xs, lp["w_gate"], sizes, routed=E)
            up = grouped_matmul(xs, lp["w_up"], sizes, routed=E)
            hh = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(x.dtype)
        else:   # two matrices an expert, the first stored [I, H]
            hh = _relu2(grouped_matmul(xs, lp["w_up_t"], sizes, routed=E,
                                       transposed=True))
        out = grouped_matmul(hh, lp["w_down"], sizes, routed=E)
        # rows of other ranks' experts, and rows that hold no token,
        # belong to no group here: the kernel never wrote them
        y = _combine(out, order, weights, jnp.sum(sizes))
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)
    if cfg.num_shared_experts > 0:
        # the shared experts: ONE dense SwiGLU of their summed width on
        # every row, added to the routed sum in float32 before the cast
        with jax.named_scope("moe_shared"):
            if gated:
                gate = dense(xt, lp["ws_gate"], "th,hi->ti")
                up = dense(xt, lp["ws_up"], "th,hi->ti")
                hs = (jax.nn.silu(gate.astype(jnp.float32))
                      * up.astype(jnp.float32)).astype(x.dtype)
            else:
                hs = _relu2(dense(xt, lp["ws_up"], "th,hi->ti"))
            y = y + dense(hs, lp["ws_down"], "ti,ih->th").astype(
                jnp.float32)
    return y.reshape(b, s, H).astype(x.dtype), rows


def _moe_mlp(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
             tp_axis: Optional[str] = None,
             valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Top-k routed MoE (mixtral, olmoe): :func:`_moe_routed`'s output."""
    return _moe_routed(cfg, lp, x, tp_axis, valid)[0]


def _default_attn(q, k, v, k_cache, v_cache, positions, cache_start, slopes):
    """Default attention path: insert chunk into cache, attend to cache.

    ``attn_impl`` hooks in ``_layer``/``stage_forward`` share this signature;
    the sequence-parallel path (parallel/sequence.py) substitutes ring /
    sharded-cache attention without duplicating the decoder block.
    """
    k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v, cache_start)
    new_len = cache_start + q.shape[1]
    out = attention(q, k_cache, v_cache, positions, new_len, slopes)
    return out, k_cache, v_cache


def _moe_mlp_ep(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
                ep_axis: str) -> jnp.ndarray:
    """Expert-parallel MoE: GShard-style capacity dispatch + all_to_all.

    BASELINE.json config #4 ("per-expert shard placement") done the TPU
    way: experts live sharded over the ``ep`` mesh axis (this rank holds
    ``E/n`` experts' weights — ``lp["w_*"]`` arrive E-sliced inside
    shard_map), tokens are data-parallel over the same axis.  Each rank
    routes its tokens into per-expert capacity slots
    (``C = ceil(T*k/E * moe_capacity_factor)``, over-capacity tokens drop
    — exactness for tests comes from a generous factor), one
    ``all_to_all`` ships slot buffers to the expert owners, the expert
    MLPs run batched on the MXU ([e_loc, n*C, H] x [e_loc, H, I]), and a
    reverse ``all_to_all`` brings outputs home for the weighted combine.

    Dispatch/combine are one-hot einsums (dense [T, E, C] masks): static
    shapes, no gather/scatter — the XLA-friendly formulation.
    """
    import math
    b, s, H = x.shape
    T = b * s
    E, k = cfg.num_experts, cfg.experts_per_token
    n = jax.lax.axis_size(ep_axis)
    e_loc = lp["w_gate"].shape[0]       # E-sliced inside shard_map
    assert e_loc * n == E, (e_loc, n, E)
    xt = x.reshape(T, H)

    weights, topi = _route(cfg, lp, xt)                    # [T, k]

    C = int(math.ceil(T * k / E * cfg.moe_capacity_factor))
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.int32)      # [T, k, E]
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - 1                     # slot per expert
    keep = (flat > 0) & (pos < C)
    slot = jnp.where(keep, pos, C)                         # C -> dropped
    disp = jax.nn.one_hot(slot, C, dtype=jnp.float32)      # [T*k, E, C]
    disp_t = disp.reshape(T, k, E, C).sum(1)               # [T, E, C]
    comb = (disp * weights.reshape(T * k)[:, None, None]
            ).reshape(T, k, E, C).sum(1)                   # [T, E, C]

    expert_in = jnp.einsum("tec,th->ech", disp_t,
                           xt.astype(jnp.float32))         # [E, C, H]
    ein = expert_in.reshape(n, e_loc, C, H)
    ein = jax.lax.all_to_all(ein, ep_axis, split_axis=0, concat_axis=0)
    h_in = ein.transpose(1, 0, 2, 3).reshape(e_loc, n * C, H)
    h_in = h_in.astype(x.dtype)

    gate = dense(h_in, lp["w_gate"], "ech,ehi->eci")
    up = dense(h_in, lp["w_up"], "ech,ehi->eci")
    hh = (jax.nn.silu(gate.astype(jnp.float32))
          * up.astype(jnp.float32)).astype(x.dtype)
    out = dense(hh, lp["w_down"], "eci,eih->ech")          # [e_loc, n*C, H]

    out = out.reshape(e_loc, n, C, H).transpose(1, 0, 2, 3)
    out = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0)
    expert_out = out.reshape(E, C, H).astype(jnp.float32)
    y = jnp.einsum("tec,ech->th", comb, expert_out)
    return y.reshape(b, s, H).astype(x.dtype)


def _whole_row_rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float,
                        tp_axis: Optional[str]) -> jnp.ndarray:
    """RMSNorm over a whole q or k projection (olmoe's ``q_norm`` /
    ``k_norm``: one mean square over all heads' channels, before the head
    split and rope).  Under manual TP a rank holds a column slice of the
    projection (and of ``w``), so the squares are summed over ``tp_axis``
    and divided by the full width: the same mean square on every rank."""
    if tp_axis is None:
        return rms_norm(x, w, eps)
    xf = x.astype(jnp.float32)
    width = x.shape[-1] * jax.lax.axis_size(tp_axis)
    ms = jax.lax.psum(jnp.sum(xf * xf, axis=-1, keepdims=True),
                      tp_axis) / width
    return (xf * jax.lax.rsqrt(ms + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def _kv_attention(cfg: ModelConfig, lp: dict, h: jnp.ndarray, k_cache,
                  v_cache, positions, cache_start, slopes, tp_axis,
                  attn_impl):
    """Attention over full keys and values, from normed rows ``h``
    [b, s, H] to the heads' outputs side by side: ``(attn [b, s, nh x
    hd], k_cache', v_cache')``.

    Head counts derive from the weight shards, not the config, so the same
    code runs full-model (GSPMD) and per-TP-rank (manual shard_map) — under
    TP this rank sees nh/tp query heads and nkv/tp kv heads."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    nh = lp["wq"].shape[-1] // hd  # QuantizedArray exposes .shape too
    nkv = lp["wk"].shape[-1] // hd
    q = dense(h, lp["wq"], "bsh,hd->bsd")
    k = dense(h, lp["wk"], "bsh,hd->bsd")
    v = dense(h, lp["wv"], "bsh,hd->bsd")
    if cfg.attn_layernorm or cfg.attn_qkv_bias:
        # bq/bk/bv are column-sharded with their weights under TP
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if cfg.qk_norm:
        q = _whole_row_rms_norm(q, lp["q_norm_w"], cfg.norm_eps, tp_axis)
        k = _whole_row_rms_norm(k, lp["k_norm_w"], cfg.norm_eps, tp_axis)
    # The head reshape may not reach the dot: XLA's simplifier would merge
    # the two into a convolution windowed over heads (``window={size=<heads>}``
    # under ``bsh,hd->bsd/dot_general``) that reads the weight as [heads, hd,
    # H], the stored matrix transposed.  Every execution then copies the whole
    # wq / wk / wv stacks, and each layer call writes its matrix (dequantized)
    # out before reading it again.  Behind the barrier each projection is one
    # fusion (slice of the stack, dequant, matmul, bias) like the MLP's, for a
    # round trip of q, k and v through memory (docs/DESIGN.md section 1).
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    if cfg.attn_scale != 1.0:
        # a softmax scale that is not ``hd ** -0.5`` (granite's
        # ``attention_multiplier``): every attention path scales its
        # float32 scores by ``hd ** -0.5``, so the rest rides on q
        q = (q.astype(jnp.float32) * cfg.attn_scale).astype(q.dtype)
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)

    kind = cfg.block_kind       # a period model's block: its own rope
    if kind is not None and kind.qk_norm:       # a head's own mean square
        q = rms_norm(q, lp["q_norm_w"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm_w"], cfg.norm_eps)
    if kind is not None:
        if kind.rotary_share > 0:       # 0: no rope (positions by order)
            q = apply_rope_kind(q, positions, kind.rope_theta,
                                kind.rotary_share, kind.yarn)
            k = apply_rope_kind(k, positions, kind.rope_theta,
                                kind.rotary_share, kind.yarn)
    elif cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    attn_fn = attn_impl if attn_impl is not None else _default_attn
    if cfg.summary_kv:
        # EVA attention: this layer's pooling vectors go to the hook, a
        # page pool's (``attn_impl.summarised``) or the dense cache's
        if attn_impl is None:
            summarised = eva_dense_attn
        elif hasattr(attn_impl, "summarised"):
            summarised = attn_impl.summarised
        else:
            raise ValueError(
                "a model with a summarised cache (eva_window) needs an "
                "attention hook that knows its two roles of row (ops."
                "paged_attention.make_paged_attn_impl, or the dense "
                "cache); this one serves a row a token")
        attn_fn = summarised(cfg.eva_window, cfg.eva_chunk,
                             lp["adaptive_mu_k"], lp["adaptive_phi"])
    attn, k_cache, v_cache = attn_fn(
        q, k, v, k_cache, v_cache, positions, cache_start, slopes)
    if kind is not None and kind.gate == "per-head":
        # headwise output gate: a head's output times the sigmoid of one
        # scalar, a linear map of the block's normed input, in float32
        gate = jax.nn.sigmoid(
            dense(h, lp["wg"], "bsh,hn->bsn").astype(jnp.float32))
        attn = (attn.astype(jnp.float32) * gate[..., None]).astype(
            attn.dtype)
    attn = attn.reshape(b, s, nh * hd)
    if kind is not None and kind.gate == "elementwise":
        # the same gate with one scalar a channel of every head
        with jax.named_scope("gqa_gate"):
            gate = jax.nn.sigmoid(
                dense(h, lp["wg"], "bsh,hd->bsd").astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * gate).astype(attn.dtype)
    return attn, k_cache, v_cache


def _kda_mixer(cfg: ModelConfig, kind, lp: dict, h: jnp.ndarray, state,
               conv, positions, valid, hook):
    """A gated delta-rule (KDA) block's mixer over normed rows ``h`` [b,
    s, H]: ``(y [b, s, heads x hd], state', conv')`` (docs/DESIGN.md
    section 27; the equations are ``ops.kda``'s first lines).

    ``state`` / ``conv`` are ``LayerOf`` the state pool ``[planes, rows,
    heads, hd, hd]`` float32 and the convolution tails ``[planes, rows,
    taps - 1, 3 x heads x hd]``, and come back the same way.  ``hook``
    (``attn_impl.for_state``) gives each batch row's ROW of the pool and
    says whether the kernels serve; ``None``: a dense cache, row ``i`` is
    batch row ``i``.  One token a row (``s == 1``) steps every row at
    once; a segment (``s > 1``) runs the rows one after another in the
    chunk form, each from the state the one before left (two segments of
    one slab may be one prompt's consecutive chunks), and from zero where
    its first position is 0: a request's first segment needs nothing
    zeroed for it.  ``valid`` [b, s]: a row's first tokens that are there;
    the others move neither state nor tail.  In a merged call
    (``ops.paged_attention.split_rows``: ``hook.rows()`` is a pair) the
    projections and the gates run over all rows at once, and the state's
    part twice: the segments in the chunk form, then the decoding rows'
    step."""
    b, s, _ = h.shape
    hd, nh = cfg.head_dim, kind.num_heads
    D, f32 = nh * hd, jnp.float32
    plane = state.layer
    S, tails = state.stack, conv.stack
    rows = hook.rows() if hook is not None else None
    interpret = hook is not None and hook.interpret
    if valid is None:
        valid = jnp.ones((b, s), bool)
    # a pair of rows: a merged call, the segments' and the decoding rows'
    merged = isinstance(rows, tuple)
    in_parts = lambda *a: split_rows(rows, a) if merged else (a,)  # noqa: E731
    ntoks = [jnp.sum(part, axis=1).astype(jnp.int32)
             for (part,) in in_parts(valid)]
    q = dense(h, lp["wq"], "bsh,hd->bsd")
    k = dense(h, lp["wk"], "bsh,hd->bsd")
    v = dense(h, lp["wv"], "bsh,hd->bsd")
    q, k, v = jax.lax.optimization_barrier((q, k, v))   # as ``_kv_attention``
    u = jnp.concatenate([q, k, v], axis=-1)             # [b, s, 3 D]
    with jax.named_scope("kda_gates"):
        f = dense(dense(h, lp["wf_dn"], "bsh,hr->bsr"), lp["wf_up"],
                  "bsr,rd->bsd").astype(f32) + lp["dt_bias"].astype(f32)
        g = -(jnp.exp(lp["A_log"].astype(f32))[:, None]
              * jax.nn.softplus(f).reshape(b, s, nh, hd))
        beta = 2.0 * jax.nn.sigmoid(
            dense(h, lp["wb"], "bsh,hn->bsn").astype(f32))
        g = jnp.where(valid[:, :, None, None], g, 0.0)
        beta = jnp.where(valid[:, :, None], beta, 0.0)
        out_gate = jax.nn.sigmoid(
            dense(dense(h, lp["wg_dn"], "bsh,hr->bsr"), lp["wg_up"],
                  "bsr,rd->bsd").astype(f32) + lp["bg"].astype(f32))
    R = tails.shape[1]

    def heads_of(y):
        """silu(conv) -> q, k, v a head, q and k of unit length (q times
        hd ** -0.5 after it); rounded to the model's dtype first, as any
        block's q, k and v are."""
        y = y.astype(cfg.dtype).astype(f32)
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
        cut = lambda x: x.reshape(x.shape[:-1] + (nh, hd))
        return (unit(cut(y[..., :D])) * hd ** -0.5,
                unit(cut(y[..., D:2 * D])), cut(y[..., 2 * D:]))

    def mix(rows, ntok, u, g, beta, positions, S, tails):
        """The state's part over rows ``[b, s]``, each through its row
        ``rows[i]`` of the pool: ``(o [b, s, heads, hd], S', tails')``."""
        b, s = u.shape[:2]
        kernel, why = (kda_ops.on_kernel(S.shape, s, hook.backend)
                       if hook is not None else (False, "dense cache"))
        if hook is not None:
            hook.note(s, "pallas_kda" if kernel else "xla_kda", why)
        if rows is None:
            at = jnp.arange(b, dtype=jnp.int32)
        else:   # the last row is nobody's: a row that holds nothing goes there
            at = jnp.where(ntok > 0, jnp.minimum(rows, R - 1), R - 1)
        if s == 1:
            with jax.named_scope("kda_conv"):
                tail = jax.lax.dynamic_index_in_dim(tails, plane, 0,
                                                    False)[at]
                y, tail = kda_ops.causal_conv(u, tail, lp["conv_w"], ntok)
                tails = tails.at[plane, at].set(tail)
                qh, kh, vh = heads_of(y[:, 0])
            with jax.named_scope("kda_step"):
                o, S = kda_ops.kda_step(
                    S, plane, None if rows is None else at, qh, kh, vh,
                    g[:, 0], beta[:, 0], ntok > 0, kernel=kernel,
                    interpret=interpret)
            return o[:, None], S, tails
        fresh = positions[:, 0] == 0
        outs = []
        for r in range(b):
            with jax.named_scope("kda_conv"):
                tail = jnp.where(fresh[r], 0,
                                 tails[plane, at[r]])[None].astype(tails.dtype)
                y, tail = kda_ops.causal_conv(u[r:r + 1], tail,
                                              lp["conv_w"], ntok[r:r + 1])
                tails = tails.at[plane, at[r]].set(tail[0])
                qh, kh, vh = heads_of(y[0])
            with jax.named_scope("kda_chunk"):
                o_r, S = kda_ops.kda_chunk(
                    S, plane, at[r], fresh[r], qh, kh, vh, g[r], beta[r],
                    kernel=kernel, interpret=interpret)
            outs.append(o_r)
        return jnp.stack(outs), S, tails

    outs = []       # (merged: the segments' chunk form, then the rows' step)
    for part_rows, ntok, part in zip(rows if merged else (rows,), ntoks,
                                     in_parts(u, g, beta, positions)):
        o, S, tails = mix(part_rows, ntok, *part, S, tails)
        outs.append(o)
    o = join_rows(outs) if merged else o
    with jax.named_scope("kda_out_norm"):
        y = rms_norm(o, lp["o_norm_w"], cfg.norm_eps)     # a head's own
        y = (y * out_gate.reshape(b, s, nh, hd)).astype(cfg.dtype)
    return (y.reshape(b, s, D), LayerOf(S, plane), LayerOf(tails, plane))


def _gated_norm(y, z, w, eps, groups: int = 1):
    """Mamba-2's gated RMSNorm over float32 ``y`` ``[.., d_inner]``: the
    gate ``silu(z)`` BEFORE the norm, then one mean square a GROUP of
    ``d_inner / groups`` channels (the kind's ``groups``: a group's heads
    side by side), times ``w`` ``[d_inner]``.  One group is one mean
    square over all of ``d_inner``."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    if groups == 1:
        return rms_norm(g, w, eps)
    by_group = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    return rms_norm(by_group, w.reshape(groups, -1), eps).reshape(g.shape)


def _ssd_mixer(cfg: ModelConfig, kind, lp: dict, h: jnp.ndarray, state,
               conv, positions, valid, hook):
    """A Mamba-2 (SSD) block's mixer over normed rows ``h`` [b, s, H]:
    ``(y [b, s, d_inner], state', conv')`` before the out-projection
    (docs/DESIGN.md section 29; the equations are ``ops.ssd``'s first
    lines):

        z | xBC | dt = h W_in;   xBC = silu(conv(xBC) + b);   x | B | C = xBC
        dt = softplus(dt + dt_bias);   the recurrence over (x, B, C, dt, A)
        y = rms_norm((y + D x) * silu(z), w)        a group of d_inner / groups

    ``state`` / ``conv`` are ``LayerOf`` the state pool ``[planes, rows,
    heads, P, N]`` float32 and the convolution tails ``[planes, rows, taps
    - 1) x (d_inner + 2 groups N)]`` (a request's tail end to end in one
    row of lanes: ``ModelConfig.state_shapes``); rows, segments, ``valid`` and a merged
    call's two parts exactly as :func:`_kda_mixer` has them, the state
    kind's other mixer."""
    b, s, _ = h.shape
    nh, P, N, G = (kind.state_heads, kind.state_head_dim, kind.state_size,
                   kind.groups)
    D, f32 = nh * P, jnp.float32
    plane = state.layer
    S, tails = state.stack, conv.stack
    rows = hook.rows() if hook is not None else None
    interpret = hook is not None and hook.interpret
    if valid is None:
        valid = jnp.ones((b, s), bool)
    merged = isinstance(rows, tuple)
    in_parts = lambda *a: split_rows(rows, a) if merged else (a,)  # noqa: E731
    ntoks = [jnp.sum(part, axis=1).astype(jnp.int32)
             for (part,) in in_parts(valid)]
    with jax.named_scope("ssd_in_proj"):
        u = dense(h, lp["w_in"], "bsh,hd->bsd")
        u = jax.lax.optimization_barrier(u)     # as ``_kv_attention``
        z, xbc = u[..., :D], u[..., D:2 * D + 2 * G * N]
        dt = jax.nn.softplus(u[..., 2 * D + 2 * G * N:].astype(f32)
                             + lp["dt_bias"].astype(f32))
        dt = jnp.where(valid[:, :, None], dt, 0.0)  # a token not there
        A = -jnp.exp(lp["A_log"].astype(f32))
    R = tails.shape[1]
    taps, chans = kind.conv, D + 2 * G * N

    def parts_of(y):
        """silu(conv + b) -> x a head, B and C a group; rounded to the
        model's dtype first, as any block's projections are."""
        y = y.astype(cfg.dtype)
        return (y[..., :D].reshape(y.shape[:-1] + (nh, P)),
                y[..., D:D + G * N].reshape(y.shape[:-1] + (G, N)),
                y[..., D + G * N:].reshape(y.shape[:-1] + (G, N)))

    def mix(rows, ntok, xbc, dt, positions, S, tails):
        """The state's part over rows ``[b, s]``, each through its row
        ``rows[i]`` of the pool: ``(y [b, s, heads, P] float32 with the D
        skip, S', tails')``."""
        b, s = xbc.shape[:2]
        kernel, why = (ssd_ops.on_kernel(S.shape, G, min(s, kind.chunk),
                                         hook.backend)
                       if hook is not None else (False, "dense cache"))
        if hook is not None:
            hook.note(s, "pallas_ssd" if kernel else "xla_ssd", why)
        if rows is None:
            at = jnp.arange(b, dtype=jnp.int32)
        else:   # the last row is nobody's: a row that holds nothing goes there
            at = jnp.where(ntok > 0, jnp.minimum(rows, R - 1), R - 1)
        skip = lambda x: lp["D"].astype(f32)[:, None] * x.astype(f32)  # noqa: E731
        if s == 1:
            with jax.named_scope("ssd_conv"):
                tail = jax.lax.dynamic_index_in_dim(tails, plane, 0,
                                                    False)[at]
                y, tail = kda_ops.causal_conv(
                    xbc, tail.reshape(b, taps - 1, chans), lp["conv_w"],
                    ntok, lp["conv_b"])
                tails = tails.at[plane, at].set(tail.reshape(b, -1))
                x, B, C = parts_of(y[:, 0])
            with jax.named_scope("ssd_step"):
                o, S = ssd_ops.ssd_step(
                    S, plane, None if rows is None else at, x, B, C,
                    dt[:, 0], A, ntok > 0, kernel=kernel,
                    interpret=interpret)
            return (o + skip(x))[:, None], S, tails

        def segment(pools, seg):
            """One segment from the state and the tail the one before left
            (two segments of a slab may be one prompt's consecutive
            chunks): a ``lax.scan`` body, so a slab of any number of
            segments lowers the convolution and the call ONCE a block."""
            S, tails = pools
            xbc, dt, at, fresh, ntok = seg
            with jax.named_scope("ssd_conv"):
                tail = jnp.where(fresh, 0, tails[plane, at]).astype(
                    tails.dtype).reshape(1, taps - 1, chans)
                y, tail = kda_ops.causal_conv(xbc[None], tail, lp["conv_w"],
                                              ntok[None], lp["conv_b"])
                tails = tails.at[plane, at].set(tail.reshape(-1))
                x, B, C = parts_of(y[0])
            with jax.named_scope("ssd_chunk"):
                o, S = ssd_ops.ssd_chunk(
                    S, plane, at, fresh, x, B, C, dt, A, chunk=kind.chunk,
                    kernel=kernel, interpret=interpret)
            return (S, tails), o.astype(f32) + skip(x)

        (S, tails), o = jax.lax.scan(
            segment, (S, tails), (xbc, dt, at, positions[:, 0] == 0, ntok))
        return o, S, tails

    outs = []       # (merged: the segments' chunk form, then the rows' step)
    for part_rows, ntok, part in zip(rows if merged else (rows,), ntoks,
                                     in_parts(xbc, dt, positions)):
        o, S, tails = mix(part_rows, ntok, *part, S, tails)
        outs.append(o)
    o = join_rows(outs) if merged else o
    with jax.named_scope("ssd_gated_norm"):
        # (one group is called as it was, with four arguments:
        # tests/test_granite_hybrid.py swaps in a norm that takes four)
        norm = _gated_norm if G == 1 else partial(_gated_norm, groups=G)
        y = norm(o.reshape(b, s, D), z, lp["ssd_norm_w"],
                 cfg.norm_eps).astype(cfg.dtype)
    return y, LayerOf(S, plane), LayerOf(tails, plane)


def _lightning_mixer(cfg: ModelConfig, kind, lp: dict, h: jnp.ndarray,
                     state, conv, positions, valid, hook):
    """A Lightning linear-attention block's mixer over normed rows ``h``
    [b, s, H]: ``(y [b, s, heads x hd], state', conv')`` before ``wo``
    (docs/DESIGN.md section 32):

        q, k <- rope(rms_norm(q), rms_norm(k))          a head's own norm
        S_t = lambda_h S_{t-1} + k_t^T v_t              float32, a head
        o_t = (q_t S_t) * hd ** -0.5;   y = rms_norm(o) * sigmoid(h W_g)

    It is :mod:`ops.ssd`'s recurrence with ``x = v``, ``B = k``, ``C = q``
    a HEAD (groups = heads), ``dt`` = 1 at a token that is there and ``A``
    the constant ``log lambda_h``: the state pool ``[planes, rows, heads,
    hd (value), hd (key)]`` float32 goes through ``ssd_step`` and
    ``ssd_chunk``, rows, segments, ``valid`` and a merged call's two parts
    exactly as :func:`_ssd_mixer` has them.  No convolution: ``conv`` (the
    pool's placeholder for tails) comes back as it came."""
    b, s, _ = h.shape
    hd, nh = cfg.head_dim, kind.num_heads
    D, f32 = nh * hd, jnp.float32
    plane = state.layer
    S = state.stack
    rows = hook.rows() if hook is not None else None
    interpret = hook is not None and hook.interpret
    if valid is None:
        valid = jnp.ones((b, s), bool)
    merged = isinstance(rows, tuple)
    in_parts = lambda *a: split_rows(rows, a) if merged else (a,)  # noqa: E731
    q = dense(h, lp["wq"], "bsh,hd->bsd")
    k = dense(h, lp["wk"], "bsh,hd->bsd")
    v = dense(h, lp["wv"], "bsh,hd->bsd")
    q, k, v = jax.lax.optimization_barrier((q, k, v))   # as ``_kv_attention``
    cut = lambda x: x.reshape(b, s, nh, hd)  # noqa: E731
    q, k, v = cut(q), cut(k), cut(v)
    with jax.named_scope("la_qk"):
        if kind.qk_norm:
            q = rms_norm(q, lp["q_norm_w"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm_w"], cfg.norm_eps)
        if kind.rotary_share > 0:
            q = apply_rope_kind(q, positions, kind.rope_theta,
                                kind.rotary_share, kind.yarn)
            k = apply_rope_kind(k, positions, kind.rope_theta,
                                kind.rotary_share, kind.yarn)
        # a token that is not there moves no state: dt = 0
        dt = jnp.broadcast_to(valid[:, :, None].astype(f32), (b, s, nh))
        A = ssd_ops.lightning_log_decay(nh)
    R = S.shape[1]

    def mix(rows, q, k, v, dt, positions, S):
        """The state's part over rows ``[b, s]``: ``(o [b, s, heads, hd]
        float32, S')``."""
        b, s = q.shape[:2]
        ntok = jnp.sum(dt[:, :, 0] > 0, axis=1).astype(jnp.int32)
        kernel, why = (ssd_ops.on_kernel(S.shape, nh, s, hook.backend)
                       if hook is not None else (False, "dense cache"))
        if hook is not None:
            hook.note(s, "pallas_la" if kernel else "xla_la", why)
        if rows is None:
            at = jnp.arange(b, dtype=jnp.int32)
        else:   # the last row is nobody's: a row that holds nothing goes there
            at = jnp.where(ntok > 0, jnp.minimum(rows, R - 1), R - 1)
        if s == 1:
            with jax.named_scope("la_step"):
                o, S = ssd_ops.ssd_step(
                    S, plane, None if rows is None else at, v[:, 0],
                    k[:, 0], q[:, 0], dt[:, 0], A, ntok > 0, kernel=kernel,
                    interpret=interpret, name="_la_step")
            return o[:, None], S

        def segment(S, seg):
            q, k, v, dt, at, fresh = seg
            with jax.named_scope("la_chunk"):
                o, S = ssd_ops.ssd_chunk(
                    S, plane, at, fresh, v, k, q, dt, A, kernel=kernel,
                    interpret=interpret, name="_la_chunk")
            return S, o.astype(f32)

        S, o = jax.lax.scan(segment, S,
                            (q, k, v, dt, at, positions[:, 0] == 0))
        return o, S

    outs = []       # (merged: the segments' chunk form, then the rows' step)
    for part_rows, part in zip(rows if merged else (rows,),
                               in_parts(q, k, v, dt, positions)):
        o, S = mix(part_rows, *part, S)
        outs.append(o)
    o = join_rows(outs) if merged else o
    with jax.named_scope("la_out_norm"):
        y = rms_norm(o * hd ** -0.5, jnp.ones((hd,), f32), cfg.norm_eps)
        y = y.reshape(b, s, D) * lp["o_norm_w"].astype(f32)
        gate = jax.nn.sigmoid(
            dense(h, lp["wg"], "bsh,hd->bsd").astype(f32))
        y = (y * gate).astype(cfg.dtype)
    return y, LayerOf(S, plane), conv


def _latent_attention(cfg: ModelConfig, lp: dict, h: jnp.ndarray, cache,
                      positions: jnp.ndarray, cache_start: jnp.ndarray,
                      attn_impl=None):
    """Multi-head latent attention over normed rows ``h`` [b, s, H], in
    the ABSORBED form on every path: ``(attn [b, s, nh * v_head_dim],
    cache')``.

    What is cached is one row a token, ``[c | k_pe | 0]``
    (``ops.latent_attention``): ``c`` the normed latent, ``k_pe`` the
    roped key part every head shares.  Head ``i``'s score against a
    cached row is ``(q_nope_i W_UK_i^T | q_pe_i) . (c | k_pe)`` and its
    output ``(softmax . c) W_UV_i``: ``kv_b``'s two halves are folded
    into the query (``mla_absorb``) and the output (``mla_unabsorb``),
    plain matmuls here, and the hook between them is multi-query
    attention over the shared row (``attn_impl.latent``: a page pool;
    else the dense cache).  The same arithmetic as decompressing keys
    and values a head, re-associated; the softmax scale ``(nope + rope)
    ** -0.5`` multiplies the hook's float32 scores."""
    b, s, _ = h.shape
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    nh = lp["wq"].shape[-1] // (dn + dr)
    if cfg.q_lora_rank:     # q_a_proj -> q_a_layernorm -> q_b_proj
        q = rms_norm(dense(h, lp["wq_a"], "bsh,hr->bsr"),
                     lp["q_a_norm_w"], cfg.norm_eps)
        q = dense(q, lp["wq"], "bsr,rd->bsd")
    else:
        q = dense(h, lp["wq"], "bsh,hd->bsd")
    ckv = dense(h, lp["wkv_a"], "bsh,hd->bsd")
    # as in ``_layer``: the head reshape may not reach the dot
    q, ckv = jax.lax.optimization_barrier((q, ckv))
    q = q.reshape(b, s, nh, dn + dr)
    c = rms_norm(ckv[..., :r], lp["kv_norm_w"], cfg.norm_eps)
    k_pe = apply_rope_interleaved(ckv[:, :, None, r:], positions,
                                  cfg.rope_theta, cfg.yarn)[:, :, 0]
    q_pe = apply_rope_interleaved(q[..., dn:], positions, cfg.rope_theta,
                                  cfg.yarn)
    pad = cache.shape[-1] - (r + dr)      # a plane's or a LayerOf's lanes
    scale = cfg.latent_scale
    with jax.named_scope("mla_absorb"):
        q_c = jnp.einsum("bshd,hdr->bshr", q[..., :dn], lp["w_uk"])
        q_abs = jnp.concatenate(
            [q_c, q_pe, jnp.zeros((b, s, nh, pad), q_c.dtype)], axis=-1)
    row = jnp.concatenate(
        [c, k_pe.astype(c.dtype), jnp.zeros((b, s, pad), c.dtype)], axis=-1)
    if getattr(attn_impl, "latent", False):
        out, cache = attn_impl(q_abs, row, cache, positions)
    elif attn_impl is not None:
        raise ValueError(
            "an attention hook made for keys and values cannot serve a "
            "latent-attention model: its cache is one latent row a token "
            "(ops.latent_attention.make_latent_attn_impl)")
    else:
        with jax.named_scope("mla_attend"):
            out, cache = latent_dense_attn(q_abs, row, cache, positions,
                                           cache_start, r, scale)
    with jax.named_scope("mla_unabsorb"):
        attn = jnp.einsum("bshr,hrv->bshv", out, lp["w_uv"])
        attn = attn.reshape(b, s, nh * dv)
    return attn, cache


def _layer(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
           k_cache: jnp.ndarray, v_cache: jnp.ndarray,
           positions: jnp.ndarray, cache_start: jnp.ndarray,
           slopes: Optional[jnp.ndarray],
           tp_axis: Optional[str] = None,
           attn_impl=None,
           ep_axis: Optional[str] = None,
           moe_stats: bool = False,
           valid: Optional[jnp.ndarray] = None):
    """One decoder block. x: [b, s, H]. Returns (x', k_cache', v_cache'),
    and with ``moe_stats`` a fourth value, the rows routed to each expert
    in this layer call ([E] int32; ``_moe_routed``, which is also all
    that reads ``valid``, the rows that hold a token; ``None`` from a
    block that has no MLP).  A block of ONE sublayer (``cfg.block_kind``:
    ``attn == "none"``, or ``mlp`` false) is one norm, that sublayer and
    one add; without a mixer the caches come and go as ``None``.  The
    caches are this
    layer's planes, or ``LayerOf`` the whole stacks where ``attn_impl``
    addresses a page pool in place; either goes to the hook untouched.

    With ``cfg.hc_streams`` ``x`` is the token's ``n`` streams side by
    side, ``[b, s, n H]`` (docs/DESIGN.md section 28): each sublayer's
    norm reads their weighted sum (``hc_pre``) and what the sublayer
    leaves goes back into every stream beside their mix (``hc_post``),
    where a one-stream block norms ``x`` and adds.
    """
    n = cfg.hc_streams

    # A merged call's streams (``stage_forward``) hold padding behind the
    # call's rows, up to the kernels' whole tiles: one call over them all,
    # recorded under the chunks of the two parts it serves
    held = positions.shape[1]      # the rows that hold a token

    def hc_read(sub, x):
        note = getattr(attn_impl, "note_streams", None)
        chunks = (x.shape[-2],)
        if x.shape[1] != held:
            r, B = (t.shape[0] for t in attn_impl.parts())
            chunks = ((held - B) // r, 1)
        with jax.named_scope("hc_pre"):
            h, coef = hc_ops.hc_pre(
                x, lp[f"hc_{sub}_phi"], lp[f"hc_{sub}_alpha"],
                lp[f"hc_{sub}_b"], **cfg.hc_args,
                note=note and (lambda path, why: [
                    note(chunk, path, why) for chunk in chunks]))
        return (h if x.shape[1] == held else h[:, :held]), coef

    def hc_write(x, y, coef):
        if x.shape[1] != held:
            y = jnp.pad(y, ((0, 0), (0, x.shape[1] - held), (0, 0)))
        with jax.named_scope("hc_post"):
            return hc_ops.hc_post(x, y, coef, n=n)

    kind = cfg.block_kind
    # the sublayers this block has: both, but for a kind of one
    # (``BlockKind``: no mixer at ``attn == "none"``, no MLP at ``mlp``
    # false); a static branch, so a block of both traces what it did
    has_mixer = kind is None or kind.attn != "none"
    has_mlp = kind is None or kind.mlp
    rm = cfg.residual_multiplier    # granite: both sublayers' outputs
    def normed(x, sub):
        """The sublayer's input: its norm over the stream, in the model's
        dtype (a float32 stream, looped or ``fp32_residual``, is cast)."""
        if cfg.attn_layernorm:
            h = layer_norm(x, lp[f"{sub}_norm_w"], lp[f"{sub}_norm_b"],
                           cfg.norm_eps)
        else:
            h = rms_norm(x, lp[f"{sub}_norm_w"], cfg.norm_eps,
                         cfg.norm_unit_offset)
        return h if x.dtype == cfg.dtype else h.astype(cfg.dtype)

    streams = x
    if has_mixer:
        if n:
            x, coef = hc_read("attn", streams)
        h = normed(x, "attn")

        if kind is not None and kind.attn == "kda":
            # the caches are the state pool and the convolution tails
            attn, k_cache, v_cache = _kda_mixer(
                cfg, kind, lp, h, k_cache, v_cache, positions, valid,
                attn_impl)
        elif kind is not None and kind.attn == "ssd":
            attn, k_cache, v_cache = _ssd_mixer(
                cfg, kind, lp, h, k_cache, v_cache, positions, valid,
                attn_impl)
        elif kind is not None and kind.attn == "lightning":
            attn, k_cache, v_cache = _lightning_mixer(
                cfg, kind, lp, h, k_cache, v_cache, positions, valid,
                attn_impl)
        elif cfg.latent_kv:  # the cache is ``k_cache`` alone: no ``v_cache``
            attn, k_cache = _latent_attention(cfg, lp, h, k_cache, positions,
                                              cache_start, attn_impl)
        else:
            attn, k_cache, v_cache = _kv_attention(
                cfg, lp, h, k_cache, v_cache, positions, cache_start, slopes,
                tp_axis, attn_impl)
        is_ssd = kind is not None and kind.attn == "ssd"
        with (jax.named_scope("ssd_out_proj") if is_ssd
              else contextlib.nullcontext()):
            attn = dense(attn, lp["wo"], "bsd,dh->bsh")
        if tp_axis is not None:
            attn = jax.lax.psum(attn, tp_axis)
        if cfg.attn_layernorm:
            attn = attn + lp["bo"]
        if cfg.sandwich_norm:
            attn = rms_norm(attn, lp["attn_post_norm_w"], cfg.norm_eps)
        if rm != 1.0:
            attn = attn * jnp.asarray(rm, attn.dtype)
        if n:
            streams = hc_write(streams, attn, coef)
        else:
            x = x + attn

    rows = None
    if has_mlp:
        if n:
            x, coef = hc_read("mlp", streams)
        h = normed(x, "mlp")
        if moe_stats and cfg.num_experts > 0:
            y, rows = _moe_routed(cfg, lp, h, tp_axis, valid)
        else:
            y = _mlp(cfg, lp, h, tp_axis, ep_axis, valid)
            if moe_stats:   # a dense MLP routes no row: a row of no expert
                rows = jnp.zeros((0,), jnp.int32)
        if cfg.sandwich_norm:
            y = rms_norm(y, lp["mlp_post_norm_w"], cfg.norm_eps)
        if rm != 1.0:
            y = y * jnp.asarray(rm, y.dtype)
        x = hc_write(streams, y, coef) if n else x + y
    elif n:
        x = streams
    if moe_stats:
        return x, k_cache, v_cache, rows
    return x, k_cache, v_cache


def _window_attn(window: int):
    """:func:`_default_attn` over a dense cache under a window: the cache
    keeps every token (nothing is freed) and the mask bounds the view."""

    def attn(q, k, v, k_cache, v_cache, positions, cache_start, slopes):
        k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v,
                                           cache_start)
        out = attention(q, k_cache, v_cache, positions,
                        cache_start + q.shape[1], slopes, window)
        return out, k_cache, v_cache

    return attn


def _period_blocks(params: StageParams, cfg: ModelConfig, x, cache: KVCache,
                   positions, cache_start, attn_impl, moe_stats, valid):
    """The blocks of a PERIOD model (docs/DESIGN.md section 25): the
    leading blocks, then a scan over the repeats whose body is one period,
    its unlike blocks in order.  Returns ``(x, keys, values, rows)``.

    ``cache.keys`` / ``cache.values`` hold one stack a POOL
    (``cfg.cache_kinds``: blocks that read alike share one) and ride the
    carry whole.  A block takes ``LayerOf(its pool, its plane)``, and its
    kind's hook: ``attn_impl.for_pool`` over a page pool (the table of
    that pool, the kind's window), a windowed :func:`_default_attn` over
    a dense cache.  The parameter stacks are one a kind
    (:func:`init_period_params`): the scan slices the small leaves by
    repeat and the block indexes its place, the expert stacks stay whole
    beside the scan (``LayerOf``, as in the one-kind scan)."""
    lead, P, R = cfg.lead_dense_layers, len(cfg.period), cfg.num_layers
    pools = len(cfg.cache_kinds)
    paged = getattr(attn_impl, "stacked_cache", False)
    if attn_impl is not None and not hasattr(attn_impl, "for_pool"):
        raise ValueError(
            "a model of more than one kind of block needs an attention "
            "hook that knows its pools (ops.paged_attention."
            "make_paged_attn_impl); this one serves one kind")

    def hook(name, kind, pool):
        if kind.attn == "none":     # no mixer: nothing to attend
            return None
        if kind.is_state:           # rows of the state pool, not pages
            return (attn_impl.for_state(name) if attn_impl is not None
                    else None)
        if kind.attn == "sparse":
            return attn_impl.for_sparse(pool, pools, kind, name)
        if attn_impl is not None:
            return attn_impl.for_pool(pool, pools, kind.window, name)
        return _window_attn(kind.window) if kind.window else None

    index_at = len(cfg.cache_kinds)     # (``ModelConfig.index_shape``)

    def block(block_cfg, lp, x, Ks, Vs, pool, plane, impl, stats):
        kc = vc = None      # a block without a mixer holds no cache
        sparse = (block_cfg.block_kind is not None
                  and block_cfg.block_kind.attn == "sparse")
        if pool is not None:
            pool %= len(Ks)     # -1, a state kind's (``cfg.state_arrays``)
            # the state pool goes whole, paged or not (``_kda_mixer``,
            # ``_ssd_mixer``)
            whole = paged or block_cfg.block_kind.is_state
            k_of, v_of = LayerOf(Ks[pool], plane), LayerOf(Vs[pool], plane)
            kc, vc = ((k_of, v_of) if whole
                      else (k_of.sliced(), v_of.sliced()))
            if sparse:      # its pages and, beside them, the index plane
                # (and the rows that hold a token, for the hook's counts)
                kc = (kc, LayerOf(Ks[index_at], plane), valid)
        x, kc, vc, *rows = _layer(block_cfg, lp, x, kc, vc, positions,
                                  cache_start, None, None, impl, None,
                                  stats, valid)
        if pool is not None and sparse:
            kc, ix, kept = kc
            Ks = Ks[:index_at] + (ix.stack,) + Ks[index_at + 1:]
        if rows and cfg.sparse_kind is not None:
            # a model with a sparse kind has no experts (``ModelConfig.
            # sparse_kind``): a block's row of counts is what its
            # selections kept (``ops.sparse_attention.kept_counts``)
            rows = [kept if sparse else jnp.zeros((3,), jnp.int32)]
        if pool is not None:
            K, V = ((kc.stack, vc.stack) if whole
                    else (k_of.updated(kc), v_of.updated(vc)))
            swap = lambda t, a: t[:pool] + (a,) + t[pool + 1:]
            Ks, Vs = swap(Ks, K), swap(Vs, V)
        return x, Ks, Vs, (rows[0] if rows else None)

    if cfg.sparse_kind is not None and attn_impl is None:
        raise ValueError(
            "a block-sparse kind reads an index plane beside its pages, "
            "which a dense cache does not have: serve it through the page "
            "pool (serve --batch-slots --prefill-chunk "
            "--mixed-token-budget)")
    Ks, Vs = tuple(cache.keys), tuple(cache.values)
    if cfg.state_planes:    # the state rides last: checked here, a trace
        cfg.state_arrays(Ks, Vs)
    # the leading blocks' paths are recorded under their kind's name where
    # the period has that kind too
    lead_name = next((n for n, k, _ in cfg.kinds if k == cfg.lead_kind),
                     "lead")
    for i in range(lead):
        pool, plane = cfg.plane_of(i)
        with jax.named_scope("lead_block"):
            x, Ks, Vs, _ = block(
                lead_block_config(cfg),
                jax.tree.map(lambda a: a[i], params.lead), x, Ks, Vs, pool,
                jnp.int32(plane), hook(lead_name, cfg.lead_kind, pool),
                False)

    # place p of the period: its kind's name and configuration, its index
    # among the kind's places, its pool and (plane at repeat 0, planes a
    # repeat adds in that pool)
    places = []
    for name, kind, at in cfg.kinds:
        for j, p in enumerate(at):
            pool, plane0 = cfg.plane_of(lead + p)
            if pool is None:
                plane0 = stride = 0
            else:
                stride = (cfg.plane_of(lead + P + p)[1] - plane0
                          if R > 1 else 0)
            places.append((p, name, kind, cfg.of_kind(kind), j, len(at),
                           pool, plane0, stride))
    places.sort()
    whole = _EXPERT_STACKS if cfg.num_experts > 0 else ()
    is_whole = lambda leaf: leaf.split(".")[0] in whole
    scanned = {k: v for k, v in params.layers.items() if not is_whole(k)}
    # [R, n, E, ...] -> [R n, E, ...]: the leading axes merge in place
    stacks = {k: jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), v)
              for k, v in params.layers.items() if is_whole(k)}

    def body(carry, xs):
        x, Ks, Vs = carry
        leaves, r = xs
        out_rows = []
        for (_, name, kind, kcfg, j, n, pool, plane0, stride) in places:
            tail = "." + name
            lp = {k[:-len(tail)]: jax.tree.map(lambda a: a[j], v)
                  for k, v in leaves.items() if k.endswith(tail)}
            lp.update({k[:-len(tail)]: LayerOf(v, r * n + j)
                       for k, v in stacks.items() if k.endswith(tail)})
            # (the scope names the block's kind: a trace splits a step by
            # it, whatever spans lie inside)
            with jax.named_scope(f"block_{name}"):
                x, Ks, Vs, rows = block(kcfg, lp, x, Ks, Vs, pool,
                                        plane0 + r * stride,
                                        hook(name, kind, pool),
                                        moe_stats and kind.mlp)
            if kind.mlp:        # a row of counts a block that has experts
                out_rows.append(rows)
        return (x, Ks, Vs), (jnp.stack(out_rows) if moe_stats else None)

    (x, Ks, Vs), rows = jax.lax.scan(body, (x, Ks, Vs),
                                     (scanned, jnp.arange(R)))
    if moe_stats:       # [R, blocks with experts, held] -> one row a block
        rows = rows.reshape((rows.shape[0] * rows.shape[1],)
                            + rows.shape[2:])
    return x, Ks, Vs, rows


def stage_forward(
    params: StageParams,
    cfg: ModelConfig,
    spec: StageSpec,
    inputs: jnp.ndarray,        # [b, s] int32 ids (first stage) or [b, s, H] hidden
    cache: KVCache,             # this stage's cache (num_layers = spec.num_layers)
    positions: jnp.ndarray,     # [b, s] absolute positions of the chunk
    tp_axis: Optional[str] = None,  # set inside shard_map for manual TP
    attn_impl=None,             # attention hook (see _default_attn)
    ep_axis: Optional[str] = None,  # expert-parallel MoE axis (shard_map)
    logits_at=None,             # [b] int32: the head's one position a row
    cache_in_carry: bool = True,  # in-place cache (inference) vs ys (train)
    moe_stats: bool = False,    # also return the experts' row counts
    valid: Optional[jnp.ndarray] = None,  # [b, s] rows that hold a token
):
    """Run this stage's layer range. Returns (hidden or logits, updated cache).

    ``moe_stats`` (a model with experts, inference layout): a third value,
    ``[layers, E]`` int32, the token-expert rows each layer call routed to
    each expert (the scheduler's routing counters read it).

    ``valid``: the rows of ``inputs`` that hold a token; the others enter
    no expert's group (``_moe_routed``).  Nothing else reads it, and
    ``None`` traces the program traced without it.

    ``logits_at`` says which positions want logits.  ``None``: all of
    them, ``[b, s, V]`` (training and scoring).  An int32 index along
    ``s``, one a row (``[b]``, or a scalar for every row): the last stage
    gathers ``x[b, logits_at[b]]`` after its last layer and runs the
    final norm and the head over those rows alone, ``[b, 1, V]`` (an
    index is read as ``lax.dynamic_slice`` reads one: a negative one
    counts from the end, and it is clamped into ``[0, s)``); ``[b, n]``:
    ``n`` positions a row, ``[b, n, V]`` (the merged call of
    ``runtime.engine``'s ``slab_step_body``, whose one row holds a slab's
    segments and the decoding rows).  Whoever
    samples reads one position a row: decode and a whole-prompt prefill
    pass ``s - 1``, a padded chunk its last real token's column, the
    mixed dispatch's slab each segment's ``seg_lens - 1``; a full
    ``[b, s, V]`` product is the head's whole weight times every
    position, and GBs of HBM at long prompts, for rows nobody reads.

    The stage seam replaces the reference's ``run_inference`` module boundary
    (``cpp/inference.cpp:145-218``): first stage embeds ids, last stage
    applies final norm + LM head.  Residual/skip routing between stages
    (reference ``LoadBalance.java:37-88`` dependencyMap machinery) is
    dissolved by construction — stages own whole decoder blocks, so the only
    inter-stage tensor is the [b, s, H] hidden state.
    """
    if spec.is_first:
        if jnp.issubdtype(inputs.dtype, jnp.floating):
            # pre-embedded [b, s, H] prefix (multimodal: projected vision
            # patches ++ token embeddings — models/vision.py); assumed to
            # be past the embedding pipeline incl. any bloom embed-norm.
            x = inputs.astype(cfg.dtype)
        else:
            x = embed_tokens(params, cfg, inputs)  # [b, s, H]
    else:
        x = inputs.astype(cfg.dtype)

    slopes = alibi_slopes(cfg.num_heads) if cfg.use_alibi else None
    if slopes is not None and tp_axis is not None:
        nh_local = params.layers["wq"].shape[-1] // cfg.head_dim
        slopes = jax.lax.dynamic_slice_in_dim(
            slopes, jax.lax.axis_index(tp_axis) * nh_local, nh_local, axis=0)
    cache_start = cache.length

    def final_norm(x):
        if cfg.attn_layernorm:
            return layer_norm(x, params.final_norm["w"],
                              params.final_norm["b"], cfg.norm_eps)
        return rms_norm(x, params.final_norm["w"], cfg.norm_eps,
                        cfg.norm_unit_offset)

    # a looped model (ouro): the layer scan below is the body of an outer
    # scan over ``ut_steps`` passes, so the program holds ONE layer body
    # however many passes run.  Pass ``t`` reads and writes its own planes
    # of the cache, ``t * L + l``, through the same seam a layer index
    # goes through; the final norm closes every pass.  Static Python
    # branches: with one pass the traced program is what it always was.
    # A scorer that knows nothing of latent attention hands over keys and
    # values a head.  Like a looped model's scratch of L planes (below):
    # for ONE call over a whole sequence from position 0 the cache is
    # read back only by the call that wrote it, so a latent model runs
    # that call over a cache of its own and hands the caller's back
    # untouched; exact there and nowhere else, so no engine builds one.
    foreign = None
    if cfg.latent_kv and attn_impl is None and (
            cache.values.size or cache.keys.shape[2:] != (
                1, cache.keys.shape[3], cfg.kv_page_shape[1])):
        foreign = cache
        cache = KVCache.create(cfg, cfg.num_layers, *inputs.shape[:2],
                               dtype=cache.keys.dtype)
    if cfg.period and attn_impl is None and not isinstance(cache.keys,
                                                           tuple):
        # the same courtesy for a period model, whose cache is a stack a
        # pool: one array of planes is a scorer's, and is handed back
        foreign = cache
        cache = KVCache.create(cfg, cfg.num_layers, *inputs.shape[:2],
                               dtype=cache.keys.dtype)
    T = cfg.ut_steps
    if cfg.period and (T > 1 or cfg.latent_kv or not spec.is_first
                       or not spec.is_last):
        require_one_kind(cfg, "a looped or latent model, or a stage of a "
                              "pipeline,")
    planes = jax.tree.leaves(cache.keys)[0].shape[0]
    # leading dense blocks (deepseek_v3) run once before the scan and hold
    # the cache's first planes; 0 for every other model
    lead = cfg.lead_dense_layers
    n_layers = (planes - lead if T == 1
                else params.layers["attn_norm_w"].shape[0])
    if lead and (T > 1 or not cache_in_carry or not spec.is_first):
        raise ValueError(
            f"a model with leading dense blocks (family {cfg.family!r}, "
            f"lead_dense_layers={lead}) runs on one stage, in one pass, in "
            f"the inference layout of the cache")
    if T > 1:
        if not (spec.is_first and spec.is_last):
            require_single_pass(cfg, "a pipeline stage")
        if not cache_in_carry:
            require_single_pass(cfg, "the training layout of the cache")
        # T x L planes: a cache that is read again (every serving path;
        # KVCache.create and the page pools are sized so).  L planes: a
        # scratch for ONE call over a whole sequence from position 0
        # (scoring): a pass overwrites what the pass before left and
        # reads back only what it wrote, which is exact there and
        # nowhere else, so no engine builds one.
        if planes not in (n_layers, T * n_layers):
            raise ValueError(
                f"a looped model's cache holds {T} x {n_layers} planes "
                f"(ModelConfig.kv_planes), or {n_layers} as a scratch for "
                f"one call over a whole sequence; got {planes}")
    own_planes = planes == T * n_layers
    if cfg.summary_kv and (tp_axis is not None or ep_axis is not None
                           or not cache_in_carry or not spec.is_first
                           or not spec.is_last):
        require_token_rows(cfg, "a mesh axis, a stage of a pipeline or "
                                "the training layout of the cache")
    if cfg.hc_streams:
        # n residual streams a token (docs/DESIGN.md section 28): the
        # embedding replicated, ``[b, s, n H]`` through the lead loop and
        # the scan.  One stage, one pass, one kind of block, no mesh axis,
        # the inference layout: nothing else has compiled or measured the
        # stream's two kernels, and the wire between stages would carry
        # ``n x H`` a token
        if (tp_axis is not None or ep_axis is not None or not cache_in_carry
                or not (spec.is_first and spec.is_last)):
            require_one_stream(cfg, "a mesh axis, a stage of a pipeline or "
                                    "the training layout of the cache")
        if T > 1 or cfg.period:
            require_one_stream(cfg, "a looped or period model")
        x = hc_ops.expand(x, cfg.hc_streams)
        if getattr(attn_impl, "parts", lambda: None)() is not None:
            # a merged call's rows (r C + B) are not whole tiles of the
            # streams' kernels: the streams ride the blocks padded to them
            # (``_layer``; one call a sublayer, not one a part)
            x = jnp.pad(x, ((0, 0), (0, hc_ops.whole_tiles(x.shape[1])
                                     - x.shape[1]), (0, 0)))
    if T > 1 or cfg.fp32_residual:
        # The stream rides the layer and pass scans in float32.  Every
        # matmul still takes the model's dtype (``_layer`` casts each
        # norm's output back) and the sublayers' outputs are added as
        # they come; what goes is the rounding of a stream that 2L
        # sublayers grow, 2L times a pass, which the next pass reads as
        # its input.  On the chip at ouro-2.6b's width, log-probabilities
        # over the whole vocabulary against the float32 reference
        # (tools/model_parity.py, PR 34): 0.122 with a bf16 stream,
        # 0.054-0.057 with this one, against 0.086 for ONE bf16 pass.
        x = x.astype(jnp.float32)

    if cfg.period:
        if tp_axis is not None or ep_axis is not None or not cache_in_carry:
            require_one_kind(cfg, "a mesh axis or the training layout of "
                                  "the cache")
        x, new_k, new_v, expert_rows = _period_blocks(
            params, cfg, x, cache, positions, cache_start, attn_impl,
            moe_stats, valid)
    elif cache_in_carry:
        # Inference layout: the full stacked cache rides the scan CARRY and
        # each iteration dynamic-slices its layer plane in/out — XLA keeps
        # the carry buffer in place, so a decode step writes one token
        # column instead of re-materializing every layer's whole
        # [b, nkv, max_seq, hd] plane as a stacked ys output.  Measured on
        # v5e (tinyllama, max_seq=2048): +16% decode tok/s at batch 8,
        # +57% at batch 64 over the ys layout.
        # The cache planes are pytrees, not bare arrays, when the pool
        # is quantized (ops.quant.QuantizedKVPages: narrow data + scale
        # leaves share the leading layer axis) — index/update per leaf.
        # One seam for what a layer must not get as a slice
        # (ops.stacked.LayerOf: the whole stack and the layer's index).
        # A slice that XLA fuses into its consumer is free; one made for
        # a custom call or a scatter is a copy in HBM, every layer call.
        # So the expert stacks stay whole beside the scan for the
        # grouped matmul's kernel (the capacity-slot EP path takes its
        # slices as before), and a PAGE POOL stays whole in the carry:
        # the hook made for it (``stacked_cache``) addresses
        # (layer, page) and hands the stacks back.  A dense cache is
        # sliced and updated here, as it always was.
        whole = (tuple(k for k in _EXPERT_STACKS if k in params.layers)
                 if cfg.num_experts > 0 and ep_axis is None else ())
        scanned_layers = {k: v for k, v in params.layers.items()
                          if k not in whole}
        stacked_cache = getattr(attn_impl, "stacked_cache", False)

        def block(block_cfg, lp, x, K, V, plane, stats):
            """One block over plane ``plane`` of the carried caches."""
            k_of, v_of = LayerOf(K, plane), LayerOf(V, plane)
            kc, vc = ((k_of, v_of) if stacked_cache
                      else (k_of.sliced(), v_of.sliced()))
            x, kc, vc, *rows = _layer(block_cfg, lp, x, kc, vc, positions,
                                      cache_start, slopes, tp_axis,
                                      attn_impl, ep_axis, stats, valid)
            K, V = ((kc.stack, vc.stack) if stacked_cache
                    else (k_of.updated(kc), v_of.updated(vc)))
            return (x, K, V), (rows[0] if rows else None)

        def run_layers(x, kv, plane0):
            def body(carry, scanned):
                lp, li = scanned
                lp = dict(lp, **{k: LayerOf(params.layers[k], li)
                                 for k in whole})
                plane = li if plane0 is None else plane0 + li
                return block(cfg, lp, *carry, plane, moe_stats)

            (x, K, V), rows = jax.lax.scan(
                body, (x, *kv), (scanned_layers, jnp.arange(n_layers)))
            return x, (K, V), rows

        kv = (cache.keys, cache.values)
        for i in range(lead):  # plane i, then the stack from plane ``lead``
            with jax.named_scope("lead_block"):
                (x, *kv), _ = block(
                    lead_block_config(cfg),
                    jax.tree.map(lambda a: a[i], params.lead), x, *kv,
                    jnp.int32(i), False)
        if T == 1:  # a plane's index is its layer's, after the lead
            x, (new_k, new_v), expert_rows = run_layers(x, kv, lead or None)
        else:
            def one_pass(carry, t):
                with jax.named_scope("ut_pass"):
                    x, kv, rows = run_layers(
                        *carry, t * n_layers if own_planes else 0)
                    return (final_norm(x), kv), rows

            (x, (new_k, new_v)), expert_rows = jax.lax.scan(
                one_pass, (x, kv), jnp.arange(T))
            if moe_stats:  # [T, L, E] -> one row a layer call
                expert_rows = expert_rows.reshape(
                    (T * n_layers,) + expert_rows.shape[2:])
    else:
        assert not moe_stats, "moe_stats is for the inference layout"
        # Training layout: per-layer cache planes as xs/ys.  Under
        # differentiation a big carry would be saved per scan iteration by
        # the VJP; ys keeps residuals at one cache's worth.
        def body(x, scanned):
            lp, kc, vc = scanned
            x, kc, vc = _layer(cfg, lp, x, kc, vc, positions, cache_start,
                               slopes, tp_axis, attn_impl, ep_axis,
                               valid=valid)
            return x, (kc, vc)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params.layers, cache.keys, cache.values))
    if foreign is not None:
        new_k, new_v = foreign.keys, foreign.values
    new_cache = KVCache(new_k, new_v, cache_start + inputs.shape[1])

    if spec.is_last:
        if logits_at is not None and jnp.ndim(logits_at) == 2:
            # several positions a row, [b, n]: a merged call's
            x = jnp.take_along_axis(x, logits_at[:, :, None], axis=1)
        elif logits_at is not None:
            at = jnp.broadcast_to(jnp.asarray(logits_at), x.shape[:1])
            assert jnp.issubdtype(at.dtype, jnp.integer), (
                f"logits_at is an index along s, not {at.dtype}")
            x = jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(
                row, i, 1, axis=0))(x, at)                 # [b, 1, H]
        if cfg.hc_streams:  # the final norm reads the streams' sum
            x = hc_ops.collapse(x, cfg.hc_streams)
        if T == 1:  # a looped model's last pass closed with it already
            x = final_norm(x)
        head = (params.embed["tokens"].T if cfg.tie_embeddings
                else params.lm_head["w"])
        if cfg.num_pred_heads > 1:  # the next token's head, the first
            head = head[:, :cfg.vocab_size]
        if cfg.fp32_logits:
            # the float32 normed row times the head in float32
            x = jnp.einsum("bsh,hv->bsv", x.astype(jnp.float32),
                           head.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
        else:
            x = jnp.einsum("bsh,hv->bsv", x.astype(cfg.dtype), head)
        if cfg.logits_scaling != 1.0:       # granite
            x = x / jnp.asarray(cfg.logits_scaling, x.dtype)
        if tp_axis is not None and x.shape[-1] != cfg.vocab_size:
            # vocab-parallel head: gather the logit shards so every rank
            # sees full logits at the sampling boundary.  Skipped when the
            # head was replicated (e.g. tied embeddings) and logits are
            # already full-width.
            x = jax.lax.all_gather(x, tp_axis, axis=-1, tiled=True)
    if moe_stats:
        return x, new_cache, expert_rows
    return x, new_cache
