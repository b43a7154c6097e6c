"""Model catalog: every family the reference supports plus BASELINE targets.

Replaces the reference's hardcoded catalog (``data/Data.kt:19-33``:
bloom560m/1b1/1b7/3b/7b each +- int8) and the per-model branches in
``server.py:796-801`` / ``init_server.py:131-136``.  Quantized variants are a
runtime dtype choice here (``-int8`` suffix), not separate exports.

Also provides tiny "-test" configs for fast unit tests and virtual-mesh
dry runs.
"""

import math

from .base import BlockKind, ModelConfig


def _bloom(hidden, layers, heads, vocab=250880) -> ModelConfig:
    return ModelConfig(
        family="bloom", vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, num_kv_heads=heads, intermediate_size=4 * hidden,
        max_seq_len=2048, use_alibi=True, use_rope=False, attn_layernorm=True,
        tie_embeddings=True, norm_eps=1e-5)


LAGUNA_TEST_WINDOW = BlockKind(attn="window", window=8, num_heads=6,
                               rope_theta=10000.0, gate="per-head")
LAGUNA_TEST_FULL = BlockKind(attn="full", num_heads=4, rope_theta=500000.0,
                             rotary_share=0.5,
                             yarn=(8.0, 32.0, 4.0, 1.0, 1.2079441541679836),
                             gate="per-head")
SOLAR_TEST_FULL = BlockKind(attn="full", num_heads=4, rotary_share=0.0,
                            gate="elementwise")
SOLAR_TEST_KDA = BlockKind(attn="kda", num_heads=4, rotary_share=0.0,
                           conv=4)
GRANITE_TEST_FULL = BlockKind(attn="full", num_heads=4, rotary_share=0.0)
GRANITE_TEST_SSD = BlockKind(attn="ssd", num_heads=4, rotary_share=0.0,
                             conv=4, state_heads=8, state_head_dim=16,
                             state_size=16, groups=1, chunk=8)

# nemotron_h's three kinds of block, each of ONE sublayer: a Mamba-2 mixer
# of 8 heads in 2 groups, a NoPE GQA attention, the experts
NEMOTRON_TEST_M = BlockKind(attn="ssd", num_heads=4, rotary_share=0.0,
                            conv=4, state_heads=8, state_head_dim=16,
                            state_size=16, groups=2, chunk=8, mlp=False)
NEMOTRON_TEST_A = BlockKind(attn="full", num_heads=4, rotary_share=0.0,
                            mlp=False)
NEMOTRON_TEST_E = BlockKind(attn="none")

# minicpm_sala's two kinds at toy size: block-sparse GQA with the published
# proportions (kernel = 2 x stride, block = 4 x stride) small enough that a
# context of a few hundred tokens selects, and Lightning linear attention
SALA_TEST_SPARSE = BlockKind(attn="sparse", num_heads=4, rotary_share=0.0,
                             gate="elementwise", qk_norm=True,
                             sparse_kernel=4, sparse_stride=2,
                             sparse_block=8, sparse_topk=3, sparse_init=1,
                             sparse_local=16, sparse_dense_len=48)
SALA_TEST_LIGHTNING = BlockKind(attn="lightning", num_heads=4,
                                gate="elementwise", qk_norm=True)

# xing4_0 (XingChen-AGI/Xing4.0-29B-A4B config.json): deepseek's YaRN on
# the 64 rope lanes of a latent head (factor 64 over 4,096 positions,
# beta 32 / 1; cos and sin times mscale / mscale_all_dim = 1) and the
# softmax scale's factor that goes with it, get_mscale(64, 1) ** 2 with
# get_mscale(s, m) = 0.1 m ln s + 1
XING_YARN = (64.0, 4096.0, 32.0, 1.0, 1.0)
XING_ATTN_SCALE = (0.1 * math.log(64.0) + 1.0) ** 2


def _xing(layers: int) -> ModelConfig:
    """Xing4.0-29B-A4B with ``layers`` expert blocks after its two leading
    dense ones (38 as published)."""
    return ModelConfig(
        family="xing4_0", vocab_size=131072, hidden_size=3584,
        num_layers=layers, num_heads=32, num_kv_heads=32,
        intermediate_size=1024, max_seq_len=262144, rope_theta=10000.0,
        norm_eps=1e-6, num_experts=64, experts_per_token=4,
        norm_topk_prob=True, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, lead_dense_layers=2,
        lead_intermediate_size=9216, num_shared_experts=1,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=2.0, hc_streams=4, hc_sinkhorn_iters=20,
        hc_eps=1e-6, hc_res_clamp=30.0, q_lora_rank=768, yarn=XING_YARN,
        attn_scale=XING_ATTN_SCALE)


MODEL_REGISTRY = {
    # --- bloom family (reference parity: data/Data.kt:19-33) ---
    "bloom560m": _bloom(1024, 24, 16),
    "bloom1b1": _bloom(1536, 24, 16),
    "bloom1b7": _bloom(2048, 24, 16),
    "bloom3b": _bloom(2560, 30, 32),
    "bloom7b1": _bloom(4096, 30, 32),
    # --- llama family (BASELINE.json configs 1-3) ---
    "tinyllama-1.1b": ModelConfig(
        family="llama", vocab_size=32000, hidden_size=2048, num_layers=22,
        num_heads=32, num_kv_heads=4, intermediate_size=5632,
        max_seq_len=2048, rope_theta=10000.0),
    "llama-3-8b": ModelConfig(
        family="llama", vocab_size=128256, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, intermediate_size=14336,
        max_seq_len=8192, rope_theta=500000.0),
    # --- qwen2 family (llama block + qkv biases; beyond-reference
    # breadth: the catalog pattern extends to new HF families without a
    # new decoder) ---
    "qwen2.5-7b": ModelConfig(
        family="qwen2", vocab_size=152064, hidden_size=3584, num_layers=28,
        num_heads=28, num_kv_heads=4, intermediate_size=18944,
        max_seq_len=32768, rope_theta=1000000.0, norm_eps=1e-6,
        attn_qkv_bias=True),
    "qwen2.5-0.5b": ModelConfig(
        family="qwen2", vocab_size=151936, hidden_size=896, num_layers=24,
        num_heads=14, num_kv_heads=2, intermediate_size=4864,
        max_seq_len=32768, rope_theta=1000000.0, norm_eps=1e-6,
        attn_qkv_bias=True, tie_embeddings=True),
    # --- gemma family (RMSNorm(1+w) folded at load, sqrt(H) embedding
    # scale, GeGLU, decoupled head_dim; gemma-2b is MQA) ---
    "gemma-7b": ModelConfig(
        family="gemma", vocab_size=256000, hidden_size=3072, num_layers=28,
        num_heads=16, num_kv_heads=16, intermediate_size=24576,
        max_seq_len=8192, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=True, head_dim_override=256, embed_scale=True,
        mlp_act="gelu_tanh"),
    "gemma-2b": ModelConfig(
        family="gemma", vocab_size=256000, hidden_size=2048, num_layers=18,
        num_heads=8, num_kv_heads=1, intermediate_size=16384,
        max_seq_len=8192, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=True, head_dim_override=256, embed_scale=True,
        mlp_act="gelu_tanh"),
    # --- mixtral MoE (BASELINE.json config 4) ---
    "mixtral-8x7b": ModelConfig(
        family="mixtral", vocab_size=32000, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, intermediate_size=14336,
        max_seq_len=8192, rope_theta=1000000.0, num_experts=8,
        experts_per_token=2),
    # --- olmoe (allenai/OLMoE-1B-7B-0125-Instruct config.json): MHA,
    # RMSNorm over the whole q and k projections, 64 experts of width
    # 1024 with 8 a token, softmax over all 64 and no renormalising ---
    "olmoe-1b-7b": ModelConfig(
        family="olmoe", vocab_size=50304, hidden_size=2048, num_layers=16,
        num_heads=16, num_kv_heads=16, intermediate_size=1024,
        max_seq_len=4096, rope_theta=10000.0, norm_eps=1e-5,
        num_experts=64, experts_per_token=8, qk_norm=True,
        norm_topk_prob=False),
    # --- ouro (ByteDance/Ouro-2.6B config.json; arXiv:2510.25741): a
    # LOOPED decoder.  The 48 llama-shaped layers (MHA, rope, SwiGLU, no
    # bias) run total_ut_steps = 4 times a token with the same weights,
    # each block norms its two sublayers' outputs as well as their inputs,
    # the final norm closes every pass, and a pass keeps its own K/V:
    # 192 planes.  early_exit_threshold is 1.0: all four passes always
    # run (the exit gate decides nothing and is not evaluated) ---
    "ouro-2.6b": ModelConfig(
        family="ouro", vocab_size=49152, hidden_size=2048, num_layers=48,
        num_heads=16, num_kv_heads=16, intermediate_size=5632,
        max_seq_len=65536, rope_theta=1000000.0, norm_eps=1e-6,
        ut_steps=4, sandwich_norm=True),
    # --- deepseek_v3 family: kanana-2-30b-a3b-instruct-2601
    # (kakaocorp, config.json).  Multi-head latent attention (kv_lora_rank
    # 512, q in one matrix, heads of 128 no-rope + 64 rope, values 128,
    # interleaved rope), ONE leading dense block of width 6144 and then 47
    # blocks of 128 experts of width 768, 6 a token, chosen by sigmoid +
    # bias (``noaux_tc``, one group), weighed by the sigmoid, renormalised
    # and scaled by 2.448, beside two shared experts.  ``num_layers``
    # counts the repeated stack: 1 + 47 = the published 48 ---
    "kanana-2-30b-a3b": ModelConfig(
        family="deepseek_v3", vocab_size=128256, hidden_size=2048,
        num_layers=47, num_heads=32, num_kv_heads=32, intermediate_size=768,
        max_seq_len=32768, rope_theta=1000000.0, norm_eps=1e-6,
        num_experts=128, experts_per_token=6, norm_topk_prob=True,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, lead_dense_layers=1, lead_intermediate_size=6144,
        num_shared_experts=2, router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=2.448),
    # --- xing4_0 family: Xing4.0-29B-A4B (``model_type: xing4_0``).
    # deepseek_v3's latent attention with a low-rank query (768) and YaRN,
    # 2 leading dense blocks of width 9216 and 38 blocks of 64 experts of
    # width 1024, 4 a token (sigmoid + bias, renormalised, x 2) beside one
    # shared expert; and FOUR residual streams a token, read, written and
    # mixed by manifold-constrained hyper-connections (``hc_streams``).
    # Its multi-token prediction module (``num_nextn_predict_layers`` 1)
    # is not loaded: the main model runs without it.  ``-7l``: the first
    # of seven pipeline stages' blocks (2 leading + 5 expert), what one
    # 16 GB chip holds in bf16 with the whole vocabulary ---
    "xing4.0-29b-a4b": _xing(38),
    "xing4.0-29b-a4b-7l": _xing(5),
    # --- evabyte (EvaByte/EvaByte config.json, ``model_type: evabyte``;
    # EVA attention, arXiv:2302.04542): a byte-level decoder, MHA of 32
    # heads of 128, SwiGLU, RMSNorm with gain 1 + w, a float32 residual
    # stream and float32 logits.  A query sees the exact keys of its own
    # 2,048-token window and every earlier window as 128 summaries, one
    # learned-pooled key and value a 16-token chunk, in one softmax; the
    # head holds 8 x 320 rows (byte t + 1 .. t + 8) and the served path
    # reads the first 320 ---
    "evabyte-6.5b": ModelConfig(
        family="evabyte", vocab_size=320, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=32, intermediate_size=11008,
        max_seq_len=32768, rope_theta=100000.0, norm_eps=1e-5,
        norm_unit_offset=True, fp32_residual=True, fp32_logits=True,
        eva_window=2048, eva_chunk=16, num_pred_heads=8),
    # --- tiny configs for tests and virtual-mesh dry runs ---
    "llama-test": ModelConfig(
        family="llama", vocab_size=256, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, intermediate_size=128, max_seq_len=128,
        dtype_name="float32"),
    "qwen2-test": ModelConfig(
        family="qwen2", vocab_size=256, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, intermediate_size=128, max_seq_len=128,
        attn_qkv_bias=True, dtype_name="float32"),
    "gemma-test": ModelConfig(
        family="gemma", vocab_size=256, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=1, intermediate_size=128, max_seq_len=128,
        tie_embeddings=True, head_dim_override=32, embed_scale=True,
        mlp_act="gelu_tanh", norm_eps=1e-6, dtype_name="float32"),
    "bloom-test": ModelConfig(
        family="bloom", vocab_size=256, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=4, intermediate_size=256, max_seq_len=128,
        use_alibi=True, use_rope=False, attn_layernorm=True,
        tie_embeddings=True, dtype_name="float32"),
    "mixtral-test": ModelConfig(
        family="mixtral", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, intermediate_size=128, max_seq_len=128,
        num_experts=4, experts_per_token=2, dtype_name="float32"),
    "olmoe-test": ModelConfig(
        family="olmoe", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, intermediate_size=32, max_seq_len=128,
        num_experts=8, experts_per_token=2, qk_norm=True,
        norm_topk_prob=False, dtype_name="float32"),
    # 4 layers x 3 passes: a test can tell the passes from the layers
    "ouro-test": ModelConfig(
        family="ouro", vocab_size=256, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=4, intermediate_size=128, max_seq_len=128,
        norm_eps=1e-6, ut_steps=3, sandwich_norm=True,
        dtype_name="float32"),
    # one leading dense block + 3 expert blocks, latent rank 32
    "kanana-test": ModelConfig(
        family="deepseek_v3", vocab_size=256, hidden_size=64, num_layers=3,
        num_heads=4, num_kv_heads=4, intermediate_size=32, max_seq_len=128,
        norm_eps=1e-6, num_experts=16, experts_per_token=3,
        norm_topk_prob=True, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, lead_dense_layers=1,
        lead_intermediate_size=96, num_shared_experts=1,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=2.448, dtype_name="float32"),
    # 2 leading dense + 2 expert blocks, four residual streams, a query
    # of rank 16, YaRN (factor 8 over 32 positions: the interpolated band
    # is reached inside 384) with its softmax scale
    "xing-bench-test": ModelConfig(
        family="xing4_0", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, intermediate_size=32, max_seq_len=384,
        norm_eps=1e-6, num_experts=16, experts_per_token=2,
        norm_topk_prob=True, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, lead_dense_layers=2,
        lead_intermediate_size=96, num_shared_experts=1,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=2.0, hc_streams=4, q_lora_rank=16,
        yarn=(8.0, 32.0, 4.0, 1.0, 1.0),
        attn_scale=(0.1 * math.log(8.0) + 1.0) ** 2, dtype_name="float32"),
    # window 16, chunk 2: with pages of 8 a summary page is one window
    # (8 chunks) and a window is 2 pages; 3 prediction heads
    "evabyte-test": ModelConfig(
        family="evabyte", vocab_size=64, hidden_size=64, num_layers=3,
        num_heads=4, num_kv_heads=4, intermediate_size=128,
        max_seq_len=128, rope_theta=100000.0, norm_eps=1e-5,
        norm_unit_offset=True, fp32_residual=True, fp32_logits=True,
        eva_window=16, eva_chunk=2, num_pred_heads=3,
        dtype_name="float32"),
    # a period of unlike blocks: one leading dense block (full attention),
    # then 2 repeats of (window, window, window, full); 6 / 4 query heads
    # over 2 kv heads, window 8, YaRN on half a head of the full kind, a
    # per-head output gate, and 4 of 16 routed experts held
    "laguna-test": ModelConfig(
        family="laguna", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim_override=16,
        intermediate_size=32, max_seq_len=256, norm_eps=1e-6,
        num_experts=16, experts_per_token=3, norm_topk_prob=True,
        lead_dense_layers=1, lead_intermediate_size=96,
        num_shared_experts=1, routed_scaling_factor=2.5,
        experts_held=(4, 0), dtype_name="float32",
        period=tuple([LAGUNA_TEST_WINDOW] * 3 + [LAGUNA_TEST_FULL]),
        lead_kind=LAGUNA_TEST_FULL),
    # 2 repeats of (full, kda, kda, kda): a gated full block without rope
    # (4 query heads over 2 kv heads) and three gated delta-rule blocks (4
    # heads, a recurrent state a request and a convolution of 4 taps: no
    # pages), a sigmoid router with a stored bias, 2 of 16 experts held
    "solar-open2-test": ModelConfig(
        family="solar_open2", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim_override=16,
        intermediate_size=32, max_seq_len=256, norm_eps=1e-5,
        num_experts=16, experts_per_token=4, norm_topk_prob=True,
        num_shared_experts=1, router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=1.0, experts_held=(2, 0),
        dtype_name="float32",
        period=tuple([SOLAR_TEST_FULL] + [SOLAR_TEST_KDA] * 3)),
    # granitemoehybrid at toy size, 2 repeats of (ssd, ssd, full, ssd):
    # Mamba-2 blocks (8 state heads of 16 x 16, one group, a convolution
    # of 4 taps with a bias, the scan in chunks of 8: a state a request,
    # no pages) around a NoPE GQA block (4 query heads over 2 kv heads,
    # softmax scale 1 / 16 = hd ** -0.5 x attn_scale), 6 of 12 experts
    # held, top-3 renormalised, a shared MLP of 2 x 32, a tied head, and
    # granite's four multipliers
    "granite-hybrid-test": ModelConfig(
        family="granite_moe_hybrid", vocab_size=256, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim_override=16,
        intermediate_size=32, max_seq_len=256, norm_eps=1e-5,
        tie_embeddings=True, num_experts=12, experts_per_token=3,
        norm_topk_prob=True, num_shared_experts=2, experts_held=(6, 0),
        attn_scale=0.25, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0,
        dtype_name="float32",
        period=tuple([GRANITE_TEST_SSD] * 2 + [GRANITE_TEST_FULL]
                     + [GRANITE_TEST_SSD])),
    # nemotron_h at toy size, 2 repeats of (M, E, M, *, E): every block ONE
    # sublayer.  M a Mamba-2 mixer (8 state heads of 16 x 16 in 2 groups,
    # B, C and the gated norm a group, a convolution of 4 taps with a
    # bias, chunks of 8: a state a request), * a NoPE GQA attention (4
    # query heads over 2 kv heads: pages), E the experts and no cache at
    # all: 8 experts of two matrices (relu2, width 24: no multiple of a
    # lane tile) top-2, 4 of them held, chosen by sigmoid score + bias and
    # weighed by the renormalised scores x 2.5, beside a shared one of 48
    "nemotron-h-test": ModelConfig(
        family="nemotron_h", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim_override=16,
        intermediate_size=24, max_seq_len=256, norm_eps=1e-5,
        mlp_act="relu2", num_experts=8, experts_per_token=2,
        norm_topk_prob=True, num_shared_experts=2,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, experts_held=(4, 0),
        dtype_name="float32",
        period=(NEMOTRON_TEST_M, NEMOTRON_TEST_E, NEMOTRON_TEST_M,
                NEMOTRON_TEST_A, NEMOTRON_TEST_E)),
    # minicpm_sala at toy size, 2 repeats of (sparse, lightning, lightning,
    # sparse): a dense SwiGLU in every block, muP's three multipliers, an
    # untied head; 4 query heads over 2 kv heads in the sparse kind, 4
    # heads of a [16, 16] float32 state in the linear one
    "minicpm-sala-test": ModelConfig(
        family="minicpm_sala", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim_override=16,
        intermediate_size=96, max_seq_len=512, norm_eps=1e-6,
        embedding_multiplier=12.0, residual_multiplier=1.4 / 32 ** 0.5,
        logits_scaling=4.0, dtype_name="float32",
        period=(SALA_TEST_SPARSE, SALA_TEST_LIGHTNING, SALA_TEST_LIGHTNING,
                SALA_TEST_SPARSE)),
}


def get_model_config(name: str) -> ModelConfig:
    """Resolve a model name; an ``-int8`` / ``-int4`` suffix selects
    weight-only quantization (the reference's quantized exports,
    ``data/Data.kt:19-33``, as a runtime transform — ops/quant.py; int4
    is group-wise and packs two weights per byte)."""
    base = name
    quant = "none"
    for suffix in ("-int8", "-int4"):
        if name.endswith(suffix):
            base = name[: -len(suffix)]
            quant = suffix[1:]
            break
    if base not in MODEL_REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(MODEL_REGISTRY)}")
    cfg = MODEL_REGISTRY[base]
    if quant != "none":
        cfg = cfg.replace(quantization=quant)
    return cfg


def get_model_family(name: str) -> str:
    return get_model_config(name).family
