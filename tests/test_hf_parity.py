"""Numerical parity against the HuggingFace reference implementations.

Every other model test in this suite is self-consistency (prefill vs decode,
pipeline vs engine) — a sign error in RoPE or ALiBi would pass all of them.
These tests earn external trust the way the reference implicitly does by
consuming HF exports (reference ``server.py:831-832``): instantiate the
*torch* reference model for each family on random weights, map its state
dict through ``models/loader.py``, and require logit-level agreement from
our jax decoder — for the full prompt (prefill path) and for the last token
produced via the KV-cached decode path.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_inference_demo_tpu.models import (  # noqa: E402
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models.decoder import (  # noqa: E402
    stage_forward)
from distributed_inference_demo_tpu.models.loader import (  # noqa: E402
    params_from_state_dict)


def _hf_model(name):
    """Build the HF twin of one of our tiny test configs."""
    cfg = get_model_config(name)
    if cfg.family in ("llama", "qwen2"):
        if cfg.family == "qwen2":
            hf_cfg = transformers.Qwen2Config(
                vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
                num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                intermediate_size=cfg.intermediate_size,
                max_position_embeddings=cfg.max_seq_len,
                rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                tie_word_embeddings=cfg.tie_embeddings)
            return cfg, transformers.Qwen2ForCausalLM(hf_cfg).float().eval()
        hf_cfg = transformers.LlamaConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.num_kv_heads,
            intermediate_size=cfg.intermediate_size,
            max_position_embeddings=cfg.max_seq_len,
            rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
            attention_bias=False, mlp_bias=False,
            tie_word_embeddings=cfg.tie_embeddings)
        model = transformers.LlamaForCausalLM(hf_cfg)
    elif cfg.family == "gemma":
        hf_cfg = transformers.GemmaConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.num_kv_heads,
            intermediate_size=cfg.intermediate_size,
            head_dim=cfg.head_dim, hidden_act="gelu_pytorch_tanh",
            hidden_activation="gelu_pytorch_tanh",
            max_position_embeddings=cfg.max_seq_len,
            rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
            tie_word_embeddings=cfg.tie_embeddings,
            attention_bias=False)
        model = transformers.GemmaForCausalLM(hf_cfg)
    elif cfg.family == "bloom":
        hf_cfg = transformers.BloomConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            n_layer=cfg.num_layers, n_head=cfg.num_heads,
            layer_norm_epsilon=cfg.norm_eps)
        model = transformers.BloomForCausalLM(hf_cfg)
    elif cfg.family == "mixtral":
        hf_cfg = transformers.MixtralConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.num_kv_heads,
            intermediate_size=cfg.intermediate_size,
            num_local_experts=cfg.num_experts,
            num_experts_per_tok=cfg.experts_per_token,
            max_position_embeddings=cfg.max_seq_len,
            rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
            tie_word_embeddings=cfg.tie_embeddings)
        model = transformers.MixtralForCausalLM(hf_cfg)
    elif cfg.family == "olmoe":
        hf_cfg = transformers.OlmoeConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.num_kv_heads,
            intermediate_size=cfg.intermediate_size,
            num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.experts_per_token,
            norm_topk_prob=cfg.norm_topk_prob,
            max_position_embeddings=cfg.max_seq_len,
            rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
            tie_word_embeddings=cfg.tie_embeddings)
        model = transformers.OlmoeForCausalLM(hf_cfg)
        # HF initialises every norm weight to 1; the q/k norms must not
        # pass by being the identity scale
        with torch.no_grad():
            for name, p in model.named_parameters():
                if name.endswith(("q_norm.weight", "k_norm.weight")):
                    p.copy_(1.0 + 0.3 * torch.randn_like(p))
    else:
        raise AssertionError(cfg.family)
    model = model.float().eval()
    return cfg, model


def _our_params(cfg, model):
    sd = {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}
    return params_from_state_dict(sd, cfg)


def _hf_logits(model, ids):
    with torch.no_grad():
        out = model(input_ids=torch.from_numpy(ids).long())
    return out.logits.float().numpy()


PROMPT = np.array([[5, 17, 42, 7, 99, 3, 12, 56, 200, 131]], dtype=np.int32)

FAMILIES = ["llama-test", "qwen2-test", "gemma-test", "bloom-test",
            "mixtral-test", "olmoe-test"]


@pytest.mark.parametrize("name", FAMILIES)
def test_prefill_logits_match_transformers(name):
    torch.manual_seed(0)
    cfg, model = _hf_model(name)
    params = _our_params(cfg, model)
    want = _hf_logits(model, PROMPT)

    spec = StageSpec(0, 1, 0, cfg.num_layers)
    pos = jnp.broadcast_to(jnp.arange(PROMPT.shape[1]), PROMPT.shape)
    got, _ = stage_forward(params, cfg, spec, jnp.asarray(PROMPT),
                           KVCache.create(cfg, cfg.num_layers, 1, 32), pos)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", FAMILIES)
def test_decode_step_matches_transformers(name):
    """KV-cached decode: prefill on the first n-1 tokens, decode token n;
    the decode-path logits must equal HF's full-sequence last-position
    logits (catches cache layout / position-offset bugs prefill can't)."""
    torch.manual_seed(0)
    cfg, model = _hf_model(name)
    params = _our_params(cfg, model)
    want = _hf_logits(model, PROMPT)[:, -1, :]

    spec = StageSpec(0, 1, 0, cfg.num_layers)
    head, last = PROMPT[:, :-1], PROMPT[:, -1:]
    pos_head = jnp.broadcast_to(jnp.arange(head.shape[1]), head.shape)
    cache = KVCache.create(cfg, cfg.num_layers, 1, 32)
    _, cache = stage_forward(params, cfg, spec, jnp.asarray(head), cache,
                             pos_head)
    pos_last = jnp.full((1, 1), head.shape[1])
    got, _ = stage_forward(params, cfg, spec, jnp.asarray(last), cache,
                           pos_last)
    np.testing.assert_allclose(np.asarray(got)[:, -1, :], want,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", FAMILIES)
def test_save_pretrained_roundtrip_loads(name, tmp_path):
    """load_or_init consumes an HF ``save_pretrained`` safetensors directory
    for every family (closes the reference's ModelCard load path for
    bloom/mixtral, SURVEY.md §2.2)."""
    from distributed_inference_demo_tpu.models.loader import load_or_init
    torch.manual_seed(0)
    cfg, model = _hf_model(name)
    model.save_pretrained(tmp_path, safe_serialization=True)
    params = load_or_init(name, cfg, checkpoint_dir=str(tmp_path))
    want = _hf_logits(model, PROMPT)

    spec = StageSpec(0, 1, 0, cfg.num_layers)
    pos = jnp.broadcast_to(jnp.arange(PROMPT.shape[1]), PROMPT.shape)
    got, _ = stage_forward(params, cfg, spec, jnp.asarray(PROMPT),
                           KVCache.create(cfg, cfg.num_layers, 1, 32), pos)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", FAMILIES)
def test_checkpoint_to_serving_e2e(name, tmp_path):
    """The whole checkpoint->serving story in one test per family:
    HF ``save_pretrained`` safetensors -> load_or_init -> the CLI's
    engine path -> greedy generation that MATCHES the torch reference's
    own greedy decode token-for-token (the reference's ModelCard
    load/split/serve pipeline, SURVEY.md §2.2, as a product-surface
    check rather than a logit fragment)."""
    import io
    import json as _json
    from contextlib import redirect_stdout

    from distributed_inference_demo_tpu import cli

    torch.manual_seed(0)
    cfg, model = _hf_model(name)
    model.save_pretrained(tmp_path, safe_serialization=True)

    new_tokens = 8
    with torch.no_grad():
        hf_out = model.generate(
            torch.tensor(np.asarray(PROMPT)), do_sample=False,
            max_new_tokens=new_tokens, use_cache=True)
    want = hf_out[0, PROMPT.shape[1]:].tolist()

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([
            "generate", "--model", name, "--checkpoint", str(tmp_path),
            "--prompt-ids", ",".join(str(int(t)) for t in PROMPT[0]),
            "--max-new-tokens", str(new_tokens), "--greedy",
            "--max-seq", "32", "--attn-backend", "jnp"])
    assert rc == 0
    got = _json.loads(buf.getvalue())["tokens"][0]
    assert got == want


# ---------------------------------------------------------------------------
# vision tower vs HF CLIPVisionModel (the LLaVA stage-0 geometry)

def _tiny_clip():
    from distributed_inference_demo_tpu.models.vision import VisionConfig
    vcfg = VisionConfig(image_size=28, patch_size=14, hidden_size=32,
                        num_layers=3, num_heads=4, intermediate_size=64,
                        dtype_name="float32", clip_arch=True,
                        feature_layer=-2, hidden_act="quick_gelu")
    hf_cfg = transformers.CLIPVisionConfig(
        image_size=28, patch_size=14, hidden_size=32,
        num_hidden_layers=3, num_attention_heads=4, intermediate_size=64,
        hidden_act="quick_gelu", layer_norm_eps=vcfg.norm_eps)
    model = transformers.CLIPVisionModel(hf_cfg).float().eval()
    return vcfg, model


@pytest.mark.slow
def test_vision_tower_matches_clip():
    """clip_arch + feature_layer=-2 reproduces HF hidden_states[-2] minus
    the class token — the exact feature LLaVA-1.5 projects.  The weights
    travel through the checkpoint mapper, so this also pins the state
    dict name/transpose mapping.  The (seed-initialized) projector is
    applied to the HF features with the same jnp math, so any feature
    mismatch surfaces as an output mismatch."""
    from distributed_inference_demo_tpu.models.loader import (
        vision_params_from_clip_state_dict)
    from distributed_inference_demo_tpu.models.vision import vision_forward

    vcfg, model = _tiny_clip()
    sd = {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}
    params = vision_params_from_clip_state_dict(sd, vcfg, decoder_hidden=16)
    rs = np.random.RandomState(0)
    pixels = rs.randn(2, 28, 28, 3).astype(np.float32)
    with torch.no_grad():
        hf = model(pixel_values=torch.from_numpy(
            pixels.transpose(0, 3, 1, 2)), output_hidden_states=True)
    want = hf.hidden_states[-2][:, 1:].numpy()          # drop cls

    got = np.asarray(vision_forward(params, vcfg, jnp.asarray(pixels)))
    h = jnp.asarray(want) @ params["proj_w1"] + params["proj_b1"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(jnp.float32)
    expected = np.asarray(h @ params["proj_w2"] + params["proj_b2"])
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)


def test_vision_clip_rejects_plain_tower():
    from distributed_inference_demo_tpu.models.loader import (
        vision_params_from_clip_state_dict)
    from distributed_inference_demo_tpu.models.vision import VisionConfig
    vcfg = VisionConfig(image_size=28, patch_size=14, hidden_size=32,
                        num_layers=2, num_heads=4, intermediate_size=64)
    with pytest.raises(ValueError, match="clip_arch"):
        vision_params_from_clip_state_dict({}, vcfg, decoder_hidden=16)
