"""The xing4_0 family (Xing4.0-29B-A4B: four residual streams a token mixed
by manifold-constrained hyper-connections, latent attention with a low-rank
query and YaRN, sigmoid-routed experts beside a shared one) at toy size on
the CPU.

``xing-bench-test`` has 2 leading dense blocks and 2 expert blocks (16
experts, 2 a token, 1 shared, latent rank 32, query rank 16, 4 streams).
The oracle is the benchmark's plain float32 reference
(``benchmark/families/xing4_0.py`` through ``benchmark/reference.py``): the
equations as published, ``[T, n, H]`` streams, ``[T, n, n]`` maps, no
cache, no kernel, no line of the program.  (That every other model is
what it was is ``tests/test_program_pins.py``'s to hold.)
"""

import dataclasses
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

from distributed_inference_demo_tpu.models import (        # noqa: E402
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models import decoder  # noqa: E402
from distributed_inference_demo_tpu.models.base import (   # noqa: E402
    ModelConfig, slice_stage, split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (  # noqa: E402
    init_full_params, stage_forward)
from distributed_inference_demo_tpu.ops import (           # noqa: E402
    hyper_connection as hc)
from distributed_inference_demo_tpu.ops.rope import (      # noqa: E402
    apply_rope_interleaved, rope_frequencies, yarn_frequencies)
from distributed_inference_demo_tpu.ops.sampling import (  # noqa: E402
    SamplingParams)
from distributed_inference_demo_tpu.runtime.batching import (  # noqa: E402
    ContinuousBatchingEngine)

CFG = get_model_config("xing-bench-test")
L, LEAD, N = CFG.num_layers, CFG.lead_dense_layers, CFG.hc_streams
GREEDY = SamplingParams(temperature=0.0)
FIELDS = dataclasses.asdict(CFG)        # what the reference is given
SPEC = StageSpec(0, 1, 0, L)
TOL = 2e-4
HC = CFG.hc_args


def _seeded(cfg=CFG):
    """Seeded weights with the norm weights moved off one, so that a norm
    left out changes the logits."""
    p = init_full_params(jax.random.PRNGKey(0), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    for tree in (p.layers, p.lead):
        for name in ("attn_norm_w", "mlp_norm_w", "kv_norm_w", "q_a_norm_w"):
            tree[name] = (1.0 + 0.3 * jax.random.normal(
                next(keys), tree[name].shape)).astype(tree[name].dtype)
    return p


@pytest.fixture(scope="module")
def params():
    return _seeded()


IDS = jnp.asarray([[(7 * i + 3) % CFG.vocab_size for i in range(90)]])
N_PROMPT = 74


def _served_logprobs(p, cfg=CFG):
    """The program's log-probability of tokens ``N_PROMPT..`` of ``IDS``:
    one forward over the whole sequence through the dense latent cache."""
    T = IDS.shape[1]
    logits, _ = stage_forward(p, cfg, SPEC, IDS,
                              KVCache.create(cfg, L, 1, 96),
                              jnp.arange(T)[None])
    lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
    return [float(lp[t - 1, IDS[0, t]]) for t in range(N_PROMPT, T)]


def _reference(p, fields=FIELDS):
    import reference
    # (the family's check also holds a reply's reading of the served
    # maps: sound here, ``benchmark/tests/test_xing4_0_family.py`` has the
    # cases that are not)
    return reference.emitted_logprobs(
        p, fields, [int(t) for t in IDS[0]], N_PROMPT,
        {"hc_sinkhorn_residual": 0.0})["logprobs"]


# ----------------------------------------------- the config and its sizes

def test_registry_entries_are_the_published_config():
    cfg = get_model_config("xing4.0-29b-a4b")
    assert (cfg.family, cfg.total_layers, cfg.lead_dense_layers,
            cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (
        "xing4_0", 40, 2, 3584, 32, 131072)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 768, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.num_shared_experts,
            cfg.intermediate_size, cfg.lead_intermediate_size,
            cfg.routed_scaling_factor) == (64, 4, 1, 1024, 9216, 2.0)
    assert (cfg.hc_streams, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp, cfg.hc_maps) == (4, 20, 1e-6, 30.0, 24)
    assert cfg.yarn == (64.0, 4096.0, 32.0, 1.0, 1.0)
    # deepseek's mscale ** 2 under YaRN, and the scale it multiplies
    assert cfg.attn_scale == pytest.approx(1.41589 ** 2, rel=1e-5)
    assert cfg.latent_scale == pytest.approx(192 ** -0.5 * 2.00474, rel=1e-5)
    cut = get_model_config("xing4.0-29b-a4b-7l")
    assert cut == cfg.replace(num_layers=5) and cut.total_layers == 7
    assert get_model_config("llama-test").hc_streams == 0


def test_a_config_built_from_json_is_the_registry_s():
    """The benchmark's replica registers ``ModelConfig(**model_config)``
    from lists and a dict: the same (hashable) configuration."""
    fields = json.loads(json.dumps(dataclasses.asdict(
        get_model_config("xing4.0-29b-a4b-7l"))))
    fields = {k: v for k, v in fields.items()
              if v != getattr(ModelConfig(), k)}
    assert ModelConfig(**fields) == get_model_config("xing4.0-29b-a4b-7l")
    by_name = dict(fields, yarn={
        "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.0})
    assert ModelConfig(**by_name) == ModelConfig(**fields)


def test_the_leaves_of_a_block(params):
    H, maps = CFG.hidden_size, CFG.hc_maps
    for tree, count in ((params.lead, LEAD), (params.layers, L)):
        for sub in ("attn", "mlp"):
            assert tree[f"hc_{sub}_phi"].shape == (count, maps, N * H)
            assert tree[f"hc_{sub}_alpha"].shape == (count, 3)
            assert tree[f"hc_{sub}_b"].shape == (count, maps)
            assert all(tree[f"hc_{sub}_{leaf}"].dtype == jnp.float32
                       for leaf in ("phi", "alpha", "b"))
        assert tree["wq_a"].shape == (count, H, CFG.q_lora_rank)
        assert tree["q_a_norm_w"].shape == (count, CFG.q_lora_rank)
        assert tree["wq"].shape == (count, CFG.q_lora_rank,
                                    CFG.num_heads * 24)
    assert "hc_attn_phi" not in init_full_params(
        jax.random.PRNGKey(0), get_model_config("kanana-test")).layers


# ------------------------------------- logits against the plain reference

@pytest.mark.parametrize("dtype,tol", [("float32", TOL), ("bfloat16", 0.1)])
def test_stage_forward_equals_the_reference(dtype, tol):
    """The whole sequence at once: the log-probability of every next token
    against the family's reference on the same leaves; the positions read
    lie past YaRN's original 32, in its interpolated band."""
    cfg = CFG.replace(dtype_name=dtype)
    p = _seeded(cfg)
    assert _served_logprobs(p, cfg) == pytest.approx(
        _reference(p, dataclasses.asdict(cfg)), abs=tol)


def _one_sinkhorn_step(monkeypatch):
    return CFG.replace(hc_sinkhorn_iters=1)


def _h_post_without_its_factor(monkeypatch):
    inner = hc._coefficients

    def halved(*a, **k):
        rows = inner(*a, **k)
        return rows[:N] + [0.5 * r for r in rows[N:2 * N]] + rows[2 * N:]

    monkeypatch.setattr(hc, "_coefficients", halved)
    return CFG


def _scale_without_mscale(monkeypatch):
    return CFG.replace(attn_scale=1.0)


def _query_norm_left_out(monkeypatch):
    inner = decoder.rms_norm
    monkeypatch.setattr(
        decoder, "rms_norm", lambda x, w, *a, **k: (
            x if w.shape[-1] == CFG.q_lora_rank else inner(x, w, *a, **k)))
    return CFG


def _collapse_reads_stream_zero(monkeypatch):
    monkeypatch.setattr(
        hc, "collapse",
        lambda x, n: x[..., :x.shape[-1] // n].astype(jnp.float32))
    return CFG


def _plain_rope(monkeypatch):
    return CFG.replace(yarn=())


CONTROLS = {
    "one Sinkhorn step": _one_sinkhorn_step,
    "h_post without the factor 2": _h_post_without_its_factor,
    "the softmax scale without mscale squared": _scale_without_mscale,
    "q_a_layernorm left out": _query_norm_left_out,
    "the collapse reads stream 0": _collapse_reads_stream_zero,
    "rope without YaRN": _plain_rope,
}


@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_a_fault_moves_the_logprobs_past_the_tolerance(fault, params,
                                                       monkeypatch):
    """The comparison above sees each mechanism: with it broken in the
    program the same positions read further from the reference than the
    tolerance, by a factor of ten and more."""
    cfg = CONTROLS[fault](monkeypatch)
    got, want = _served_logprobs(params, cfg), _reference(params)
    assert max(abs(a - b) for a, b in zip(got, want)) > 10 * TOL


# ------------------------------------------------------- the three maps

def _streams(T, H, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = (2.0 * jax.random.normal(k[0], (T, N * H))).astype(dtype)
    y = jax.random.normal(k[1], (T, H)).astype(dtype)
    phi = jax.random.normal(k[2], (24, N * H)) * (N * H) ** -0.5
    b = (jnp.concatenate([jnp.zeros(8), 1.5 * jnp.eye(4).reshape(-1)])
         + 0.25 * jax.random.normal(k[3], (24,)))
    return x, y, phi, jnp.asarray([0.5, 0.5, 0.25]), b


@pytest.mark.parametrize("iters,holds", [(20, True), (1, False)])
def test_sinkhorn_makes_the_map_doubly_stochastic(iters, holds, params):
    """On the seeded model's own leaves and streams: after 20 steps every
    row and column of every token's map sums to 1 within 1e-5; after one
    step some column is further than 1e-2 from it."""
    x = hc.expand(decoder.embed_tokens(params, CFG, IDS)[0], N)
    worst = 0.0
    for tree in (params.lead, params.layers):
        for j in range(2):
            for sub in ("attn", "mlp"):
                _, coef = hc.hc_pre(
                    x, tree[f"hc_{sub}_phi"][j], tree[f"hc_{sub}_alpha"][j],
                    tree[f"hc_{sub}_b"][j], **dict(HC, iters=iters))
                res = float(hc.sinkhorn_residual(coef, x.shape[0], N))
                worst = max(worst, res)
                assert (res < 1e-5) == holds, (sub, j, res)
    assert holds or worst > 1e-2


def test_the_seeded_maps_are_neither_constant_nor_trivial(params):
    """``init_layer_params``' draw: over the tokens of a sequence the
    doubly-stochastic map stands more than 0.1 an entry from the identity
    and from the uniform map, and h_pre and h_post spread over more than
    0.2: a path that left a map out could not pass for one that has it."""
    x = hc.expand(decoder.embed_tokens(params, CFG, IDS)[0], N)
    T = x.shape[0]
    for tree in (params.lead, params.layers):
        for sub in ("attn", "mlp"):
            _, coef = hc.hc_pre(x, tree[f"hc_{sub}_phi"][0],
                                tree[f"hc_{sub}_alpha"][0],
                                tree[f"hc_{sub}_b"][0], **HC)
            rows = np.asarray(hc.coef_rows(coef, T, N))
            res = rows[2 * N:].reshape(N, N, T)
            assert np.abs(res - np.eye(N)[:, :, None]).mean() > 0.1
            assert np.abs(res - 1.0 / N).mean() > 0.1
            assert np.ptp(rows[:N]) > 0.2 and np.ptp(rows[N:2 * N]) > 0.2
            assert rows[N:2 * N].max() > 1.0        # 2 x a sigmoid


@pytest.mark.parametrize("dtype,T,H", [
    ("float32", 16, 128), ("float32", 256, 128), ("bfloat16", 32, 256),
    ("bfloat16", 128, 128)])
def test_the_kernels_equal_the_plain_path(dtype, T, H):
    """Both Pallas calls in interpret mode against ``jax.numpy``: one
    grid step of few tokens (a decode step's shape), whole steps of 128 (a
    slab's), float32 streams (the matmul at the highest precision) and
    bf16 ones (``phi`` split in three)."""
    x, y, phi, alpha, b = _streams(T, H, jnp.dtype(dtype))
    h0, c0 = hc.hc_pre(x, phi, alpha, b, **HC)
    h1, c1 = hc.hc_pre(x, phi, alpha, b, interpret=True, **HC)
    assert c0.ndim == 2 and c1.ndim == 3        # rows, and a step's slabs
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(hc.coef_rows(c1, T, N)), f32(c0),
                               atol=2e-6)
    tol = 2e-5 if dtype == "float32" else 0.07
    np.testing.assert_allclose(f32(h1), f32(h0), atol=tol)
    o0 = hc.hc_post(x, y, c0, n=N)
    o1 = hc.hc_post(x, y, c1, n=N, interpret=True)
    np.testing.assert_allclose(f32(o1), f32(o0), atol=tol)
    # and against the definition with [T, n, n] maps
    xs = f32(x).reshape(T, N, H)
    rows = f32(c0)
    want = (np.einsum("ijt,tjh->tih", rows[2 * N:].reshape(N, N, T), xs)
            + rows[N:2 * N].T[:, :, None] * f32(y)[:, None, :])
    np.testing.assert_allclose(f32(o0).reshape(T, N, H), want, atol=tol)
    np.testing.assert_allclose(
        f32(h0), np.einsum("nt,tnh->th", rows[:N], xs), atol=tol)


def test_which_shapes_the_kernels_take():
    ok = lambda *a, **k: hc.on_kernel(*a, **k)[0]
    assert ok(512, 4 * 3584, 4, platform="tpu")
    assert ok(16, 4 * 3584, 4, platform="tpu")
    assert not ok(16, 4 * 3584, 4, platform="cpu")
    assert ok(16, 4 * 3584, 4, "pallas", platform="cpu")
    assert not ok(16, 4 * 3584, 4, "xla", platform="tpu")
    assert "64 lanes" in hc.on_kernel(16, 4 * 64, 4, platform="tpu")[1]
    assert "200 tokens" in hc.on_kernel(200, 4 * 128, 4, platform="tpu")[1]
    assert "8 tokens" in hc.on_kernel(8, 4 * 128, 4, platform="tpu")[1]


def test_expand_and_collapse():
    x = jnp.arange(12.0).reshape(2, 6)
    wide = hc.expand(x, 3)
    assert wide.shape == (2, 18)
    np.testing.assert_array_equal(wide[:, 6:12], x)
    np.testing.assert_array_equal(hc.collapse(wide, 3), 3 * x)


# ------------------------------------------ the query, the rope, the scale

def test_yarn_bites_inside_the_test_model_s_positions():
    """The test model's YaRN (factor 8 over 32 positions): the slowest
    pairs turn 8 times slower than plain rope, the fastest as before, and
    the softmax scale carries deepseek's mscale squared."""
    factor, original, fast, slow, gain = CFG.yarn
    d = CFG.qk_rope_head_dim
    plain = np.asarray(rope_frequencies(d, CFG.rope_theta))
    yarn = np.asarray(yarn_frequencies(d, CFG.rope_theta, factor, original,
                                       fast, slow))
    assert yarn[0] == pytest.approx(plain[0])
    assert yarn[-1] == pytest.approx(plain[-1] / factor)
    assert CFG.attn_scale == pytest.approx((0.1 * math.log(8) + 1) ** 2)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, d))
    pos = jnp.asarray([[0, 1, 40, 200, 383]])
    a = apply_rope_interleaved(x, pos, CFG.rope_theta, CFG.yarn)
    b = apply_rope_interleaved(x, pos, CFG.rope_theta)
    np.testing.assert_allclose(a[:, 0], b[:, 0], atol=1e-6)  # position 0
    assert float(jnp.abs(a[:, 2:] - b[:, 2:]).max()) > 0.1
    # the family's own frequencies (no line of the program) agree
    from families import xing4_0
    assert xing4_0.yarn_inv_freq(d, CFG.rope_theta, CFG.yarn) == \
        pytest.approx(list(yarn), rel=1e-6)


# --------------------------------------------- what refuses, in a sentence

PLAIN = get_model_config("llama-test").replace(hc_streams=4)
STREAMS = "does not support a model with 4 residual streams"


def _plain_params():
    return init_full_params(jax.random.PRNGKey(0), PLAIN)


def _forward(cfg, p, **kw):
    spec = kw.pop("spec", StageSpec(0, 1, 0, cfg.num_layers))
    return stage_forward(p, cfg, spec, jnp.asarray([[1, 2]]),
                         KVCache.create(cfg, cfg.num_layers, 1, 8),
                         jnp.arange(2)[None], **kw)


def _engine(cfg, p, **kw):
    return ContinuousBatchingEngine(
        cfg, p, sampling=GREEDY, max_seq=64, max_batch=2, kv_block_tokens=8,
        prefill_chunk=8, decode_block=4, mixed_token_budget=16, **kw)


def _tp():
    from distributed_inference_demo_tpu.parallel.mesh import (MeshConfig,
                                                              make_mesh)
    _engine(PLAIN, _plain_params(), mesh=make_mesh(MeshConfig(tp=2)))


def _draft():
    base = get_model_config("llama-test")
    ContinuousBatchingEngine(
        base, init_full_params(jax.random.PRNGKey(0), base), max_seq=64,
        max_batch=2, draft_cfg=PLAIN, draft_params=_plain_params(),
        num_draft=2)


def _looped():
    cfg = get_model_config("ouro-test").replace(hc_streams=4)
    _forward(cfg, init_full_params(jax.random.PRNGKey(0), cfg))


def _period():
    cfg = get_model_config("laguna-test").replace(hc_streams=4)
    _forward(cfg, init_full_params(jax.random.PRNGKey(0), cfg))


REFUSALS = {
    "tensor parallelism": (r"tensor parallelism \(--tp\) " + STREAMS, _tp),
    "a pipeline of stages": (
        "a pipeline of stages " + STREAMS,
        lambda: slice_stage(_plain_params(), PLAIN,
                            split_layer_ranges(PLAIN.num_layers, 2)[0])),
    "a stage that is not first and last": (
        "a mesh axis, a stage of a pipeline or the training layout of the "
        "cache " + STREAMS,
        lambda: _forward(PLAIN, _plain_params(), spec=StageSpec(0, 2, 0, 4))),
    "the training layout of the cache": (
        "the training layout of the cache " + STREAMS,
        lambda: _forward(PLAIN, _plain_params(), cache_in_carry=False)),
    "prompt lookup": (r"speculation \(a draft model or prompt lookup\) "
                      + STREAMS,
                      lambda: _engine(PLAIN, _plain_params(),
                                      prompt_lookup=True, num_draft=2)),
    "the draft side": ("the draft side of speculation " + STREAMS, _draft),
    "a looped model": ("a looped or period model " + STREAMS, _looped),
    "a period model": ("a looped or period model " + STREAMS, _period),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_built_for_one_stream_refuses_in_a_sentence(what):
    sentence, build = REFUSALS[what]
    with pytest.raises(ValueError, match=sentence):
        build()


def test_streams_ride_any_block_s_attention():
    """The residual path is the block's, not the latent kind's: a GQA
    block with four streams runs, and differs from the one-stream model."""
    p = _plain_params()
    wide, _ = _forward(PLAIN, p)
    one, _ = _forward(PLAIN.replace(hc_streams=0), p)
    assert wide.shape == one.shape and np.isfinite(np.asarray(wide)).all()
    assert float(jnp.abs(wide - one).max()) > 1e-3

