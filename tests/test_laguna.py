"""A period of unlike blocks (family ``laguna``, PR 46): 1 full : 3 window
attention, two head counts, a per-head output gate, YaRN on half a head, a
page pool a kind of block that frees behind the window, and a chip's share
of the routed experts.  CPU, toy widths (``laguna-test``)."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import (BlockKind, KVCache,
                                                        ModelConfig,
                                                        StageSpec,
                                                        slice_stage,
                                                        split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (_moe_routed,
                                                           init_full_params,
                                                           stage_forward)
from distributed_inference_demo_tpu.models.registry import (MODEL_REGISTRY,
                                                            get_model_config)
from distributed_inference_demo_tpu.ops import rope
from distributed_inference_demo_tpu.ops.paged_attention import (
    paged_flash_attention, paged_gather_attention, paged_prefill_attention,
    sub_chunk, window_tables)
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.ops.stacked import LayerOf
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import model_parity  # noqa: E402  (tools/)

CFG = get_model_config("laguna-test")
MC = dataclasses.asdict(CFG)
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
GREEDY = SamplingParams(temperature=0.0)
MIXED = dict(prefill_chunk=8, decode_block=4, mixed_token_budget=24)
FAM = families.load("laguna")


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_seq", 200)
    kw.setdefault("max_batch", 3)
    kw.setdefault("kv_block_tokens", 4)
    return ContinuousBatchingEngine(cfg, params, sampling=GREEDY, **kw)


# ------------------------------------------------------------ configuration

def test_a_period_is_built_from_json_and_is_hashable():
    conf = json.loads((ROOT / "benchmark" / "configs"
                       / "laguna-s-2.1-bf16-ep4.json").read_text())
    a = ModelConfig(**conf["model_config"])
    b = ModelConfig(**json.loads(json.dumps(conf["model_config"])))
    assert a == b and hash(a) == hash(b)
    assert [k.attn for k in a.period] == ["window"] * 3 + ["full"]
    assert [k.num_heads for k in a.period] == [72, 72, 72, 48]
    assert a.lead_kind == a.period[3] and a.lead_kind.yarn[0] == 128.0
    assert a.cache_kinds == ((0, 2), (512, 3)) and a.kv_planes == 5
    assert a.experts_held == (64, 0) and a.experts_here == 64
    # the first plane of the full pool is the leading block's
    assert [a.plane_of(i) for i in range(5)] == [
        (0, 0), (1, 0), (1, 1), (1, 2), (0, 1)]
    r = ModelConfig(**conf["rehearsal"]["model_config"])
    assert r == dataclasses.replace(CFG, max_seq_len=384)


def test_every_older_model_is_a_period_of_nothing():
    for name, cfg in MODEL_REGISTRY.items():
        if name not in ("laguna-test", "solar-open2-test",      # (PR 56)
                        "granite-hybrid-test",                  # (PR 62)
                        "nemotron-h-test",                      # (PR 66)
                        "minicpm-sala-test"):                   # (PR 69)
            assert not cfg.mixed_kinds and cfg.cache_kinds == (
                (0, cfg.kv_planes),), name


@pytest.mark.parametrize("bad", [
    dict(attn="window"), dict(attn="full", window=4), dict(attn="ring"),
    dict(gate="per-token")])
def test_a_block_kind_that_contradicts_itself_is_refused(bad):
    with pytest.raises(ValueError):
        BlockKind(**bad)


def test_the_parameter_stacks_are_one_a_kind_with_repeats_leading(params):
    R = CFG.num_layers
    for name, n, nh in (("window", 3, 6), ("full", 1, 4)):
        assert params.layers[f"wq.{name}"].shape == (R, n, 64, nh * 16)
        assert params.layers[f"wg.{name}"].shape == (R, n, 64, nh)
        assert params.layers[f"w_gate.{name}"].shape == (R, n, 4, 64, 32)
        assert params.layers[f"router.{name}"].shape == (R, n, 64, 16)
    assert params.lead["w_gate"].shape == (1, 64, 96)


# --------------------------------------------------------------------- rope

def test_yarn_frequencies_are_the_reference_s_table():
    kind = MC["period"][3]
    inv, rd, factor = FAM.kind_inv_freq(MC, kind)
    assert rd == 8 and factor == pytest.approx(1.2079441541679836)
    ours = rope.yarn_frequencies(rd, kind["rope_theta"], *kind["yarn"][:4])
    np.testing.assert_allclose(np.asarray(ours), inv, rtol=1e-6)


def test_yarn_at_published_numbers_by_hand():
    """Laguna-S-2.1's full kind: rotary dim 64, theta 5e5, factor 128 over
    8192, beta 32 / 1.  The correction range from the formula (channels 9
    to 18), then the table: up to channel 9 a channel keeps its frequency,
    from 18 on it is divided by 128, between them the ramp."""
    import math
    dim = lambda rot: 64 * math.log(8192 / (rot * 2 * math.pi)) / (
        2 * math.log(5e5))
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (9, 18)
    inv = FAM.yarn_inv_freq(64, 5e5, 128, 8192, 32, 1)
    ours = np.asarray(rope.yarn_frequencies(64, 5e5, 128.0, 8192.0, 32.0,
                                            1.0))
    np.testing.assert_allclose(ours, inv, rtol=1e-6)
    extrap = lambda i: 5e5 ** (-2 * i / 64)
    assert inv[0] == 1.0 and inv[9] == pytest.approx(extrap(9))
    assert inv[31] == pytest.approx(extrap(31) / 128)
    mid = 13        # ramp (13 - 9) / 9
    assert inv[mid] == pytest.approx(
        extrap(mid) / 128 * 4 / 9 + extrap(mid) * 5 / 9)
    assert 0.1 * math.log(128) + 1 == pytest.approx(1.4852030263919618)


def test_partial_rotary_turns_the_first_share_and_passes_the_rest():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 2, 16)),
                    jnp.float32)
    pos = jnp.arange(5)[None]
    out = rope.apply_rope_kind(x, pos, 1e4, 0.5)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(out[..., :8],
                               rope.apply_rope(x[..., :8], pos, 1e4),
                               rtol=1e-6)
    np.testing.assert_allclose(rope.apply_rope_kind(x, pos, 1e4),
                               rope.apply_rope(x, pos, 1e4), rtol=1e-6)


# ------------------------------------------------------------------ kernels

def _pool(rng, n=40, nkv=2, bt=8, hd=128):
    make = lambda: jnp.asarray(rng.normal(size=(2, n, nkv, bt, hd)),
                               jnp.float32)
    return LayerOf(make(), jnp.int32(1)), LayerOf(make(), jnp.int32(1))


def _window_table(rng, rows, n, width, bt, window):
    """Tables whose entries behind ``lo - window + 1`` are sentinel, for
    rows that hold tokens ``[0, hi)`` and read from ``lo``."""
    pages = iter(rng.permutation(n))
    tables = np.full((len(rows), width), n, np.int32)
    for i, (lo, hi) in enumerate(rows):
        for j in range(max(0, lo - window + 1) // bt, -(-hi // bt)):
            tables[i, j] = next(pages)
    return jnp.asarray(tables)


@pytest.mark.parametrize("groups", [3, 9])
def test_decode_kernel_under_a_window_is_the_gather_path(groups):
    rng = np.random.default_rng(groups)
    kp, vp = _pool(rng)
    lens = np.array([5, 37, 64, 90])
    tables = _window_table(rng, [(n - 1, n) for n in lens], 40, 12, 8, 20)
    q = jnp.asarray(rng.normal(size=(4, 1, 2 * groups, 128)), jnp.float32)
    want = paged_gather_attention(q, kp, vp, tables,
                                  jnp.asarray(lens - 1)[:, None], window=20)
    got = paged_flash_attention(q, kp, vp, tables, jnp.asarray(lens),
                                interpret=True, window=20)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("groups", [3, 9])
def test_prefill_kernel_under_a_window_is_the_gather_path(groups):
    rng = np.random.default_rng(groups)
    kp, vp = _pool(rng)
    starts, chunk = np.array([0, 24, 56]), 16
    tables = _window_table(rng, [(s, s + chunk) for s in starts], 40, 12, 8,
                           20)
    q = jnp.asarray(rng.normal(size=(3, chunk, 2 * groups, 128)),
                    jnp.float32)
    pos = jnp.asarray(starts)[:, None] + jnp.arange(chunk)[None]
    want = paged_gather_attention(q, kp, vp, tables, pos, window=20)
    got = paged_prefill_attention(q, kp, vp, tables, pos, interpret=True,
                                  window=20)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_window_s_table_is_cut_to_what_a_chunk_can_see():
    tables = jnp.arange(3 * 50, dtype=jnp.int32).reshape(3, 50)
    cut, page0 = window_tables(tables, jnp.asarray([0, 600, 5000]), 256, 512,
                               128)
    assert cut.shape == (3, 7)         # ceil((256 + 511) / 128) + 1
    assert list(np.asarray(page0)) == [0, 0, 35]
    assert int(cut[2, 0]) == 2 * 50 + 35


def test_a_chunk_of_too_many_query_rows_is_cut_into_sub_chunks():
    assert sub_chunk(256, 6) == 64 and sub_chunk(256, 9) == 32
    assert sub_chunk(256, 1) == 256 and sub_chunk(16, 9) == 16


# ------------------------------------ the served path against the reference

@pytest.mark.parametrize("dtype,tol", [("float32", 5e-5), ("bfloat16", 0.4)])
def test_served_path_agrees_with_the_family_s_full_forward(dtype, tol):
    """Prefill in chunks, then decode, through both pools: past the
    window's edge (8) and through pages that came back from behind it (the
    ring of ``model_parity.period_tables``), against the float32 reference
    over the whole sequence, on log-probabilities over the vocabulary."""
    cfg = CFG.replace(dtype_name=dtype)
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    prompts = np.stack([model_parity.seeded_ids(7 + i, 40, cfg.vocab_size)
                        for i in range(2)])
    args = type("A", (), dict(page=4, chunk=8, steps=12, kv_dtype="bf16"))
    toks, served, paths = model_parity.served_logprobs(cfg, params, prompts,
                                                       args)
    assert set(paths) == {"prefill/full", "prefill/window", "decode/full",
                          "decode/window"}
    for r in range(2):
        ids = np.concatenate([prompts[r], toks[r]])
        ref, _ = model_parity.reference_logprobs(cfg, params, ids, 40)
        assert np.abs(served[r] - ref).max() < tol


def test_a_window_ignored_is_far_from_the_reference(params):
    prompts = model_parity.seeded_ids(5, 40, CFG.vocab_size)[None]
    args = type("A", (), dict(page=4, chunk=8, steps=4, kv_dtype="bf16"))
    toks, served, _ = model_parity.served_logprobs(
        model_parity.window_ignored(CFG), params, prompts, args)
    ref, _ = model_parity.reference_logprobs(
        CFG, params, np.concatenate([prompts[0], toks[0]]), 40)
    assert np.abs(served[0] - ref).max() > 0.5


def test_the_reference_s_mask_is_the_window_s_edge():
    assert FAM.allowed(10, 10, 8) and FAM.allowed(10, 3, 8)
    assert not FAM.allowed(10, 2, 8) and not FAM.allowed(3, 4, 8)
    assert FAM.allowed(100, 0, 0)


def test_dense_cache_in_chunks_is_the_whole_sequence(params):
    ids = jnp.asarray(model_parity.seeded_ids(1, 40, 256))[None]
    whole, _ = stage_forward(params, CFG, SPEC, ids,
                             KVCache.create(CFG, CFG.num_layers, 1, 48),
                             jnp.arange(40)[None])
    cache = KVCache.create(CFG, CFG.num_layers, 1, 48)
    parts = []
    for lo, hi in ((0, 24), (24, 25), (25, 40)):
        out, cache = stage_forward(params, CFG, SPEC, ids[:, lo:hi], cache,
                                   jnp.arange(lo, hi)[None])
        parts.append(out)
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole, atol=5e-5)


# -------------------------------------------------------------- the share

def test_the_four_shares_add_up_to_the_uncut_layer_in_the_program():
    """Routed parts of shares [0,4) .. [12,16) plus the shared expert once
    = the layer with every expert here (``_moe_routed``, float32)."""
    cfg = CFG.of_kind(CFG.period[0]).replace(experts_held=())
    from distributed_inference_demo_tpu.models.decoder import (
        init_layer_params)
    lp = jax.tree.map(lambda a: a[0], init_layer_params(
        jax.random.PRNGKey(5), cfg, 1))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 10, 64)),
                    jnp.float32)
    whole, rows = _moe_routed(cfg, lp, x)
    none = cfg.replace(num_shared_experts=0)
    shared = whole - _moe_routed(none, lp, x)[0]
    total, held = shared, 0
    for e0 in range(0, 16, 4):
        part = {k: (v[e0:e0 + 4] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in lp.items()}
        y, r = _moe_routed(none.replace(experts_held=(4, e0)), part, x)
        np.testing.assert_array_equal(r, rows[e0:e0 + 4])
        total, held = total + y, held + int(r.sum())
    assert held == 10 * 3
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_the_four_shares_add_up_to_the_uncut_layer_in_the_reference(params):
    """The family's period with shares of 4 against all 16 held: routed
    parts summed plus everything else once."""
    wide = CFG.replace(experts_held=())
    p = init_full_params(jax.random.PRNGKey(6), wide)
    one = {k: np.asarray(v[0], np.float32) for k, v in p.layers.items()}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(12, 64)),
                    jnp.float32)
    stacks = ("w_gate", "w_up", "w_down")

    def block(held, first):
        mc = dict(dataclasses.asdict(wide), experts_held=[held, first],
                  num_layers=1, period=MC["period"][:1])
        leaves = {k: (v[:, first:first + held]
                      if k.split(".")[0] in stacks else v)
                  for k, v in one.items() if k.endswith(".window")}
        return FAM.blocks(mc)[1](leaves, x)

    whole, nothing = block(16, 0), block(0, 0)
    parts = sum(block(4, e0) - nothing for e0 in range(0, 16, 4))
    np.testing.assert_allclose(parts + nothing, whole, atol=2e-5)


