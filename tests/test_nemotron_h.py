"""Blocks of ONE sublayer (family ``nemotron_h``, PR 66): a period whose
places are a Mamba-2 mixer of heads in GROUPS, a NoPE GQA attention, or the
experts (two matrices an expert, ``relu ** 2``, a sigmoid router with a
selection bias and a scale, a shared expert) and NO cache.  The kinds'
planes and stacks, the program against the benchmark family's equations
(dense and served), the two shares of the experts, the faults that must
stand far from the reference, both SSD calls and the grouped matmul at the
new shapes in interpret mode against the XLA form.  CPU, toy widths
(``nemotron-h-test``); ``tests/test_nemotron_h_engine.py`` holds the
engine."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models import decoder
from distributed_inference_demo_tpu.models.base import (BlockKind, KVCache,
                                                        StageSpec)
from distributed_inference_demo_tpu.models.decoder import (
    _gated_norm, _moe_routed, init_full_params, init_layer_params,
    stage_forward)
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops import grouped_matmul as gmm
from distributed_inference_demo_tpu.ops import ssd

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import model_parity  # noqa: E402  (tools/)

CFG = get_model_config("nemotron-h-test")
MC = dataclasses.asdict(CFG)
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
FAM = families.load("nemotron_h")
M, E, A = CFG.period[0], CFG.period[1], CFG.period[3]


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _logprobs(params, cfg, ids):
    logits, cache = stage_forward(
        params, cfg, SPEC, jnp.asarray(ids)[None],
        KVCache.create(cfg, cfg.num_layers, 1, 48),
        jnp.arange(len(ids))[None])
    return np.asarray(jax.nn.log_softmax(logits[0], -1)), cache


# ------------------------------------------------------------ configuration

def test_a_kind_states_its_sublayers_and_a_block_of_none_holds_no_cache():
    assert [k.name for k in CFG.period] == ["ssd", "mlp", "ssd", "full",
                                            "mlp"]
    assert [(k.attn != "none", k.mlp) for k in CFG.period] == [
        (True, False), (False, True), (True, False), (True, False),
        (False, True)]
    assert not E.is_state and not E.has_pages and A.has_pages
    assert [(n, at) for n, _, at in CFG.kinds] == [
        ("ssd", (0, 2)), ("mlp", (1, 4)), ("full", (3,))]
    # 2 repeats x 5 blocks, of which 2 hold pages, 4 a state, 4 nothing
    assert CFG.total_layers == 10 and CFG.mlp_blocks == 4
    assert CFG.cache_kinds == ((0, 2),) and CFG.kv_planes == 2
    assert CFG.state_planes == 4
    assert [CFG.plane_of(b) for b in range(10)] == [
        (-1, 0), (None, None), (-1, 1), (0, 0), (None, None),
        (-1, 2), (None, None), (-1, 3), (0, 1), (None, None)]
    assert CFG.state_shapes == ((8, 16, 16), (3 * (128 + 2 * 2 * 16),))
    assert CFG.state_bytes_per_slot == 4 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    cache = KVCache.create(CFG, CFG.num_layers, 3, 24)
    assert [tuple(k.shape) for k in cache.keys] == [
        (2, 3, 2, 24, 16), (4, 3, 8, 16, 16)]
    assert tuple(cache.values[-1].shape) == (4, 3, 576)
    # a period of blocks of both sublayers counts as it did
    granite = get_model_config("granite-hybrid-test")
    assert granite.mlp_blocks == granite.total_layers == 8
    assert granite.kv_planes == 2 and granite.state_planes == 6
    assert get_model_config("laguna-test").kv_planes == 9
    assert get_model_config("qwen2-test").mlp_blocks == get_model_config(
        "qwen2-test").num_layers


def test_a_block_kind_with_neither_sublayer_is_refused():
    with pytest.raises(ValueError, match="neither sublayer"):
        BlockKind(attn="none", mlp=False)
    with pytest.raises(ValueError, match="'none'"):
        BlockKind(attn="nothing")
    assert BlockKind(attn="none").mlp and BlockKind(attn="none").name == "mlp"


def test_a_kind_s_stacks_hold_the_leaves_of_its_sublayer_alone(params):
    shapes = {k: tuple(v.shape) for k, v in params.layers.items()}
    by_kind = {name: sorted(k.split(".")[0] for k in shapes
                            if k.endswith("." + name))
               for name in ("ssd", "mlp", "full")}
    assert by_kind["ssd"] == sorted([
        "attn_norm_w", "w_in", "conv_w", "conv_b", "A_log", "D", "dt_bias",
        "ssd_norm_w", "wo"])
    assert by_kind["full"] == sorted(["attn_norm_w", "wq", "wk", "wv", "wo"])
    # two matrices an expert and no gate, routed or shared; no mixer leaf
    assert by_kind["mlp"] == sorted([
        "mlp_norm_w", "router", "router_bias", "w_up_t", "w_down", "ws_up",
        "ws_down"])
    assert shapes["w_in.ssd"] == (2, 2, 64, 2 * 128 + 2 * 2 * 16 + 8)
    assert shapes["w_up_t.mlp"] == (2, 2, 4, 24, 64)    # stored [I, H]
    assert shapes["w_down.mlp"] == (2, 2, 4, 24, 64)
    assert shapes["ws_up.mlp"] == (2, 2, 64, 48)
    assert shapes["router.mlp"] == (2, 2, 64, 8)
    assert float(jnp.abs(params.layers["router_bias.mlp"]).max()) > 0
    assert "w" in params.lm_head                        # an untied head


def test_the_experts_rows_are_counted_in_the_blocks_that_have_them(params):
    ids = model_parity.seeded_ids(1, 20, CFG.vocab_size)
    _, _, rows = stage_forward(
        params, CFG, SPEC, jnp.asarray(ids)[None],
        KVCache.create(CFG, CFG.num_layers, 1, 24), jnp.arange(20)[None],
        moe_stats=True)
    assert rows.shape == (4, 4)             # 4 E blocks, 4 experts held
    assert int(rows.sum()) <= 4 * 20 * 2 and int(rows.sum()) > 0


# ------------------------------------------------- the ops at the new shapes

def _vectors(s, seed, heads, p, n, groups):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (s, heads, p))
    B = 0.3 * jax.random.normal(ks[1], (s, groups, n))
    C = 0.3 * jax.random.normal(ks[2], (s, groups, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (s, heads)) - 2.0)
    A_ = -jnp.exp(jax.random.uniform(ks[4], (heads,), minval=0.0, maxval=2.7))
    return x, B, C, dt, A_


# (heads, groups): nemotron's eight heads a group, one head block a group;
# two groups of sixteen; granite's one group over two head blocks
GROUPED = [(16, 2), (32, 2), (32, 1)]


@pytest.mark.parametrize("heads,groups", GROUPED)
def test_the_chunk_kernel_in_groups_is_the_recurrence(heads, groups):
    p, n = 8, 128
    x, B, C, dt, A_ = _vectors(40, heads, heads, p, n, groups)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 3, heads, p, n))
    want_y, want_S = ssd.ssd_recurrence(pool[1, 2], x, B, C, dt, A_)
    state, outs, lo = pool, [], 0
    for seg in (16, 16, 8):     # a partial last segment, one chunk each
        cut = lambda a: a[lo:lo + seg]
        y, state = ssd.ssd_chunk(state, jnp.int32(1), jnp.int32(2),
                                 jnp.bool_(False), cut(x), cut(B), cut(C),
                                 cut(dt), A_, chunk=16, kernel=True,
                                 interpret=True)
        outs.append(y)
        lo += seg
    np.testing.assert_allclose(jnp.concatenate(outs), want_y, atol=2e-5)
    np.testing.assert_allclose(state[1, 2], want_S, atol=2e-5)
    np.testing.assert_array_equal(state[0], pool[0])
    # ... and the XLA form of the same call
    y_xla, s_xla = ssd.ssd_chunk(pool, jnp.int32(1), jnp.int32(2),
                                 jnp.bool_(False), x[:16], B[:16], C[:16],
                                 dt[:16], A_, chunk=16)
    np.testing.assert_allclose(outs[0], y_xla, atol=2e-5)


@pytest.mark.parametrize("heads,groups", GROUPED)
def test_the_step_kernel_in_groups_is_the_recurrence(heads, groups):
    p, n = 8, 128
    x, B, C, dt, A_ = _vectors(3, 4, heads, p, n, groups)
    pool = jax.random.normal(jax.random.PRNGKey(5), (2, 5, heads, p, n))
    rows, live = jnp.asarray([3, 0, 1]), (True, False, True)
    y, state = ssd.ssd_step(pool, jnp.int32(1), rows, x, B, C, dt, A_,
                            jnp.asarray(live), kernel=True, interpret=True)
    y_xla, s_xla = ssd.ssd_step(pool, jnp.int32(1), rows, x, B, C, dt, A_,
                                jnp.asarray(live))
    np.testing.assert_allclose(y, y_xla, atol=2e-5)
    np.testing.assert_allclose(state, s_xla, atol=2e-5)
    for i in (0, 2):
        want_y, want_S = ssd.ssd_recurrence(
            pool[1, rows[i]], x[i:i + 1], B[i:i + 1], C[i:i + 1],
            dt[i:i + 1], A_)
        np.testing.assert_allclose(y[i], want_y[0], atol=2e-5)
        np.testing.assert_allclose(state[1, rows[i]], want_S, atol=2e-5)
    np.testing.assert_array_equal(state[1, 0], pool[1, 0])  # the dead row's


def test_where_the_grouped_kernels_serve():
    ok = lambda *a, **k: ssd.on_kernel(*a, platform="tpu", **k)  # noqa: E731
    nano = (4, 66, 64, 64, 128)
    assert ok(nano, 8, 128) == (True, "") and ok(nano, 8, 1)[0]
    assert ok((4, 66, 64, 64, 128), 4, 128)[0]          # 16 heads a group
    assert not ok(nano, 16, 128)[0]                     # 4 heads a group
    assert not ok(nano, 8, 64)[0]                       # not whole lanes
    assert not ok(nano, 1, 128)[0]      # one group: 128 k heads, as it was
    assert ssd._chunk_heads(64, 8) == 8 and ssd._chunk_heads(128, 1) == 16
    # 24 heads of 8 channels are a tile and a half of the step's
    assert ok((4, 66, 32, 8, 128), 4, 128)[0]
    assert not ok((4, 66, 24, 8, 128), 3, 128)[0]


def test_the_gated_norm_goes_a_group_at_a_time():
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(2, 3, 32)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, 3, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    got = _gated_norm(y, z, w, 1e-5, groups=4)
    g = np.asarray(y * jax.nn.silu(z)).reshape(2, 3, 4, 8)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 3, 32) * np.asarray(w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    one = _gated_norm(y, z, w, 1e-5)
    assert float(jnp.abs(one - got).max()) > 0.05
    np.testing.assert_allclose(one, _gated_norm(y, z, w, 1e-5, groups=1))


# rows, k, n: a width that is no multiple of the lane tile as the ``n`` of
# the up projection (stored transposed) and the ``k`` of the down
@pytest.mark.parametrize("m,k,n,transposed", [
    (48, 256, 232, True), (48, 232, 256, False), (37, 128, 48, True),
    (37, 48, 128, False)])
def test_the_grouped_matmul_at_a_width_off_the_lanes(m, k, n, transposed):
    rng = np.random.default_rng(m + k)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(3, 5, k, n)), jnp.float32)
    sizes = jnp.asarray([9, 0, 17, 1, 6], jnp.int32)    # 33 rows in groups
    held = int(sizes.sum())
    stack = jnp.swapaxes(rhs, 2, 3) if transposed else rhs
    assert gmm.tiling(m, k, n, 4, 5, 4)[1] == k        # one contraction tile
    if n % 128:
        assert gmm.tiling(m, k, n, 4, 5, 4)[2] == n    # and one column tile
    want = jnp.concatenate([
        lhs[lo:lo + s] @ rhs[1, e] for e, (lo, s) in enumerate(zip(
            np.concatenate([[0], np.cumsum(sizes)[:-1]]), np.asarray(sizes)))])
    for backend in ("pallas", "xla"):
        got = gmm.grouped_matmul(lhs, gmm.LayerOf(stack, jnp.int32(1)),
                                 sizes, backend=backend, interpret=True,
                                 transposed=transposed)
        np.testing.assert_allclose(got[:held], want, rtol=2e-5, atol=2e-4)


def test_where_the_grouped_matmul_takes_a_width_off_the_lanes():
    route = gmm.route_grouped_matmul
    assert route("tpu", 2688, 1856) == route("tpu", 1856, 2688) == "pallas_gmm"
    assert route("tpu", 2048, 1024) == "pallas_gmm"        # as it was
    assert route("tpu", 64, 32) == "ragged_dot"             # under the lanes
    assert route("tpu", 2688, 1860) == "ragged_dot"         # not 16 k
    assert route("tpu", 1856 * 8 + 16, 2688) == "ragged_dot"    # no room
    assert route("cpu", 2688, 1856) == "ragged_dot"
    up = gmm.call_shape(384, 2688, 1856, 2, 128)
    down = gmm.call_shape(4608, 1856, 2688, 2, 128)
    assert up["tiles"] == [32, 2688, 1856] and up["tiles_k"] == 1
    assert down["tiles"] == [64, 1856, 896] and down["tiles_k"] == 1
    assert up["vmem_limit_bytes"] <= gmm._VMEM_BUDGET
    with pytest.raises(ValueError, match="plain array"):
        from distributed_inference_demo_tpu.ops.quant import quantize_array
        gmm.grouped_matmul(jnp.zeros((8, 128)), quantize_array(
            jnp.ones((2, 128, 128))), jnp.asarray([4, 4]), transposed=True)


# ------------------------------------ the program against the reference

@pytest.mark.parametrize("form", ["transposed", "padded"])
def test_the_table_tool_times_both_stored_forms_of_a_width_off_the_lanes(
        form):
    """``tools/gmm_table.py --two-matrix``: the experts of two matrices
    are rows of their own (the gated experts' table keeps its six
    configurations), an up and a down call in each stored form, each
    against ``ragged_dot`` on the plain matrices: the padded form's zero
    columns and rows change nothing."""
    import argparse
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "gmm_table", Path(__file__).resolve().parent.parent / "tools"
        / "gmm_table.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert [c["name"] for c in tool.expert_configs([], two_matrix=True)] == [
        "nemotron-3-nano-30b-a3b-bf16-ep2"]
    assert len(list(tool.expert_configs([]))) == 6
    toy = dict(name="toy", hidden=256, inter=144, routed=8, held=4, first=0,
               top_k=2, int8=False, tokens={"decode": 8})
    rows = [r for r in tool.two_matrix_rows_of(toy, argparse.Namespace(
        seed=0, even=False, reps=1, rehearse=True)) if r["form"] == form]
    width = 144 if form == "transposed" else 256
    assert [(r["proj"], r["k"], r["n"]) for r in rows] == [
        ("up", 256, width), ("down", width, 256)]
    for r in rows:
        assert r["err"] < 1e-2 and r["us"] > 0
        assert r["tiles"][1] == r["k"]          # one contraction tile
        assert r["hbm_us"] == round(
            1e-3 * r["touched"] * 256 * 144 * 2 / tool.PEAKS.hbm_gbs, 1)


@pytest.mark.parametrize("seed", [3])
def test_dense_forward_agrees_with_the_family_s_full_forward(seed):
    params = init_full_params(jax.random.PRNGKey(seed), CFG)
    ids = model_parity.seeded_ids(seed, 40, CFG.vocab_size)
    ref, _ = model_parity.reference_logprobs(CFG, params, ids, 1)
    got, cache = _logprobs(params, CFG, ids)
    np.testing.assert_allclose(got, ref, atol=2e-4)
    states = model_parity.reference_states(CFG, params, ids)
    assert states.shape[0] == 4                     # the four M planes
    np.testing.assert_allclose(
        np.asarray(cache.keys[-1])[:, 0, ::2, ::8], states, atol=2e-5)


# bfloat16: the stream, the matmuls' operands and the pages are rounded to
# 8 bits of mantissa through ten sublayers and an untied head over seeded
# weights; the router and the state stay float32.  0.25 is what the other
# period models' toy readings are held to within a factor of two (granite
# 0.2 under its head's / 16); the faults below read 10 x that in float32
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.25)])
def test_served_path_agrees_with_the_family_s_full_forward(dtype, tol):
    """Prefill in chunks (the last partial and padded: 45 = 16 + 16 + 13, a
    chunk of two of the scan's), then decode, through the ONE attention
    kind's pages and rows of the state pool, against the float32 reference
    over the whole sequence, on log-probabilities over the vocabulary."""
    cfg = CFG.replace(dtype_name=dtype)
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    prompts = np.stack([model_parity.seeded_ids(7 + i, 45, cfg.vocab_size)
                        for i in range(2)])
    args = type("A", (), dict(page=4, chunk=16, steps=6, kv_dtype="bf16"))
    toks, served, paths, state = model_parity.served(cfg, params, prompts,
                                                     args)
    assert set(paths) == {"prefill/full", "prefill/ssd", "decode/full",
                          "decode/ssd"}         # an E block attends nothing
    for r in range(2):
        ids = np.concatenate([prompts[r], toks[r]])
        ref, _ = model_parity.reference_logprobs(cfg, params, ids, 45)
        assert np.abs(served[r] - ref).max() < tol
        if dtype == "float32":
            readings = FAM.state_readings(
                state[:, r], model_parity.reference_states(cfg, params, ids))
            assert max(readings["rel_err"]) < 1e-4
            assert FAM.state_problem(readings, "float32") is None


def _faulty_route(cfg, lp, h):
    """The selection bias used as a weight."""
    scores = jax.nn.sigmoid(decoder._router_logits(h, lp["router"]))
    choice = scores + lp["router_bias"]
    weights, experts = jax.lax.top_k(choice, cfg.experts_per_token)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor, experts.astype(jnp.int32)


def _zeroed(params, leaf, place=None):
    a = params.layers[leaf]
    a = a.at[-1, -1].set(0.0) if place == "last" else 0.0 * a
    return dataclasses.replace(params, layers=dict(params.layers, **{leaf: a}))


FAULTS = {
    # (patches of ``decoder`` / ``ssd``, a changed configuration, parameters)
    "gate_left_in": dict(patch=[(decoder, "_relu2", lambda up: (
        jax.nn.silu(up) * up))]),
    "relu_for_relu2": dict(patch=[(decoder, "_relu2", jax.nn.relu)]),
    "one_norm_group": dict(patch=[(decoder, "_gated_norm", lambda y, z, w,
                                   eps, groups=1: _gated_norm(y, z, w,
                                                              eps))]),
    "group_0_for_every_head": dict(patch=[(ssd, "_of_heads", lambda a, heads:
                                           jnp.repeat(a[..., :1, :], heads,
                                                      axis=-2))]),
    "bias_as_weight": dict(patch=[(decoder, "_route", _faulty_route)]),
    "scaling_1": dict(cfg=CFG.replace(routed_scaling_factor=1.0)),
    "rope_applied": dict(cfg=CFG.replace(period=tuple(
        dataclasses.replace(k, rotary_share=1.0) if k.attn == "full" else k
        for k in CFG.period))),
    "last_routed_sum_dropped": dict(params=lambda p: _zeroed(
        p, "w_down.mlp", "last")),
    "shared_dropped": dict(params=lambda p: _zeroed(p, "ws_down.mlp")),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_is_far_from_the_reference(params, fault, monkeypatch):
    """Each against the family's log-probabilities over the vocabulary, in
    float32: 20 x the tolerance the sound program is held to (the last
    two touch one part of one sublayer: 5 x)."""
    spec = FAULTS[fault]
    for mod, name, fn in spec.get("patch", ()):
        monkeypatch.setattr(mod, name, fn)
    ids = model_parity.seeded_ids(3, 40, CFG.vocab_size)
    ref, _ = model_parity.reference_logprobs(CFG, params, ids, 1)
    got, _ = _logprobs(spec.get("params", lambda p: p)(params),
                       spec.get("cfg", CFG), ids)
    small = fault in ("last_routed_sum_dropped", "shared_dropped")
    assert np.abs(got - ref).max() > (5 if small else 20) * 2e-4


def test_a_state_rounded_to_bfloat16_fails_the_family_s_limit(params):
    ids = model_parity.seeded_ids(5, 40, CFG.vocab_size)
    want = model_parity.reference_states(CFG, params, ids)
    sound = FAM.state_readings(want.copy(), want)
    assert FAM.state_problem(sound, "float32") is None
    rounded = FAM.state_readings(FAM.rounded_to_bf16(want), want)
    assert max(rounded["f32_residue"]) == 0.0
    assert "not the float32 state" in FAM.state_problem(rounded, "float32")
    assert "not the float32 state" in FAM.state_problem(sound, "bfloat16")


# -------------------------------------------------------------- the share

def test_the_two_shares_add_up_to_the_uncut_block_in_the_program():
    """Routed parts of shares [0, 4) and [4, 8) plus the shared expert once
    = the E block with every expert here (``_moe_routed``, float32)."""
    cfg = CFG.of_kind(E).replace(experts_held=())
    lp = jax.tree.map(lambda a: a[0], init_layer_params(
        jax.random.PRNGKey(5), cfg, 1))
    assert "w_gate" not in lp and "attn_norm_w" not in lp
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 10, 64)),
                    jnp.float32)
    whole, rows = _moe_routed(cfg, lp, x)
    none = cfg.replace(num_shared_experts=0)
    shared = whole - _moe_routed(none, lp, x)[0]
    total, held = shared, 0
    for e0 in (0, 4):
        part = {k: (v[e0:e0 + 4] if k in ("w_up_t", "w_down") else v)
                for k, v in lp.items()}
        y, r = _moe_routed(none.replace(experts_held=(4, e0)), part, x)
        np.testing.assert_array_equal(r, rows[e0:e0 + 4])
        total, held = total + y, held + int(r.sum())
    assert held == 10 * 2
    scale = float(jnp.abs(whole - shared).max())    # the routed sum alone
    assert scale > 0 and float(jnp.abs(shared).max()) > 0
    np.testing.assert_allclose(total, whole, atol=1e-4 * scale + 1e-7)


def test_the_two_shares_add_up_to_the_uncut_block_in_the_reference():
    wide = CFG.replace(experts_held=())
    p = init_full_params(jax.random.PRNGKey(6), wide)
    one = {k: np.asarray(v[0], np.float32) for k, v in p.layers.items()}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(12, 64)),
                    jnp.float32)

    def block(held, first):
        mc = dict(dataclasses.asdict(wide), experts_held=[held, first],
                  num_layers=1, period=MC["period"][1:2])
        leaves = {k: (v[:, first:first + held]
                      if k.split(".")[0] in ("w_up_t", "w_down") else v)
                  for k, v in one.items() if k.endswith(".mlp")}
        return FAM.blocks(mc)[0](leaves, x)

    whole, nothing = block(8, 0), block(0, 0)
    parts = sum(block(4, e0) - nothing for e0 in (0, 4))
    routed = float(jnp.abs(whole - nothing).max())
    assert routed > 0 and float(jnp.abs(nothing - x).max()) > 0  # the shared
    np.testing.assert_allclose(parts + nothing, whole,
                               atol=1e-3 * routed + 1e-7)


def test_the_capacity_slot_path_refuses_experts_of_two_matrices():
    cfg = CFG.of_kind(E)
    with pytest.raises(ValueError, match="relu2"):
        decoder._mlp(cfg, {}, jnp.zeros((1, 2, 64)), ep_axis="ep")


# ------------------------------------------------- Mosaic, ahead of time

@pytest.fixture(scope="module")
def v5e():
    """One device of a device-less TPU v5e topology (``tests/test_bring_up``
    says how): libtpu compiles for it, Mosaic included, with no chip."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                   # no libtpu, or no such target
        pytest.skip(f"no ahead-of-time TPU compiler here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def test_the_new_shapes_get_through_mosaic_and_copy_no_stack(v5e):
    """The published widths: both grouped matmuls of an ``E`` block over
    the whole ``[4, 64, 1856, 2688]`` stacks at a decode step's rows (the
    up projection stored transposed: the compiler's temporaries stay under
    a MiB, where the stack stored ``[.., 2688, 1856]`` was copied whole,
    2.6 GB a call), and both SSD calls at ``[64, 64, 128]`` in 8 groups
    over a pool of 66 rows."""
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=v5e)  # noqa: E731
    for k, n, transposed in ((2688, 1856, True), (1856, 2688, False)):
        compiled = jax.jit(
            lambda x, w, g, i, t=transposed: gmm.grouped_matmul(
                x, gmm.LayerOf(w, i), g, routed=128, backend="pallas",
                transposed=t)
        ).lower(S((384, k), jnp.bfloat16),
                S((4, 64, 1856, 2688), jnp.bfloat16), S((64,), jnp.int32),
                S((), jnp.int32)).compile()
        assert "moe_gmm" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    pool = S((4, 66, 64, 64, 128), jnp.float32)
    bf, f32 = jnp.bfloat16, jnp.float32
    step = jax.jit(lambda st, p, r, x, B, C, dt, A_, live: ssd.ssd_step(
        st, p, r, x, B, C, dt, A_, live, kernel=True)).lower(
        pool, S((), jnp.int32), S((64,), jnp.int32), S((64, 64, 64), bf),
        S((64, 8, 128), bf), S((64, 8, 128), bf), S((64, 64), f32),
        S((64,), f32), S((64,), jnp.bool_)).compile()
    assert "_ssd_step" in step.as_text()
    # ... and the step's tile loop at granite's state, 128 heads in one
    # group (PR 67: two transposes a tile of two heads)
    wide = jax.jit(lambda st, p, r, x, B, C, dt, A_, live: ssd.ssd_step(
        st, p, r, x, B, C, dt, A_, live, kernel=True)).lower(
        S((9, 34, 128, 64, 128), f32), S((), jnp.int32), S((32,), jnp.int32),
        S((32, 128, 64), bf), S((32, 1, 128), bf), S((32, 1, 128), bf),
        S((32, 128), f32), S((128,), f32), S((32,), jnp.bool_)).compile()
    assert "_ssd_step" in wide.as_text()
    chunk = jax.jit(lambda st, p, r, fr, x, B, C, dt, A_: ssd.ssd_chunk(
        st, p, r, fr, x, B, C, dt, A_, chunk=128, kernel=True)).lower(
        pool, S((), jnp.int32), S((), jnp.int32), S((), jnp.bool_),
        S((256, 64, 64), bf), S((256, 8, 128), bf), S((256, 8, 128), bf),
        S((256, 64), f32), S((64,), f32)).compile()
    assert "_ssd_chunk" in chunk.as_text()
    # the pool is worked on where it lies: no temporary of a plane's size
    for c in (step, wide, chunk):
        assert c.memory_analysis().temp_size_in_bytes < 64 << 20
