"""A model with a recurrent state (family ``solar_open2``, PR 56): a period
of one gated full block without rope and three gated delta-rule (KDA)
blocks, its two ops against the token-by-token recurrence, the program
against the benchmark's reference (dense, and prefill in chunks then
decode through pages and state rows), the three faults a long reading must
show, and the eight shares of the experts.  CPU, toy widths
(``solar-open2-test``); ``tests/test_solar_open2_engine.py`` holds the
engine."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import (BlockKind, KVCache,
                                                        ModelConfig,
                                                        StageSpec)
from distributed_inference_demo_tpu.models.decoder import (
    _moe_routed, init_full_params, init_layer_params, stage_forward)
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops import kda

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import kda_tinv_table  # noqa: E402  (tools/)
import model_parity  # noqa: E402  (tools/)

CFG = get_model_config("solar-open2-test")
MC = dataclasses.asdict(CFG)
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
FAM = families.load("solar_open2")
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _vectors(s, heads, d, seed, repeated=False):
    """q, k, v, log alpha, beta of ``s`` tokens as a kda block makes them:
    q and k of unit length, a decay a channel, beta in (0, 2).
    ``repeated``: a prompt that repeats one token, every key one direction
    a head plus 5 % noise, hardly any decay, beta 1.9."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (s, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (s, heads, d)))
    v = jax.random.normal(ks[2], (s, heads, d))
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (s, heads, d)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (s, heads)))
    if repeated:
        k = unit(unit(jax.random.normal(ks[5], (1, heads, d))) + 0.05 * k)
        g, beta = 0.01 * g, jnp.full_like(beta, 1.9)
    return q, k, v, g, beta


# ------------------------------------------------------------ configuration

def test_the_period_s_cache_is_one_pool_of_pages_and_a_state_pool():
    """Only the full kind holds pages; the three kda places of each repeat
    hold planes of the state pool, repeat by repeat."""
    assert CFG.cache_kinds == ((0, 2),)
    assert CFG.state_planes == 6
    assert [CFG.plane_of(b) for b in range(8)] == [
        (0, 0), (-1, 0), (-1, 1), (-1, 2), (0, 1), (-1, 3), (-1, 4), (-1, 5)]
    assert CFG.state_shapes == ((4, 16, 16), (3, 3 * 4 * 16))
    assert CFG.state_bytes_per_slot == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert [n for n, _, _ in CFG.kinds] == ["full", "kda"]
    assert get_model_config("laguna-test").state_planes == 0
    assert get_model_config("qwen2-test").state_planes == 0


@pytest.mark.parametrize("bad", [
    dict(attn="kda", num_heads=4),                  # no taps
    dict(attn="kda", num_heads=4, conv=1),          # one tap is no conv
    dict(attn="full", num_heads=4, conv=4),         # taps on a full kind
    dict(attn="kda", num_heads=4, conv=4, window=8),
    dict(attn="full", num_heads=4, gate="per-channel"),
    dict(attn="delta", num_heads=4),
])
def test_a_block_kind_that_contradicts_itself_is_refused(bad):
    with pytest.raises(ValueError):
        BlockKind(**bad)


def test_the_parameter_stacks_are_one_a_kind(params):
    L = params.layers
    assert L["wq.full"].shape == (2, 1, 64, 64)
    assert L["wk.full"].shape == (2, 1, 64, 32)
    assert L["wg.full"].shape == (2, 1, 64, 64)         # one a channel
    assert L["wq.kda"].shape == L["wk.kda"].shape == (2, 3, 64, 64)
    assert L["conv_w.kda"].shape == (2, 3, 4, 192)
    assert L["wf_dn.kda"].shape == (2, 3, 64, 16)
    assert L["wf_up.kda"].shape == L["wg_up.kda"].shape == (2, 3, 16, 64)
    assert L["A_log.kda"].shape == (2, 3, 4)
    assert L["dt_bias.kda"].shape == L["bg.kda"].shape == (2, 3, 64)
    assert L["wb.kda"].shape == (2, 3, 64, 4)
    assert L["o_norm_w.kda"].shape == (2, 3, 16)
    # the router's bias is over every expert it scores, the stacks hold 2
    assert L["router_bias.kda"].shape == (2, 3, 16)
    assert L["w_gate.kda"].shape == (2, 3, 2, 64, 32)
    # seeded as the published initialiser draws them, and never zero
    A = np.exp(np.asarray(L["A_log.kda"]))
    assert A.min() >= 1.0 and A.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(L["dt_bias.kda"])))   # softplus
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 0.1 * 1.001
    assert np.abs(np.asarray(L["bg.kda"])).max() > 0


# ------------------------------------------------- the two ops, by the rule

@pytest.mark.parametrize("s,chunk,sub,repeated,atol", [
    (24, 8, 4, False, 2e-6),    # whole chunks, sub-blocks against earlier ones
    (40, 16, 8, False, 2e-6),
    (16, 16, 16, False, 2e-6),  # one chunk, one sub-block
    (21, 8, 4, False, 2e-6),    # a partial last chunk
    (7, 128, 32, False, 2e-6),  # shorter than a chunk
    (1, 128, 32, False, 2e-6),  # a single token
    # the cell's chunk and base on one key repeated (|N| up to 1.9, |S| up
    # to 5.3): against the float32 recurrence, substitution over all 128
    # rows (``unit_lower_inverse(N, 128)``, and the solve before PR 68)
    # reads 1.0e-5 on the outputs and 5.6e-5 on the state, the blocks of 32
    # 1.9e-5 and 6.7e-5 (of 16: 2.0e-5, 5.4e-5); the limit is 2.5 x that
    (256, 128, 32, True, 1.5e-4),
])
def test_the_chunk_form_is_the_recurrence(s, chunk, sub, repeated, atol):
    q, k, v, g, beta = _vectors(s, 2, 16, s, repeated)
    S0 = jax.random.normal(KEY, (2, 16, 16))
    want_o, want_S = kda.kda_recurrence(S0, q, k, v, g, beta)
    state = jnp.zeros((2, 3, 2, 16, 16)).at[1, 1].set(S0)
    o, out = kda.kda_chunk(state, jnp.int32(1), jnp.int32(1),
                           jnp.bool_(False), q, k, v, g, beta, chunk=chunk,
                           sub=sub)
    np.testing.assert_allclose(o, want_o, atol=atol)
    np.testing.assert_allclose(out[1, 1], want_S, atol=atol)
    # nothing else of the pool moved
    assert float(jnp.abs(out.at[1, 1].set(0.0)).max()) == 0.0


def _served(N, beta):
    del beta
    return kda.unit_lower_inverse(N)


def test_the_inverse_is_the_float64_inverse_on_keys_like_the_cell_s():
    """``[2, 4, 128, 128]``, random unit keys, the configuration's decay,
    ``beta = 2 sigmoid`` (``tools/kda_tinv_table.cell_input``; ``error``:
    max |difference| from numpy's float64 inverse over its largest entry):
    ``|N|`` under 0.5, every form reads 3e-8 to 1.1e-7 (PERF.md section 6,
    PR 68)."""
    N, _ = kda_tinv_table.cell_input(68, 2, 4)
    assert 0.2 < np.abs(N).max() < 0.5
    assert kda_tinv_table.error(_served, N) <= 5e-7


def test_the_inverse_holds_where_one_key_repeats_and_a_series_does_not():
    """One direction + 5 % noise, the decay x 0.01, ``beta`` 1.9 (a prompt
    that repeats a token): ``|N|`` up to 1.9.  The limit 5e-5 lies between
    the worst sound form (substitution in blocks of 16 then merges: 1.5e-5)
    and the first unsound one (the series inside blocks of 8: 1.2e-4); the
    whole chunk's product of ``(I + N^(2^j))``, the control, overflows."""
    N, _ = kda_tinv_table.repeated_input(68, 2, 4)
    assert np.abs(N).max() > 1.8
    assert kda_tinv_table.error(_served, N) <= 5e-5
    assert not kda_tinv_table.error(kda_tinv_table.neumann, N) <= 5e-5


@pytest.mark.parametrize("s,heads,d,chunk,sub", [
    (256, 64, 128, 128, 32),        # the cell's segment
    (8, 2, 16, 8, 4),
])
def test_the_chunk_s_matrices_hold_no_triangular_solve(s, heads, d, chunk,
                                                       sub):
    x = jax.ShapeDtypeStruct((s, heads, d), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda q, k, v, g, beta: kda.chunk_matrices(q, k, v, g, beta, chunk,
                                                    sub))(
        x, x, x, x, jax.ShapeDtypeStruct((s, heads), jnp.float32)))
    assert "dot_general" in text and "triangular_solve" not in text


def test_a_segment_that_starts_a_request_starts_from_zero():
    q, k, v, g, beta = _vectors(12, 2, 16, 3)
    dirty = jax.random.normal(KEY, (1, 2, 2, 16, 16))
    want_o, want_S = kda.kda_recurrence(jnp.zeros((2, 16, 16)), q, k, v, g,
                                        beta)
    o, out = kda.kda_chunk(dirty, jnp.int32(0), jnp.int32(1),
                           jnp.bool_(True), q, k, v, g, beta, chunk=4, sub=4)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(out[0, 1], want_S, atol=2e-6)


def test_every_exponent_of_the_chunk_form_is_at_most_zero(monkeypatch):
    """A decay so strong that ``exp(+cumulative decay)`` overflows float32
    (-40 a token over 16 tokens: e^640): the chunk form stays finite and
    is the recurrence."""
    q, k, v, g, beta = _vectors(16, 1, 16, 5)
    g = jnp.full_like(g, -40.0)
    S0 = jax.random.normal(KEY, (1, 16, 16))
    want_o, want_S = kda.kda_recurrence(S0, q, k, v, g, beta)
    seen = []
    exp = jnp.exp
    monkeypatch.setattr(jnp, "exp", lambda x: (seen.append(
        float(jnp.max(x))), exp(x))[1])
    o, out = kda.kda_chunk(S0[None, None], jnp.int32(0), jnp.int32(0),
                           jnp.bool_(False), q, k, v, g, beta, chunk=16,
                           sub=4)
    assert seen and max(seen) <= 0.0
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(out[0, 0], want_S, atol=2e-6)


def test_tokens_that_are_not_there_leave_the_state_bit_for_bit():
    """A padded tail (log alpha 0, beta 0) after 5 real tokens: the state
    is what the 5 left, to the bit, whatever the pad's q, k and v."""
    q, k, v, g, beta = _vectors(8, 2, 16, 7)
    S0 = jax.random.normal(KEY, (1, 1, 2, 16, 16))
    run = lambda *a: kda.kda_chunk(S0, jnp.int32(0), jnp.int32(0),
                                   jnp.bool_(False), *a, chunk=8, sub=4)
    held = jnp.arange(8) < 5
    gm = jnp.where(held[:, None, None], g, 0.0)
    bm = jnp.where(held[:, None], beta, 0.0)
    o, out = run(q, k, v, gm, bm)
    junk = lambda a: jnp.where(held.reshape((8,) + (1,) * (a.ndim - 1)), a,
                               100.0)
    o2, out2 = run(junk(q), junk(k), junk(v), gm, bm)
    np.testing.assert_array_equal(out, out2)
    np.testing.assert_array_equal(o[:5], o2[:5])
    _, want = kda.kda_recurrence(S0[0, 0], q[:5], k[:5], v[:5], g[:5],
                                 beta[:5])
    np.testing.assert_allclose(out[0, 0], want, atol=2e-6)


def test_a_step_moves_live_rows_and_no_other():
    q, k, v, g, beta = _vectors(3, 2, 16, 9)
    state = jax.random.normal(KEY, (2, 5, 2, 16, 16))
    rows, live = jnp.array([3, 0, 7]), jnp.array([True, False, True])
    o, out = kda.kda_step(state, jnp.int32(0), rows, q, k, v, g, beta, live)
    want_o, want_S = kda.kda_recurrence(state[0, 3], q[:1], k[:1], v[:1],
                                        g[:1], beta[:1])
    np.testing.assert_allclose(o[0], want_o[0], atol=1e-6)
    np.testing.assert_allclose(out[0, 3], want_S, atol=1e-6)
    # the dead row's state (row 0), every other row and the other plane:
    # bit for bit; a row past the pool (7) is the last row, nobody's
    np.testing.assert_array_equal(out[0, :3], state[0, :3])
    np.testing.assert_array_equal(out[1], state[1])
    assert float(jnp.abs(o[1]).max()) == 0.0


def test_a_dense_cache_s_rows_are_the_batch_s():
    q, k, v, g, beta = _vectors(2, 2, 16, 11)
    state = jax.random.normal(KEY, (1, 2, 2, 16, 16))
    o, out = kda.kda_step(state, jnp.int32(0), None, q, k, v, g, beta,
                          jnp.array([False, True]))
    np.testing.assert_array_equal(out[0, 0], state[0, 0])
    want_o, want_S = kda.kda_recurrence(state[0, 1], q[1:], k[1:], v[1:],
                                        g[1:], beta[1:])
    np.testing.assert_allclose(out[0, 1], want_S, atol=1e-6)
    np.testing.assert_allclose(o[1], want_o[0], atol=1e-6)


def test_the_step_kernel_is_the_xla_step():
    """The Pallas call, interpreted, at the head size it serves (128)."""
    q, k, v, g, beta = _vectors(3, 8, 128, 13)
    state = jax.random.normal(KEY, (2, 4, 8, 128, 128))
    rows, live = jnp.array([2, 1, 0]), jnp.array([True, True, False])
    args = (state, jnp.int32(1), rows, q, k, v, g, beta, live)
    o1, s1 = kda.kda_step(*args)
    o2, s2 = kda.kda_step(*args, kernel=True, interpret=True)
    np.testing.assert_allclose(o1, o2, atol=1e-6)
    # (the kernel sends a dead row to the last row, nobody's)
    np.testing.assert_allclose(s1[:, :3], s2[:, :3], atol=1e-6)


def test_the_chunk_kernel_is_the_xla_pass():
    q, k, v, g, beta = _vectors(256, 4, 128, 15)
    state = jax.random.normal(KEY, (2, 3, 4, 128, 128))
    for fresh in (False, True):
        args = (state, jnp.int32(1), jnp.int32(2), jnp.bool_(fresh), q, k,
                v, g, beta)
        o1, s1 = kda.kda_chunk(*args)
        o2, s2 = kda.kda_chunk(*args, kernel=True, interpret=True)
        np.testing.assert_allclose(o1, o2, atol=1e-5)
        np.testing.assert_allclose(s1, s2, atol=1e-5)


def test_where_the_kernels_serve():
    big, toy = (3, 66, 64, 128, 128), (6, 5, 4, 16, 16)
    assert kda.on_kernel(big, 1, platform="tpu") == (True, "")
    assert kda.on_kernel(big, 256, platform="tpu") == (True, "")
    assert not kda.on_kernel(big, 192, platform="tpu")[0]
    assert not kda.on_kernel(toy, 1, platform="tpu")[0]
    assert kda.on_kernel(big, 1, platform="cpu") == (False, "platform cpu")
    assert kda.on_kernel(big, 1, "xla", platform="tpu") == (False,
                                                            "backend xla")


def test_both_kernels_pass_mosaic_at_the_cell_s_shapes():
    """libtpu compiles for a v5e that is not there (tests/test_bring_up)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                   # no libtpu, or no such target
        pytest.skip(f"no ahead-of-time TPU compiler here: {e}")
    sh = jax.sharding.SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt,
                                                           sharding=sh)
    H, d, B = 64, 128, 64
    pool = S((3, B + 2, H, d, d))

    def step(state, rows, q, k, v, g, beta, live):
        return kda.kda_step(state, jnp.int32(1), rows, q, k, v, g, beta,
                            live, kernel=True)

    row = S((B, H, d))
    jax.jit(step).lower(pool, S((B,), jnp.int32), row, row, row, row,
                        S((B, H)), S((B,), jnp.bool_)).compile()

    def chunk(state, q, k, v, g, beta):
        return kda.kda_chunk(state, jnp.int32(1), jnp.int32(5),
                             jnp.bool_(False), q, k, v, g, beta, kernel=True)

    seg = S((256, H, d))
    jax.jit(chunk).lower(pool, seg, seg, seg, seg, S((256, H))).compile()


def test_the_convolution_in_pieces_is_the_convolution():
    """A tail carried from piece to piece, a partial piece included, is
    the whole sequence's convolution."""
    u = jax.random.normal(KEY, (1, 21, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    zero = jnp.zeros((1, 3, 6))
    whole, _ = kda.causal_conv(u, zero, w, jnp.array([21]))
    tail, parts = zero, []
    for lo, hi, pad in ((0, 8, 0), (8, 9, 0), (9, 21, 4)):
        piece = jnp.pad(u[:, lo:hi], ((0, 0), (0, pad), (0, 0)),
                        constant_values=9.0)
        y, tail = kda.causal_conv(piece, tail, w, jnp.array([hi - lo]))
        parts.append(y[:, :hi - lo])
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole, atol=1e-6)
    np.testing.assert_array_equal(tail, u[:, 18:21])
    # a row that holds no token keeps its tail
    _, kept = kda.causal_conv(u[:, :1], tail, w, jnp.array([0]))
    np.testing.assert_array_equal(kept, tail)


# ------------------------------------ the program against the reference

@pytest.mark.parametrize("seed", [3, 4])
def test_dense_forward_agrees_with_the_family_s_full_forward(seed):
    params = init_full_params(jax.random.PRNGKey(seed), CFG)
    ids = model_parity.seeded_ids(seed, 40, CFG.vocab_size)
    ref, _ = model_parity.reference_logprobs(CFG, params, ids, 1)
    logits, _ = stage_forward(params, CFG, SPEC, jnp.asarray(ids)[None],
                              KVCache.create(CFG, CFG.num_layers, 1, 48),
                              jnp.arange(40)[None])
    np.testing.assert_allclose(jax.nn.log_softmax(logits[0], -1), ref,
                               atol=2e-4)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.6)])
def test_served_path_agrees_with_the_family_s_full_forward(dtype, tol):
    """Prefill in chunks (the last partial and padded: 45 = 16 + 16 + 13),
    then decode, through pages and rows of the state pool, against the
    float32 reference over the whole sequence, on log-probabilities over
    the vocabulary."""
    cfg = CFG.replace(dtype_name=dtype)
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    prompts = np.stack([model_parity.seeded_ids(7 + i, 45, cfg.vocab_size)
                        for i in range(2)])
    args = type("A", (), dict(page=4, chunk=16, steps=12, kv_dtype="bf16"))
    toks, served, paths = model_parity.served_logprobs(cfg, params, prompts,
                                                       args)
    assert set(paths) == {"prefill/full", "prefill/kda", "decode/full",
                          "decode/kda"}
    for r in range(2):
        ids = np.concatenate([prompts[r], toks[r]])
        ref, _ = model_parity.reference_logprobs(cfg, params, ids, 45)
        assert np.abs(served[r] - ref).max() < tol


@pytest.mark.parametrize("fault", ["bf16_state", "not_carried",
                                   "tail_dropped"])
def test_each_fault_of_the_state_is_far_from_the_reference(params, fault,
                                                           monkeypatch):
    """The three controls of the long reading, at toy size: a state
    rounded to bfloat16, a state not carried between chunks, a
    convolution tail dropped at a chunk's edge."""
    for name in ("kda_step", "kda_chunk", "causal_conv"):
        monkeypatch.setattr(kda, name, getattr(kda, name))   # restored
    model_parity.state_controls(**{fault: True})
    prompts = model_parity.seeded_ids(5, 45, CFG.vocab_size)[None]
    args = type("A", (), dict(page=4, chunk=16, steps=4, kv_dtype="bf16"))
    toks, served, _ = model_parity.served_logprobs(CFG, params, prompts,
                                                   args)
    ref, _ = model_parity.reference_logprobs(
        CFG, params, np.concatenate([prompts[0], toks[0]]), 45)
    assert np.abs(served[0] - ref).max() > (
        5e-3 if fault == "bf16_state" else 0.5)


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

def test_the_eight_shares_add_up_to_the_uncut_layer_in_the_program():
    """Routed parts of shares [0,2) .. [14,16) plus the shared expert once
    = the layer with every expert here (``_moe_routed``, float32)."""
    cfg = CFG.of_kind(CFG.period[1]).replace(experts_held=())
    lp = jax.tree.map(lambda a: a[0], init_layer_params(
        jax.random.PRNGKey(5), cfg, 1))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 10, 64)),
                    jnp.float32)
    whole, rows = _moe_routed(cfg, lp, x)
    none = cfg.replace(num_shared_experts=0)
    total, held = whole - _moe_routed(none, lp, x)[0], 0
    for e0 in range(0, 16, 2):
        part = {k: (v[e0:e0 + 2] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in lp.items()}
        y, r = _moe_routed(none.replace(experts_held=(2, e0)), part, x)
        np.testing.assert_array_equal(r, rows[e0:e0 + 2])
        total, held = total + y, held + int(r.sum())
    assert held == 10 * 4
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer_in_the_reference():
    """The family's kda block with shares of 2 against all 16 held: routed
    parts summed plus everything else once."""
    wide = CFG.replace(experts_held=())
    p = init_full_params(jax.random.PRNGKey(6), wide)
    one = {k: np.asarray(v[0], np.float32) for k, v in p.layers.items()}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(12, 64)),
                    jnp.float32)
    stacks = ("w_gate", "w_up", "w_down")

    def block(held, first):
        mc = dict(dataclasses.asdict(wide), experts_held=[held, first],
                  num_layers=1, period=MC["period"][1:2])
        leaves = {k: (v[:, first:first + held]
                      if k.split(".")[0] in stacks else v)
                  for k, v in one.items() if k.endswith(".kda")}
        return FAM.blocks(mc)[0](leaves, x)

    whole, nothing = block(16, 0), block(0, 0)
    parts = sum(block(2, e0) - nothing for e0 in range(0, 16, 2))
    np.testing.assert_allclose(parts + nothing, whole, atol=2e-5)


def test_the_state_pool_s_size_is_the_family_s():
    conf = ModelConfig(**__import__("json").loads(
        (ROOT / "benchmark" / "configs" /
         "solar-open2-250b-bf16-ep8.json").read_text())["model_config"])
    assert conf.state_bytes_per_slot == 13_025_280
    assert conf.state_bytes_per_slot == FAM.kda_state_bytes_per_slot(
        dataclasses.asdict(conf))
    assert conf.cache_kinds == ((0, 1),)
