"""A second state kind (family ``granite_moe_hybrid``, PR 62): a period of
Mamba-2 (SSD) blocks around one NoPE GQA block, granite's four multipliers,
routed experts beside a shared MLP.  The two forms of ``ops.ssd`` (XLA and
interpreted Pallas) against the token-by-token recurrence, the convolution
with its bias, the program against the benchmark family's equations, the
two shares of the experts, the loader's name map.  CPU, toy widths
(``granite-hybrid-test``); ``tests/test_granite_hybrid_engine.py`` holds the
engine."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models import loader
from distributed_inference_demo_tpu.models.base import (BlockKind, KVCache,
                                                        ModelConfig,
                                                        StageSpec)
from distributed_inference_demo_tpu.models.decoder import (
    _moe_routed, init_full_params, init_layer_params, stage_forward)
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops import kda, ssd

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import model_parity  # noqa: E402  (tools/)

CFG = get_model_config("granite-hybrid-test")
MC = dataclasses.asdict(CFG)
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
FAM = families.load("granite_moe_hybrid")
H, P, N = 16, 8, 128        # a state the interpreted kernels take


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _vectors(s, seed, heads=H, p=P, n=N, groups=1):
    """x, B, C, dt (after its softplus) and A of ``s`` tokens as an ssd
    block makes them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (s, heads, p))
    B = 0.3 * jax.random.normal(ks[1], (s, groups, n))
    C = 0.3 * jax.random.normal(ks[2], (s, groups, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (s, heads)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (heads,), minval=0.0, maxval=2.7))
    return x, B, C, dt, A


# ------------------------------------------------------------ configuration

def test_the_period_s_cache_is_one_pool_of_pages_and_a_state_pool():
    assert [k.attn for k in CFG.period] == ["ssd", "ssd", "full", "ssd"]
    assert CFG.cache_kinds == ((0, 2),) and CFG.state_planes == 6
    assert CFG.state_kind is CFG.period[0] and CFG.state_kind.is_state
    assert CFG.state_shapes == ((8, 16, 16), (3 * (8 * 16 + 2 * 16),))
    assert CFG.state_bytes_per_slot == 6 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert [CFG.plane_of(b) for b in range(8)] == [
        (-1, 0), (-1, 1), (0, 0), (-1, 2), (-1, 3), (-1, 4), (0, 1), (-1, 5)]
    cache = KVCache.create(CFG, CFG.num_layers, 3, 24)
    assert [tuple(k.shape) for k in cache.keys] == [
        (2, 3, 2, 24, 16), (6, 3, 8, 16, 16)]
    assert tuple(cache.values[-1].shape) == (6, 3, 480)
    # solar's kda kind reads its own shapes through the same properties
    solar = get_model_config("solar-open2-test")
    assert solar.state_kind.attn == "kda"
    assert solar.state_shapes == ((4, 16, 16), (3, 192))


@pytest.mark.parametrize("bad", [
    dict(attn="ssd", conv=4),                               # no sizes
    dict(attn="ssd", conv=0, state_heads=8, state_head_dim=16,
         state_size=16, chunk=8),                           # no taps
    dict(attn="full", state_heads=8),                       # not its to say
    dict(attn="ssd", conv=4, state_heads=8, state_head_dim=16,
         state_size=16, chunk=8, groups=3),                 # 3 does not divide 8
], ids=["no-sizes", "no-taps", "sizes-on-full", "groups"])
def test_a_block_kind_that_contradicts_itself_is_refused(bad):
    with pytest.raises(ValueError):
        BlockKind(**bad)


def test_two_state_kinds_in_one_period_are_refused():
    solar = get_model_config("solar-open2-test")
    both = CFG.replace(period=CFG.period + solar.period[1:2])
    with pytest.raises(ValueError, match="one state kind"):
        both.state_shapes


def test_the_parameter_stacks_are_one_a_kind(params):
    shapes = {k: tuple(v.shape) for k, v in params.layers.items()}
    assert shapes["w_in.ssd"] == (2, 3, 64, 2 * 128 + 2 * 16 + 8)
    assert shapes["conv_w.ssd"] == (2, 3, 4, 160)
    assert shapes["conv_b.ssd"] == (2, 3, 160)
    assert shapes["A_log.ssd"] == shapes["D.ssd"] == (2, 3, 8)
    assert shapes["ssd_norm_w.ssd"] == (2, 3, 128)
    assert shapes["wo.ssd"] == (2, 3, 128, 64)
    assert shapes["wq.full"] == (2, 1, 64, 64)
    assert shapes["w_gate.ssd"] == (2, 3, 6, 64, 32)        # 6 of 12 held
    assert shapes["router.ssd"] == (2, 3, 64, 12)
    assert shapes["ws_gate.full"] == (2, 1, 64, 64)         # 2 x 32 shared
    assert params.lm_head == {} and float(params.layers["D.ssd"].min()) == 1
    assert float(jnp.abs(params.layers["conv_b.ssd"]).max()) > 0


# -------------------------------------------------- the two ops, two forms

@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("s,chunk,segments", [
    (32, 16, (32,)),        # two whole chunks, one segment
    (20, 16, (20,)),        # across a chunk's edge, the last chunk padded
    (8, 16, (8,)),          # a segment shorter than a chunk
    (48, 16, (24, 24)),     # a --prefill-chunk that is not the scan's chunk,
                            # the second segment from the first one's state
    (40, 16, (16, 16, 8)),  # ... and a partial last segment
])
def test_the_chunk_form_is_the_recurrence(s, chunk, segments, kernel):
    x, B, C, dt, A = _vectors(s, s)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 3, H, P, N))
    want_y, want_S = ssd.ssd_recurrence(pool[1, 2], x, B, C, dt, A)
    state, outs, lo = pool, [], 0
    for n in segments:
        cut = lambda a: a[lo:lo + n]
        y, state = ssd.ssd_chunk(state, jnp.int32(1), jnp.int32(2),
                                 jnp.bool_(False), cut(x), cut(B), cut(C),
                                 cut(dt), A, chunk=chunk, kernel=kernel,
                                 interpret=kernel)
        outs.append(y)
        lo += n
    np.testing.assert_allclose(jnp.concatenate(outs), want_y, atol=2e-5)
    np.testing.assert_allclose(state[1, 2], want_S, atol=2e-5)
    # no other row, no other plane
    np.testing.assert_array_equal(state[0], pool[0])
    np.testing.assert_array_equal(state[1, :2], pool[1, :2])


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_a_segment_that_starts_a_request_starts_from_zero(kernel):
    x, B, C, dt, A = _vectors(16, 1)
    pool = jnp.ones((1, 2, H, P, N), jnp.float32)
    want_y, want_S = ssd.ssd_recurrence(jnp.zeros((H, P, N)), x, B, C, dt, A)
    y, state = ssd.ssd_chunk(pool, jnp.int32(0), jnp.int32(0),
                             jnp.bool_(True), x, B, C, dt, A, chunk=16,
                             kernel=kernel, interpret=kernel)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(state[0, 0], want_S, atol=2e-5)


def test_tokens_that_are_not_there_leave_the_state_bit_for_bit():
    """``dt = 0`` at a padded position: whatever x, B and C hold there."""
    x, B, C, dt, A = _vectors(16, 2)
    dt = dt.at[11:].set(0.0)
    pool = jax.random.normal(jax.random.PRNGKey(3), (1, 2, H, P, N))
    _, short = ssd.ssd_chunk(pool, jnp.int32(0), jnp.int32(1),
                             jnp.bool_(False), x[:11], B[:11], C[:11],
                             dt[:11], A, chunk=16)
    _, padded = ssd.ssd_chunk(pool, jnp.int32(0), jnp.int32(1),
                              jnp.bool_(False), x.at[11:].add(9.0),
                              B.at[11:].add(9.0), C, dt, A, chunk=16)
    _, same = ssd.ssd_chunk(pool, jnp.int32(0), jnp.int32(1),
                            jnp.bool_(False), x, B, C, dt, A, chunk=16)
    np.testing.assert_array_equal(padded[0, 1], same[0, 1])
    np.testing.assert_allclose(short[0, 1], same[0, 1], atol=1e-6)
    # an ssd_step of a dead row does not touch its state at all
    pool = jnp.concatenate([pool, pool[:, :1]], 1)  # the last row: nobody's
    _, stepped = ssd.ssd_step(pool, jnp.int32(0), jnp.asarray([1, 0]),
                              x[:2], B[:2], C[:2], dt[:2], A,
                              jnp.asarray([True, False]))
    np.testing.assert_array_equal(stepped[0, 0], pool[0, 0])
    assert float(jnp.abs(stepped[0, 1] - pool[0, 1]).max()) > 0


@pytest.mark.parametrize("live", [
    (True, False, True),            # a dead row between two live ones
    (False, False, True),           # dead rows first: nobody's row opens
    (True, False, False),           # dead rows last: the block stays
    (False, False, False),          # nothing decodes
], ids=["101", "001", "100", "000"])
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_a_step_is_the_recurrence_for_live_rows_and_no_other(kernel, live):
    x, B, C, dt, A = _vectors(3, 4)
    pool = jax.random.normal(jax.random.PRNGKey(5), (2, 5, H, P, N))
    rows = jnp.asarray([3, 0, 1])
    y, state = ssd.ssd_step(pool, jnp.int32(1), rows, x, B, C, dt, A,
                            jnp.asarray(live), kernel=kernel,
                            interpret=kernel)
    want = np.array(pool)
    for i in range(3):
        if not live[i]:
            assert not np.asarray(y[i]).any()
            continue
        want_y, want_S = ssd.ssd_recurrence(
            pool[1, rows[i]], x[i:i + 1], B[i:i + 1], C[i:i + 1],
            dt[i:i + 1], A)
        np.testing.assert_allclose(y[i], want_y[0], atol=2e-5)
        np.testing.assert_allclose(state[1, rows[i]], want_S, atol=2e-5)
        want[1, rows[i]] = state[1, rows[i]]
    # a dead row's, nobody's, the other plane: bit for bit
    np.testing.assert_array_equal(state, want)


# the two cells' states (heads, P, N, groups): granite's, nemotron's
CELL_STATES = {"granite": (128, 64, 128, 1), "nemotron": (64, 64, 128, 8)}
PATTERNS = {"0110": (False, True, True, False),     # a dead row first
            "1011": (True, False, True, True),      # ... between two live
            "0000": (False, False, False, False),   # nothing decodes
            "1111": (True, True, True, True)}


@pytest.mark.parametrize("live", PATTERNS.values(), ids=PATTERNS)
@pytest.mark.parametrize("cell", CELL_STATES)
def test_the_step_kernel_at_the_cells_states_is_the_step_s_arithmetic(
        cell, live):
    """The tile loop (interpreted) at both cells' head counts and
    groupings against ``_step_math`` a live row, whatever the dead rows
    lie between: their states, nobody's row and the other plane bit for
    bit."""
    heads, p, n, groups = CELL_STATES[cell]
    assert ssd.on_kernel((2, 6, heads, p, n), groups, platform="tpu")[0]
    x, B, C, dt, A = _vectors(4, 7, heads, p, n, groups)
    pool = jax.random.normal(jax.random.PRNGKey(8), (2, 6, heads, p, n))
    rows = jnp.asarray([4, 0, 3, 1])
    y, state = ssd.ssd_step(pool, jnp.int32(1), rows, x, B, C, dt, A,
                            jnp.asarray(live), kernel=True, interpret=True)
    want = np.array(pool)
    for i in range(4):
        if not live[i]:
            assert not np.asarray(y[i]).any()
            continue
        want_y, want_S = ssd._step_math(
            pool[1, rows[i]], x[i], ssd._of_heads(B[i], heads),
            ssd._of_heads(C[i], heads), dt[i], A)
        np.testing.assert_allclose(y[i], want_y, atol=2e-5)
        np.testing.assert_allclose(state[1, rows[i]], want_S, atol=2e-5)
        want[1, rows[i]] = state[1, rows[i]]
    np.testing.assert_array_equal(state, want)


@pytest.mark.parametrize("live,at,n", [
    ((True, False, True, True), (0, 2, 3, 3), 3),
    ((False, True, True, False), (1, 2, 2, 2), 2),
    ((False, False, False, True), (3, 3, 3, 3), 1),
    ((False, False, False, False), None, 0),
], ids=["1011", "0110", "0001", "0000"])
def test_the_live_rows_go_first_and_a_dead_step_names_the_last_live_one(
        live, at, n):
    """What the step kernel walks: no dead step lies between two live
    ones (it would hold the next row's block back), and every dead step
    names what the call already holds."""
    rows = jnp.asarray([7, 5, 2, 4])
    got_rows, got_at, got_n = ssd._blocks_of(rows, jnp.asarray(live), 9)
    assert int(got_n[0]) == n and got_n.shape == (1,)
    if n:
        assert tuple(np.asarray(got_at)) == at
        assert tuple(np.asarray(got_rows)) == tuple(
            int(rows[i]) for i in at)
    else:       # nobody's row, at every step
        assert tuple(np.asarray(got_rows)) == (9,) * 4


@pytest.mark.parametrize("form", ["before", "served", "columns", "nt"])
def test_the_table_tool_holds_every_form_to_the_step_s_arithmetic(form):
    """``tools/ssd_step_table.py``: the loop as it stood before PR 67 and
    each form tried since run the same rows (the dead ones between the
    live), each against ``ssd_step``'s XLA form, a dead row's state left
    bit for bit; the two cells' states are its rows."""
    import argparse
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ssd_step_table", ROOT / "tools" / "ssd_step_table.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert {c["name"]: (c["heads"], c["p"], c["n"], c["groups"], c["planes"],
                        c["slots"], c["live"])
            for c in tool.ssd_configs([])} == {
        "granite-4.0-h-small-bf16-ep2": (128, 64, 128, 1, 9, 32, 20),
        "nemotron-3-nano-30b-a3b-bf16-ep2": (64, 64, 128, 8, 4, 64, 64),
        # (PR 69: the linear kind rides the call, B and C a head's own)
        "minicpm-sala-9b-bf16": (32, 128, 128, 32, 6, 16, 12)}
    toy = dict(name="toy", planes=2, slots=5, live=3, heads=16, p=16, n=128,
               groups=2)
    row, = tool.rows_of(toy, argparse.Namespace(
        seed=3, dead="seeded", reps=1, rehearse=True, trace=False,
        form=[form]))
    assert row["bytes_us"] == round(
        1e-3 * 3 * 2 * 16 * 16 * 128 * 4 / tool.PEAKS.hbm_gbs, 1)
    assert row[form]["err"] < 1e-6 and row[form]["dead_rows_untouched"]
    assert row[form]["us"] > 0


def test_groups_share_b_and_c_among_their_heads():
    """Two groups (the XLA form; the kernels serve one): heads 0-3 read
    group 0's B and C, heads 4-7 group 1's."""
    x, B, C, dt, A = _vectors(12, 6, heads=8, p=4, n=8, groups=2)
    pool = jnp.zeros((1, 1, 8, 4, 8), jnp.float32)
    y, state = ssd.ssd_chunk(pool, jnp.int32(0), jnp.int32(0),
                             jnp.bool_(True), x, B, C, dt, A, chunk=8)
    for g in range(2):
        hs = slice(4 * g, 4 * g + 4)
        want_y, want_S = ssd.ssd_recurrence(
            jnp.zeros((4, 4, 8)), x[:, hs], B[:, g:g + 1], C[:, g:g + 1],
            dt[:, hs], A[hs])
        np.testing.assert_allclose(y[:, hs], want_y, atol=2e-5)
        np.testing.assert_allclose(state[0, 0, hs], want_S, atol=2e-5)


def test_where_the_kernels_serve():
    ok = lambda *a, **k: ssd.on_kernel(*a, platform="tpu", **k)
    assert ok((9, 34, 128, 64, 128), 1, 256) == (True, "")
    assert ok((9, 34, 128, 64, 128), 1, 1)[0]
    assert not ok((9, 34, 128, 64, 128), 1, 64)[0]      # not whole lanes
    assert not ok((6, 5, 8, 16, 16))[0]                 # the toy state
    assert not ok((9, 34, 128, 64, 128), 2)[0]          # two groups
    # the step's tile is 128 (h, p) rows: P divides the lanes
    assert ok((9, 34, 128, 128, 128))[0] and ok((9, 34, 128, 16, 128))[0]
    assert not ok((9, 34, 128, 24, 128))[0]
    assert not ok((9, 34, 128, 256, 128))[0]
    assert ssd.on_kernel((9, 34, 128, 64, 128), platform="cpu") == (
        False, "platform cpu")
    assert ssd.on_kernel((1, 2, 128, 8, 128), backend="pallas",
                         platform="cpu")[0]


def test_the_convolution_s_bias_and_its_tail_in_pieces():
    """``silu(conv(x) + b)`` whole against the same in pieces, the tail
    carried: 4 taps over 160 channels, pieces of 7, 1 and 12."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(1, 20, 160)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 160)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(160,)), jnp.float32)
    zeros = jnp.zeros((1, 3, 160), jnp.float32)
    whole, tail = kda.causal_conv(u, zeros, w, jnp.asarray([20]), b)
    padded = jnp.concatenate([zeros, u], 1)[0]
    by_hand = jax.nn.silu(b + sum(w[t] * padded[t:t + 20] for t in range(4)))
    np.testing.assert_allclose(whole[0], by_hand, atol=1e-6)
    assert float(jnp.abs(whole - kda.causal_conv(
        u, zeros, w, jnp.asarray([20]))[0]).max()) > 0.1    # the bias bites
    parts, kept = [], zeros
    for lo, hi in ((0, 7), (7, 8), (8, 20)):
        y, kept = kda.causal_conv(u[:, lo:hi], kept, w,
                                  jnp.asarray([hi - lo]), b)
        parts.append(y)
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole, atol=1e-6)
    np.testing.assert_array_equal(kept, tail)


# ------------------------------------ the program against the reference

@pytest.mark.parametrize("seed", [3])
def test_dense_forward_agrees_with_the_family_s_full_forward(seed):
    params = init_full_params(jax.random.PRNGKey(seed), CFG)
    ids = model_parity.seeded_ids(seed, 40, CFG.vocab_size)
    ref, _ = model_parity.reference_logprobs(CFG, params, ids, 1)
    logits, cache = stage_forward(
        params, CFG, SPEC, jnp.asarray(ids)[None],
        KVCache.create(CFG, CFG.num_layers, 1, 48), jnp.arange(40)[None])
    np.testing.assert_allclose(jax.nn.log_softmax(logits[0], -1), ref,
                               atol=2e-4)
    # the logits are small under the tied head's / 16: hold them relatively
    # too, and the six states the sequence leaves
    want = np.asarray(ref - ref.mean(-1, keepdims=True))
    got = np.asarray(logits[0] - logits[0].mean(-1, keepdims=True))
    assert np.abs(got - want).max() < 1e-3 * np.abs(want).max()
    states = model_parity.reference_states(CFG, params, ids)
    np.testing.assert_allclose(
        np.asarray(cache.keys[-1])[:, 0, ::2, ::8], states, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.2)])
def test_served_path_agrees_with_the_family_s_full_forward(dtype, tol):
    """Prefill in chunks (the last partial and padded: 45 = 16 + 16 + 13,
    a chunk of two of the scan's), then decode, through pages and rows of
    the state pool, against the float32 reference over the whole sequence,
    on log-probabilities over the vocabulary."""
    cfg = CFG.replace(dtype_name=dtype)
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    prompts = np.stack([model_parity.seeded_ids(7 + i, 45, cfg.vocab_size)
                        for i in range(2)])
    args = type("A", (), dict(page=4, chunk=16, steps=6, kv_dtype="bf16"))
    toks, served, paths, state = model_parity.served(cfg, params, prompts,
                                                     args)
    assert set(paths) == {"prefill/full", "prefill/ssd", "decode/full",
                          "decode/ssd"}
    for r in range(2):
        ids = np.concatenate([prompts[r], toks[r]])
        ref, _ = model_parity.reference_logprobs(cfg, params, ids, 45)
        assert np.abs(served[r] - ref).max() < tol
        if dtype == "float32":
            readings = FAM.state_readings(
                state[:, r], model_parity.reference_states(cfg, params, ids))
            assert max(readings["rel_err"]) < 1e-4
            assert FAM.state_problem(readings, "float32") is None


_control = lambda **kw: model_parity.state_controls(kind="ssd", **kw)  # noqa: E731
FAULTS = {
    "not_carried": lambda p: _control(not_carried=True),
    "tail_dropped": lambda p: _control(tail_dropped=True),
    "bf16_state": lambda p: _control(bf16_state=True),
    "skip_dropped": lambda p: {"D.ssd": 0.0 * p.layers["D.ssd"]},
    "dt_bias_dropped": lambda p: {"dt_bias.ssd": 0.0 * p.layers["dt_bias.ssd"]},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_is_far_from_the_reference(params, fault, monkeypatch):
    """The controls, at toy size: the served state against the reference's
    after the same ids reads past the family's limit (a rounded state: its
    own residue reads 0), where the sound path reads under 1e-4."""
    for mod, names in ((kda, ("causal_conv",)),
                       (ssd, ("ssd_step", "ssd_chunk"))):
        for name in names:
            monkeypatch.setattr(mod, name, getattr(mod, name))  # restored
    swap = FAULTS[fault](params)
    served_params = (dataclasses.replace(
        params, layers=dict(params.layers, **swap)) if swap else params)
    prompts = model_parity.seeded_ids(5, 45, CFG.vocab_size)[None]
    args = type("A", (), dict(page=4, chunk=16, steps=4, kv_dtype="bf16"))
    toks, _, _, state = model_parity.served(CFG, served_params, prompts,
                                            args)
    readings = FAM.state_readings(state[:, 0], model_parity.reference_states(
        CFG, params, np.concatenate([prompts[0], toks[0]])))
    problem = FAM.state_problem(readings, "float32")
    if fault == "bf16_state":
        assert max(readings["f32_residue"]) == 0.0
        assert "not the float32 state" in problem
    else:
        assert max(readings["rel_err"]) > FAM.STATE_REL_TOL
        assert "after the same ids" in problem


@pytest.mark.parametrize("fault", ["residual", "embedding", "logits",
                                   "attention", "gate_after_norm",
                                   "shared_dropped"])
def test_a_multiplier_left_at_one_is_far_from_the_reference(params, fault,
                                                            monkeypatch):
    """Each of granite's four multipliers, the gate's place and the shared
    MLP: the dense forward against the family's log-probabilities."""
    from distributed_inference_demo_tpu.models import decoder
    cfg = {"residual": CFG.replace(residual_multiplier=1.0),
           "embedding": CFG.replace(embedding_multiplier=1.0),
           "logits": CFG.replace(logits_scaling=1.0),
           "attention": CFG.replace(attn_scale=1.0)}.get(fault, CFG)
    served_params = params
    if fault == "gate_after_norm":
        monkeypatch.setattr(
            decoder, "_gated_norm", lambda y, z, w, eps: decoder.rms_norm(
                y, w, eps) * jax.nn.silu(z.astype(jnp.float32)))
    if fault == "shared_dropped":
        served_params = dataclasses.replace(params, layers={
            k: (0.0 * v if k.startswith("ws_down") else v)
            for k, v in params.layers.items()})
    ids = model_parity.seeded_ids(3, 40, CFG.vocab_size)
    ref, _ = model_parity.reference_logprobs(CFG, params, ids, 1)
    logits, _ = stage_forward(
        served_params, cfg, SPEC, jnp.asarray(ids)[None],
        KVCache.create(CFG, CFG.num_layers, 1, 48), jnp.arange(40)[None])
    err = np.abs(np.asarray(jax.nn.log_softmax(logits[0], -1)) - ref).max()
    assert err > 20 * 2e-4, err


# -------------------------------------------------------------- the share

def test_the_two_shares_add_up_to_the_uncut_layer_in_the_program():
    """Routed parts of shares [0, 6) and [6, 12) plus the shared MLP once =
    the layer with every expert here (``_moe_routed``, float32)."""
    cfg = CFG.of_kind(CFG.period[0]).replace(experts_held=())
    lp = jax.tree.map(lambda a: a[0], init_layer_params(
        jax.random.PRNGKey(5), cfg, 1))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 10, 64)),
                    jnp.float32)
    whole, rows = _moe_routed(cfg, lp, x)
    none = cfg.replace(num_shared_experts=0)
    shared = whole - _moe_routed(none, lp, x)[0]
    total, held = shared, 0
    for e0 in (0, 6):
        part = {k: (v[e0:e0 + 6] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in lp.items()}
        y, r = _moe_routed(none.replace(experts_held=(6, e0)), part, x)
        np.testing.assert_array_equal(r, rows[e0:e0 + 6])
        total, held = total + y, held + int(r.sum())
    assert held == 10 * 3
    scale = float(jnp.abs(whole - shared).max())    # the routed sum alone
    assert scale > 0
    np.testing.assert_allclose(total, whole, atol=1e-4 * scale + 1e-7)
    assert float(jnp.abs(shared).max()) > 0


def test_the_two_shares_add_up_to_the_uncut_block_in_the_reference():
    """The family's ssd block with shares of 6 against all 12 held: routed
    parts summed plus everything else (the shared MLP too) once."""
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
                  for k, v in one.items() if k.endswith(".ssd")}
        return FAM.blocks(mc)[0](leaves, x)

    whole, nothing = block(12, 0), block(0, 0)
    parts = sum(block(6, e0) - nothing for e0 in (0, 6))
    routed = float(jnp.abs(whole - nothing).max())
    assert routed > 0
    np.testing.assert_allclose(parts + nothing, whole,
                               atol=1e-3 * routed + 1e-7)


# ------------------------------------------------------------- the loader

def test_the_loader_maps_a_granitemoehybrid_state_dict(params):
    """A synthetic HF state dict (two repeats of the period at toy widths,
    all 12 experts, 300 embedding rows) loads to the tree the program
    seeds: names, transposes, gate-then-up halves, the share of experts,
    the cut vocabulary; and runs."""
    rng = np.random.default_rng(0)
    t = lambda *shape: rng.normal(size=shape).astype(np.float32)
    raw = {"model.embed_tokens.weight": t(300, 64),
           "model.norm.weight": t(64)}
    for i in range(8):
        p = f"model.layers.{i}."
        raw[p + "input_layernorm.weight"] = t(64)
        raw[p + "post_attention_layernorm.weight"] = t(64)
        if CFG.period[i % 4].attn == "ssd":
            raw[p + "mamba.in_proj.weight"] = t(296, 64)
            raw[p + "mamba.conv1d.weight"] = t(160, 1, 4)
            raw[p + "mamba.conv1d.bias"] = t(160)
            for name in ("A_log", "D", "dt_bias"):
                raw[p + "mamba." + name] = t(8)
            raw[p + "mamba.norm.weight"] = t(128)
            raw[p + "mamba.out_proj.weight"] = t(64, 128)
        else:
            raw[p + "self_attn.q_proj.weight"] = t(64, 64)
            raw[p + "self_attn.k_proj.weight"] = t(32, 64)
            raw[p + "self_attn.v_proj.weight"] = t(32, 64)
            raw[p + "self_attn.o_proj.weight"] = t(64, 64)
        raw[p + "block_sparse_moe.router.layer.weight"] = t(12, 64)
        raw[p + "block_sparse_moe.input_linear.weight"] = t(12, 64, 64)
        raw[p + "block_sparse_moe.output_linear.weight"] = t(12, 64, 32)
        raw[p + "shared_mlp.input_linear.weight"] = t(128, 64)
        raw[p + "shared_mlp.output_linear.weight"] = t(64, 64)
    cfg = CFG.replace(experts_held=(6, 6))      # the second share
    got = loader.params_from_state_dict(raw, cfg)
    seeded = {k: tuple(v.shape) for k, v in params.layers.items()}
    assert {k: tuple(v.shape) for k, v in got.layers.items()} == seeded
    eq = lambda a, b: np.testing.assert_array_equal(np.asarray(a), b)
    # block 5 = repeat 1, place 1: the ssd kind's second place
    p = "model.layers.5."
    eq(got.layers["w_in.ssd"][1, 1], raw[p + "mamba.in_proj.weight"].T)
    eq(got.layers["conv_w.ssd"][1, 1],
       raw[p + "mamba.conv1d.weight"][:, 0].T)
    eq(got.layers["conv_b.ssd"][1, 1], raw[p + "mamba.conv1d.bias"])
    eq(got.layers["D.ssd"][1, 1], raw[p + "mamba.D"])
    eq(got.layers["wo.ssd"][1, 1], raw[p + "mamba.out_proj.weight"].T)
    moe_in = raw[p + "block_sparse_moe.input_linear.weight"]
    eq(got.layers["w_gate.ssd"][1, 1, 2], moe_in[8, :32].T)   # expert 6 + 2
    eq(got.layers["w_up.ssd"][1, 1, 2], moe_in[8, 32:].T)
    eq(got.layers["w_down.ssd"][1, 1, 2],
       raw[p + "block_sparse_moe.output_linear.weight"][8].T)
    eq(got.layers["router.ssd"][1, 1],
       raw[p + "block_sparse_moe.router.layer.weight"].T)
    shared = raw[p + "shared_mlp.input_linear.weight"]
    eq(got.layers["ws_gate.ssd"][1, 1], shared[:64].T)
    eq(got.layers["ws_up.ssd"][1, 1], shared[64:].T)
    # block 6 = repeat 1, place 2: the full kind
    eq(got.layers["wk.full"][1, 0],
       raw["model.layers.6.self_attn.k_proj.weight"].T)
    eq(got.embed["tokens"], raw["model.embed_tokens.weight"][:256])
    assert got.lm_head == {}
    logits, _ = stage_forward(got, cfg, SPEC, jnp.asarray([[1, 2, 3]]),
                              KVCache.create(cfg, 2, 1, 8),
                              jnp.arange(3)[None])
    assert np.isfinite(np.asarray(logits)).all()


def test_the_state_pool_s_size_is_the_family_s():
    conf = ModelConfig(**__import__("json").loads(
        (ROOT / "benchmark" / "configs" /
         "granite-4.0-h-small-bf16-ep2.json").read_text())["model_config"])
    assert conf.state_planes == 9
    assert conf.state_shapes == ((128, 64, 128), (3 * 8448,))
    assert conf.state_bytes_per_slot == 38_204_928
    assert conf.state_bytes_per_slot == FAM.ssd_state_bytes_per_slot(
        dataclasses.asdict(conf))
