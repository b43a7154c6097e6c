"""The routed expert layer (``decoder._route`` / ``_moe_routed`` over
``ops.grouped_matmul``) against its definition, olmoe through the mixed
engine, and the routing counters.

The definition of a routed layer is "every expert for every token, weights
zero off the chosen k": written here by hand, with dequantized matrices,
no sort and no grouping.  The routed layer must equal it whatever the
routing does: spread evenly, pile every token onto the same experts, or
leave experts empty.  Nothing is dropped.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_inference_demo_tpu.models import (
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models.decoder import (
    _moe_mlp, _moe_mlp_ep, _moe_routed, _route, init_full_params,
    stage_forward)
from distributed_inference_demo_tpu.ops.grouped_matmul import (
    LayerOf, grouped_matmul, route_grouped_matmul, tiling)
from distributed_inference_demo_tpu.ops.quant import (
    QuantizedArray, QuantizedArray4, quantize_array, quantize_array4)
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.parallel import MeshConfig, make_mesh
from distributed_inference_demo_tpu.parallel.tensor import make_tp_stage_fn
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.telemetry.tracing import (
    DISPATCH_FIELDS, DISPATCH_LAST_FIELDS, MOE_DISPATCH_FIELDS, DispatchTrace,
    MoeCounters)

OLMOE = get_model_config("olmoe-test")          # 8 experts, 2 a token
MIXTRAL = get_model_config("mixtral-test")      # 4 experts, 2 a token
# granite's kind at toy width: 10 of 72 a token, the first 36 held here
GRANITE = OLMOE.replace(num_experts=72, experts_per_token=10,
                        norm_topk_prob=True, experts_held=(36, 0))


def _layer(rng, cfg, routing="uniform", dtype=jnp.float32):
    """One layer's router and expert matrices, float32.  ``routing``
    "one": every token's logits order the experts the same way, so all
    tokens land on the same k experts and the rest stay empty (with
    positive rows, below)."""
    E, H, I = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    ks = jax.random.split(rng, 4)
    router = jax.random.normal(ks[0], (H, E), dtype) * H ** -0.5
    if routing == "one":
        router = jnp.ones((H, 1), dtype) * jnp.linspace(-2.0, 2.0, E) / H
    return {"router": router,
            "w_gate": jax.random.normal(ks[1], (E, H, I), dtype) * H ** -0.5,
            "w_up": jax.random.normal(ks[2], (E, H, I), dtype) * H ** -0.5,
            "w_down": jax.random.normal(ks[3], (E, I, H), dtype) * I ** -0.5}


def _held(cfg, lp):
    """The layer with its expert stacks cut to the share held here."""
    held, first = cfg.experts_held or (cfg.num_experts, 0)
    return {n: w[first:first + held] if n in ("w_gate", "w_up", "w_down")
            else w for n, w in lp.items()}


def _f32(w):
    return (w.dequantize(jnp.float32)
            if isinstance(w, (QuantizedArray, QuantizedArray4)) else w)


def _definition(cfg, lp, x, valid=None):
    """Every expert for every token, weights zero off the top k, off the
    experts held here (``experts_held``: the stacks hold those alone) and
    on the rows that hold no token (``valid`` [T] bool)."""
    T, E, k = x.shape[0], cfg.num_experts, cfg.experts_per_token
    probs = np.asarray(jax.nn.softmax(
        np.asarray(x, np.float64) @ np.asarray(lp["router"], np.float64)))
    order = np.argsort(-probs, axis=-1)[:, :k]
    w = np.zeros((T, E))
    for t in range(T):
        w[t, order[t]] = probs[t, order[t]]
    if cfg.norm_topk_prob:
        w /= w.sum(-1, keepdims=True)
    if valid is not None:
        w[~np.asarray(valid)] = 0
    if cfg.experts_held:
        held, first = cfg.experts_held
        w, E = w[:, first:first + held], held
    g, u, d = (np.asarray(_f32(lp[n]), np.float64)
               for n in ("w_gate", "w_up", "w_down"))
    xs = np.asarray(x, np.float64)
    y = np.zeros_like(xs)
    for e in range(E):
        a = xs @ g[e]
        y += w[:, e:e + 1] * ((a / (1 + np.exp(-a)) * (xs @ u[e])) @ d[e])
    return y, (w > 0).sum(0)


# ------------------------------------------------------------- the router

@pytest.mark.parametrize("cfg", [OLMOE, MIXTRAL], ids=["olmoe", "mixtral"])
def test_route_by_hand(cfg):
    """olmoe keeps the k largest of softmax over ALL experts as they are
    (they sum to less than 1); mixtral's renormalised k equal its own
    "top-k of the logits, then softmax over the k" to 1e-6."""
    lp = _layer(jax.random.PRNGKey(0), cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.hidden_size))
    w, e = _route(cfg, lp, h)
    assert w.dtype == jnp.float32 and e.dtype == jnp.int32
    assert w.shape == e.shape == (12, cfg.experts_per_token)
    logits = np.asarray(h, np.float64) @ np.asarray(lp["router"], np.float64)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    top = np.argsort(-logits, -1)[:, :cfg.experts_per_token]
    assert (np.asarray(e) == top).all()
    if cfg.norm_topk_prob:
        tl = np.take_along_axis(logits, top, -1)
        want = np.exp(tl) / np.exp(tl).sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    else:
        want = np.take_along_axis(probs, top, -1)
        assert (np.asarray(w).sum(-1) < 1.0 - 1e-3).all()
    np.testing.assert_allclose(np.asarray(w), want, atol=1e-6)


def test_route_stays_float32_on_bf16_rows():
    """bf16 activations and a bf16 router leaf: the matmul, the softmax
    and the top-k still run in float32 on the float32-cast rows."""
    lp = _layer(jax.random.PRNGKey(0), OLMOE, dtype=jnp.bfloat16)
    h = jax.random.normal(jax.random.PRNGKey(1), (9, OLMOE.hidden_size),
                          jnp.bfloat16)
    w, e = _route(OLMOE, lp, h)
    logits = (np.asarray(h, np.float32).astype(np.float64)
              @ np.asarray(lp["router"], np.float32).astype(np.float64))
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(probs, np.asarray(e), -1),
        atol=1e-6)


# ------------------------------------------------------- the routed layer

@pytest.mark.parametrize("quant", ["float32", "int8", "int4"])
@pytest.mark.parametrize("routing", ["uniform", "one", "empty"])
@pytest.mark.parametrize("cfg", [OLMOE, MIXTRAL], ids=["olmoe", "mixtral"])
def test_routed_layer_equals_its_definition(cfg, routing, quant):
    """Uniform routing; every token on the same k experts (a group as
    long as all the rows, E - k empty groups); three tokens (most experts
    empty).  float32 to 2e-5; the quantized stacks against the definition
    on their own dequantized matrices (the scale multiplies the output
    instead of the matrix: float32 rounding), 1e-4."""
    lp = _layer(jax.random.PRNGKey(2), cfg,
                "one" if routing == "one" else "uniform")
    if quant != "float32":
        q = quantize_array if quant == "int8" else quantize_array4
        lp = dict(lp, **{n: q(lp[n]) for n in ("w_gate", "w_up", "w_down")})
    T = 3 if routing == "empty" else 24
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (T, cfg.hidden_size)))
    want, want_rows = _definition(cfg, lp, x)
    got, rows = _moe_routed(cfg, lp, x.reshape(2 if T == 24 else 1, -1,
                                              cfg.hidden_size))
    assert (np.asarray(rows) == want_rows).all()
    assert int(rows.sum()) == T * cfg.experts_per_token      # none dropped
    if routing == "one":
        assert sorted(np.asarray(rows))[-cfg.experts_per_token:] \
            == [T] * cfg.experts_per_token
        assert int((np.asarray(rows) == 0).sum()) \
            == cfg.num_experts - cfg.experts_per_token
    if routing == "empty":
        assert int((np.asarray(rows) == 0).sum()) >= 1
    np.testing.assert_allclose(
        np.asarray(got).reshape(T, -1), want,
        atol=2e-5 if quant == "float32" else 1e-4)
    assert (np.asarray(_moe_mlp(cfg, lp, x[None])) == np.asarray(
        got).reshape(1, T, -1)).all()


def _unwritten_rows_are_nan(monkeypatch):
    """Every grouped matmul of the routed layer leaves NaN in the rows
    past the last group, as a kernel that never writes them may."""
    from distributed_inference_demo_tpu.models import decoder

    def poisoned(lhs, rhs, sizes, **kw):
        out = grouped_matmul(lhs, rhs, sizes, **kw)
        written = jnp.arange(out.shape[0]) < jnp.sum(sizes)
        return jnp.where(written[:, None], out, jnp.nan)
    monkeypatch.setattr(decoder, "grouped_matmul", poisoned)


@pytest.mark.parametrize("case", ["held", "valid", "held-valid"])
def test_unwritten_rows_are_masked_not_weighted(case, monkeypatch):
    """PR 65: a layer of granite's kind at toy width (10 of 72 experts a
    token, bf16 rows; the first 36 held here, a ``valid`` mask with idle
    rows) against the definition, while the grouped matmul writes NaN into
    every row no group holds (the other chip's experts' rows, the idle
    rows').  The combine masks a row by its place in expert order: a mask
    applied as a zero weight gives NaN, and fails here."""
    cfg = GRANITE if "held" in case else GRANITE.replace(experts_held=())
    lp = _layer(jax.random.PRNGKey(2), cfg, dtype=jnp.bfloat16)
    T, H = 24, cfg.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(3), (T, H), jnp.bfloat16)
    valid = None
    if "valid" in case:
        valid = jnp.asarray(np.random.RandomState(4).rand(T) < 0.6)
        assert 0 < int(valid.sum()) < T
    want, want_rows = _definition(
        cfg, jax.tree.map(lambda a: a.astype(jnp.float32), lp),
        x.astype(jnp.float32), valid)
    _unwritten_rows_are_nan(monkeypatch)
    got, rows = _moe_routed(cfg, _held(cfg, lp), x.reshape(2, T // 2, H),
                            None,
                            None if valid is None else valid.reshape(2, -1))
    got = np.asarray(got.astype(jnp.float32)).reshape(T, H)
    assert np.isfinite(got).all()
    assert (np.asarray(rows) == want_rows).all()
    tokens = T if valid is None else int(valid.sum())
    # some rows lie in no group: there was something to mask
    assert 0 < int(rows.sum()) < T * cfg.experts_per_token
    assert int(rows.sum()) <= tokens * cfg.experts_per_token
    if valid is not None:
        assert (got[~np.asarray(valid)] == 0).all()
    # bf16 rows: the hidden rows and the down projection's are rounded
    np.testing.assert_allclose(got, want, atol=2e-2 * np.abs(want).max())


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("case", ["plain", "held-valid"])
def test_the_combine_holds_no_float32_copy_of_the_rows(case):
    """PR 65, static: ``_moe_routed`` at ``T`` = 24, ``k`` = 10 on bf16
    rows.  The rows come back from expert order by ONE gather, in the
    dtype the down projection wrote, as ``[k, T, H]``; no value has the
    shape ``[T, k, H]`` (top-k on a tiled axis); the mask is a ``select``
    on the bf16 rows, so nothing unwritten is ever multiplied; and behind
    the gather the float32 values of ``T k H`` elements are two, the
    widened rows and their product with the weights, which the sum over
    the major axis consumes (one fusion on the chip:
    ``tests/test_bring_up.py`` compiles it there)."""
    cfg = GRANITE if case == "held-valid" else GRANITE.replace(
        experts_held=())
    lp = _held(cfg, _layer(jax.random.PRNGKey(2), cfg, dtype=jnp.bfloat16))
    T, k, H = 24, cfg.experts_per_token, cfg.hidden_size
    x = jnp.zeros((2, T // 2, H), jnp.bfloat16)
    valid = jnp.ones((2, T // 2), bool) if case == "held-valid" else None
    jaxpr = jax.make_jaxpr(
        lambda lp_, x_: _moe_routed(cfg, lp_, x_, None, valid))(lp, x)
    eqns = list(_eqns(jaxpr.jaxpr))
    outs = [(eqn.primitive.name, v.aval) for eqn in eqns
            for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert not [o for o in outs if o[1].shape == (T, k, H)]
    gathers = [o for o in outs if o[0] == "gather"
               and o[1].shape == (k, T, H)]
    assert len(gathers) == 1 and gathers[0][1].dtype == jnp.bfloat16
    behind = outs[outs.index(gathers[0]) + 1:]    # in the program's order
    rows = [o for o in behind if o[1].size >= T * k * H
            and o[0] not in ("jit", "pjit")]      # their equations are here
    # (the broadcast is the mask's zero)
    assert [name for name, aval in rows if aval.dtype == jnp.bfloat16] == [
        "broadcast_in_dim", "select_n"]
    assert [name for name, aval in rows if aval.dtype == jnp.float32] == [
        "convert_element_type", "mul"]
    assert all(aval.shape == (k, T, H) for _, aval in rows
               if aval.dtype != jnp.bool_)
    summed, = [e for e in eqns if e.primitive.name == "reduce_sum"
               and e.invars[0].aval.shape == (k, T, H)]
    assert summed.params["axes"] == (0,)
    assert summed.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("masked", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("cfg", [OLMOE, MIXTRAL, GRANITE],
                         ids=["olmoe", "mixtral", "granite"])
def test_rows_are_the_histogram_the_token_major_order_gave(cfg, masked):
    """PR 65 lays the token-expert rows k-major before the sort; a group
    is the same rows in another order, so ``rows`` (the group sizes, and
    what ``/stats.moe`` sums) are what the order before gave: a count of
    the valid tokens' experts, the held ones' alone."""
    lp = _held(cfg, _layer(jax.random.PRNGKey(2), cfg))
    held, first = cfg.experts_held or (cfg.num_experts, 0)
    T, k = 24, cfg.experts_per_token
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, cfg.hidden_size))
    valid = (jnp.asarray(np.random.RandomState(4).rand(1, T) < 0.5)
             if masked else None)
    _, rows = _moe_routed(cfg, lp, x, None, valid)
    _, experts = _route(cfg, lp, x[0])
    flat = np.asarray(experts).reshape(T * k)        # row t k + j
    if masked:
        flat = flat[np.repeat(np.asarray(valid[0]), k)]
    want = np.bincount(flat, minlength=cfg.num_experts)
    assert (np.asarray(rows) == want[first:first + held]).all()
    assert rows.dtype == jnp.int32 and rows.shape == (held,)


def test_int8_scales_are_an_expert_s_own():
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 16, 8)) \
        * jnp.arange(1, 5)[None, :, None, None]
    q = quantize_array(w)
    assert q.scale.shape == (3, 4, 1, 8)
    np.testing.assert_allclose(np.asarray(q.scale),
                               np.abs(np.asarray(w)).max(-2, keepdims=True)
                               / 127.0, rtol=1e-6)


@pytest.mark.parametrize("quant", ["float32", "int8"])
def test_kernel_form_equals_the_xla_form(quant):
    """The Pallas form (interpreted) against ``ragged_dot`` on the same
    sorted rows: ragged groups, empty groups, rows that are no multiple
    of the tile, a layer picked out of a stack by index."""
    rs = np.random.RandomState(0)
    L, E, K, N = 3, 8, 256, 128
    w = jnp.asarray(rs.randn(L, E, K, N), jnp.float32) * K ** -0.5
    rhs = quantize_array(w) if quant == "int8" else w
    for m, sizes in ((64, [8] * 8), (64, [64] + [0] * 7),
                     (64, [0, 3, 0, 40, 0, 0, 21, 0]), (40, [5] * 8),
                     (64, [0, 3, 0, 30, 0, 0, 21, 0])):
        x = jnp.asarray(rs.randn(m, K), jnp.float32)
        gs = jnp.asarray(sizes, jnp.int32)
        n = int(sum(sizes))
        for layer in (0, 2):
            one = jax.tree.map(lambda a: a[layer], rhs)
            want = grouped_matmul(x, one, gs, backend="xla")
            got = grouped_matmul(x, LayerOf(rhs, jnp.int32(layer)), gs,
                                 backend="pallas", interpret=True)
            np.testing.assert_allclose(np.asarray(got)[:n],
                                       np.asarray(want)[:n], atol=1e-4)
            got1 = grouped_matmul(x, one, gs, backend="pallas",
                                  interpret=True)
            np.testing.assert_allclose(np.asarray(got1)[:n],
                                       np.asarray(want)[:n], atol=1e-4)


def _exact(rs, shape, top, keep=1.0):
    """Small whole numbers (a share ``keep`` of them, the rest zero):
    their products and sums are exact in float32 and, kept under 256, in
    bfloat16, so two orders of the same sum agree to the bit."""
    return rs.randint(-top, top + 1, shape) * (rs.rand(*shape) < keep)


@pytest.mark.parametrize("quant, k", [("bf16", 2048), ("int8", 4096)])
def test_kernel_form_reads_the_contraction_in_one_tile(quant, k):
    """bf16 rows on a bf16 and an int8 stack at shapes whose contraction
    was cut in two before PR 63 (a right-hand tile of 2 MiB), the layer
    picked out of the stack: groups that span several row tiles, an empty
    group, rows past the last group that no group holds.  The kernel
    (interpreted) equals ``ragged_dot`` on the rows the groups hold."""
    rs = np.random.RandomState(5)
    L, E, n, m = 2, 4, 1024, 160
    item = 1 if quant == "int8" else 2
    assert tiling(m, k, n, item, E) == (32, k, n)
    assert k * n * item > 2 << 20                # two tiles before
    if quant == "int8":
        rhs = QuantizedArray(
            q=jnp.asarray(_exact(rs, (L, E, k, n), 3), jnp.int8),
            scale=jnp.asarray(2.0 ** -rs.randint(1, 4, (L, E, 1, n)),
                              jnp.float32))
    else:
        rhs = jnp.asarray(_exact(rs, (L, E, k, n), 1), jnp.bfloat16)
    x = jnp.asarray(_exact(rs, (m, k), 1, keep=1 / 16), jnp.bfloat16)
    gs = jnp.asarray([70, 0, 50, 20], jnp.int32)     # 20 rows in no group
    for layer in (0, 1):
        want = grouped_matmul(x, jax.tree.map(lambda a: a[layer], rhs), gs,
                              backend="xla")
        got = grouped_matmul(x, LayerOf(rhs, jnp.int32(layer)), gs,
                             backend="pallas", interpret=True)
        assert got.shape == want.shape == (m, n) and got.dtype == x.dtype
        assert float(jnp.abs(want[:140].astype(jnp.float32)).max()) > 8
        np.testing.assert_allclose(
            np.asarray(got[:140], np.float32),
            np.asarray(want[:140], np.float32), atol=1e-4)


def _table_tool():
    """``tools/gmm_table.py`` as a module: it reads the configurations
    with experts, and their two calls' rows, from the benchmark's files."""
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "gmm_table", Path(__file__).resolve().parent.parent / "tools"
        / "gmm_table.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def _expert_shapes():
    """``(m, k, n, itemsize, routed)``: gate / up and down of every
    benchmark configuration with experts, at its slab's and its decode
    step's rows."""
    for c in _table_tool().expert_configs([]):
        for call, tokens in c["tokens"].items():
            for proj, (k, n) in (("gate_up", (c["hidden"], c["inter"])),
                                 ("down", (c["inter"], c["hidden"]))):
                yield pytest.param(
                    tokens * c["top_k"], k, n, 1 if c["int8"] else 2,
                    c["routed"], id=f"{c['name']}-{call}-{proj}")


@pytest.mark.parametrize("m, k, n, item, routed", _expert_shapes())
def test_a_benchmark_configuration_s_matrix_is_read_once_a_group(
        m, k, n, item, routed):
    """Every expert shape the benchmark holds: the contraction is one
    tile, and the limit the call declares covers its tiles and stays
    under the budget, a quarter of the chip's VMEM."""
    from distributed_inference_demo_tpu.ops import grouped_matmul as gmm
    tm, tk, tn = tiles = tiling(m, k, n, item, routed)
    line = gmm.call_shape(m, k, n, item, routed)
    assert tk == k and line["tiles_k"] == 1 and n % tn == 0
    assert line["tiles"] == list(tiles)
    assert line["rhs_tile_bytes"] == k * tn * item
    held = (2 * k * tn * item + (k * tn * 2 if item == 1 else 0)
            + 2 * tm * k * 2 + 2 * tm * tn * 2 + tm * tn * 4)
    assert held < gmm.vmem_bytes(tiles, item, 2) \
        < line["vmem_limit_bytes"] <= gmm._VMEM_BUDGET < gmm._VMEM_BYTES
    # the row tile: two passes of the matrix unit where a group fills
    # one, what the call's rows gave before PR 63 elsewhere
    assert tm == (256 if m >= 128 * routed else 64 if m >= 2048 else 32)


@pytest.mark.parametrize("case", [
    "route-tpu", "route-cpu", "route-narrow", "olmoe-decode", "olmoe-down",
    "mixtral-down", "mixtral-up"])
def test_grouped_matmul_routing_and_tiles(case):
    from distributed_inference_demo_tpu.ops import grouped_matmul as gmm
    if case == "route-tpu":
        assert route_grouped_matmul("tpu", 2048, 1024) == "pallas_gmm"
    elif case == "route-cpu":
        assert route_grouped_matmul("cpu", 2048, 1024) == "ragged_dot"
    elif case == "route-narrow":
        assert route_grouped_matmul("tpu", 64, 32) == "ragged_dot"
    # the olmoe cell's two shapes: a whole int8 expert matrix is one tile
    elif case == "olmoe-decode":
        assert tiling(256, 2048, 1024, 1, 64) == (32, 2048, 1024)
    elif case == "olmoe-down":
        assert tiling(4096, 1024, 2048, 1, 64) == (64, 1024, 1024)
    elif case == "mixtral-down":
        # 28 MiB a [14336, 1024] tile: the contraction still splits, into
        # the largest tiles the budget holds
        tiles = tiling(8, 14336, 4096, 2, 8)
        assert tiles == (16, 3584, 1024)
        assert gmm.vmem_limit(tiles, 2, 2) <= gmm._VMEM_BUDGET \
            < gmm.vmem_limit((16, 7168, 1024), 2, 2)
    else:
        assert tiling(8, 4096, 14336, 2, 8) == (16, 4096, 1024)


# ----------------------------------------------------------- whole models

def _full_spec(cfg):
    return StageSpec(0, 1, 0, cfg.num_layers)


@pytest.mark.parametrize("name", ["olmoe-test", "mixtral-test"])
def test_routed_moe_under_manual_tp(name, devices):
    """tp=2: experts sharded over tp (a rank's groups are its local
    experts, the partial sums meet in the psum), and olmoe's q/k RMSNorm
    takes its mean square over the whole projection (psum of squares),
    with norm weights that are not the identity."""
    cfg = get_model_config(name)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    if cfg.qk_norm:
        layers = dict(params.layers)
        for i, key in enumerate(("q_norm_w", "k_norm_w")):
            layers[key] = 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(5 + i), layers[key].shape)
        params.layers = layers
    spec = _full_spec(cfg)
    ids = jnp.arange(10, dtype=jnp.int32).reshape(1, 10) % cfg.vocab_size
    pos = jnp.arange(10)[None, :]
    ref, _ = stage_forward(params, cfg, spec, ids,
                           KVCache.create(cfg, cfg.num_layers, 1, 32), pos)
    mesh = make_mesh(MeshConfig(tp=2), devices)
    with mesh:
        fn = make_tp_stage_fn(cfg, spec, mesh, params)
        out, _ = fn(params, ids, KVCache.create(cfg, cfg.num_layers, 1, 32),
                    pos)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("quant", ["float32", "int8"])
@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("cfg", [OLMOE, MIXTRAL], ids=["olmoe", "mixtral"])
def test_a_row_that_holds_no_token_enters_no_group(cfg, tp, quant, devices):
    """``_moe_routed(valid=...)``: on the rows that hold a token exactly
    what the unmasked layer returns for them, bit for bit; zeros on the
    others; ``rows`` counts the valid rows' ``k`` and nothing else (the
    group sizes the grouped matmul runs on).  Also with the experts
    sharded over ``tp`` (another rank's rows and the rows that hold no
    token sort behind the local groups together), and with no row valid
    at all (an idle engine's step: no group, all zeros)."""
    lp = _layer(jax.random.PRNGKey(2), cfg)
    if quant == "int8":
        lp = dict(lp, **{n: quantize_array(lp[n])
                         for n in ("w_gate", "w_up", "w_down")})
    k, E, H = cfg.experts_per_token, cfg.num_experts, cfg.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 8, H))
    valid = jnp.asarray(np.random.RandomState(4).rand(3, 8) < 0.4)
    valid = valid.at[1].set(jnp.arange(8) < 5)     # a short final's rows
    assert 0 < int(valid.sum()) < 24

    if tp == 1:
        def run(v):
            return _moe_routed(cfg, lp, x, None, v)
    else:
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh(MeshConfig(tp=2), devices)
        stack = {n: P("tp") for n in ("w_gate", "w_up", "w_down")}
        specs = jax.tree.map(lambda _: P(), lp)
        specs.update({n: jax.tree.map(lambda _: P("tp"), lp[n])
                      for n in stack})

        # (jitted, as the engine runs it: called bare a shard_map runs op
        # by op, seven seconds a call, and there are three)
        routed = jax.jit(jax.shard_map(
            lambda lp_, x_, v_: _moe_routed(cfg, lp_, x_, "tp", v_),
            mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), P()), check_vma=False))

        def run(v):
            return routed(lp, x, v)

    whole, whole_rows = run(None)
    got, rows = run(valid)
    keep = np.asarray(valid)
    assert (np.asarray(got)[keep] == np.asarray(whole)[keep]).all()
    assert (np.asarray(got)[~keep] == 0).all()
    assert np.abs(np.asarray(whole)[~keep]).min() > 0
    assert int(rows.sum()) == int(valid.sum()) * k
    assert int(whole_rows.sum()) == 24 * k
    # the counts are those of routing the valid rows alone
    _, alone = _moe_routed(cfg, lp, x[valid][None])
    assert (np.asarray(rows) == np.asarray(alone)).all()
    none, no_rows = run(jnp.zeros((3, 8), bool))
    assert (np.asarray(none) == 0).all() and int(no_rows.sum()) == 0


def test_ep_path_routes_like_the_routed_layer(devices):
    """``--ep`` with a ``norm_topk_prob: false`` model: the capacity-slot
    path calls the same ``_route``; with capacity to spare it equals the
    routed layer."""
    from jax.sharding import PartitionSpec as P
    cfg = OLMOE.replace(moe_capacity_factor=8.0)
    lp = _layer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, cfg.hidden_size))
    mesh = make_mesh(MeshConfig(ep=2), devices)
    specs = {"router": P(), "w_gate": P("ep", None, None),
             "w_up": P("ep", None, None), "w_down": P("ep", None, None)}
    with mesh:
        ep = jax.shard_map(
            lambda lp_, x_: _moe_mlp_ep(cfg, lp_, x_, "ep"), mesh=mesh,
            in_specs=(specs, P("ep")), out_specs=P("ep"),
            check_vma=False)(lp, x)
    np.testing.assert_allclose(np.asarray(ep), np.asarray(
        _moe_mlp(cfg, lp, x)), rtol=2e-4, atol=2e-4)


def _engine(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params, max_seq=128, max_batch=4,
        sampling=SamplingParams(temperature=0.0), prefill_chunk=16,
        decode_block=4, mixed_token_budget=48, kv_cache_blocks=64,
        kv_block_tokens=8, **kw)


@pytest.fixture(scope="module")
def olmoe_run():
    """olmoe-test (int8 experts) through the mixed path: three prompts
    (one longer than two chunks), ten tokens each, with logprobs."""
    cfg = get_model_config("olmoe-test-int8")
    params = init_full_params(jax.random.PRNGKey(0), cfg, quantize=True)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (37, 9, 20)]
    with _engine(cfg, params) as eng:
        reqs = [eng.submit(p, 10) for p in prompts]
        outs = [np.asarray(r.wait(timeout=300)) for r in reqs]
        lps = [list(r.lps) for r in reqs]
        # the last dispatch delivers its tokens before its record is
        # committed: read /stats once the two counts agree
        for _ in range(200):
            stats = eng.stats()
            if stats["dispatch_trace"]["seq"] == stats["moe"]["dispatches"]:
                break
            time.sleep(0.02)
    return cfg, params, prompts, outs, lps, stats


def test_olmoe_mixed_path_equals_stage_forward(olmoe_run):
    """Prefill in chunks, then decode through pages: the tokens and their
    log-probabilities equal ``stage_forward`` over the whole sequence."""
    cfg, params, prompts, outs, lps, _ = olmoe_run
    for p, o, lp in zip(prompts, outs, lps):
        ids = np.concatenate([p, o])[None]
        logits, _ = stage_forward(
            params, cfg, _full_spec(cfg), jnp.asarray(ids),
            KVCache.create(cfg, cfg.num_layers, 1, 128),
            jnp.arange(ids.shape[1])[None])
        ref = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
        rows = ref[len(p) - 1: ids.shape[1] - 1]
        assert [int(t) for t in o] == [int(t) for t in rows.argmax(-1)]
        np.testing.assert_allclose(
            lp, [float(rows[i, t]) for i, t in enumerate(o)], atol=1e-4)


def test_moe_counters_sum_to_tokens_times_k_times_layers(olmoe_run):
    """A pass routes the rows that hold a token and no other: on every
    record the device's ``moe_rows`` equals the host's
    ``moe_valid_rows`` = (prompt tokens packed + decoding slots x steps)
    x k x layers, whatever the slab's padding (a short final, the rows
    past it) and however many slots idle; over the run the experts' rows
    sum to it, and the dispatch records carry the same numbers."""
    cfg, _, prompts, outs, _, st = olmoe_run
    k, L, E = cfg.experts_per_token, cfg.num_layers, cfg.num_experts
    moe, dt = st["moe"], st["dispatch_trace"]
    assert moe["gmm"] == []         # 64 x 32: no shape the kernel covers
    assert dt["fields"] == list(DISPATCH_FIELDS + MOE_DISPATCH_FIELDS
                                + DISPATCH_LAST_FIELDS)
    recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
    assert len(recs) == moe["dispatches"] == dt["seq"]
    chunk, slots = 16, 4
    for r in recs:
        # the slab runs where a segment was packed, and only there; the
        # step of the decoding rows that rode it is no pass of its own, and
        # a final it installs joins the loop behind that step (PR 61)
        rode = r["slab_carried_step"] > 0
        passes = (r["segments"] > 0) + r["steps"] - rode
        assert r["moe_rows"] == r["moe_valid_rows"] == (
            r["prefill_tokens"] + r["active_rows"] * r["steps"]
            + r["finals"] * (r["steps"] - rode)) * k * L
        # ... of the rows its program computed
        assert r["moe_rows"] <= (chunk * r["segments"]
                                 + slots * r["steps"]) * k * L
        assert 0 < r["moe_touched"] <= passes * L * E
        assert r["moe_load_max"] <= max(r["prefill_tokens"] + slots * rode,
                                        slots)
    # the run did pad: finals of 5 (37 = 2 x 16 + 5), 9 and 4 tokens
    assert any(r["prefill_tokens"] % chunk for r in recs)
    assert moe["rows"] == moe["valid_rows"] \
        == sum(r["moe_rows"] for r in recs) == sum(moe["expert_rows"])
    assert any(r["slab_carried_step"] for r in recs)
    assert moe["layer_calls"] == sum(
        ((r["segments"] > 0) + r["steps"] - (r["slab_carried_step"] > 0)) * L
        for r in recs)
    assert moe["touched"] == sum(r["moe_touched"] for r in recs)
    assert moe["load_max"] == max(r["moe_load_max"] for r in recs)
    assert len(moe["expert_rows"]) == moe["experts"] == E
    # prompt tokens are routed once each; a token decoded is routed in
    # the step that reads it (the last of a request never is) and rows
    # that finish inside a block still step to its end
    prompt_rows = sum(len(p) for p in prompts) * k * L
    assert prompt_rows == sum(r["prefill_tokens"] for r in recs) * k * L
    assert moe["valid_rows"] >= prompt_rows + sum(
        len(o) - 1 for o in outs) * k * L


def test_a_decode_only_record_counts_the_decode_steps_alone():
    """PR 33: one request, so the dispatches are scripted: the first
    packs the prompt's final (slab + 4 steps), the rest pack nothing and
    run no slab.  Their counters hold the decode loop's layer calls and
    rows and nothing of a slab's: ``steps x layers`` calls of the ONE
    decoding slot's ``k`` rows (the three idle slots enter no expert's
    group), no expert with more than that one row."""
    cfg = get_model_config("olmoe-test-int8")
    params = init_full_params(jax.random.PRNGKey(0), cfg, quantize=True)
    k, L = cfg.experts_per_token, cfg.num_layers
    with _engine(cfg, params) as eng:
        before = eng.stats()["moe"]["layer_calls"]
        eng.submit(np.arange(1, 10, dtype=np.int32), 10).wait(timeout=300)
        for _ in range(200):
            st = eng.stats()
            if st["dispatch_trace"]["seq"] == st["moe"]["dispatches"] == 3:
                break
            time.sleep(0.02)
    dt = st["dispatch_trace"]
    first, *alone = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
    assert (first["segments"], first["steps"]) == (1, 4)
    # the nine prompt tokens of a sixteen-wide segment, then the
    # installed row's four steps
    assert first["moe_rows"] == first["moe_valid_rows"] == (9 + 4) * k * L
    assert [(r["segments"], r["steps"]) for r in alone] == [(0, 4), (0, 1)]
    for r in alone:
        assert r["moe_rows"] == r["moe_valid_rows"] == r["steps"] * k * L
        # the parent's four routed slots touched up to ``slots x k`` a call
        assert 0 < r["moe_touched"] <= r["steps"] * L * k
        assert r["moe_load_max"] == 1
    assert before == 0 and st["moe"]["layer_calls"] == (1 + 4 + 4 + 1) * L
    assert dt["decode_only"] == 2 and dt["prefill"] == 1


def test_dense_engine_has_no_moe_section_and_the_record_is_unchanged():
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    with _engine(cfg, params) as eng:
        eng.submit(np.arange(1, 20, dtype=np.int32), 4).wait(timeout=300)
        st = eng.stats()
    assert "moe" not in st
    assert st["dispatch_trace"]["fields"] == list(DISPATCH_FIELDS
                                                  + DISPATCH_LAST_FIELDS)


def test_dispatch_trace_extra_fields_and_counters():
    tr = DispatchTrace(MOE_DISPATCH_FIELDS)
    c = MoeCounters(4)
    tr.enter("pack")
    extra = c.add([3, 0, 5, 0], touched=2, load_max=5, layer_calls=1,
                  valid_rows=6)
    assert extra == dict(moe_rows=8, moe_valid_rows=6, moe_touched=2,
                         moe_load_max=5)
    tr.commit(t_launch=1.0, t_done=2.0, with_finals=False, segments=0,
              finals=0, prefill_tokens=0, active_rows=1, steps=1,
              kv_tokens=3, **extra)
    snap = tr.snapshot()
    # a model's own columns, then the one that ends every record
    assert snap["fields"][-5:] == list(MOE_DISPATCH_FIELDS
                                       + DISPATCH_LAST_FIELDS)
    assert snap["recent"][0][-5:] == [8, 6, 2, 5, 0]
    c.add([1, 1, 0, 0], touched=2, load_max=1, layer_calls=1, valid_rows=2)
    assert c.snapshot() == {
        "experts": 4, "dispatches": 2, "rows": 10, "valid_rows": 8,
        "touched": 4, "load_max": 5, "layer_calls": 2,
        "expert_rows": [4, 1, 5, 0]}
    c.reset()
    assert c.snapshot()["rows"] == 0


def test_stats_list_the_tiles_of_every_grouped_matmul_traced():
    """``/stats.moe.gmm``: one line a distinct call shape the engine's
    programs were traced with (a slab of one to budget // chunk segments
    with the rows' first step, and a decode step; gate / up and down), each the
    rule's output for it, whatever path ran (here ``ragged_dot``); the
    olmoe-test engines above, whose widths fill no lane, list none."""
    from distributed_inference_demo_tpu.ops.grouped_matmul import call_shape
    cfg = OLMOE.replace(hidden_size=128, intermediate_size=256, num_layers=1)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    with _engine(cfg, params) as eng:
        eng.submit(np.arange(1, 38, dtype=np.int32), 6).wait(timeout=300)
        lines = eng.stats()["moe"]["gmm"]
    k, E, chunk, slots = cfg.experts_per_token, cfg.num_experts, 16, 4
    want = [call_shape(tokens * k, a, b, 4, E, 4)
            for tokens in (*(r * chunk + slots for r in (1, 2, 3)), slots)
            for a, b in ((128, 256), (256, 128))]
    assert sorted(lines, key=str) == sorted(want, key=str)
    assert all(line["tiles_k"] == 1 and set(line) == {
        "m", "k", "n", "tiles", "tiles_k", "rhs_tile_bytes",
        "vmem_limit_bytes"} for line in lines)


@pytest.mark.parametrize("table", ["gmm", "combine"])
def test_the_table_tool_reads_six_configurations_and_the_rule_before(table):
    """``tools/gmm_table.py``: the configurations with experts and their
    two calls' rows come from the benchmark's files, and its ``before``
    column is the rule as it stood before PR 63 (a 2 MiB right-hand
    tile): one contraction tile at olmoe's int8 widths, 2 to 4 at the
    five bf16 configurations', two for solar's down projection.
    ``--combine`` (PR 65): the bytes a form moves at granite's slab, and at
    toy rows the new form against the one before on the same rows."""
    tool = _table_tool()
    if table == "combine":
        import argparse
        nbytes = tool.combine_bytes(1312, 10, 4096)
        rows = 13120 * 4096
        assert nbytes["floor"] == 2 * rows + 4 * 1312 * 4096
        assert nbytes["bf16"] == nbytes["floor"] + 4 * rows
        assert 1.6e9 < nbytes["before"] < 1.8e9 and nbytes["f32"] < 1.0e9
        # k = 8 fills a float32 tile's sublanes: nothing was padded
        assert tool.combine_bytes(64, 8, 128)["before"] == (
            6 + 8 + 8 + 4) * 64 * 8 * 128 + 4 * 64 * 128
        granite = next(tool.expert_configs(["granite-4.0-h-small-bf16-ep2"]))
        granite["tokens"] = {"slab": 8}
        row, = tool.combine_rows_of(granite,
                                    argparse.Namespace(seed=0, reps=1))
        assert (row["call"], row["tokens"], row["top_k"]) == ("slab", 8, 10)
        assert 0 < row["rows_written"] < 80          # half the experts held
        assert all(row[f"{f}_us"] > 0 and row[f"{f}_mb"] > 0
                   for f in tool.COMBINE_FORMS)
        assert list(tool.COMBINE_FORMS) == ["before", "bf16", "f32"]
        assert row["err"] < 1e-6
        return
    before = {}
    for c in tool.expert_configs([]):
        m, item = c["tokens"]["slab"] * c["top_k"], 1 if c["int8"] else 2
        before[c["name"]] = (
            tool.tiles_before(m, c["hidden"], c["inter"], item),
            c["inter"] // tool.tiles_before(m, c["inter"], c["hidden"],
                                            item)[1])
    assert before == {
        "olmoe-1b-7b-int8": ((64, 2048, 1024), 1),
        "kanana-2-30b-a3b-bf16": ((64, 1024, 768), 1),
        "laguna-s-2.1-bf16-ep4": ((64, 1024, 1024), 1),
        "xing4.0-29b-a4b-bf16": ((64, 896, 1024), 1),
        "granite-4.0-h-small-bf16-ep2": ((64, 1024, 768), 1),
        "solar-open2-250b-bf16-ep8": ((64, 1024, 640), 2)}
    granite = next(tool.expert_configs(["granite-4.0-h-small-bf16-ep2"]))
    assert granite["tokens"] == {"slab": 1312, "decode": 32}
    assert (granite["held"], granite["routed"]) == (36, 72)
    sizes = tool.group_sizes(granite, 1312, 0, even=False)
    assert sizes.shape == (36,) and 6000 < sizes.sum() < 7100
    assert (tool.group_sizes(granite, 1312, 0, even=True) == 182).all()


def test_model_parity_tool_at_toy_size():
    """``tools/model_parity.py`` walks its whole path on the CPU (paged
    prefill in chunks, decode through pages, the benchmark's reference,
    router margins): float32 toy weights pass, int8 KV pages are refused."""
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "model_parity", Path(__file__).resolve().parent.parent / "tools"
        / "model_parity.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    toy = ["--model", "olmoe-test-int8", "--prompt", "48", "--chunk", "16",
           "--steps", "8", "--page", "8", "--batch", "2"]
    assert tool.main(toy) == 0
    assert tool.main(toy + ["--kv-dtype", "int8"]) == 1
