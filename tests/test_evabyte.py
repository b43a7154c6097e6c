"""EVA attention on the serving path (family ``evabyte``, PR 53): a window
of exact keys and values that closes into a page of learned summaries, two
roles of row in one page pool, RMSNorm with gain 1 + w, a float32 stream
and float32 logits, a head of several prediction heads.  CPU, toy widths
(``evabyte-test``: window 16, chunk 2, pages of 8)."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import (KVCache, ModelConfig,
                                                        StageSpec, eva_rows,
                                                        slice_stage,
                                                        split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (init_full_params,
                                                           stage_forward)
from distributed_inference_demo_tpu.models.registry import (MODEL_REGISTRY,
                                                            get_model_config)
from distributed_inference_demo_tpu.ops import eva_attention as eva
from distributed_inference_demo_tpu.ops.paged_attention import (
    AttnPathRecord)
from distributed_inference_demo_tpu.ops.quant import alloc_kv_pool
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.parallel.tensor import (
    make_paged_forward_seam)
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import reference  # noqa: E402

CFG = get_model_config("evabyte-test")
MC = dataclasses.asdict(CFG)
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
W, C, BT = CFG.eva_window, CFG.eva_chunk, 8
GREEDY = SamplingParams(temperature=0.0)
MIXED = dict(prefill_chunk=8, decode_block=4, mixed_token_budget=24)


def _params(cfg=CFG, seed=0):
    return init_full_params(jax.random.PRNGKey(seed), cfg)


@pytest.fixture(scope="module")
def params():
    return _params()


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


def _reference_logprobs(params, ids, n_prompt, mc=MC):
    """The family's float32 account of rows ``n_prompt - 1 ..``."""
    rows, _ = reference.halves(params, mc)
    x = rows(ids)[n_prompt - 1:]
    _, _, final_norm = families.load("evabyte").equations(mc)
    with jax.default_matmul_precision("highest"):
        x = final_norm(params, x)
        head = reference._f32(params.lm_head["w"])[:, :mc["vocab_size"]]
        return np.asarray(jax.nn.log_softmax(x @ head, -1))


# ------------------------------------------------------------ configuration

def test_the_configuration_file_builds_the_model_and_its_toy():
    conf = json.loads((ROOT / "benchmark" / "configs"
                       / "evabyte-6.5b-bf16.json").read_text())
    a = ModelConfig(**conf["model_config"])
    assert (a.eva_window, a.eva_chunk, a.num_pred_heads) == (2048, 16, 8)
    assert a.summary_kv and a.head_dim == 128 and a.kv_planes == 16
    assert a.norm_unit_offset and a.fp32_residual and a.fp32_logits
    assert a == dataclasses.replace(get_model_config("evabyte-6.5b"),
                                    num_layers=16)
    assert conf["window_size"] // conf["chunk_size"] == int(
        conf["serve_flags"][conf["serve_flags"].index("--kv-block-tokens")
                            + 1])
    toy = ModelConfig(**conf["rehearsal"]["model_config"])
    assert toy == CFG


def test_every_older_model_keeps_a_row_a_token():
    for name, cfg in MODEL_REGISTRY.items():
        if not name.startswith("evabyte"):
            assert not cfg.summary_kv and cfg.num_pred_heads == 1, name
            assert not (cfg.norm_unit_offset or cfg.fp32_residual
                        or cfg.fp32_logits), name


def test_the_parameters_hold_the_pooling_vectors_and_every_head(params):
    L, nkv, hd = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
    for leaf in ("adaptive_mu_k", "adaptive_phi"):
        v = np.asarray(params.layers[leaf], np.float32)
        assert v.shape == (L, nkv, hd)
        assert np.abs(v).max() <= hd ** -0.5 + 1e-6 and v.std() > 0.05
    assert params.lm_head["w"].shape == (
        CFG.hidden_size, CFG.num_pred_heads * CFG.vocab_size)
    # the norms' stored weight is the gain's offset, not the gain
    assert abs(float(jnp.mean(params.layers["attn_norm_w"]))) < 0.1


# ---------------------------------------------- t -> rows, table and length

@pytest.mark.parametrize("n, want", [
    (0, (0, 0)), (1, (0, 1)), (15, (0, 15)), (16, (0, 16)), (17, (8, 1)),
    (32, (8, 16)), (33, (16, 1)), (48, (16, 16)), (49, (24, 1))])
def test_rows_that_hold_n_tokens(n, want):
    assert eva_rows(W, C, n) == want


@pytest.mark.parametrize("t", [0, 1, 14, 15, 16, 17, 31, 32, 33, 47, 48])
def test_the_attended_table_and_the_row_of_a_token_at_every_edge(t):
    """``[S_0 .. S_{w-1}, P_0, P_1, sentinel ..]`` at length ``8 w + t %
    16 + 1``; the pending summary page is in no table."""
    n_sum, n_win, sentinel = 4, W // BT, 99
    raw = jnp.asarray([[10, 11, 12, 13, 20, 21]], jnp.int32)
    w = t // W
    table = np.asarray(eva.eva_tables(raw, jnp.asarray([w]), n_win,
                                      sentinel))[0]
    want = [10 + j for j in range(w)] + [20, 21]
    assert table.tolist() == want + [sentinel] * (n_sum + n_win - len(want))
    row = int(eva.eva_positions(jnp.asarray(t), W, BT))
    assert row == BT * w + t % W
    assert row + 1 == sum(eva_rows(W, C, t + 1))
    assert 10 + w not in table.tolist()[:w + n_win]      # S_w is pending


def test_a_row_past_its_last_summary_page_shows_no_window_page_as_one():
    raw = jnp.asarray([[10, 11, 20, 21]], jnp.int32)
    table = np.asarray(eva.eva_tables(raw, jnp.asarray([3]), 2, 99))[0]
    assert table.tolist() == [10, 11, 99, 20]


# ---------------------------------------------------- the mask, by itself

def _qkv(t, seed=0, nh=2, hd=8):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    return f(1, t, nh, hd), f(1, nh, t, hd), f(1, nh, t, hd), f(nh, hd), \
        f(nh, hd)


def test_a_summary_is_invisible_in_its_own_window_and_seen_from_the_next():
    t = 40
    q, k, v, mu, phi = _qkv(t)
    pos = jnp.arange(t)[None]
    a = np.asarray(eva.eva_attention(q, k, v, pos, W, C, mu, phi))
    b = np.asarray(eva.eva_attention(q, k, v, pos, W, C, mu + 1.0, phi - 1.0))
    moved = np.abs(a - b).max(axis=(0, 2, 3))
    assert np.all(moved[:W] == 0.0)          # the first window: no summary
    assert np.all(moved[W:] > 1e-4)          # from token W on: every query


def test_a_query_sees_its_window_s_keys_and_no_earlier_exact_one():
    t = 40
    q, k, v, mu, phi = _qkv(t, seed=1)
    pos = jnp.arange(t)[None]
    a = np.asarray(eva.eva_attention(q, k, v, pos, W, C, mu, phi))
    # token 3's VALUE moves its own window's later queries directly and
    # later windows only through chunk 1's summary; the key at 3 too
    v2 = v.at[:, :, 3].add(5.0)
    b = np.asarray(eva.eva_attention(q, k, v2, pos, W, C, mu, phi))
    moved = np.abs(a - b).max(axis=(0, 2, 3))
    assert np.all(moved[:3] == 0.0) and np.all(moved[3:W] > 0)
    assert np.all(moved[W:] > 0)
    # with the pooling weight of token 3 driven to zero, later windows
    # do not see it at all: no exact key crosses a window's edge
    k3 = k.at[:, :, 3].set(-50.0 * jnp.sign(phi)[None])
    c = np.asarray(eva.eva_attention(q, k3, v, pos, W, C, mu, phi))
    d = np.asarray(eva.eva_attention(q, k3, v2, pos, W, C, mu, phi))
    assert np.abs(c - d).max(axis=(0, 2, 3))[W:].max() < 1e-6


# ------------------------------------------- the model against the family

def _leaves(params, dtype):
    cast = lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a
    return jax.tree.map(cast, params)


@pytest.mark.parametrize("leaves, tol", [("float32", 2e-4),
                                         ("bfloat16", 0.06)])
def test_the_dense_forward_agrees_with_the_family(params, leaves, tol):
    """70 positions: four windows close (at 16, 32, 48, 64)."""
    cfg = CFG.replace(dtype_name=leaves)
    p = _leaves(params, jnp.dtype(leaves))
    ids = _ids(70)
    cache = KVCache.create(cfg, cfg.num_layers, 1, 72)
    logits, _ = stage_forward(p, cfg, SPEC, jnp.asarray([ids], jnp.int32),
                              cache, jnp.arange(70, dtype=jnp.int32)[None])
    got = np.asarray(jax.nn.log_softmax(logits[0].astype(jnp.float32), -1))
    want = _reference_logprobs(p, ids, 1)
    assert got.shape == (70, CFG.vocab_size)
    assert np.abs(got - want).max() <= tol


def _paged_logprobs(cfg, p, ids, n_prompt, steps, chunk, backend="auto",
                    interpret=False, bt=BT):
    """Prefill in ``chunk``-token chunks straight into a page pool, then
    ``steps`` teacher-forced decode steps through the pages: the
    log-softmax at the prompt's last position and after each step."""
    window = cfg.eva_window
    total = n_prompt + steps
    Wt = -(-total // window) + window // bt
    record = AttnPathRecord()
    fwd, bind, _ = make_paged_forward_seam(
        cfg, StageSpec(0, 1, 0, cfg.num_layers), None, p, bt,
        backend=backend, interpret=interpret, record=record)
    pk, pv = alloc_kv_pool((cfg.kv_planes, Wt + 2, cfg.num_kv_heads, bt,
                            cfg.head_dim), "bf16", cfg.dtype)
    tables = jnp.arange(Wt, dtype=jnp.int32)[None]

    @jax.jit
    def run(pk, pv, tok, start, last):
        bind(tables, "t")
        pos = start + jnp.arange(tok.shape[1])[None]
        logits, cache = fwd(p, tok, KVCache(pk, pv, jnp.int32(0)), pos, last)
        return (jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), -1),
                cache.keys, cache.values)

    out = []
    for s in range(0, n_prompt, chunk):
        part = ids[s:min(s + chunk, n_prompt)]
        last = len(part) - 1
        part = part + [0] * (chunk - len(part))         # the slab's padding
        lp, pk, pv = run(pk, pv, jnp.asarray([part], jnp.int32),
                         jnp.int32(s), jnp.int32(last))
    out.append(np.asarray(lp[0]))
    for t in range(n_prompt, total):
        lp, pk, pv = run(pk, pv, jnp.asarray([[ids[t]]], jnp.int32),
                         jnp.int32(t), jnp.int32(0))
        out.append(np.asarray(lp[0]))
    return np.stack(out), record


@pytest.mark.parametrize("leaves, tol", [("float32", 2e-4),
                                         ("bfloat16", 0.08)])
@pytest.mark.parametrize("n_prompt, chunk", [(37, 8), (44, 16)])
def test_paged_prefill_then_decode_agrees_with_the_family(params, leaves,
                                                          tol, n_prompt,
                                                          chunk):
    """Two windows close in prefill and two more while decoding; the
    prompt ends inside a chunk, so the slab's padding writes a summary
    that the decode steps write again before any query sees it."""
    cfg = CFG.replace(dtype_name=leaves)
    p = _leaves(params, jnp.dtype(leaves))
    steps = 30
    ids = _ids(n_prompt + steps)
    got, _ = _paged_logprobs(cfg, p, ids, n_prompt, steps, chunk)
    want = _reference_logprobs(p, ids + [0], n_prompt)[:steps + 1]
    assert np.abs(got - want).max() <= tol


LANES = ModelConfig(
    family="evabyte", vocab_size=64, hidden_size=256, num_layers=2,
    num_heads=2, num_kv_heads=2, intermediate_size=128, max_seq_len=512,
    rope_theta=100000.0, norm_unit_offset=True, fp32_residual=True,
    fp32_logits=True, eva_window=128, eva_chunk=8, num_pred_heads=2,
    dtype_name="float32")


def test_the_kernels_interpreted_agree_with_the_gather():
    """Heads of 128 lanes, pages of 16, float32 pages (tile groups of 8 =
    the chunk): the paged decode and prefill kernels over the attended
    table, the Pallas page write and the Pallas pooling call, interpreted,
    against the XLA gather path; one window closes in prefill, one while
    decoding."""
    p = _params(LANES, seed=2)
    n_prompt, steps = 250, 12
    ids = np.random.default_rng(5).integers(1, 64, n_prompt + steps).tolist()
    a, _ = _paged_logprobs(LANES, p, ids, n_prompt, steps, 32, bt=16)
    b, record = _paged_logprobs(LANES, p, ids, n_prompt, steps, 32,
                                backend="pallas", interpret=True, bt=16)
    assert record.snapshot() == {"t": {"chunk=32": "pallas_prefill",
                                       "chunk=1": "pallas_decode"}}
    assert set(record.addressing()["t"].values()) == {"kernel write"}
    assert np.abs(a - b).max() <= 2e-4
    want = _reference_logprobs(p, ids + [0], n_prompt,
                               dataclasses.asdict(LANES))[:steps + 1]
    assert np.abs(b - want).max() <= 5e-4


def test_the_pooling_call_compiles_for_a_v5e_at_the_published_shape():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                   # no libtpu, or no such target
        pytest.skip(f"no ahead-of-time TPU compiler here: {e}")
    on = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=on)
    i32, b = jnp.int32, 16
    pool = shape((2, 24, 32, 128, 128), jnp.bfloat16)
    vec = shape((32, 1, 128), jnp.float32)
    jax.jit(lambda *a: eva._eva_summarise(*a, chunk=16, interpret=False)
            ).lower(shape((b,), i32), shape((b,), i32), shape((b,), i32),
                    shape((1,), i32), vec, vec, pool, pool).compile()


# --------------------------------------------- what refuses, in a sentence

def _draft(params):
    llama = get_model_config("llama-test")
    ContinuousBatchingEngine(
        llama, init_full_params(jax.random.PRNGKey(0), llama), max_seq=64,
        max_batch=2, draft_cfg=CFG, draft_params=params, num_draft=2)


def _tp(params):
    from distributed_inference_demo_tpu.parallel.mesh import (MeshConfig,
                                                              make_mesh)
    from distributed_inference_demo_tpu.parallel.tensor import validate_tp
    validate_tp(CFG, make_mesh(MeshConfig(tp=2)))


def _engine(params, **kw):
    kw.setdefault("max_seq", 128)
    kw.setdefault("max_batch", 2)
    kw.setdefault("kv_block_tokens", BT)
    return ContinuousBatchingEngine(CFG, params, sampling=GREEDY, **kw)


def _export(params):
    with _engine(params, **MIXED) as eng:
        eng.export_request("nobody")


def _import(params):
    with _engine(params, **MIXED) as eng:
        eng.import_request({})


def _premigrated(params):
    with _engine(params, **MIXED) as eng:
        eng.submit_premigrated([1, 2, 3], 2, np.zeros((1,)), np.zeros((1,)))


def _flash_hook(params):
    from distributed_inference_demo_tpu.ops.flash_attention import (
        make_flash_attn_impl)
    cache = KVCache.create(CFG, CFG.num_layers, 1, 16)
    stage_forward(params, CFG, SPEC, jnp.asarray([[1, 2]], jnp.int32), cache,
                  jnp.arange(2, dtype=jnp.int32)[None],
                  attn_impl=make_flash_attn_impl())


def _loader(params):
    from distributed_inference_demo_tpu.models.loader import (
        params_from_state_dict)
    params_from_state_dict({}, CFG)


REFUSALS = {
    "the serialized interleave": (
        lambda p: _engine(p, prefill_chunk=8), "serialized interleave"),
    "prompt lookup": (
        lambda p: _engine(p, prompt_lookup=True, num_draft=2, **MIXED),
        "speculation"),
    "a draft model beside it": (
        lambda p: _engine(p, draft_cfg=CFG, draft_params=p, num_draft=2,
                          **MIXED), "speculation"),
    "the draft side": (_draft, "the draft side of speculation"),
    "int8 pages": (lambda p: _engine(p, kv_dtype="int8", **MIXED),
                   "a page pool of int8 pages"),
    "int4 pages": (lambda p: _engine(p, kv_dtype="int4", **MIXED),
                   "a page pool of int4 pages"),
    "the host tier": (
        lambda p: _engine(p, kv_host_tier_bytes=1 << 20, **MIXED),
        "the host tier"),
    "export": (_export, "export_request"),
    "import": (_import, "import_request"),
    "a premigrated prefill": (_premigrated, "premigrated prefill"),
    "tensor parallelism": (_tp, "tensor parallelism"),
    "a pipeline of stages": (
        lambda p: slice_stage(p, CFG, split_layer_ranges(3, 3)[0]),
        "a pipeline of stages"),
    "a hook for a row a token": (_flash_hook, "two roles of row"),
    "the loader": (_loader, "no state-dict mapper for family 'evabyte'"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_built_for_a_row_a_token_refuses_in_a_sentence(params, what):
    build, sentence = REFUSALS[what]
    with pytest.raises((ValueError, NotImplementedError)) as e:
        build(params)
    assert sentence in str(e.value)
    if "does not support a model with a summarised cache" in str(e.value):
        assert "serve --batch-slots" in str(e.value)


@pytest.mark.parametrize("kw, sentence", [
    (dict(prefill_chunk=12, decode_block=4, mixed_token_budget=24),
     "must divide the window"),
    (dict(kv_block_tokens=4, **MIXED), "--kv-block-tokens must be"),
])
def test_a_shape_the_layout_cannot_hold_is_refused(params, kw, sentence):
    with pytest.raises(ValueError, match=sentence):
        _engine(params, **kw)
