"""The deepseek_v3 family (kanana-2-30b-a3b: latent attention read in
absorbed form, a leading dense block before the expert blocks, a sigmoid
router with a selection bias and a scale, shared experts) at toy size on
the CPU.

``kanana-test`` has 1 leading dense block and 3 expert blocks (16 experts,
3 a token, 1 shared, latent rank 32).  The oracle is the benchmark's plain
float32 reference (``benchmark/families/deepseek_v3.py`` through
``benchmark/reference.py``): the DECOMPRESSED form, no cache, every expert
computed for every row, no line of the program.  (That every other model
is what it was is ``tests/test_program_pins.py``'s to hold.)
"""

import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

from distributed_inference_demo_tpu.models import (        # noqa: E402
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models import decoder  # noqa: E402
from distributed_inference_demo_tpu.models.base import (   # noqa: E402
    slice_stage, split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (  # noqa: E402
    init_full_params, stage_forward)
from distributed_inference_demo_tpu.ops import (           # noqa: E402
    latent_attention as la)
from distributed_inference_demo_tpu.ops.quant import (     # noqa: E402
    alloc_kv_pool)
from distributed_inference_demo_tpu.ops.rope import (      # noqa: E402
    apply_rope, apply_rope_interleaved)
from distributed_inference_demo_tpu.ops.sampling import (  # noqa: E402
    SamplingParams)
from distributed_inference_demo_tpu.ops.stacked import LayerOf  # noqa: E402
from distributed_inference_demo_tpu.parallel.tensor import (  # noqa: E402
    make_paged_forward_seam)
from distributed_inference_demo_tpu.runtime.batching import (  # noqa: E402
    ContinuousBatchingEngine)

CFG = get_model_config("kanana-test")
L, LEAD = CFG.num_layers, CFG.lead_dense_layers
WIDTH = CFG.kv_page_shape[1]
GREEDY = SamplingParams(temperature=0.0)
FIELDS = dataclasses.asdict(CFG)        # what the reference is given
SPEC = StageSpec(0, 1, 0, L)


def _seeded(cfg=CFG):
    """Seeded weights with the norm weights moved off one, so that a norm
    left out changes the logits."""
    p = init_full_params(jax.random.PRNGKey(0), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for tree in (p.layers, p.lead):
        for name in ("attn_norm_w", "mlp_norm_w", "kv_norm_w"):
            tree[name] = (1.0 + 0.3 * jax.random.normal(
                next(keys), tree[name].shape)).astype(tree[name].dtype)
    return p


@pytest.fixture(scope="module")
def params():
    return _seeded()


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_seq", 96)
    kw.setdefault("max_batch", 4)
    kw.setdefault("kv_block_tokens", 8)
    kw.setdefault("kv_cache_blocks", 40)
    return ContinuousBatchingEngine(cfg, params, sampling=GREEDY, **kw)


MIXED = dict(prefill_chunk=8, decode_block=4, mixed_token_budget=24)


def _reference(params, prompt, tokens, fields=FIELDS):
    import reference
    ids = [int(t) for t in prompt] + [int(t) for t in tokens]
    return reference.emitted_logprobs(params, fields, ids, len(prompt))


def _settled(eng):
    """``/stats`` once the last dispatch's record is committed."""
    for _ in range(200):
        st = eng.stats()
        if st["dispatch_trace"]["seq"] == st["mixed"]["dispatches"]:
            return st
        time.sleep(0.02)
    raise AssertionError("the last dispatch never committed")


# ----------------------------------------------- the config and its sizes

def test_registry_entry_is_the_published_config():
    cfg = get_model_config("kanana-2-30b-a3b")
    assert (cfg.family, cfg.total_layers, cfg.lead_dense_layers,
            cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (
        "deepseek_v3", 48, 1, 2048, 32, 128256)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.num_shared_experts,
            cfg.intermediate_size, cfg.lead_intermediate_size) == (
        128, 6, 2, 768, 6144)
    assert (cfg.router_scoring, cfg.router_bias, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.rope_theta, cfg.norm_eps) == (
        "sigmoid", True, True, 2.448, 1e6, 1e-6)
    # one latent row a token a block: 576 values in 640 lanes, bf16
    assert cfg.kv_planes == 48 and cfg.kv_streams == 1
    assert cfg.kv_page_shape == (1, 640)
    assert get_model_config("llama-test").kv_streams == 2
    assert get_model_config("llama-test").kv_page_shape == (2, 16)


def test_one_tensor_a_token_sizes_every_kv_structure(params):
    """Dense cache, page pool, the manager's block bytes and ``/stats``:
    ``lead + layers`` planes of ONE row, and a second array of no
    element."""
    from distributed_inference_demo_tpu.runtime.kvcache import (
        PagedKVCacheManager)
    assert (CFG.kv_planes, WIDTH) == (LEAD + L, 128)
    cache = KVCache.create(CFG, L, 2, 32)
    assert cache.keys.shape == (4, 2, 1, 32, WIDTH)
    assert cache.values.size == 0
    block = 4 * 8 * WIDTH * 4                   # planes x bt x width x f32
    assert PagedKVCacheManager.for_model(CFG, 4, 8).block_bytes == block
    with _engine(params, **MIXED) as eng:
        assert eng._pk.shape == (4, 40, 1, 8, WIDTH)
        assert eng._pv.size == 0 and eng._pv.nbytes == 0
        assert eng.stats()["kvcache"]["bytes_per_token"] == WIDTH * 4 * 4
    # the published sizes in bf16: 8 blocks x 640 lanes x 2 B
    cut = get_model_config("kanana-2-30b-a3b").replace(num_layers=7)
    assert PagedKVCacheManager.for_model(cut, 4, 128).block_bytes \
        == 128 * 10240


# ------------------------------------- logits against the plain reference

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.08)])
def test_stage_forward_equals_the_reference(dtype, tol):
    """The whole sequence at once through the dense latent cache: the
    log-probability of every next token against the family's reference
    on the same leaves (bf16 leaves: the reference reads them as float32,
    the program computes in bf16)."""
    cfg = CFG.replace(dtype_name=dtype)
    p = _seeded(cfg)
    ids = jnp.asarray([[(5 * i + 2) % cfg.vocab_size for i in range(26)]])
    logits, cache = stage_forward(p, cfg, SPEC, ids,
                                  KVCache.create(cfg, L, 1, 32),
                                  jnp.arange(26)[None])
    assert cache.keys.shape[0] == LEAD + L
    ref = _reference(p, ids[0, :10], ids[0, 10:], dataclasses.asdict(cfg))
    lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
    assert [float(lp[t - 1, ids[0, t]]) for t in range(10, 26)] == \
        pytest.approx(ref["logprobs"], abs=tol)


def test_a_scorer_s_cache_of_keys_and_values_serves_one_whole_sequence(
        params):
    """A cache built by one who knows nothing of latent pages (keys and
    values a head, ``num_layers`` planes: ``benchmark/tests/
    test_reference.py`` builds it so): exact for one call over a whole
    sequence from position 0, and handed back untouched."""
    ids = jnp.asarray([[3, 9, 27, 81, 243 % 256, 5, 15]])
    pos = jnp.arange(7)[None]
    full, _ = stage_forward(params, CFG, SPEC, ids,
                            KVCache.create(CFG, L, 1, 16), pos)
    z = jnp.zeros((L, 1, CFG.num_kv_heads, 16, CFG.head_dim))
    got, cache = stage_forward(params, CFG, SPEC, ids,
                               KVCache(z, z, jnp.int32(0)), pos)
    np.testing.assert_array_equal(full, got)
    assert cache.keys.shape == z.shape and int(cache.length) == 7
    assert not np.asarray(cache.keys).any()


@pytest.mark.parametrize("mode", ["mixed", "serialized", "chunked"])
def test_served_logprobs_equal_the_float32_reference(params, mode):
    """Prefill (in chunks through the mixed slab, in one bucket, or in
    serialized chunks), then decode through the latent page pool: every
    emitted token's log-probability against the reference's full forward,
    and the tokens the reference would have chosen."""
    kw = {"mixed": MIXED, "serialized": {},
          "chunked": dict(prefill_chunk=8)}[mode]
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, CFG.vocab_size, size=n).astype(np.int32)
               for n in (29, 7)]
    with _engine(params, **kw) as eng:
        reqs = [eng.submit(p, 9) for p in prompts]
        outs = [np.asarray(r.wait(timeout=300)) for r in reqs]
        lps = [list(r.lps) for r in reqs]
    for p, o, lp in zip(prompts, outs, lps):
        ref = _reference(params, p, o)
        assert lp == pytest.approx(ref["logprobs"], abs=2e-4)
        assert [int(t) for t in o] == ref["best_ids"]


def test_absorbed_attention_equals_the_decompressed_form(params):
    """One block's attention in float32: the program's absorbed form
    (``W_UK`` folded into the query, ``W_UV`` applied to the output, one
    shared row a token) against keys and values decompressed a head and
    plain causal softmax attention, written here."""
    lp = jax.tree.map(lambda a: a[1], params.layers)
    dn, dr, dv, r = (CFG.qk_nope_head_dim, CFG.qk_rope_head_dim,
                     CFG.v_head_dim, CFG.kv_lora_rank)
    nh, T = CFG.num_heads, 19
    h = jax.random.normal(jax.random.PRNGKey(5), (1, T, CFG.hidden_size))
    pos = jnp.arange(T)[None]
    got, cache = decoder._latent_attention(
        CFG, lp, h, jnp.zeros((1, 1, 24, WIDTH)), pos, jnp.int32(0))
    q = (h @ lp["wq"]).reshape(1, T, nh, dn + dr)
    ckv = h @ lp["wkv_a"]
    c = decoder.rms_norm(ckv[..., :r], lp["kv_norm_w"], CFG.norm_eps)
    k_pe = apply_rope_interleaved(ckv[:, :, None, r:], pos, CFG.rope_theta)
    q_pe = apply_rope_interleaved(q[..., dn:], pos, CFG.rope_theta)
    k_nope = jnp.einsum("btr,hdr->bthd", c, lp["w_uk"])
    v = jnp.einsum("btr,hrv->bthv", c, lp["w_uv"])
    qq = jnp.concatenate([q[..., :dn], q_pe], -1)
    kk = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (1, T, nh, dr))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(got, want.reshape(1, T, nh * dv), atol=2e-5)
    # the cached row: [c | k_pe | zeros], nothing decompressed
    np.testing.assert_allclose(cache[0, 0, :T, :r], c[0], atol=1e-6)
    np.testing.assert_allclose(cache[0, 0, :T, r:r + dr], k_pe[0, :, 0],
                               atol=1e-6)
    assert not np.asarray(cache[0, 0, :, r + dr:]).any()


def test_interleaved_rope_is_rotate_half_on_permuted_columns():
    """Why the loader moves no column: roping interleaved pairs on the
    stored columns is roping in rotate-half form on the de-interleaved
    ones (what HF's ``apply_rotary_pos_emb_interleave`` does), up to that
    same permutation of the output, which a dot product of two vectors
    permuted alike does not see."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 3, 8))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    perm = np.asarray([0, 2, 4, 6, 1, 3, 5, 7])
    np.testing.assert_allclose(
        apply_rope_interleaved(x, pos, 1e4)[..., perm],
        apply_rope(x[..., perm], pos, 1e4), atol=1e-6)


# ------------------------------------------------------------- the router

def _router_case(bias, logits, **kw):
    cfg = CFG.replace(num_experts=len(logits), experts_per_token=3, **kw)
    eye = jnp.eye(len(logits), dtype=jnp.float32)
    lp = {"router": eye, "router_bias": jnp.asarray(bias, jnp.float32)}
    w, e = decoder._route(cfg, lp, jnp.asarray([logits], jnp.float32))
    return np.asarray(w[0]), [int(i) for i in e[0]]


LOGITS = [2.0, 1.0, 0.5, 0.0, -1.0, -2.0]
SIG = 1.0 / (1.0 + np.exp(-np.asarray(LOGITS)))


def test_router_bias_changes_the_choice_and_not_the_weight():
    w0, e0 = _router_case([0.0] * 6, LOGITS)
    assert e0 == [0, 1, 2]
    w1, e1 = _router_case([0, 0, 0, 0, 0, 5.0], LOGITS)
    assert e1 == [5, 0, 1]              # chosen by sigmoid + bias
    want = SIG[[5, 0, 1]] / SIG[[5, 0, 1]].sum() * 2.448
    np.testing.assert_allclose(w1, want, rtol=1e-6)   # weighed by sigmoid


def test_router_renormalises_then_scales():
    w, e = _router_case([0.0] * 6, LOGITS)
    np.testing.assert_allclose(w.sum(), 2.448, rtol=1e-6)
    np.testing.assert_allclose(w, SIG[:3] / SIG[:3].sum() * 2.448,
                               rtol=1e-6)
    raw, _ = _router_case([0.0] * 6, LOGITS, norm_topk_prob=False,
                          routed_scaling_factor=1.0)
    np.testing.assert_allclose(raw, SIG[:3], rtol=1e-6)


def test_router_tie_at_the_last_rank_takes_the_lower_index():
    _, e = _router_case([0.0] * 6, [2.0, 1.0, 0.5, 0.5, 0.5, -1.0])
    assert e == [0, 1, 2]


def test_softmax_router_is_untouched_by_the_new_fields():
    cfg = get_model_config("olmoe-test")
    lp = {"router": jnp.eye(8, dtype=jnp.float32)}
    w, e = decoder._route(cfg, lp, jnp.asarray([[3.0, 1, 0, 2, 0, 0, 0, 0]]))
    p = np.exp([3.0, 2.0]) / np.exp([3.0, 1, 0, 2, 0, 0, 0, 0]).sum()
    assert [int(i) for i in e[0]] == [0, 3]
    np.testing.assert_allclose(w[0], p, rtol=1e-6)


# --------------------------------------- shared expert, lead block, valid

def test_shared_expert_is_counted_once(params):
    """Routed sum + ONE shared SwiGLU: with the routed experts' down
    projections zeroed the layer's output is the shared expert's alone,
    and with the shared expert's zeroed, the routed sum's; the two add up
    to the whole."""
    lp = jax.tree.map(lambda a: a[0], params.layers)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, CFG.hidden_size))
    whole, rows = decoder._moe_routed(CFG, lp, x)
    only_shared, _ = decoder._moe_routed(
        CFG, dict(lp, w_down=jnp.zeros_like(lp["w_down"])), x)
    only_routed, _ = decoder._moe_routed(
        CFG, dict(lp, ws_down=jnp.zeros_like(lp["ws_down"])), x)
    shared = (jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])
              ) @ lp["ws_down"]
    np.testing.assert_allclose(only_shared, shared, atol=1e-5)
    np.testing.assert_allclose(whole, only_shared + only_routed, atol=1e-5)
    assert int(rows.sum()) == 10 * CFG.experts_per_token


def test_lead_block_runs_once_and_holds_plane_zero(params):
    """Zeroing the leading block's output projections makes it the
    identity: the logits are then those of a model without it run on the
    same expert stack, and its plane (plane 0) still holds ITS rows while
    the stack's planes moved up by one."""
    ids = jnp.asarray([[(7 * i + 3) % CFG.vocab_size for i in range(12)]])
    pos = jnp.arange(12)[None]
    off = dataclasses.replace(params, lead=dict(
        params.lead, wo=jnp.zeros_like(params.lead["wo"]),
        w_down=jnp.zeros_like(params.lead["w_down"])))
    with_lead, c1 = stage_forward(off, CFG, SPEC, ids,
                                  KVCache.create(CFG, L, 1, 16), pos)
    bare_cfg = CFG.replace(lead_dense_layers=0)
    bare = dataclasses.replace(params, lead=None)
    without, c0 = stage_forward(bare, bare_cfg, SPEC, ids,
                                KVCache.create(bare_cfg, L, 1, 16), pos)
    np.testing.assert_allclose(with_lead, without, atol=1e-5)
    assert c1.keys.shape[0] == c0.keys.shape[0] + 1
    np.testing.assert_allclose(c1.keys[1:], c0.keys, atol=1e-6)
    assert float(jnp.abs(c1.keys[0]).max()) > 0
    # and it changes the logits when it is there
    real, _ = stage_forward(params, CFG, SPEC, ids,
                            KVCache.create(CFG, L, 1, 16), pos)
    assert float(jnp.abs(real - without).max()) > 1e-3


def test_idle_rows_enter_no_group_and_write_no_page(params):
    """A decode step over four slots of which two hold a request: the
    routing counters count the two, and the idle slots' (sentineled)
    table rows write nothing to the pool."""
    bt, W, n = 8, 3, 11
    fwd, bind, _ = make_paged_forward_seam(CFG, SPEC, None, params, bt)
    pk, pv = alloc_kv_pool((CFG.kv_planes, 4 * W, 1, bt, WIDTH), "bf16",
                           CFG.dtype, streams=1)
    N = 4 * W
    tables = jnp.arange(N, dtype=jnp.int32).reshape(4, W)
    tables = tables.at[jnp.asarray([1, 3])].set(N)       # idle slots
    valid = jnp.asarray([True, False, True, False])
    bind(tables, "test")
    _, cache, rows = fwd(
        params, jnp.asarray([[5], [6], [7], [8]]),
        KVCache(pk, pv, jnp.int32(0)), jnp.full((4, 1), n), 0,
        moe_stats=True, valid=valid[:, None])
    assert rows.shape == (L, CFG.num_experts)
    assert [int(r.sum()) for r in rows] == [2 * CFG.experts_per_token] * L
    written = np.asarray(jnp.abs(cache.keys).sum((0, 2, 3, 4)) > 0)
    assert written.tolist() == [p in (n // bt, 2 * W + n // bt)
                                for p in range(N)]


def test_dispatch_record_counts_what_the_prefill_kernel_attends_over(params):
    """``prefill_kv_tokens``: a prompt of n tokens prefilled from 0 in
    chunks attends over n (n + 1) / 2 pairs in all; ``moe_*`` columns as
    for olmoe."""
    n = 21
    with _engine(params, **MIXED) as eng:
        eng.submit(np.arange(1, n + 1, dtype=np.int32), 3).wait(timeout=300)
        dt = _settled(eng)["dispatch_trace"]
        assert eng.stats()["fold_pages"] == {}       # the CPU gathers
    col = dt["fields"].index("prefill_kv_tokens")
    assert "moe_rows" in dt["fields"]
    assert sum(r[col] for r in dt["recent"]) == n * (n + 1) // 2


# ------------------------------------------------------------ the kernels

# rows of one call, by where their last group of pages ends (the table is
# as wide as two groups and a half): positions of a decode step, first
# positions of a chunk of 32; ``None`` is a freed slot
_GROUP_ENDS = {1: [170, 255, 70, 7, None], 32: [135, 209, 0, 32, None]}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [1, 32])
@pytest.mark.parametrize("rows", ["mixed", "group_ends"])
def test_latent_kernels_equal_the_xla_paths(dtype, chunk, rows, monkeypatch):
    """Interpreted: the page write against the scatter (bit for bit) and
    the page-walking kernel against the gather, decode (one of whose rows
    is a freed slot) and a chunk over its cached context.  ``group_ends``:
    a fold takes 8 pages here, and the rows end inside a group (11 pages),
    on a group's boundary (16), before one is full (5; a chunk's 2 and 4)
    and after exactly one page (a decode step's; a chunk's first tile),
    beside a freed slot; the chunk runs as two query tiles of its own
    frontiers.  Every page outside the live part of a table is NaN, a
    table's dead entries are the sentinel, whose clamp is such a page,
    and the interpreter's VMEM starts as NaN: a part of a slot that no
    copy filled, or a dead entry that was copied, reaches a product and
    fails the comparison."""
    dt = jnp.dtype(dtype)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(chunk), 3)
    N, bt, nh, rank, width = 20, 16, 4, 128, 256
    interpret = True
    if rows == "mixed":
        W = 5
        starts = [37, None, 5] if chunk == 1 else [16, 48]
    else:
        N, W, starts = 128, 20, _GROUP_ENDS[chunk]
        monkeypatch.setattr(la, "_TILE_ROWS", 16 * nh)
        assert la.latent_fold(min(chunk, 16) * nh, bt, width, dt.itemsize,
                              W)[0] == 8
        interpret = pltpu.InterpretParams()    # uninitialized VMEM is NaN
    b = len(starts)
    pool = jax.random.normal(k1, (3, N, 1, bt, width)).astype(dt)
    q = (0.3 * jax.random.normal(k2, (b, chunk, nh, width))).astype(dt)
    row = jax.random.normal(k3, (b, chunk, width)).astype(dt)
    tables = np.random.RandomState(0).permutation(N - 1)[:b * W].reshape(b, W)
    held = np.zeros(N, bool)
    for r, s in enumerate(starts):
        n_live = 0 if s is None else (s + chunk + bt - 1) // bt
        held[tables[r, :n_live]] = True
        if rows == "group_ends" or s is None:
            tables[r, n_live:] = N
    tables = jnp.asarray(tables, jnp.int32)
    pos = (jnp.asarray([0 if s is None else s for s in starts])[:, None]
           + jnp.arange(chunk)[None])
    pages = LayerOf(pool, jnp.int32(1))
    wrote = {form: la.write_latent_pages(pages, row, tables, pos, form=form,
                                         interpret=True).stack
             for form in (la.WRITE_SCATTER, la.WRITE_KERNEL)}
    np.testing.assert_array_equal(wrote[la.WRITE_SCATTER],
                                  wrote[la.WRITE_KERNEL])
    np.testing.assert_array_equal(wrote[la.WRITE_KERNEL][0], pool[0])
    pages = LayerOf(wrote[la.WRITE_KERNEL], jnp.int32(1))
    want = la.latent_gather_attention(q, pages, tables, pos, rank, 0.3)
    if rows == "group_ends":
        pages = LayerOf(jnp.where(jnp.asarray(held)[None, :, None, None,
                                                    None], pages.stack,
                                  jnp.nan), pages.layer)
    got = la.latent_paged_attention(q, pages, tables, pos, rank, 0.3,
                                    interpret=interpret)
    live = np.asarray(tables[:, 0] < N)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=5e-6 if dtype == "float32" else 4e-3)
    assert not np.asarray(got, np.float32)[~live].any()


def test_the_pages_a_fold_takes_are_recorded_beside_the_path():
    """``AttnPathRecord.fold_pages`` (``/stats["fold_pages"]``): the group
    the traced kernel call has, per program and chunk; a call that
    gathers has none."""
    N, bt, nh, rank, width, W = 24, 16, 8, 128, 256, 6
    pool = jnp.zeros((2, N, 1, bt, width), jnp.bfloat16)
    tables = jnp.arange(2 * W, dtype=jnp.int32).reshape(2, W)
    record = la.AttnPathRecord()
    for backend, program in (("pallas", "kernels"), ("xla", "gather")):
        impl, bind = la.make_latent_attn_impl(rank, 0.3, backend=backend,
                                              interpret=True, record=record)
        for chunk in (1, 16):
            bind(tables, program)
            q = jnp.zeros((2, chunk, nh, width), jnp.bfloat16)
            pos = 20 + jnp.zeros((2, 1), jnp.int32) + jnp.arange(chunk)
            jax.eval_shape(lambda q, p, pos: impl(
                q, q[:, :, 0], LayerOf(p, jnp.int32(0)), pos), q, pool, pos)
    want = {f"chunk={c}": la.latent_fold(c * nh, bt, width, 2, W)[0]
            for c in (1, 16)}
    assert want == {"chunk=1": W, "chunk=16": W}    # never past the table
    assert record.fold_pages() == {"kernels": want}
    assert set(record.snapshot()) == {"kernels", "gather"}


def test_routing_of_latent_pages():
    pool = jnp.zeros((2, 4, 1, 128, 640), jnp.bfloat16)
    assert la.route_latent_attention("auto", "tpu", pool, 1, 32) == (
        la.PATH_DECODE_KERNEL, "")
    assert la.route_latent_attention("auto", "tpu", pool, 256, 32) == (
        la.PATH_PREFILL_KERNEL, "")
    assert la.route_latent_attention("auto", "cpu", pool, 1, 32)[0] \
        == la.PATH_GATHER
    assert la.route_pool("auto", "tpu", pool, 256) == la.WRITE_KERNEL
    assert la.route_pool("auto", "cpu", pool, 256) == la.WRITE_SCATTER
    narrow = jnp.zeros((2, 4, 1, 128, 576), jnp.bfloat16)
    assert "lane" in la.route_latent_attention("auto", "tpu", narrow, 1,
                                               32)[1]
    with pytest.raises(ValueError, match="cannot take this shape"):
        la.route_latent_attention("pallas", "tpu", narrow, 1, 32)
    assert la.latent_page_width(512, 64) == 640
    assert la.latent_tile_tokens(256, 32) == 32


# -------------------------------------------------------------- the loader

def test_checkpoint_name_map_round_trips(params):
    """A DeepseekV3ForCausalLM state dict built from the seeded tree by
    the published names ([out, in] linears, ``kv_b_proj`` whole) loads
    back to the same leaves, the first checkpoint layer as the leading
    block."""
    from distributed_inference_demo_tpu.models.loader import (
        params_from_state_dict)
    dn, dv, r, nh = (CFG.qk_nope_head_dim, CFG.v_head_dim, CFG.kv_lora_rank,
                     CFG.num_heads)
    raw = {"model.embed_tokens.weight": params.embed["tokens"],
           "model.norm.weight": params.final_norm["w"],
           "lm_head.weight": params.lm_head["w"].T}
    for i in range(LEAD + L):
        tree, j = ((params.lead, i) if i < LEAD
                   else (params.layers, i - LEAD))
        t = {k: np.asarray(v[j]) for k, v in tree.items()}
        p = f"model.layers.{i}."
        kv_b = np.concatenate([t["w_uk"], t["w_uv"].transpose(0, 2, 1)], 1)
        raw.update({
            p + "input_layernorm.weight": t["attn_norm_w"],
            p + "post_attention_layernorm.weight": t["mlp_norm_w"],
            p + "self_attn.q_proj.weight": t["wq"].T,
            p + "self_attn.kv_a_proj_with_mqa.weight": t["wkv_a"].T,
            p + "self_attn.kv_a_layernorm.weight": t["kv_norm_w"],
            p + "self_attn.kv_b_proj.weight": kv_b.reshape(nh * (dn + dv),
                                                           r),
            p + "self_attn.o_proj.weight": t["wo"].T})
        names = (("gate", "gate_proj"), ("up", "up_proj"),
                 ("down", "down_proj"))
        if i < LEAD:
            raw.update({p + f"mlp.{hf}.weight": t["w_" + ours].T
                        for ours, hf in names})
            continue
        raw[p + "mlp.gate.weight"] = t["router"].T
        raw[p + "mlp.gate.e_score_correction_bias"] = t["router_bias"]
        for ours, hf in names:
            raw[p + f"mlp.shared_experts.{hf}.weight"] = t["ws_" + ours].T
            for e in range(CFG.num_experts):
                raw[p + f"mlp.experts.{e}.{hf}.weight"] = t["w_" + ours][e].T
    loaded = params_from_state_dict({k: np.asarray(v) for k, v in raw.items()},
                                    CFG)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)


# --------------------------------------------- what refuses, in a sentence

def _draft(params):
    ContinuousBatchingEngine(
        get_model_config("llama-test"),
        init_full_params(jax.random.PRNGKey(0),
                         get_model_config("llama-test")),
        max_seq=64, max_batch=2, draft_cfg=CFG, draft_params=params,
        num_draft=2)


def _tp(params):
    from distributed_inference_demo_tpu.parallel.mesh import (MeshConfig,
                                                              make_mesh)
    from distributed_inference_demo_tpu.parallel.tensor import validate_tp
    validate_tp(CFG, make_mesh(MeshConfig(tp=2)))


def _migration(params):
    with _engine(params, **MIXED) as eng:
        eng.export_request("nobody")


def _premigrated(params):
    with _engine(params, **MIXED) as eng:
        z = np.zeros((1, 4, 1, 8, WIDTH), np.float32)
        eng.submit_premigrated(np.arange(1, 12, dtype=np.int32), 2, z, z)


def _imported(params):
    with _engine(params, **MIXED) as eng:
        eng.import_request({"tokens": [1], "length": 3})


def _ring(params):
    from distributed_inference_demo_tpu.parallel.sequence import (
        _make_ring_cores)
    _make_ring_cores(CFG, SPEC, 16, GREEDY, None)


def _ulysses(params):
    from distributed_inference_demo_tpu.parallel.ulysses import (
        _make_ulysses_cores)
    _make_ulysses_cores(CFG, 32, 2, GREEDY, None)


def _kv_hook(params):
    hook = lambda *a: None
    stage_forward(params, CFG, SPEC, jnp.asarray([[1, 2]]),
                  KVCache.create(CFG, L, 1, 8), jnp.arange(2)[None],
                  attn_impl=hook)


def _training_layout(params):
    stage_forward(params, CFG, SPEC, jnp.asarray([[1, 2]]),
                  KVCache.create(CFG, L, 1, 8), jnp.arange(2)[None],
                  cache_in_carry=False)


def _serialise(params):
    from distributed_inference_demo_tpu.models.loader import (
        stage_params_to_bytes)
    stage_params_to_bytes(params)


LATENT = "does not support a latent-attention model"
REFUSALS = {
    "int8 pages": (ValueError, "a page pool of int8 pages " + LATENT,
                   lambda p: _engine(p, kv_dtype="int8", **MIXED)),
    "int4 pages": (ValueError, "a page pool of int4 pages " + LATENT,
                   lambda p: _engine(p, kv_dtype="int4", **MIXED)),
    "host tier": (ValueError, "the host tier of the KV cache " + LATENT,
                  lambda p: _engine(p, kv_host_tier_bytes=1 << 20, **MIXED)),
    "export_request": (ValueError, r"export_request \(migration\) " + LATENT,
                       _migration),
    "import_request": (ValueError, r"import_request \(migration\) " + LATENT,
                       _imported),
    "premigrated prefill": (ValueError, "a premigrated prefill", _premigrated),
    "draft": (ValueError, "the draft side of speculation " + LATENT, _draft),
    "manual TP": (ValueError, r"tensor parallelism \(--tp\) " + LATENT, _tp),
    "pipeline stages": (ValueError, "a pipeline of stages " + LATENT,
                        lambda p: slice_stage(p, CFG,
                                              split_layer_ranges(L, 2)[0])),
    "ring sequence parallelism": (ValueError,
                                  "ring sequence parallelism " + LATENT,
                                  _ring),
    "ulysses": (ValueError, "Ulysses sequence parallelism " + LATENT,
                _ulysses),
    "a hook for keys and values": (ValueError,
                                   "made for keys and values cannot serve",
                                   _kv_hook),
    "training layout": (ValueError, "runs on one stage, in one pass",
                        _training_layout),
    "artifact channel": (TypeError, "are not shipped to pipeline stages",
                         _serialise),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_needs_keys_and_values_refuses_in_a_sentence(what, params):
    error, sentence, build = REFUSALS[what]
    with pytest.raises(error, match=sentence):
        build(params)


def test_serve_chain_refuses_a_latent_model_in_a_sentence(capsys):
    from distributed_inference_demo_tpu import cli
    assert cli.main(["serve", "--model", "kanana-test", "--chain",
                     "w1@127.0.0.1:1", "--device-id", "h"]) == 1
    assert LATENT in capsys.readouterr().err

