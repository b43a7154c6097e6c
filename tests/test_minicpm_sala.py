"""MiniCPM-SALA's two kinds of block (family ``minicpm_sala``, PR 69) on the
CPU at toy widths (``minicpm-sala-test``: kernel 4 = 2 x stride, block 8 =
4 x stride, top-3, one initial block, a local window of two blocks,
``dense_len`` 48, so that a context of a hundred tokens selects): the
block-sparse kind (``ops.sparse_attention``: the index plane, the
selection, the fold and its lists) and the Lightning linear kind (``ops.ssd``
with B and C a head's own) against the family's plain reference
(``benchmark/families/minicpm_sala.py``).  ``tests/test_minicpm_sala_engine.py``
holds the engine."""
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
from distributed_inference_demo_tpu.models.decoder import (init_full_params,
                                                           stage_forward)
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops import sparse_attention as sa
from distributed_inference_demo_tpu.ops import ssd
from distributed_inference_demo_tpu.ops.stacked import LayerOf

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import model_parity  # noqa: E402  (tools/)

CFG = get_model_config("minicpm-sala-test")
MC = dataclasses.asdict(CFG)
FAM = families.load("minicpm_sala")
SPARSE, LIGHT = CFG.period[0], CFG.period[1]
SIZES = SPARSE.sparse_sizes


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(3), CFG)


def _args(**kw):
    return type("A", (), dict(dict(page=32, chunk=32, steps=6,
                                   kv_dtype="bf16"), **kw))


# ------------------------------------------------------------ configuration

def test_the_two_kinds_state_their_caches():
    assert SPARSE.has_pages and not SPARSE.is_state
    assert LIGHT.is_state and not LIGHT.has_pages
    assert CFG.sparse_kind == SPARSE and CFG.state_kind == LIGHT
    # one pool of pages (the sparse kind's four planes), its index plane
    # beside it, and the state pool: three arrays of keys
    assert CFG.cache_kinds == ((0, 4),) and CFG.cache_arrays == 2
    assert CFG.state_planes == 4
    assert CFG.state_shapes == ((4, 16, 16), (1,))
    assert CFG.state_bytes_per_slot == 4 * (4 * 16 * 16 * 4 + 4)
    # a page of 32 tokens holds 16 pooled keys, a row of nkv x hd each
    assert CFG.index_shape(10, 32) == (4, 160, 32)
    assert [CFG.plane_of(b) for b in range(8)] == [
        (0, 0), (-1, 0), (-1, 1), (0, 1), (0, 2), (-1, 2), (-1, 3), (0, 3)]


@pytest.mark.parametrize("bad,sentence", [
    (dict(attn="sparse", sparse_kernel=8), "a sparse kind states"),
    (dict(attn="sparse", sparse_kernel=6, sparse_stride=4, sparse_block=16,
          sparse_topk=1, sparse_local=16), "a sparse kind states"),
    (dict(attn="full", sparse_topk=4), "only a sparse kind"),
    (dict(attn="lightning", conv=4), "behind a convolution"),
])
def test_a_kind_that_states_the_wrong_sizes_is_refused(bad, sentence):
    with pytest.raises(ValueError, match=sentence):
        BlockKind(**bad)


def test_a_page_that_holds_no_whole_block_is_refused():
    with pytest.raises(ValueError, match="whole blocks"):
        CFG.index_shape(10, 12)


def test_a_dense_cache_refuses_the_sparse_kind_in_a_sentence(params):
    with pytest.raises(ValueError, match="index plane"):
        stage_forward(params, CFG, StageSpec(0, 1, 0, CFG.num_layers),
                      jnp.zeros((1, 8), jnp.int32),
                      KVCache.create(CFG, CFG.num_layers, 1, 16),
                      jnp.arange(8)[None])


# --------------------------------------------------------- the linear kind

def _vectors(s, seed, heads=8, p=16, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return f(s, heads, p), f(s, heads, n) * 0.3, f(s, heads, n) * 0.3


@pytest.mark.parametrize("s,chunk", [(40, 16), (64, 64), (7, 16)])
def test_the_chunk_form_with_b_and_c_a_head_is_the_token_scan(s, chunk):
    """The Lightning recurrence: ``dt`` = 1, the decay a constant a head,
    B = k and C = q a HEAD (groups = heads)."""
    x, B, C = _vectors(s, 1)
    A = ssd.lightning_log_decay(8)
    dt = jnp.ones((s, 8), jnp.float32)
    state = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 3, 8, 16, 16)), jnp.float32)
    want_y, want_S = ssd.ssd_recurrence(state[1, 1], x, B, C, dt, A)
    y, got = ssd.ssd_chunk(state, jnp.int32(1), jnp.int32(1),
                           jnp.asarray(False), x, B, C, dt, A, chunk=chunk)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(got[1, 1], want_S, atol=2e-5)
    assert (got[0] == state[0]).all() and (got[1, 0] == state[1, 0]).all()


def test_the_slopes_are_the_lightning_schedule():
    lam = np.exp(np.asarray(ssd.lightning_log_decay(32)))
    np.testing.assert_allclose(
        lam, np.exp(-2.0 ** (-8.0 * np.arange(1, 33) / 32)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(FAM.lightning_decay(32)), lam,
                               rtol=1e-6)
    assert 0.36 < lam[0] < 0.44 and 0.996 < lam[-1] < 0.9962


def test_the_pallas_calls_with_b_and_c_a_head_are_the_recurrence():
    """``_la_chunk`` / ``_la_step`` in interpret mode at the served tile
    (P = N = 128), a block of eight heads with its own B C^T each."""
    H = 8
    x, B, C = _vectors(256, 4, H, 128, 128)
    A = ssd.lightning_log_decay(H)
    dt = jnp.ones((256, H), jnp.float32)
    state = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 3, H, 128, 128)), jnp.float32)
    assert ssd.on_kernel(state.shape, H, 256, "pallas") == (True, "")
    assert not ssd.on_kernel((1, 3, 4, 128, 128), 4, 256, "pallas")[0]
    want_y, want_S = ssd.ssd_recurrence(state[0, 1], x, B, C, dt, A)
    y, got = ssd.ssd_chunk(state, jnp.int32(0), jnp.int32(1),
                           jnp.asarray(False), x, B, C, dt, A, chunk=256,
                           kernel=True, interpret=True)
    np.testing.assert_allclose(y, want_y, atol=5e-4)
    np.testing.assert_allclose(got[0, 1], want_S, atol=5e-4)
    live = jnp.asarray([True, False])
    y1, s1 = ssd.ssd_step(state, jnp.int32(0), jnp.asarray([1, 0]), x[:2],
                          B[:2], C[:2], dt[:2], A, live, kernel=True,
                          interpret=True)
    y0, s0 = ssd.ssd_step(state, jnp.int32(0), jnp.asarray([1, 0]), x[:2],
                          B[:2], C[:2], dt[:2], A, live)
    np.testing.assert_allclose(y1, y0, atol=1e-4)
    np.testing.assert_allclose(s1, s0, atol=1e-5)


# -------------------------------------------------------- the sparse kind

def _pool(seed, L=2, N=24, nkv=2, bt=32, hd=16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(L, N, nkv, bt, hd)), jnp.float32),
            jnp.asarray(rng.normal(size=(L, N, nkv, bt, hd)), jnp.float32))


@pytest.mark.parametrize("b,s,hd", [(3, 1, 128), (2, 32, 128)])
def test_the_sparse_fold_is_the_masked_softmax(b, s, hd):
    """The Pallas fold (interpret mode) over a random kept set against the
    gather: a token a row is a tile of one query, a segment tiles of many
    whose union is folded under a word of bits."""
    rng = np.random.default_rng(0)
    K, V = _pool(1, hd=hd)
    W, nh, NB = 8, 8, 16
    tables = jnp.asarray(rng.permutation(24)[:b * W].reshape(b, W), jnp.int32)
    start = np.array([200, 150, 90][:b])
    pos = jnp.asarray(start[:, None] + np.arange(s)[None], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    keep = jnp.asarray(rng.random((b, s, 2, NB)) < 0.4).at[..., 0].set(True)
    kp, vp = LayerOf(K, jnp.int32(1)), LayerOf(V, jnp.int32(1))
    want = sa.sparse_gather_attention(q, kp, vp, tables, pos, keep, block=16)
    got = sa.sparse_fold(q, kp, vp, tables, pos, keep, block=16, cap=NB,
                         interpret=True)
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_a_tile_s_list_is_the_union_of_its_queries_blocks():
    rng = np.random.default_rng(3)
    keep = jnp.asarray(rng.random((2, 8, 2, 20)) < 0.2)
    ids, words, counts = sa.tile_entries(keep, 4, 20)
    k = np.asarray(keep).reshape(4, 4, 2, 20)
    for tile in range(4):
        for h in range(2):
            union = np.flatnonzero(k[tile, :, h].any(0))
            n = int(counts[tile, h])
            assert n == len(union)
            assert (np.asarray(ids[tile, h, :n]) == union).all()
            for e, blk in enumerate(union):
                bits = [(int(words[tile, h, e]) >> i) & 1 for i in range(4)]
                assert bits == list(k[tile, :, h, blk].astype(int))


def test_the_index_rows_are_the_kernels_means_across_pages_and_chunks():
    """Chunks of 20 tokens over pages of 16 (stride 4, kernel 8: the
    published proportions at a quarter): kernels straddle a chunk's first
    token and a page's edge, and the last chunk is one token (a decode
    step)."""
    SIZES = (8, 4, 16, 3, 1, 32, 96)
    rng = np.random.default_rng(4)
    nkv, hd, bt, total = 2, 16, 16, 61
    keys = jnp.asarray(rng.normal(size=(1, total, nkv, hd)), jnp.float32)
    N, W = 12, 4
    tables = jnp.asarray([[7, 2, 9, 4]], jnp.int32)
    K = jnp.zeros((1, N, nkv, bt, hd), jnp.float32)
    ix = jnp.zeros((1, N * 4, nkv * hd), jnp.float32)
    spans = [(0, 20), (20, 40), (40, 60), (60, 61)]
    from distributed_inference_demo_tpu.ops.paged_attention import (
        write_paged_kv)
    V = K
    for lo, hi in spans:
        pos = jnp.arange(lo, hi)[None]
        kp, vp = write_paged_kv(LayerOf(K, jnp.int32(0)),
                                LayerOf(V, jnp.int32(0)), keys[:, lo:hi],
                                keys[:, lo:hi], tables, pos,
                                form="scatter write")
        K, V = kp.stack, vp.stack
        ix = sa.write_index(LayerOf(ix, jnp.int32(0)), kp, keys[:, lo:hi],
                            tables, pos, SIZES).stack
    closed = (total - 8) // 4 + 1
    assert closed == 14 == sa.blocks_kept(total - 1, SIZES)[2]
    for j in range(closed):
        page, slot = int(tables[0, (4 * j) // bt]), (4 * j % bt) // 4
        want = np.asarray(keys[0, 4 * j:4 * j + 8]).mean(0).reshape(-1)
        np.testing.assert_allclose(ix[0, page * 4 + slot], want, atol=1e-6)
    # a kernel that has not closed wrote nothing
    assert not np.asarray(ix[0, int(tables[0, 3]) * 4 + 2]).any()


@pytest.mark.parametrize("t", [48, 67, 100, 127])
def test_the_selection_is_the_equations_for_one_query(t):
    """The program's mask for one query against the selection written out
    in numpy a block at a time (``kept_blocks``)."""
    rng = np.random.default_rng(t)
    nkv, g, hd = 2, 2, 16
    kernel, stride, block = SIZES[:3]
    q = rng.normal(size=(nkv, g, hd)).astype(np.float32)
    k = rng.normal(size=(t + 1, nkv, hd)).astype(np.float32)
    J = 64
    c = np.zeros((nkv, J, hd), np.float32)
    for j in range((t + 1 - kernel) // stride + 1):
        c[:, j] = k[stride * j:stride * j + kernel].mean(0)
    keep = np.asarray(sa._select(jnp.asarray(q)[None], jnp.asarray(c),
                                 jnp.asarray([t]), SIZES))[0]
    for h in range(nkv):
        want = FAM.kept_blocks(q[h], k[:, h], t, SIZES)
        assert list(np.flatnonzero(keep[h])) == want
        live, kept, _ = sa.blocks_kept(t, SIZES)
        assert len(want) == kept and live == t // block + 1
    # 1 initial + 2 local + 3 by score, of the blocks that exist


def test_fewer_blocks_than_topk_keeps_them_all_and_ties_go_low():
    q = jnp.zeros((1, 2, 2, 16))        # every score ties
    c = jnp.ones((2, 128, 16))
    keep = np.asarray(sa._select(q, c, jnp.asarray([200]), SIZES))[0, 0]
    # forced: block 0 and 24, 25; of 1 .. 23 the three lowest ids
    assert list(np.flatnonzero(keep)) == [0, 1, 2, 3, 24, 25]
    keep = np.asarray(sa._select(q, c, jnp.asarray([52]), SIZES))[0, 0]
    assert list(np.flatnonzero(keep)) == [0, 1, 2, 3, 5, 6]     # 7 live, 6 kept
    keep = np.asarray(sa._select(q, c, jnp.asarray([49]), SIZES))[0, 0]
    assert list(np.flatnonzero(keep)) == [0, 1, 2, 3, 5, 6]
    keep = np.asarray(sa._select(q, c, jnp.asarray([40]), SIZES))[0, 0]
    assert list(np.flatnonzero(keep)) == [0, 1, 2, 3, 4, 5]     # dense rule


@pytest.mark.parametrize("b,s,g", [(3, 1, 4), (2, 32, 4), (2, 64, 2)])
def test_the_scores_call_is_the_plain_scores(b, s, g):
    """``_sparse_scores`` (interpret mode) through ``scores_on_kernel``'s
    row layout (row ``r tq + c`` of a tile is query ``c``'s head ``r``)
    against ``_scores`` a row: the same softmax a head over the kernels a
    query's position has closed, summed over the group, and ``-inf`` at the
    same kernels.  A row starts under the first kernel's close."""
    rng = np.random.default_rng(11 * s + g)
    nkv, hd, J = 2, 16, 128
    q = jnp.asarray(rng.normal(size=(b, s, nkv, g, hd)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, nkv, J, hd)), jnp.float32)
    start = np.array([1, 100, 250][:b])
    pos = jnp.asarray(start[:, None] + np.arange(s)[None], jnp.int32)
    got = np.asarray(sa.scores_on_kernel(q, c, pos, SIZES, interpret=True))
    want = np.asarray(jax.vmap(
        lambda q, c, t: sa._scores(q, c, t, SIZES))(q, c, pos))
    assert got.shape == want.shape == (b, s, nkv, J)
    assert (np.isfinite(got) == np.isfinite(want)).all()
    # (a head's probabilities sum to one: the group's sum to g)
    live = np.isfinite(want)
    np.testing.assert_allclose(got[live], want[live], atol=2e-6)
    rows = live.any(-1)
    np.testing.assert_allclose(np.where(live, got, 0).sum(-1)[rows], g,
                               rtol=1e-5)


def _filled_pool(total, bt, nkv, hd, seed):
    """A request of ``total`` tokens written as the served path writes it,
    chunks of 32 then one token: ``(keys, values [total, nkv, hd], pools K,
    V, the index plane, its table)`` at the toy sizes, pages in a shuffled
    order, in plane 1 of two."""
    from distributed_inference_demo_tpu.ops.paged_attention import (
        write_paged_kv)
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.normal(size=(total, nkv, hd)), jnp.float32)
    vals = jnp.asarray(rng.normal(size=(total, nkv, hd)), jnp.float32)
    W = -(-total // bt)
    N = W + 3
    table = jnp.asarray(rng.permutation(N)[:W][None], jnp.int32)
    K = jnp.zeros((2, N, nkv, bt, hd), jnp.float32)
    V = K
    ix = jnp.zeros((2, N * (bt // SIZES[1]), nkv * hd), jnp.float32)
    one = jnp.int32(1)
    spans = [(lo, min(lo + 32, total - 1)) for lo in range(0, total - 1, 32)]
    for lo, hi in spans + [(total - 1, total)]:
        pos = jnp.arange(lo, hi)[None]
        kp, vp = write_paged_kv(LayerOf(K, one), LayerOf(V, one),
                                keys[None, lo:hi], vals[None, lo:hi], table,
                                pos, form="scatter write")
        K, V = kp.stack, vp.stack
        ix = sa.write_index(LayerOf(ix, one), kp, keys[None, lo:hi], table,
                            pos, SIZES).stack
    return keys, vals, K, V, ix, table


def _equations(q, keys, vals, t, h):
    """One query's output by the equations: ``q`` ``[g, hd]``, the group's
    keys and values to ``t``, the family's kept blocks (every block under
    ``dense_len``), one softmax over their tokens ``<= t``.  Returns
    ``(kept, out [g, hd])``."""
    block, dense = SIZES[2], SIZES[6]
    k, v = np.asarray(keys[:t + 1, h]), np.asarray(vals[:t + 1, h])
    kept = (list(range(t // block + 1)) if t < dense
            else FAM.kept_blocks(q, k, t, SIZES))
    see = np.isin(np.arange(t + 1) // block, kept)
    sc = np.where(see[None], (q @ k.T) * q.shape[-1] ** -0.5, -np.inf)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    return kept, (w / w.sum(-1, keepdims=True)) @ v


@pytest.mark.parametrize("lo,s", [(118, 32), (149, 1), (32, 32)])
def test_the_served_dispatch_is_the_family_s_selection_and_its_softmax(lo, s):
    """What a request's dispatch runs on the chip, in interpret mode at the
    toy sizes: ``select_blocks(kernel=True)`` (the scores' Pallas call over
    the index rows the path wrote, then the bisection), ``tile_entries``'
    lists and words, and the Pallas fold under them, against the family's
    ``kept_blocks`` and one masked softmax a query in numpy.  A segment of
    32 queries past ``dense_len`` (a tile's union under a word of bits), one
    decode query (its own list), and a segment that goes from under
    ``dense_len`` to over it (positions 32 .. 63 around 48)."""
    nkv, g, hd, bt, total = 2, 4, 16, 32, 150
    keys, vals, K, V, ix, table = _filled_pool(total, bt, nkv, hd, 21)
    rng = np.random.default_rng(lo)
    q = jnp.asarray(rng.normal(size=(1, s, nkv * g, hd)), jnp.float32)
    pos = jnp.arange(lo, lo + s, dtype=jnp.int32)[None]
    one = jnp.int32(1)
    keep = sa.select_blocks(q, LayerOf(ix, one), table, pos, SIZES, nkv, bt,
                            kernel=True, interpret=True)
    NB = keep.shape[-1]
    assert NB == table.shape[1] * bt // SIZES[2]
    cap = NB if s > 1 else min(NB, max(SIZES[4] + SIZES[5] // SIZES[2]
                                       + SIZES[3], -(-SIZES[6] // SIZES[2])))
    out = sa.sparse_fold(q, LayerOf(K, one), LayerOf(V, one), table, pos,
                         keep, block=SIZES[2], cap=cap, interpret=True)
    qn = np.asarray(q).reshape(s, nkv, g, hd)
    for i in range(s):
        for h in range(nkv):
            kept, want = _equations(qn[i, h], keys, vals, lo + i, h)
            assert list(np.flatnonzero(np.asarray(keep[0, i, h]))) == kept
            np.testing.assert_allclose(
                np.asarray(out[0, i]).reshape(nkv, g, hd)[h], want,
                atol=2e-5)
    # the device's own count of what it kept is the scheduler's arithmetic
    assert int(keep.sum()) == nkv * int(sa.blocks_kept(
        np.arange(lo, lo + s), SIZES)[1].sum())


def test_a_fold_that_ignores_its_words_is_not_the_equations():
    """The same dispatch with every word's bits set (a fold that walks its
    tile's union for every query): far from the masked softmax, so the test
    above can tell."""
    nkv, g, hd, bt, total = 2, 4, 16, 32, 150
    keys, vals, K, V, ix, table = _filled_pool(total, bt, nkv, hd, 21)
    q = jnp.asarray(np.random.default_rng(118).normal(
        size=(1, 32, nkv * g, hd)), jnp.float32)
    pos = jnp.arange(118, 150, dtype=jnp.int32)[None]
    one = jnp.int32(1)
    keep = sa.select_blocks(q, LayerOf(ix, one), table, pos, SIZES, nkv, bt,
                            kernel=True, interpret=True)
    union = jnp.broadcast_to(keep.any(axis=1, keepdims=True), keep.shape)
    out = sa.sparse_fold(q, LayerOf(K, one), LayerOf(V, one), table, pos,
                         union, block=SIZES[2], cap=keep.shape[-1],
                         interpret=True)
    qn = np.asarray(q).reshape(32, nkv, g, hd)
    _, want = _equations(qn[5, 0], keys, vals, 123, 0)
    assert np.abs(np.asarray(out[0, 5]).reshape(nkv, g, hd)[0]
                  - want).max() > 1e-2


# ------------------------------------------- the model against its reference

# float32 end to end: the served path and the reference agree to the
# rounding of float32 sums (1e-6 of a log-probability, 1e-4 of a state
# whose entries reach ~50), so 2e-4 holds both with room and is 50 times
# under what any control below reads
@pytest.mark.parametrize("plen,page,chunk", [(150, 32, 32), (233, 32, 32),
                                             (131, 16, 48)])
def test_served_path_agrees_with_the_family_s_full_forward(params, plen,
                                                           page, chunk):
    """Prefill in chunks then decode through both pools.  150 = 4 x 32 +
    22: the prompt ends inside a kernel, kernels straddle every chunk's
    edge (4 tokens every 2) and every page's; a row's queries go from under
    ``dense_len`` (48) to over it inside the second chunk; 233 + 6 opens a
    new block during decode; pages of 16 under chunks of 48 put page edges
    inside chunks."""
    prompts = np.stack([model_parity.seeded_ids(7 + i, plen, CFG.vocab_size)
                        for i in range(2)])
    toks, served, paths, state = model_parity.served(
        CFG, params, prompts, _args(page=page, chunk=chunk))
    assert set(paths) == {"prefill/sparse", "prefill/lightning",
                          "decode/sparse", "decode/lightning"}
    for r in range(2):
        ids = np.concatenate([prompts[r], toks[r]])
        ref, _ = model_parity.reference_logprobs(CFG, params, ids, plen)
        assert np.abs(served[r] - ref).max() < 2e-4
        readings = FAM.state_readings(
            state[:, r], model_parity.reference_states(CFG, params, ids))
        assert max(readings["rel_err"]) < 1e-4
        assert FAM.state_problem(readings, "float32") is None


def _swappable(monkeypatch):
    """The functions the tool swaps in ``ops.sparse_attention``, put back
    when the test ends."""
    for name in ("_choose", "_scores", "select_blocks", "_keys_before",
                 "sparse_fold", "sparse_gather_attention"):
        monkeypatch.setattr(sa, name, getattr(sa, name))


@pytest.mark.parametrize("control", ["", "forced-only", "one-head",
                                     "edge-dropped"])
def test_the_tool_reads_the_served_kept_sets_and_refuses_each_control(
        params, control, monkeypatch):
    """The tool's long reading at toy size: the kept sets come from the
    served program (``keep_tap``: what its selection handed its fold, for
    the prompt's last queries and the last decode steps'), the three
    controls (``--selection``) are planted in that program
    (``selection_control``).  Sound, no block differs and the
    log-probabilities are the reference's; under a control blocks differ
    and the log-probabilities move 50 times past the sound reading's
    1e-6."""
    _swappable(monkeypatch)
    if control:
        model_parity.selection_control(control)
    plen, steps = 233, 6
    kept = model_parity.keep_tap(model_parity.read_positions(
        plen, plen + steps - 1))
    prompts = model_parity.seeded_ids(7, plen, CFG.vocab_size)[None]
    toks, served, _, _ = model_parity.served(CFG, params, prompts, _args())
    jax.effects_barrier()
    ids = np.concatenate([prompts[0], toks[0]])
    ref, _ = model_parity.reference_logprobs(CFG, params, ids, plen)
    reading = model_parity.selection_reading(CFG, params, ids[:-1], plen,
                                             kept)
    assert reading["kept_of"] == 6      # 1 initial + 2 local + 3 by score
    assert reading["kept_served"] == ([3] if control == "forced-only"
                                      else [6])
    if control:
        assert reading["kept_differ_max"] >= 1
        assert np.abs(served[0] - ref).max() > 1e-4
    else:
        assert reading["kept_differ_max"] == 0
        assert np.abs(served[0] - ref).max() < 2e-4


def test_bfloat16_selection_scores_fail_the_float32_tolerance(
        params, monkeypatch):
    """The selection's scores rounded to bfloat16, the next precision down
    (the op keeps float32): near ties fall the other way."""
    scores = sa._scores
    monkeypatch.setattr(sa, "_scores", lambda *a: scores(*a).astype(
        jnp.bfloat16).astype(jnp.float32))
    prompts = np.stack([model_parity.seeded_ids(7 + i, 233, CFG.vocab_size)
                        for i in range(2)])
    toks, served, _, _ = model_parity.served(CFG, params, prompts, _args())
    worst = 0.0
    for r in range(2):
        ids = np.concatenate([prompts[r], toks[r]])
        ref, _ = model_parity.reference_logprobs(CFG, params, ids, 233)
        worst = max(worst, float(np.abs(served[r] - ref).max()))
    assert worst > 2e-4


def test_a_state_rounded_to_bfloat16_fails_the_family_s_limit(params):
    prompts = model_parity.seeded_ids(7, 150, CFG.vocab_size)[None]
    toks, _, _, state = model_parity.served(CFG, params, prompts, _args())
    want = model_parity.reference_states(
        CFG, params, np.concatenate([prompts[0], toks[0]]))
    rounded = np.asarray(jnp.asarray(state[:, 0]).astype(
        jnp.bfloat16).astype(jnp.float32))
    readings = FAM.state_readings(rounded, want)
    assert FAM.state_problem(readings, "float32") is not None
    assert FAM.state_problem(FAM.state_readings(state[:, 0], want),
                             "bfloat16") is not None


def test_bfloat16_weights_stay_inside_the_period_models_toy_limit():
    """bfloat16: the stream, the matmuls' operands, the pages and the
    pooled keys rounded to 8 bits of mantissa through eight blocks; the
    state and the selection's scores stay float32.  0.25 is what the other
    period models' toy readings are held to."""
    cfg = CFG.replace(dtype_name="bfloat16")
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    prompts = model_parity.seeded_ids(9, 150, cfg.vocab_size)[None]
    toks, served, _, _ = model_parity.served(cfg, params, prompts, _args())
    ids = np.concatenate([prompts[0], toks[0]])
    ref, _ = model_parity.reference_logprobs(cfg, params, ids, 150)
    assert np.abs(served[0] - ref).max() < 0.25


def test_the_fold_table_reads_both_folds_at_toy_size(tmp_path, capsys):
    """``tools/sparse_fold_table.py`` (the chip's table of the sparse
    prefill fold beside the accepted dense one) runs its four calls in
    interpret mode and says what a tile folds: every block under
    ``dense_len``, and past it the first query's choice beside the forced
    blocks where a tile's queries are made to agree."""
    import sparse_fold_table
    out = tmp_path / "table.jsonl"
    assert sparse_fold_table.main([
        "--model", "minicpm-sala-test", "--page", "32", "--chunk", "32",
        "--segments", "2", "--starts", "8", "160", "--reps", "1",
        "--out", str(out)]) == 0
    rows = [__import__("json").loads(line)
            for line in out.read_text().splitlines()]
    assert [r["start"] for r in rows] == [8, 160]
    assert rows[0]["kept_a_query"] == rows[0]["blocks_live"] == 5
    assert rows[1]["kept_a_query"] == 6 and rows[1]["blocks_live"] == 24
    assert 6 <= rows[1]["shared_union"] <= 10 < 24 * rows[1]["union_share"]
    assert all(r[k] > 0 for r in rows for k in (
        "dense_ms", "select_ms", "fold_ms", "fold_shared_ms"))
    assert "SPARSE_FOLD" in capsys.readouterr().out


# ------------------------------------------------------- the shape arithmetic

def test_the_family_counts_the_published_bytes():
    conf = __import__("json").loads(
        (ROOT / "benchmark/configs/minicpm-sala-9b-bf16.json").read_text())
    mc = conf["model_config"]
    assert FAM.kv_bytes_per_token(mc) == 2048 + 64 == conf["pool"][
        "bytes_per_token"]
    assert FAM.la_state_bytes_per_slot(mc) == 12582912 == 6 * 32 * 128 * 128 * 4
    sparse, light = mc["period"][0], mc["period"][1]
    assert round(FAM.mixer_elements(mc, light) / 1e6 + 201.33, 1) == 285.2
    assert round(FAM.mixer_elements(mc, sparse) / 1e6 + 201.33, 1) == 253.8
    assert round(FAM.layer_matrix_elements(mc) / 1e6, 1) == 2218.8
    cfg = ModelConfig(**mc)
    assert cfg.state_bytes_per_slot == 12582912 + 6 * 2
    assert cfg.index_shape(4096, 128) == (2, 32768, 256)
    # a query at 30,000 keeps 97 of its 469 blocks
    assert tuple(int(v) for v in sa.blocks_kept(
        30000, cfg.sparse_kind.sparse_sizes)) == (469, 97, 1874)
    assert FAM.sparse_kernel_bytes(mc, 97, 1874) == 2 * 2 * (
        97 * 2 * 64 * 128 + 1874 * 128) * 2
