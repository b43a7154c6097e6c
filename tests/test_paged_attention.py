"""Block-table paged attention (ops/paged_attention.py) vs the dense
reference: the property the whole paged layout stands on is that
attending through a block table is bit-for-bit the same computation as
attending a linear cache holding the same K/V.

The sweep covers the shapes that break naive implementations: ragged
per-row lengths, lengths exactly on block boundaries, single-token tail
blocks, sentinel (unallocated) table entries, GQA group sizes from MHA
to 8x, and ALiBi.  The Pallas kernel runs in interpret mode on CPU
against the same oracle the XLA fallback uses.
"""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_inference_demo_tpu.ops.attention import attention
from distributed_inference_demo_tpu.ops.paged_attention import (
    make_paged_attn_impl, paged_flash_attention, paged_gather_attention,
    paged_prefill_attention, route_pool, write_paged_kv)


def _random_paged(rng, b, nkv, hd, bt, W, lens, extra_pages=3,
                  append_room=0):
    """Pages + tables realizing per-row lengths ``lens``; unallocated
    tail entries get the sentinel (>= num_pages).  ``append_room``
    allocates pages for that many tokens past each length (the engine
    preallocates a request's whole prompt+max_new table)."""
    needed = sum(-(-(int(l) + append_room) // bt) for l in lens)
    N = needed + extra_pages
    pk = jnp.asarray(rng.standard_normal((N, nkv, bt, hd)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((N, nkv, bt, hd)), jnp.float32)
    tables = np.full((b, W), N + 7, np.int32)
    nxt = 0
    for i, l in enumerate(lens):
        for j in range(-(-(int(l) + append_room) // bt)):
            tables[i, j] = nxt
            nxt += 1
    return pk, pv, jnp.asarray(tables), N


def _linearize(pk, pv, tables, N, bt, W):
    """The dense cache a row's table describes (zeros where sentinel)."""
    b = tables.shape[0]
    nkv, hd = pk.shape[1], pk.shape[3]
    k_lin = np.zeros((b, nkv, W * bt, hd), np.float32)
    v_lin = np.zeros_like(k_lin)
    tt = np.asarray(tables)
    for i in range(b):
        for j in range(W):
            if tt[i, j] < N:
                k_lin[i, :, j * bt:(j + 1) * bt] = np.asarray(pk)[tt[i, j]]
                v_lin[i, :, j * bt:(j + 1) * bt] = np.asarray(pv)[tt[i, j]]
    return jnp.asarray(k_lin), jnp.asarray(v_lin)


def _apply_mode(mode, pk, pv, nh):
    """``(pk, pv, slopes)`` of a kernel sweep's mode: plain f32 pages,
    ALiBi slopes, or int8 pages with their scale sidecar."""
    slopes = None
    if mode == "int8":
        from distributed_inference_demo_tpu.ops.quant import (
            quantize_kv_pages)
        pk, pv = quantize_kv_pages(pk, 8), quantize_kv_pages(pv, 8)
    elif mode == "alibi":
        from distributed_inference_demo_tpu.ops.attention import (
            alibi_slopes)
        slopes = alibi_slopes(nh)
    return pk, pv, slopes


# lengths chosen to hit: mid-block, exact block boundary, single-token
# tail block, single-token sequence, full table
SWEEP = [
    dict(nh=4, nkv=2, hd=16, bt=8, W=4, lens=[5, 8, 17]),
    dict(nh=8, nkv=1, hd=8, bt=16, W=3, lens=[1, 33, 48]),
    dict(nh=2, nkv=2, hd=32, bt=8, W=2, lens=[16, 9]),
    dict(nh=8, nkv=4, hd=8, bt=24, W=5, lens=[25, 120, 24, 1]),
]


@pytest.mark.parametrize("case", SWEEP)
@pytest.mark.parametrize("alibi", [False, True])
def test_gather_matches_dense_reference(case, alibi):
    rng = np.random.default_rng(hash(str(case)) % 2**32)
    lens = case["lens"]
    b, bt, W = len(lens), case["bt"], case["W"]
    pk, pv, tables, N = _random_paged(rng, b, case["nkv"], case["hd"],
                                      bt, W, lens)
    q = jnp.asarray(rng.standard_normal((b, 1, case["nh"], case["hd"])),
                    jnp.float32)
    qpos = jnp.asarray([l - 1 for l in lens], jnp.int32)[:, None]
    slopes = None
    if alibi:
        from distributed_inference_demo_tpu.ops.attention import (
            alibi_slopes)
        slopes = alibi_slopes(case["nh"])

    k_lin, v_lin = _linearize(pk, pv, tables, N, bt, W)
    ref = attention(q, k_lin, v_lin, qpos, jnp.int32(W * bt), slopes)
    got = paged_gather_attention(q, pk, pv, tables, qpos, slopes)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# what the decode kernel's page loop depends on (heads of 128 take the
# loop; narrower heads and int8 pages under 128 tokens take the prefill
# kernel's pipeline as a 1-token chunk): a freed slot (all-sentinel
# table, stale length), one partial page, a length exactly on a page
# boundary, a row that fills its whole table; the 7 -> 8 row padding of
# qwen's GQA; 128-token pages, where int8 pages take the loop too
DECODE_SWEEP = SWEEP + [
    dict(nh=4, nkv=2, hd=128, bt=8, W=4, lens=[0, 3, 16, 32]),
    dict(nh=7, nkv=1, hd=128, bt=16, W=5, lens=[80, 0, 1, 33]),
    dict(nh=8, nkv=2, hd=128, bt=128, W=3, lens=[0, 100, 128, 384]),
]
STALE_LEN = 21       # what a freed slot's `lengths` entry may still say


@pytest.mark.parametrize("case", DECODE_SWEEP)
@pytest.mark.parametrize("mode", ["f32", "alibi", "int8"])
def test_pallas_interpret_matches_gather(case, mode):
    """The TPU kernel (interpret mode) against the XLA fallback — same
    pages, same tables, f32 tolerance (online softmax vs one-shot).  A
    row with no page (length 0 here, a stale length at the kernel) is a
    freed slot: its output is discarded by the caller and only has to
    be finite."""
    if case["bt"] % 8:
        pytest.skip("pallas path needs 8-aligned pages")
    rng = np.random.default_rng(hash(str(case) + mode) % 2**32)
    lens = case["lens"]
    b, bt, W = len(lens), case["bt"], case["W"]
    pk, pv, tables, N = _random_paged(rng, b, case["nkv"], case["hd"],
                                      bt, W, lens)
    pk, pv, slopes = _apply_mode(mode, pk, pv, case["nh"])
    q = jnp.asarray(rng.standard_normal((b, 1, case["nh"], case["hd"])),
                    jnp.float32)
    live = np.asarray(lens) > 0
    qpos = jnp.asarray([max(l - 1, 0) for l in lens], jnp.int32)[:, None]
    ref = paged_gather_attention(q, pk, pv, tables, qpos, slopes)
    kv_lens = jnp.asarray([l or STALE_LEN for l in lens], jnp.int32)
    got = paged_flash_attention(q, pk, pv, tables, kv_lens, slopes,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_decode_kernel_grid_has_no_table_width():
    """A call's cost must not scale with the table's width: the page
    loop runs inside the kernel body for a row's live pages, so the
    ``pallas_call`` has one grid step a row whatever ``W`` is."""
    from distributed_inference_demo_tpu.ops.paged_attention import (
        _paged_call)

    def grid(W):
        b, nkv, rows, hd, bt, N = 3, 2, 8, 128, 16, 5
        S = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(
            lambda *a: _paged_call(*a, block_tokens=bt, use_alibi=False,
                                   interpret=False))(
            S((b, nkv, rows, hd), jnp.float32),
            S((2, N, nkv, bt, hd), jnp.float32),
            S((2, N, nkv, bt, hd), jnp.float32), S((1,), jnp.int32),
            S((b, W), jnp.int32), S((b,), jnp.int32),
            S((nkv, rows, 1), jnp.float32))
        calls = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        assert len(calls) == 1
        return tuple(calls[0].params["grid_mapping"].grid)

    assert grid(8) == grid(64) == (3,)


# per-row starts hit: chunk from zero, chunk mid-page, chunk crossing a
# page boundary, deep prior context; chunk lengths hit sub-page, exact
# page, and multi-page spans (rows = chunk x group padded to 8)
PREFILL_SWEEP = [
    dict(nh=4, nkv=2, hd=16, bt=8, W=6, chunk=8, starts=[0, 8, 19]),
    dict(nh=8, nkv=2, hd=8, bt=8, W=8, chunk=5, starts=[3, 0, 40]),
    dict(nh=2, nkv=2, hd=32, bt=16, W=3, chunk=16, starts=[0, 13]),
    dict(nh=4, nkv=4, hd=8, bt=8, W=5, chunk=17, starts=[1, 20]),
]


@pytest.mark.parametrize("case", PREFILL_SWEEP)
@pytest.mark.parametrize("mode", ["f32", "alibi", "int8"])
def test_pallas_prefill_interpret_matches_gather(case, mode):
    """The ISSUE-15 prefill kernel (interpret mode) against the XLA
    gather fallback: a chunk's queries attend causally over prior pages
    plus in-chunk keys already written to the pool (write-before-attend
    contract), per-row ragged starts, GQA row packing, ALiBi, and int8
    sidecar dequant.  f32 tolerance — the online softmax reduces in a
    different order than the one-shot gather."""
    rng = np.random.default_rng(hash(str(case) + mode) % 2**32)
    starts, chunk = case["starts"], case["chunk"]
    b, bt, W = len(starts), case["bt"], case["W"]
    lens = [s + chunk for s in starts]     # in-chunk keys already paged
    pk, pv, tables, N = _random_paged(rng, b, case["nkv"], case["hd"],
                                      bt, W, lens)
    pk, pv, slopes = _apply_mode(mode, pk, pv, case["nh"])
    q = jnp.asarray(
        rng.standard_normal((b, chunk, case["nh"], case["hd"])),
        jnp.float32)
    qpos = (jnp.asarray(starts, jnp.int32)[:, None]
            + jnp.arange(chunk, dtype=jnp.int32)[None, :])
    ref = paged_gather_attention(q, pk, pv, tables, qpos, slopes)
    got = paged_prefill_attention(q, pk, pv, tables, qpos, slopes,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _walk_tables(rng, b, W, N, rows):
    """Tables of ``b`` rows whose pages ``[lo, hi)`` are live (distinct
    pages of the pool) and every other entry the sentinel."""
    pages = iter(rng.permutation(N))
    tables = np.full((b, W), N + 7, np.int32)
    for i, (lo, hi) in enumerate(rows):
        for j in range(lo, hi):
            tables[i, j] = next(pages)
    return tables


# the page loop (heads of 128; int8 at 128-token pages): what its walk
# depends on.  ``rows`` says which pages of each row's table are live,
# ``same`` that every row reads ONE table (an admission's chunk cut into
# sub-chunks, each a row of the call that starts where the last ended)
PREFILL_LOOP_SWEEP = {
    # chunk from zero, mid-page, across a page boundary, deep context;
    # behind each frontier the table's tail is sentinel
    "ragged": dict(nh=4, nkv=2, bt=128, W=6, chunk=16,
                   starts=[0, 70, 120, 600]),
    # six query heads a kv head (48 rows of 8 x 6), small pages
    "group-6": dict(nh=6, nkv=1, bt=16, W=9, chunk=8, starts=[0, 13, 120]),
    "sub-chunks": dict(nh=4, nkv=2, bt=128, W=5, chunk=8, same=True,
                       starts=[240, 248, 256, 264, 500]),
    # a window kind: the pages behind the window went back to the pool
    # (sentinel entries the walk must not read), the first chunk sees
    # less than a window, the last starts deep in its table
    "window": dict(nh=4, nkv=2, bt=128, W=6, chunk=24, window=130,
                   starts=[0, 100, 250, 700]),
    "window-sub-chunks": dict(nh=6, nkv=2, bt=16, W=12, chunk=8, window=40,
                              same=True, starts=[96, 104, 112, 120]),
}


@pytest.mark.parametrize("name,mode", [
    (name, mode) for name, case in PREFILL_LOOP_SWEEP.items()
    for mode in ("f32", "alibi", "int8")
    # int8 pages take the loop at 128-token pages only
    if mode != "int8" or case["bt"] % 128 == 0])
def test_prefill_page_loop_matches_gather_and_the_grid_kernel(name, mode):
    """Heads of 128 take the page loop in interpret mode as on the chip:
    against the XLA gather at f32 tolerance, and EQUAL to the grid kernel
    on the same tiles (kept for narrow heads; called here directly): the
    two fold the same live pages in the same order with the same
    arithmetic, and a page outside the walk contributed nothing."""
    from distributed_inference_demo_tpu.ops import paged_attention as pa
    case = PREFILL_LOOP_SWEEP[name]
    rng = np.random.default_rng(hash(name + mode) % 2**32)
    starts, chunk, bt, W = (case[k] for k in ("starts", "chunk", "bt", "W"))
    window = case.get("window", 0)
    b, nkv, hd, N = len(starts), case["nkv"], 128, 40
    hi = [-(-(s + chunk) // bt) for s in starts]
    lo = [max(0, s - window + 1) // bt if window else 0 for s in starts]
    if case.get("same"):
        tables = np.repeat(_walk_tables(rng, 1, W, N,
                                        [(min(lo), max(hi))]), b, axis=0)
    else:
        tables = _walk_tables(rng, b, W, N, zip(lo, hi))
    tables = jnp.asarray(tables)
    pk = jnp.asarray(rng.standard_normal((N, nkv, bt, hd)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((N, nkv, bt, hd)), jnp.float32)
    pk, pv, slopes = _apply_mode(mode, pk, pv, case["nh"])
    assert pa._page_loop_covers(pk)
    q = jnp.asarray(rng.standard_normal((b, chunk, case["nh"], hd)),
                    jnp.float32)
    start = jnp.asarray(starts, jnp.int32)
    qpos = start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    kw = {"window": window} if window else {}
    ref = paged_gather_attention(q, pk, pv, tables, qpos, slopes, **kw)
    got = paged_prefill_attention(q, pk, pv, tables, qpos, slopes,
                                  interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    q_g, slopes_g = pa._query_tiles(q, nkv, slopes)
    K, V, li = pa._stacked(pk, pv)
    tiles = dict(block_tokens=bt, chunk=chunk, groups=case["nh"] // nkv,
                 use_alibi=slopes is not None, interpret=True, **kw)
    loop, grid = (call(q_g, K, V, li.reshape(1), tables, start, slopes_g,
                       **tiles)
                  for call in (pa._paged_prefill_loop_call,
                               pa._paged_prefill_grid_call))
    np.testing.assert_array_equal(np.asarray(loop), np.asarray(grid))


@pytest.mark.parametrize("window", [0, 512])
def test_prefill_kernel_grid_has_no_table_width(window):
    """Beside the decode kernel's: the prefill ``pallas_call`` of both
    jitted names has one grid step a query tile and block of kv heads,
    whatever the table's width (the page loop runs inside the step)."""
    from distributed_inference_demo_tpu.ops import paged_attention as pa
    call = (functools.partial(pa._paged_prefill_call_window, window=window)
            if window else pa._paged_prefill_call)

    def grid(W):
        b, nkv, rows, hd, bt, N = 8, 8, 384, 128, 128, 16
        S = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(
            lambda *a: call(*a, block_tokens=bt, chunk=64, groups=6,
                            use_alibi=False, interpret=False))(
            S((b, nkv, rows, hd), jnp.bfloat16),
            S((2, N, nkv, bt, hd), jnp.bfloat16),
            S((2, N, nkv, bt, hd), jnp.bfloat16), S((1,), jnp.int32),
            S((b, W), jnp.int32), S((b,), jnp.int32),
            S((nkv, 1, rows), jnp.float32))
        calls = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        assert len(calls) == 1
        return tuple(calls[0].params["grid_mapping"].grid)

    assert grid(8) == grid(200) == (8, 1)


def test_narrow_heads_keep_the_grid_kernel():
    """The gate is the decode kernel's, a property of the pool's shape:
    Mosaic slices an HBM ref by hand only where its minor dimension fills
    the lanes, so heads under 128 and int8 pages under 128 tokens keep
    the BlockSpec pipeline (the narrow cases of ``PREFILL_SWEEP`` run
    it)."""
    from distributed_inference_demo_tpu.ops import paged_attention as pa
    from distributed_inference_demo_tpu.ops.quant import quantize_kv_pages
    pages = lambda bt, hd: jnp.zeros((2, 2, bt, hd), jnp.float32)
    assert pa._page_loop_covers(pages(8, 128))
    assert pa._page_loop_covers(pages(16, 256))
    assert not pa._page_loop_covers(pages(128, 64))
    assert pa._page_loop_covers(quantize_kv_pages(pages(128, 128), 8))
    assert not pa._page_loop_covers(quantize_kv_pages(pages(32, 128), 8))
    # as many kv heads a step as the state's bytes buy: all of laguna's
    # eight, half of bloom's thirty-two, a shard's one
    assert pa._heads_a_step(8, 384, 128) == 8
    assert pa._heads_a_step(32, 256, 128) == 16
    assert pa._heads_a_step(1, 448, 128) == 1


def test_pages_walked_on_the_host_are_the_kernel_s_loop_bounds():
    """``prefill_pages_walked`` (Python integers, for the scheduler's
    record) against ``_walk_bounds`` (what the kernel computes from the
    same starts), a segment cut into tiles: full and window kind, from
    zero, across pages, up against the table's end."""
    from distributed_inference_demo_tpu.ops import paged_attention as pa
    for chunk, tile, bt, W, window in [(256, 64, 128, 200, 0),
                                       (256, 32, 128, 200, 512),
                                       (256, 256, 128, 16, 0),
                                       (8, 8, 4, 50, 8), (24, 8, 16, 6, 0)]:
        for start in (0, 1, bt - 1, 5 * bt + 3, W * bt - chunk, W * bt):
            tiles = start + tile * np.arange(chunk // tile)
            first, end = pa._walk_bounds(tiles, tile, bt, W, window)
            assert pa.prefill_pages_walked(
                start, chunk, tile, bt, W, window) == int(
                    np.sum(np.asarray(end) - np.asarray(first)))
    # laguna's full kind at a context of 6,528 tokens: 4 tiles that end on
    # pages 52, 52, 53, 53 of 200
    assert pa.prefill_pages_walked(6528, 256, 64, 128, 200) == 210
    assert pa.prefill_pages_walked(6528, 256, 32, 128, 200, 512) == 8 * 5


def test_prefill_kernel_rejects_int4_and_unaligned_pages():
    """int4 packed pages and non-8-aligned page sizes stay on the
    gather fallback — the kernel refuses them loudly instead of
    decoding garbage nibbles."""
    from distributed_inference_demo_tpu.ops.quant import (
        quantize_kv_pages)
    rng = np.random.default_rng(7)
    pk, pv, tables, N = _random_paged(rng, 1, 2, 8, 8, 4, [8])
    q = jnp.asarray(rng.standard_normal((1, 8, 4, 8)), jnp.float32)
    qpos = jnp.arange(8, dtype=jnp.int32)[None, :]
    with pytest.raises(ValueError, match="gather"):
        paged_prefill_attention(q, quantize_kv_pages(pk, 4),
                                quantize_kv_pages(pv, 4), tables, qpos,
                                interpret=True)
    pk3, pv3, tables3, _ = _random_paged(rng, 1, 2, 8, 12, 4, [12])
    q3 = jnp.asarray(rng.standard_normal((1, 12, 4, 8)), jnp.float32)
    qpos3 = jnp.arange(12, dtype=jnp.int32)[None, :]
    with pytest.raises(ValueError, match="block_tokens"):
        paged_prefill_attention(q3, pk3, pv3, tables3, qpos3,
                                interpret=True)


def test_write_lands_in_right_page_and_offset():
    rng = np.random.default_rng(0)
    b, nkv, hd, bt, W = 3, 2, 8, 8, 4
    lens = [5, 8, 17]
    pk, pv, tables, N = _random_paged(rng, b, nkv, hd, bt, W, lens,
                                      append_room=1)
    k_new = jnp.asarray(rng.standard_normal((b, 1, nkv, hd)), jnp.float32)
    v_new = k_new * 2
    pos = jnp.asarray(lens, jnp.int32)[:, None]   # append position
    pk2, pv2 = write_paged_kv(pk, pv, k_new, v_new, tables, pos)
    tt = np.asarray(tables)
    for i, l in enumerate(lens):
        page, off = tt[i, l // bt], l % bt
        assert page < N, "append position must have an allocated page"
        np.testing.assert_array_equal(np.asarray(pk2)[page, :, off],
                                      np.asarray(k_new)[i, 0])
        np.testing.assert_array_equal(np.asarray(pv2)[page, :, off],
                                      np.asarray(v_new)[i, 0])


def test_write_through_sentinel_drops():
    """A freed slot's writes route through sentinel entries and vanish —
    no pool page may change (the paged stale-slot guarantee)."""
    rng = np.random.default_rng(1)
    pk, pv, tables, N = _random_paged(rng, 2, 2, 8, 8, 3, [8, 16])
    all_sentinel = jnp.full_like(tables, N + 7)
    k_new = jnp.ones((2, 1, 2, 8), jnp.float32)
    pk2, pv2 = write_paged_kv(pk, pv, k_new, k_new, all_sentinel,
                              jnp.asarray([[3], [9]], jnp.int32))
    np.testing.assert_array_equal(np.asarray(pk2), np.asarray(pk))
    np.testing.assert_array_equal(np.asarray(pv2), np.asarray(pv))


def test_impl_binds_tables_and_matches_manual_sequence():
    """The attn_impl seam: bind + impl inside a jit reproduces
    write-then-attend done by hand."""
    rng = np.random.default_rng(2)
    b, nkv, nh, hd, bt, W = 2, 2, 4, 8, 8, 3
    lens = [7, 12]
    pk, pv, tables, N = _random_paged(rng, b, nkv, hd, bt, W, lens)
    impl, bind = make_paged_attn_impl(bt, backend="xla")
    q = jnp.asarray(rng.standard_normal((b, 1, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, 1, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, 1, nkv, hd)), jnp.float32)
    pos = jnp.asarray(lens, jnp.int32)[:, None]

    @jax.jit
    def step(q, k, v, pk, pv, tables, pos):
        bind(tables, "step")
        li = jnp.int32(0)
        return impl(q, k, v, LayerOf(pk[None], li), LayerOf(pv[None], li),
                    pos, jnp.int32(0), None)

    out, pk2, pv2 = step(q, k, v, pk, pv, tables, pos)
    epk, epv = write_paged_kv(pk, pv, k, v, tables, pos)
    eout = paged_gather_attention(q, epk, epv, tables, pos, None)
    np.testing.assert_array_equal(np.asarray(pk2.stack[0]), np.asarray(epk))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(eout))


# ---------------------------------------------------------------------------
# the pool addressed in place: (stack, layer) against the layer's plane


from distributed_inference_demo_tpu.ops.stacked import LayerOf  # noqa: E402

LAYERS = 3
# GQA with heads of 64 (the decode path through the prefill kernel) and
# MHA with heads of 128 (the decode kernel's page loop; int8 pages take
# it at 128-token pages only)
STACK_SHAPES = {
    "gqa-hd64": dict(nh=4, nkv=2, hd=64, bt=8, W=3, lens=[5, 8, 17]),
    "mha-hd128": dict(nh=2, nkv=2, hd=128, bt=128, W=2, lens=[130, 7]),
}


def _quantized(pages, mode):
    if mode == "bf16":
        return pages.astype(jnp.bfloat16)
    from distributed_inference_demo_tpu.ops.quant import quantize_kv_pages
    return quantize_kv_pages(pages, {"int8": 8, "int4": 4}[mode])


def _stacked_case(shape, mode, alibi, seed):
    """``(stacks, tables, N, q, lens, slopes)``: ``LAYERS`` unlike
    layers of pages behind one set of tables."""
    c = STACK_SHAPES[shape]
    rng = np.random.default_rng(seed)
    b = len(c["lens"])
    layers = [_random_paged(rng, b, c["nkv"], c["hd"], c["bt"], c["W"],
                            c["lens"], append_room=1)
              for _ in range(LAYERS)]
    tables, N = layers[0][2], layers[0][3]
    K = _quantized(jnp.stack([l[0] for l in layers]), mode)
    V = _quantized(jnp.stack([l[1] for l in layers]), mode)
    q = jnp.asarray(rng.standard_normal((b, 1, c["nh"], c["hd"])),
                    jnp.float32)
    slopes = None
    if alibi:
        from distributed_inference_demo_tpu.ops.attention import (
            alibi_slopes)
        slopes = alibi_slopes(c["nh"])
    return (K, V), tables, N, q, c, slopes


def _plane(stack, layer):
    return jax.tree.map(lambda a: a[layer], stack)


def _assert_trees_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("form,shape,chunk", [
    ("scatter write", "gqa-hd64", 5), ("scatter write", "mha-hd128", 5),
    # the Pallas write moves whole sublane tiles of lane-filling heads,
    # a token or whole tiles a row: route_pool keeps every other shape on
    # the scatter, on the chip over the layer's plane
    ("kernel write", "mha-hd128", 1), ("kernel write", "mha-hd128", 16),
    ("kernel write", "mha-hd128", 5)])
def test_stacked_write_is_the_planes_write_and_touches_no_other_layer(
        form, shape, chunk, layer):
    """``write_paged_kv`` at ``(layer, page, :, off)`` of the stacked
    pool: that layer's pages are what the per-plane write gives, every
    other layer's are bit-identical, and a sentinel row and positions
    past the table drop.  Both forms of the write (the Pallas one
    interpreted; its rows here are of different tables, so any chunk is
    sound)."""
    (K, V), tables, N, _, c, _ = _stacked_case(shape, "bf16", False, 11)
    rng = np.random.default_rng(12)
    b, bt, W = len(c["lens"]), c["bt"], c["W"]
    k_new = jnp.asarray(rng.standard_normal((b, chunk, c["nkv"], c["hd"])),
                        jnp.float32)
    v_new = -k_new
    # row 0 appends in its pages, row 1 runs off the end of the table,
    # the last row is a freed slot (all sentinel)
    tables = tables.at[-1].set(N + 7)
    starts = jnp.asarray([c["lens"][0] - 3, W * bt - 2]
                         + [3] * (b - 2), jnp.int32)[:b]
    pos = starts[:, None] + jnp.arange(chunk, dtype=jnp.int32)
    kernel = shape == "mha-hd128" and chunk in (1, 16)
    assert route_pool("pallas", "cpu", K, chunk) == (
        "kernel write" if kernel else "scatter write")
    assert route_pool("auto", "tpu", LayerOf(K, 0), chunk) == (
        "kernel write" if kernel else "plane")
    assert route_pool("auto", "cpu", K, chunk) == "scatter write"
    assert route_pool("xla", "cpu", K, chunk) == "scatter write"
    assert route_pool("xla", "tpu", K, chunk) == "plane"
    li = jnp.int32(layer)
    k2, v2 = jax.jit(functools.partial(
        write_paged_kv, form=form, interpret=True))(
            LayerOf(K, li), LayerOf(V, li), k_new, v_new, tables, pos)
    assert isinstance(k2, LayerOf) and isinstance(v2, LayerOf)
    want_k, want_v = write_paged_kv(_plane(K, layer), _plane(V, layer),
                                    k_new, v_new, tables, pos)
    for got, want, before in ((k2.stack, want_k, K), (v2.stack, want_v, V)):
        for l in range(LAYERS):
            _assert_trees_equal(_plane(got, l),
                                want if l == layer else _plane(before, l))
    # something was written, and the freed slot's row and the tail past
    # the table changed nothing: only rows 0 and 1 can differ
    changed = np.asarray(k2.stack[layer] != K[layer]).any(axis=(1, 2, 3))
    tt = np.asarray(tables)
    assert changed.any() and set(np.flatnonzero(changed)) <= set(
        tt[:2].ravel().tolist())


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("shape", list(STACK_SHAPES))
@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("mode", ["bf16", "int8", "int4"])
def test_stacked_gather_is_the_planes_gather(mode, alibi, shape, layer):
    (K, V), tables, _, q, c, slopes = _stacked_case(shape, mode, alibi, 21)
    qpos = jnp.asarray([l - 1 for l in c["lens"]], jnp.int32)[:, None]
    li = jnp.int32(layer)
    got = paged_gather_attention(q, LayerOf(K, li), LayerOf(V, li), tables,
                                 qpos, slopes)
    want = paged_gather_attention(q, _plane(K, layer), _plane(V, layer),
                                  tables, qpos, slopes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("shape", list(STACK_SHAPES))
@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_stacked_decode_kernel_is_the_planes_kernel(mode, alibi, shape,
                                                    layer):
    """``paged_flash_attention`` (interpreted) reading ``(layer, page)``
    of the stacked pool, the int8 sidecar gathered for the table's
    pages: bit for bit the kernel over that layer's plane."""
    (K, V), tables, _, q, c, slopes = _stacked_case(shape, mode, alibi, 31)
    kv_lens = jnp.asarray(c["lens"], jnp.int32)
    li = jnp.int32(layer)
    got = paged_flash_attention(q, LayerOf(K, li), LayerOf(V, li), tables,
                                kv_lens, slopes, interpret=True)
    want = paged_flash_attention(q, _plane(K, layer), _plane(V, layer),
                                 tables, kv_lens, slopes, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ref = paged_gather_attention(q, LayerOf(K, li), LayerOf(V, li), tables,
                                 (kv_lens - 1)[:, None], slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("shape", list(STACK_SHAPES))
@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_stacked_prefill_kernel_is_the_planes_kernel(mode, alibi, shape,
                                                     layer):
    (K, V), tables, _, _, c, slopes = _stacked_case(shape, mode, alibi, 41)
    rng = np.random.default_rng(42)
    b, chunk = len(c["lens"]), 4
    q = jnp.asarray(rng.standard_normal((b, chunk, c["nh"], c["hd"])),
                    jnp.float32)
    qpos = (jnp.asarray(c["lens"], jnp.int32)[:, None] - chunk
            + jnp.arange(chunk, dtype=jnp.int32))
    li = jnp.int32(layer)
    got = paged_prefill_attention(q, LayerOf(K, li), LayerOf(V, li), tables,
                                  qpos, slopes, interpret=True)
    want = paged_prefill_attention(q, _plane(K, layer), _plane(V, layer),
                                   tables, qpos, slopes, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hook_made_for_a_pool_takes_the_stack_and_says_so():
    """The paged hook asks the layer scan for the stacks
    (``stacked_cache``), hands them back as ``LayerOf``, and the record
    says how the pool reached each call."""
    from distributed_inference_demo_tpu.ops.paged_attention import (
        AttnPathRecord)
    (K, V), tables, _, q, c, _ = _stacked_case("gqa-hd64", "bf16", False, 51)
    record = AttnPathRecord()
    impl, bind = make_paged_attn_impl(c["bt"], backend="xla", record=record)
    assert impl.stacked_cache
    k = jnp.ones((len(c["lens"]), 1, c["nkv"], c["hd"]), jnp.float32)
    pos = jnp.asarray(c["lens"], jnp.int32)[:, None]
    bind(tables, "stack")
    _, k2, _ = impl(q, k, k, LayerOf(K, jnp.int32(1)), LayerOf(V, jnp.int32(1)),
                    pos, jnp.int32(0), None)
    assert isinstance(k2, LayerOf)
    k3, _ = write_paged_kv(_plane(K, 1), _plane(V, 1), k, k, tables, pos)
    _assert_trees_equal(k2.stack[1], k3)
    # a plane is not a pool: the hook takes the stack alone
    with pytest.raises(AssertionError, match="stacked"):
        impl(q, k, k, _plane(K, 1), _plane(V, 1), pos, jnp.int32(0), None)
    assert record.addressing() == {"stack": {"chunk=1": "scatter write"}}
    assert set(record.snapshot()) == {"stack"}


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("chunk", [1, 5])
@pytest.mark.parametrize("shape,mode", [
    ("gqa-hd64", "bf16"), ("mha-hd128", "int8"), ("gqa-hd64", "int4")])
def test_hook_through_the_layers_plane_is_the_hook_in_place(shape, mode,
                                                            chunk, layer):
    """What the Pallas write does not cover reaches the pool, on the
    chip, through the layer's plane (``route_pool``: narrow heads, int8
    and int4 pages): the hook slices the plane out, writes and attends on
    it, and puts it back.  Same output and same pool, leaf for leaf and
    bit for bit, as the hook addressing the stack in place, and every
    other layer untouched.  (The platform is what the route reads; the
    XLA paths run anywhere.)"""
    from unittest import mock
    from distributed_inference_demo_tpu.ops.paged_attention import (
        AttnPathRecord)
    (K, V), tables, N, _, c, slopes = _stacked_case(shape, mode, True, 71)
    rng = np.random.default_rng(72)
    b = len(c["lens"])
    q = jnp.asarray(rng.standard_normal((b, chunk, c["nh"], c["hd"])),
                    jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, chunk, c["nkv"], c["hd"])),
                    jnp.float32)
    tables = tables.at[-1].set(N + 7)            # a freed slot
    pos = (jnp.asarray(c["lens"], jnp.int32)[:, None] - chunk + 1
           + jnp.arange(chunk, dtype=jnp.int32))
    li = jnp.int32(layer)

    def run(platform):
        record = AttnPathRecord()
        impl, bind = make_paged_attn_impl(c["bt"], backend="xla",
                                          record=record)
        bind(tables, "layer")
        with mock.patch.object(jax, "default_backend", lambda: platform):
            out, k2, v2 = impl(q, k, -k, LayerOf(K, li), LayerOf(V, li),
                               pos, jnp.int32(0), slopes)
        return out, k2, v2, record.addressing()["layer"][f"chunk={chunk}"]

    out, k2, v2, how = run("tpu")
    want, wk, wv, in_place = run("cpu")
    assert (how, in_place) == ("plane", "scatter write")
    assert isinstance(k2, LayerOf) and isinstance(v2, LayerOf)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    _assert_trees_equal(k2.stack, wk.stack)
    _assert_trees_equal(v2.stack, wv.stack)
    for l in range(LAYERS):
        if l != layer:
            _assert_trees_equal(_plane(k2.stack, l), _plane(K, l))
    assert any(np.asarray(g != w).any() for g, w in zip(
        jax.tree.leaves(_plane(k2.stack, layer)),
        jax.tree.leaves(_plane(K, layer))))


@pytest.mark.parametrize("chunk,write", [
    (16, "kernel write"), (32, "kernel write"),
    (24, "scatter write"), (8, "scatter write"), (40, "scatter write")])
def test_rows_of_one_table_in_one_call_write_what_the_scatter_writes(
        chunk, write):
    """The mixed slab packs sequential chunks of ONE admission as rows of
    one call: the same table, each row starting where the last ended, the
    first on a page boundary.  The Pallas write reads a batch of tile
    groups before it writes any back, so two rows must not share a group:
    at a chunk of whole groups (16 tokens of bf16) none does and the
    kernel writes bit for bit what the scatter writes; at any other chunk
    the last group of one row is the first of the next, and the hook
    keeps the scatter (``route_pool``; on the chip, over the layer's
    plane).  Through the hook, as a program runs it, the kernel
    interpreted."""
    from distributed_inference_demo_tpu.ops.paged_attention import (
        AttnPathRecord)
    c = STACK_SHAPES["mha-hd128"]
    rng = np.random.default_rng(61)
    rows, N, bt = 3, 5, c["bt"]
    K = jnp.asarray(rng.standard_normal((LAYERS, N, c["nkv"], bt, c["hd"])),
                    jnp.bfloat16)
    V = -K
    table = jnp.asarray([3, 1, 4], jnp.int32)
    tables = jnp.broadcast_to(table, (rows, 3))
    # the admission starts on its second page (a prefix hit of one page)
    pos = (bt + chunk * jnp.arange(rows, dtype=jnp.int32)[:, None]
           + jnp.arange(chunk, dtype=jnp.int32))
    k = jnp.asarray(rng.standard_normal((rows, chunk, c["nkv"], c["hd"])),
                    jnp.float32)
    q = jnp.zeros((rows, chunk, c["nh"], c["hd"]), jnp.float32)
    record = AttnPathRecord()
    impl, bind = make_paged_attn_impl(bt, backend="pallas", interpret=True,
                                      record=record)
    li = jnp.int32(1)

    @jax.jit
    def slab(K, V):
        bind(tables, "slab")
        _, k2, v2 = impl(q, k, -k, LayerOf(K, li), LayerOf(V, li), pos,
                         jnp.int32(0), None)
        return k2.stack, v2.stack

    got_k, got_v = slab(K, V)
    assert record.addressing() == {"slab": {f"chunk={chunk}": write}}
    want_k, want_v = write_paged_kv(LayerOf(K, li), LayerOf(V, li), k, -k,
                                    tables, pos)
    _assert_trees_equal(got_k, want_k.stack)
    _assert_trees_equal(got_v, want_v.stack)
    # every token of every row is in its place: none was restored to what
    # the page held before a neighbour's write-back
    flat = np.asarray(pos).ravel()
    np.testing.assert_array_equal(
        np.asarray(got_k[1].astype(jnp.float32))[
            np.asarray(table)[flat // bt], :, flat % bt],
        np.asarray(k.astype(jnp.bfloat16).astype(jnp.float32)).reshape(
            rows * chunk, c["nkv"], c["hd"]))
