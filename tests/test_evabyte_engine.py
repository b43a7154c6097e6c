"""The engine under mixed dispatch with a summarised cache (family
``evabyte``, PR 53): what a request leases, what the dispatch records and
``/stats.kvcache.eva`` count, and greedy tokens against the dense forward
through windows that close in the slab, in the middle of a fused decode
block and across rows that finish and are replaced.  CPU, toy widths
(``evabyte-test``: window 16, chunk 2, pages of 8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import (KVCache, StageSpec,
                                                        eva_rows)
from distributed_inference_demo_tpu.models.decoder import (init_full_params,
                                                           stage_forward)
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops import eva_attention as eva
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from test_mixed_batching import settle

CFG = get_model_config("evabyte-test")
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
W, C, BT = CFG.eva_window, CFG.eva_chunk, 8
GREEDY = SamplingParams(temperature=0.0)


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _engine(params, **kw):
    kw.setdefault("max_seq", 128)
    kw.setdefault("max_batch", 3)
    kw.setdefault("kv_block_tokens", BT)
    kw.setdefault("kv_cache_blocks", 40)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_block", 4)
    kw.setdefault("mixed_token_budget", 24)
    return ContinuousBatchingEngine(CFG, params, sampling=GREEDY, **kw)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


@jax.jit
def _dense_forward(params, ids, cache, start, last):
    pos = start + jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    cache = KVCache(cache.keys, cache.values, start)
    logits, cache = stage_forward(params, CFG, SPEC, ids, cache, pos,
                                  logits_at=last)
    return jnp.argmax(logits[0, 0]), cache


def _dense_greedy(params, prompt, new):
    """Greedy tokens over a DENSE cache that keeps every token: no page,
    no table, the summaries pooled from the cache at every call.  The
    prompt is padded to one length (what the pad writes lies behind the
    length and is written again before any query sees it), so the two
    programs compile once for the whole file."""
    n = len(prompt)
    ids = jnp.asarray([list(prompt) + [0] * (64 - n)], jnp.int32)
    cache = KVCache.create(CFG, CFG.num_layers, 1, 128)
    tok, cache = _dense_forward(params, ids, cache, jnp.int32(0),
                                jnp.int32(n - 1))
    out = [int(tok)]
    for t in range(n, n + new - 1):
        tok, cache = _dense_forward(params, jnp.asarray([[out[-1]]]), cache,
                                    jnp.int32(t), jnp.int32(0))
        out.append(int(tok))
    return out


def _records(eng):
    settle(eng)     # a request's wait returns before its last commit
    dt = eng.stats()["dispatch_trace"]
    return [dict(zip(dt["fields"], row)) for row in dt["recent"]]


# ---------------------------------------------------------------- the leases

@pytest.mark.parametrize("n, pages", [
    (1, 1 + 1), (8, 1 + 1), (9, 2 + 1), (16, 2 + 1), (17, 2 + 2),
    (33, 2 + 3), (100, 2 + 7), (128, 2 + 8)])
def test_pages_a_request_leases(params, n, pages):
    """``min(W / bt, ceil(n / bt)) + ceil(n / W)``: one window's pages at
    most and a summary page a window, the pending one included."""
    with _engine(params) as eng:
        assert sum(eng._pages_needed(n)) == pages
        assert eng._table_width == 8 + 2         # 128 / 16 summary + 2


@pytest.mark.parametrize("plen, new", [(37, 12), (16, 1), (5, 30)])
def test_a_request_holds_its_leases_and_leaves_nothing(params, plen, new):
    with _engine(params) as eng:
        req = eng.submit(_prompt(plen, 3), new)
        held = []
        while not req.done.is_set():
            held.append(eng.kv_cache.used_blocks)
            req.done.wait(0.002)
        req.wait(60)
        want = sum(eng._pages_needed(plen + new))
        assert max(held, default=want) in (0, want)
        assert eng.kv_cache.used_blocks == 0     # nothing leaked
        assert eng.kv_cache.tree.block_count == 0   # and nothing shared
        table = req._pkv["table"]
        n_sum = -(-(plen + new) // W)
        live = table < eng._page_sentinel
        assert live[:n_sum].all() and not live[n_sum:8].any()
        assert live[8:].sum() == min(2, -(-(plen + new) // BT))


def test_a_request_the_pool_cannot_hold_is_refused_at_submit(params):
    with _engine(params, kv_cache_blocks=6) as eng:
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(_prompt(60, 1), 30)       # 2 + 6 pages
        assert eng.submit(_prompt(40, 1), 8).wait(60).shape == (8,)


# --------------------------------------------------- tokens, through closes

@pytest.mark.parametrize("plens, new, kw", [
    # windows close in the slab, at a decode block's 2nd and 3rd step
    ((37, 50, 9, 61), 30, {}),
    # a chunk is a window: every segment is its request's only one
    ((44, 17, 16), 20, dict(prefill_chunk=16, mixed_token_budget=48)),
    # more requests than slots: rows finish and are replaced mid-window
    ((21, 35, 10, 27, 40, 13, 30), 11, dict(max_batch=2)),
])
def test_greedy_tokens_are_the_dense_forward_s(params, plens, new, kw):
    prompts = [_prompt(n, 10 + i) for i, n in enumerate(plens)]
    with _engine(params, **kw) as eng:
        reqs = [eng.submit(p, new) for p in prompts]
        outs = [r.wait(120).tolist() for r in reqs]
        st = eng.stats()["kvcache"]
    for p, out in zip(prompts, outs):
        assert out == _dense_greedy(params, p, new), len(p)
    assert st["blocks_used"] == 0
    # every request's cached tokens, by window and by chunk
    cached = [n + new - 1 for n in plens]
    assert st["eva"]["windows_closed"] == sum(n // W for n in cached)
    assert st["eva"]["summaries_written"] == sum(n // C for n in cached)


def test_a_window_closes_inside_a_fused_block(params):
    """A prompt of 13 tokens: token #1 comes from the slab, then the first
    decode block writes tokens 13, 14, 15 and 16, so the window closes
    after its third step and the fourth attends the summaries."""
    prompt = _prompt(13, 7)
    with _engine(params, max_batch=1) as eng:
        out = eng.submit(prompt, 9).wait(60).tolist()
        recs = _records(eng)
    assert out == _dense_greedy(params, prompt, 9)
    block = [r for r in recs if r["steps"] == 4][0]
    assert block["windows_closed"] == 1
    # at its launch the row attends 14 exact rows and no summary
    assert (block["kv_attended_rows"], block["kv_summary_rows"]) == (14, 0)
    nxt = recs[recs.index(block) + 1]
    assert (nxt["kv_attended_rows"], nxt["kv_summary_rows"]) == (8 + 2, 8)


# ------------------------------------------------------------- the counters

def test_the_dispatch_records_count_rows_not_tokens(params):
    plens, new = (40, 23), 14
    with _engine(params) as eng:
        for i, n in enumerate(plens):
            eng.submit(_prompt(n, 20 + i), new).wait(60)
        recs = _records(eng)
        st = eng.stats()
    assert st["dispatch_trace"]["fields"][-5:] == [
        "kv_attended_rows", "kv_summary_rows", "prefill_attended_rows",
        "windows_closed", "early"]
    # a slab's (query, row) pairs: each token its window's earlier keys,
    # itself and every closed window's summaries
    want = sum(sum(eva_rows(W, C, p + 1)) for n in plens for p in range(n))
    assert sum(r["prefill_attended_rows"] for r in recs) == want
    for r in recs:
        assert 0 <= r["kv_summary_rows"] <= r["kv_attended_rows"]
        assert r["kv_attended_rows"] <= r["kv_tokens"] + r["finals"]
        assert r["kv_summary_rows"] % (W // C) == 0
    assert sum(r["windows_closed"] for r in recs) == \
        st["kvcache"]["eva"]["windows_closed"] == sum(
            (n + new - 1) // W for n in plens)
    eva_st = st["kvcache"]["eva"]
    assert 0 < eva_st["rows_held_peak"] < eva_st["tokens_held_peak"]
    # fullest at 48 tokens, just before the third window closes
    assert eva_st["rows_held_peak"] == sum(eva_rows(W, C, 48)) == 32
    assert (eva_st["window"], eva_st["chunk"], eva_st["window_pages"]) == (
        W, C, 2)


def test_a_request_s_segments_of_one_dispatch_lie_in_one_window(params):
    """A budget of three chunks of 8 over a window of 16: the second
    segment of a dispatch may not start a new window, whose pages the
    first segment's queries still read."""
    with _engine(params, max_batch=1) as eng:
        eng.submit(_prompt(60, 4), 2).wait(60)
        recs = [r for r in _records(eng) if r["segments"]]
    # 60 tokens = 7 full chunks and a final: windows start at 0, 16, 32, 48
    assert [r["segments"] for r in recs] == [2, 2, 2, 2]
    assert [r["windows_closed"] for r in recs] == [1, 1, 1, 0]


# ------------------------------------------------------- rows that idle

def test_an_idle_row_writes_no_summary_and_no_key():
    """The hook over a pool of ones: a live row whose step completes a
    chunk writes that chunk's summary into its pending page; a row whose
    table is sentinel (a freed slot, a blank segment) writes nowhere, and
    neither does a live row in the middle of a chunk."""
    from distributed_inference_demo_tpu.ops.paged_attention import (
        make_paged_attn_impl)
    from distributed_inference_demo_tpu.ops.stacked import LayerOf
    nkv, hd, N = 2, 8, 12
    impl, bind = make_paged_attn_impl(BT)
    r = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    hook_vectors = f(nkv, hd), f(nkv, hd)
    hook = impl.summarised(W, C, *hook_vectors)
    pool = jnp.ones((1, N, nkv, BT, hd), jnp.float32)
    raw = np.full((3, 4 + 2), N, np.int32)
    raw[0] = [0, 1, 2, 3, 4, 5]          # row 0 at t = 21: completes a chunk
    raw[2] = [6, 7, 8, 9, 10, 11]        # row 2 at t = 20: mid-chunk
    bind(jnp.asarray(raw), "t")
    pos = jnp.asarray([[21], [21], [20]], jnp.int32)
    _, K, V = hook(f(3, 1, nkv, hd), f(3, 1, nkv, hd), f(3, 1, nkv, hd),
                   LayerOf(pool, jnp.int32(0)), LayerOf(pool, jnp.int32(0)),
                   pos, None, None)
    touched = np.argwhere(np.abs(np.asarray(K.stack)[0] - 1).max((1, 3)) > 0)
    # row 0: its key at row 5 of window page P_0 (= page 4) and the summary
    # of chunk (21 % 16) // 2 = 2 of the pending page S_1 (= page 1);
    # row 2: its key at row 4 of its P_0 (= page 10) and nothing else
    assert touched.tolist() == [[1, 2], [4, 5], [10, 4]]
    assert np.abs(np.asarray(V.stack)[0] - 1).max((1, 3)).nonzero()[0].tolist() \
        == [1, 4, 10]
    # the summary row holds the pooled keys of rows 4 and 5 of page 4
    mu, phi = hook_vectors
    rows = jnp.asarray(np.asarray(K.stack)[0, 4, :, 4:6]).transpose(1, 0, 2)
    want, _ = eva.eva_pool(rows[None], rows[None], mu, phi)
    np.testing.assert_allclose(np.asarray(K.stack)[0, 1, :, 2],
                               np.asarray(want)[0], rtol=1e-5, atol=1e-6)
