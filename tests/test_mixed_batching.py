"""Mixed prefill+decode token-budget dispatch (docs/DESIGN.md §19).

The ISSUE-15 acceptance, pinned:

- EXACTNESS: greedy and sampled streams out of the mixed dispatch are
  bit-identical to the serialized interleave (same chunk boundaries,
  same rng split order) — mixed packing is a throughput change, never
  a semantics change;
- decode fusion SURVIVES admission: with prefill chunks in flight the
  measured dispatches/step ratio stays ≈ 1/K (the pre-§19 fuse
  suppression during admission is gone);
- the paged prefill path writes prompt K/V straight into the page
  pool: ``h2d_bytes`` stays 0 across cold admission (the dense
  temp-row gather→prefill→scatter round trip is deleted);
- a dispatch failure with packed admissions fails THOSE requests and
  leaves the engine serving, with zero leaked pages
  (``used == tree.block_count``);
- the mixed stats fragment (dispatches / prefill_tokens /
  budget_utilization) and ``pending_prefill_tokens`` surface through
  ``stats()``.

Runs on CPU through the XLA-gather fallback — the same control flow
the TPU prefill kernel's auto-dispatch falls back to.
"""

import contextlib
import dataclasses
import sys
import time
from functools import partial
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.telemetry.profiling import (
    DispatchProfiler)

CFG = get_model_config("llama-test")
DRAFT_CFG = dataclasses.replace(CFG, num_layers=2)
GREEDY = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def draft_params():
    # different seed AND depth: a genuinely different (bad) proposer
    return init_full_params(jax.random.PRNGKey(1), DRAFT_CFG)


@pytest.fixture(scope="module")
def oracle(params):
    return InferenceEngine(CFG, params, max_seq=96, sampling=GREEDY)


def expected(oracle, prompt, n):
    return oracle.generate(np.asarray(prompt)[None, :], n).tokens[0]


def mixed_engine(params, **kw):
    kw.setdefault("max_seq", 96)
    kw.setdefault("max_batch", 4)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("prompt_buckets", (16, 48))
    kw.setdefault("kv_block_tokens", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_block", 4)
    kw.setdefault("mixed_token_budget", 24)
    return ContinuousBatchingEngine(CFG, params, **kw)


def assert_no_leak(eng):
    mgr = eng.kv_cache
    assert mgr.used_blocks == mgr.tree.block_count, (
        mgr.used_blocks, mgr.tree.block_count)
    assert mgr.debug_state()["leased_nodes"] == 0


def assert_both_kinds_ran(eng):
    """The parity above held over both variants of ``mixed_step``: the
    run launched dispatches that packed a segment (slab + decode loop)
    and dispatches that packed none (the decode loop alone)."""
    dt = eng.stats()["dispatch_trace"]
    assert dt["decode_only"] > 0 and dt["prefill"] > 0, dt
    assert dt["decode_only"] + dt["prefill"] == dt["seq"]


@pytest.mark.quick
def test_mixed_cold_parity_stats_and_zero_h2d(params, oracle):
    """Concurrent cold requests through the mixed loop: greedy tokens
    bit-identical to the one-shot oracle, every prompt token prefilled
    INSIDE mixed dispatches, zero bytes gathered through the host, no
    page leaked."""
    prompts = [[3, 14, 15], list(range(2, 24)), [9, 2, 6, 5, 3, 5],
               list(range(40, 75))]
    ns = [10, 12, 8, 9]
    with mixed_engine(params) as eng:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, ns)]
        for p, n, r in zip(prompts, ns, reqs):
            np.testing.assert_array_equal(r.wait(timeout=300),
                                          expected(oracle, p, n))
        st = eng.stats()
        assert st["mixed"]["token_budget"] == 24
        assert st["mixed"]["dispatches"] > 0
        # cold + disjoint prompts: every prompt token went through a
        # packed prefill segment
        assert (st["mixed"]["prefill_tokens"]
                == sum(len(p) for p in prompts))
        u = st["mixed"]["budget_utilization"]
        # the stall-free floor (>= 1 segment per dispatch) may nudge a
        # packed step past the budget; utilization stays near (0, 1]
        assert u is not None and 0.0 < u <= 1.5
        assert st["pending_prefill_tokens"] == 0
        assert eng.kv_cache.snapshot()["h2d_bytes"] == 0
        assert_no_leak(eng)
        assert_both_kinds_ran(eng)


def abstract_mixed_call(eng, slab: bool, segments=None):
    """Abstract arguments of ``mixed_step``: of a dispatch that packed
    prefill segments (``slab``: the budget's ``n_seg`` of them, or
    ``segments``) or of one that packed none."""
    S, i32, u32 = jax.ShapeDtypeStruct, np.int32, np.uint32
    B, W = eng.max_batch, eng._table_width
    r = eng._mixed_seg_cap if segments is None else segments
    seg = tuple(S(x.shape, x.dtype)
                for x in eng._slab_of(eng._blank_segments(), r))
    return (eng.params, eng._pk, eng._pv, seg if slab else None,
            S((B, W), i32), S((B,), i32), S((B,), i32), S((B,), np.bool_),
            S((2,), u32), S((), i32), S((B,), i32), eng.decode_block)


@pytest.mark.quick
@pytest.mark.parametrize("model", ["llama-test", "olmoe-test"])
def test_a_dispatch_that_packed_nothing_runs_no_slab(model):
    """PR 33: the program of a decode-only dispatch, as lowered, holds
    nothing under the scopes ``slab_body`` / ``slab_finals``, traces no
    prefill attention (only ``chunk=1`` reaches the paged hook), has as
    many matmuls as ``paged_multi_step`` (the fused decode loop alone),
    and returns what the variant with a slab returns, shape for shape.
    Traced FIRST here: the slab variant then adds its chunk shape under
    the same program name and nothing else."""
    cfg = get_model_config(model)
    # an engine that launched nothing before it was ready (as it stands
    # it has traced every variant by then): what ONE trace reaches
    with mock.patch.object(ContinuousBatchingEngine, "_warm_mixed_variants",
                           lambda self: None), ContinuousBatchingEngine(
            cfg, init_full_params(jax.random.PRNGKey(0), cfg), max_seq=96,
            max_batch=4, sampling=GREEDY, kv_block_tokens=8,
            prefill_chunk=8, decode_block=4, mixed_token_budget=24) as eng:
        step = eng._mixed_step.inner
        call = abstract_mixed_call(eng, slab=False)
        alone = step.lower(*call)
        paths = eng.attn_paths.snapshot()
        assert list(paths) == ["mixed_step"]
        assert list(paths["mixed_step"]) == ["chunk=1"]
        # scopes as the ops' name stack has them, `jit(mixed_step)/<scope>`
        # (a bare function name may also be a frame of some cached
        # sub-program's first trace, which says nothing)
        text = alone.as_text(debug_info=True)
        assert "/decode_loop" in text
        assert "/slab_body" not in text and "/slab_finals" not in text
        multi = eng._paged_multi_step.inner.lower(
            *call[:3], *call[4:]).as_text()
        matmuls = alone.as_text().count("dot_general")
        assert matmuls == multi.count("dot_general") > 0

        packed = step.lower(*abstract_mixed_call(eng, slab=True))
        slab_text = packed.as_text(debug_info=True)
        assert "/slab_body" in slab_text and "/slab_finals" in slab_text
        assert packed.as_text().count("dot_general") > matmuls
        shapes = [jax.tree.map(lambda x: (x.shape, x.dtype), p.out_info)
                  for p in (alone, packed)]
        assert shapes[0] == shapes[1]
        # with experts: one more output, the [E + 3] routing counters
        assert len(shapes[0]) == (10 if cfg.num_experts else 9)
        assert eng.attn_paths.snapshot() == {
            "mixed_step": {"chunk=1": paths["mixed_step"]["chunk=1"],
                           "chunk=8": paths["mixed_step"]["chunk=1"]},
            "paged_multi_step": {"chunk=1": paths["mixed_step"]["chunk=1"]}}


def _hand_packed(eng, r: int):
    """The slab of a dispatch that packed ``r`` segments, by hand, over
    pages nobody else holds: the first ``r - 1`` chunks of a prompt of
    sixteen tokens (pages 8, 9) and the six-token final of another
    (page 4), which installs at slot 1 with five tokens left."""
    C, sent = eng.prefill_chunk, eng._page_sentinel
    seg = [x.copy() for x in eng._slab_of(eng._blank_segments(), r)]
    ids, tables, starts, lens, slot, plen, keys, *ntok = seg
    long_p = np.arange(30, 46)
    for i in range(r - 1):
        ids[i] = long_p[i * C:(i + 1) * C]
        tables[i, :2] = (8, 9)
        starts[i] = i * C
        if ntok:
            ntok[0][i] = C
    ids[r - 1, :6] = (9, 8, 7, 6, 5, 4)
    tables[r - 1, 0] = 4
    lens[r - 1], slot[r - 1], plen[r - 1] = 6, 1, 6
    keys[r - 1] = (3, 4)
    if ntok:
        ntok[0][r - 1] = 6
    assert (tables == sent).sum() == tables.size - 2 * (r - 1) - 1
    return tuple(seg)


@pytest.mark.parametrize("r", [1, 2, 3])
@pytest.mark.parametrize("model", ["llama-test", "olmoe-test"])
def test_a_slab_of_the_packed_segments_is_the_full_slab_s_dispatch(model, r):
    """The slab is as many segments as were packed: the same dispatch
    (``r`` segments, a row decoding beside them) through the
    ``r``-segment program and through the budget's full ``n_seg`` slab,
    its other rows blank as an unused row always was, gives the same
    tokens (the final's token #1 and every decoded one), installs the
    same slot, and leaves the same pool and, with experts, the same
    routing counters: a blank row enters no expert's group.  Log-
    probabilities and pages agree to float32 rounding: two shapes of one
    matmul are two XLA programs, and on the CPU they differ by 1-2 ulp
    in a few places (as the serialized and the mixed schedule do,
    above); through one shape they are bit-identical."""
    cfg = get_model_config(model)
    with ContinuousBatchingEngine(
            cfg, init_full_params(jax.random.PRNGKey(0), cfg), max_seq=96,
            max_batch=4, sampling=GREEDY, kv_block_tokens=8,
            prefill_chunk=8, decode_block=4, mixed_token_budget=24) as eng:
        n_seg, B = eng._mixed_seg_cap, eng.max_batch
        assert n_seg == 3
        step, sent = eng._mixed_step.inner, eng._page_sentinel
        copy = lambda t: jax.tree.map(jnp.copy, t)       # noqa: E731
        key, eos = jax.random.PRNGKey(1), jnp.int32(-1)

        def call(pool, seg, tables, lengths, last, active, budget):
            out = step(eng.params, *copy(pool), seg, jnp.asarray(tables),
                       lengths, last, jnp.asarray(active), key, eos,
                       jnp.asarray(budget, jnp.int32), eng.decode_block)
            return out[:2], out[2:]

        # a row that decodes: five tokens installed at slot 0 (page 0)
        first = [x.copy() for x in eng._slab_of(eng._blank_segments(), 1)]
        first[0][0, :5] = (5, 4, 3, 2, 1)
        first[1][0, 0] = 0
        first[3][0], first[4][0], first[5][0] = 5, 0, 5
        if len(first) == 8:
            first[7][0] = 5
        tables = np.full((B, eng._table_width), sent, np.int32)
        tables[0, :2] = (0, 1)
        pool, (lengths, last, *_) = call(
            (eng._pk, eng._pv), tuple(first), tables,
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            [False] * B, [20, 0, 0, 0])
        assert int(lengths[0]) == 9

        tables[1, :2] = (4, 5)       # the final's row, live before it runs
        seg = _hand_packed(eng, r)
        blank = eng._slab_of(eng._blank_segments(), n_seg - r)
        full = tuple(np.concatenate([a, b]) for a, b in zip(seg, blank))
        assert full[0].shape[0] == n_seg
        args = (tables, lengths, last, [True, False, False, False],
                [16, 5, 0, 0])
        (pk, pv), cut = call(pool, seg, *args)
        (pk_f, pv_f), whole = call(pool, full, *args)
    names = ("lengths", "last_tok", "final_toks", "final_lps", "toks",
             "lps", "steps", "moe_acc")
    cut, whole = dict(zip(names, cut)), dict(zip(names, whole))
    assert ("moe_acc" in cut) == (cfg.num_experts > 0)
    # (the row that decodes takes its first step in the slab's pass; the
    # final, installed behind it, the loop's three)
    assert int(cut["steps"]) == 4 and list(cut["lengths"][:2]) == [13, 9]
    for name in cut:
        a, b = np.asarray(cut[name]), np.asarray(whole[name])
        if name in ("final_toks", "final_lps"):      # a row a segment
            assert a.shape == (r,) and b.shape == (n_seg,)
            a, b = a[r - 1], b[r - 1]
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=0,
                                       err_msg=name)
        else:
            assert (a == b).all(), name
    for a, b in zip(jax.tree.leaves((pk, pv)), jax.tree.leaves((pk_f, pv_f))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    if cfg.num_experts:
        # the counters are the rows that hold a token, padding or none
        k, L, E = cfg.experts_per_token, cfg.num_layers, cfg.num_experts
        tokens = (r - 1) * 8 + 6 + 4 + 3     # slab, then two rows' steps
        assert int(np.asarray(cut["moe_acc"])[:E].sum()) == tokens * k * L


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("r", [1, 3])
@pytest.mark.parametrize("model", ["llama-test", "olmoe-test"])
def test_a_final_s_token_is_the_serialized_admission_s(model, r, sampled):
    """PR 48: the slab's head runs on ONE position a segment.  A slab of
    one segment and of the budget's ``n_seg``, the last a final that
    holds six tokens of its chunk's eight (``seg_lens < C``), the others
    chunks of another prompt that is not done (``seg_slot = B``): the
    final's token #1 and its log-probability are those the serialized
    admission gives (``paged_prefill`` over a bucket of sixteen, the same
    table, the same batch-1 key), greedy and sampled; a chunk installs
    nothing; and its pages hold what ``paged_chunk_mid`` writes there."""
    cfg = get_model_config(model)
    samp = (SamplingParams(greedy=False, temperature=0.9, top_k=40)
            if sampled else GREEDY)
    with ContinuousBatchingEngine(
            cfg, init_full_params(jax.random.PRNGKey(0), cfg), max_seq=96,
            max_batch=4, sampling=samp, prompt_buckets=(16, 48),
            kv_block_tokens=8, prefill_chunk=8, decode_block=4,
            mixed_token_budget=24) as eng:
        B, sent = eng.max_batch, eng._page_sentinel
        copy = lambda t: jax.tree.map(jnp.copy, t)       # noqa: E731
        seg = _hand_packed(eng, r)
        tables = np.full((B, eng._table_width), sent, np.int32)
        tables[1, :2] = (4, 5)       # the final's row, live before it runs
        zeros = jnp.zeros((B,), jnp.int32)
        out = eng._mixed_step.inner(
            eng.params, *copy((eng._pk, eng._pv)), seg,
            jnp.asarray(tables), zeros, zeros, jnp.zeros((B,), bool),
            jax.random.PRNGKey(1), jnp.int32(-1),
            jnp.asarray([0, 5, 0, 0], jnp.int32), eng.decode_block)
        pool, lengths, last = out[:2], out[2], out[3]
        final_toks, final_lps = np.asarray(out[4]), np.asarray(out[5])
        assert final_toks.shape == final_lps.shape == (r,)
        # the serialized admission of the same six tokens
        ids = np.zeros((1, 16), np.int32)
        ids[0, :6] = seg[0][r - 1, :6]
        pk, pv, tok, lp = eng._paged_prefill.inner(
            eng.params, *copy((eng._pk, eng._pv)), jnp.asarray(ids),
            jnp.asarray(tables[1][None]), jnp.int32(0), jnp.int32(6),
            jnp.asarray(seg[6][r - 1]))
        if r > 1:       # ... and of the other prompt's first chunks
            long_t = np.full((1, eng._table_width), sent, np.int32)
            long_t[0, :2] = (8, 9)
            for i in range(r - 1):
                pk, pv = eng._paged_chunk_mid.inner(
                    eng.params, pk, pv, jnp.asarray(seg[0][i][None]),
                    jnp.asarray(long_t), jnp.int32(8 * i))
    assert final_toks[r - 1] == int(tok)
    np.testing.assert_allclose(final_lps[r - 1], float(lp), rtol=1e-5)
    # slot 1 took the final (six tokens, then four steps); a chunk's
    # sample went nowhere
    assert list(np.asarray(lengths)) == [0, 10, 0, 0]
    assert np.asarray(last)[[0, 2, 3]].tolist() == [0, 0, 0]
    # the prompts' pages: the six tokens' rows of page 4, the chunks'
    for got, want in zip(jax.tree.leaves(pool), jax.tree.leaves((pk, pv))):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got[:, 4, :, :6], want[:, 4, :, :6],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got[:, 8:8 + r - 1], want[:, 8:8 + r - 1],
                                   rtol=1e-5, atol=1e-6)
        assert r == 1 or np.abs(want[:, 8:8 + r - 1]).sum() > 0


# ------------------------------------------------------------------
# PR 61: a slab's pass over the weights carries the decoding rows' first
# step.  The order before it (the slab's forward alone, then every step
# in the loop) is kept here, composed from the engine's own pieces, as
# the reference the merged pass is held to (by hand below; over whole runs
# of every family in ``tests/test_mixed_order.py``).

def old_order_mixed_step(eng):
    """``mixed_step`` as it was before PR 61, over ``eng``'s forward seam:
    ``slab_body``, the finals, then ``_fused_loop`` over all
    ``num_steps``.  Not donated: the caller keeps its pool."""
    p, cfg, n_seg = eng._mixed_parts, eng.cfg, eng._mixed_seg_cap
    moe_, state_ = cfg.num_experts > 0, cfg.state_planes > 0
    from distributed_inference_demo_tpu.models.base import KVCache

    @partial(jax.jit, static_argnums=(11,))
    def mixed_step(params, pk, pv, seg, dec_tables, lengths, last_tok,
                   active, dec_rng, eos, budget, num_steps):
        B_ = last_tok.shape[0]
        cache = KVCache(pk, pv, jnp.zeros((), jnp.int32))
        moe_acc = p.moe_acc0() if moe_ else None
        if seg is None:
            final_toks = jnp.zeros((n_seg,), jnp.int32)
            final_lps = jnp.zeros((n_seg,), jnp.float32)
            done0 = None
        else:
            (seg_ids, seg_tables, seg_starts, seg_lens, seg_slot,
             seg_plen, seg_keys) = seg[:7]
            slab_kw = ({"moe_stats": True, "ntok": seg[7]} if moe_ else {})
            with jax.named_scope("slab_body"):
                logits, cache, *moe = p.slab_body(
                    params, cache, seg_ids, seg_tables, seg_starts,
                    seg_lens - 1, "mixed_step", **slab_kw)
            if moe:
                moe_acc = p.moe_fold(moe_acc, moe[0])
            with jax.named_scope("slab_finals"):
                final_toks, final_lps = p.slab_finals(logits, seg_keys)
            lengths = lengths.at[seg_slot].set(seg_plen, mode="drop")
            last_tok = last_tok.at[seg_slot].set(final_toks, mode="drop")
            active = active.at[seg_slot].set(True, mode="drop")
            done0 = jnp.zeros((B_,), bool).at[seg_slot].set(
                (eos >= 0) & (final_toks == eos), mode="drop")
            done0 = done0 | (budget <= 0)
        p.bind_tables(dec_tables, "mixed_step")
        with jax.named_scope("decode_loop"):
            if moe_:
                limit = ((lengths + budget,) if state_ else ())
                ((cache, moe_acc, *_), lengths, tok, toks, lps,
                 steps) = p.fused_loop(
                    p.one_step_moe, params, (cache, moe_acc, *limit),
                    lengths, last_tok, active, dec_rng, eos, budget,
                    num_steps, done0=done0)
                return (cache.keys, cache.values, lengths, tok, final_toks,
                        final_lps, toks, lps, steps, moe_acc)
            cache, lengths, tok, toks, lps, steps = p.fused_loop(
                p.one_step, params, cache, lengths, last_tok, active,
                dec_rng, eos, budget, num_steps, done0=done0)
        return (cache.keys, cache.values, lengths, tok, final_toks,
                final_lps, toks, lps, steps)

    return mixed_step


_OUT = ("pk", "pv", "lengths", "last_tok", "final_toks", "final_lps",
        "toks", "lps", "steps", "moe_acc")


def _one_riding_row(eng, slab, budget, eos=-1, tables_too=()):
    """Row 0 installed with five tokens by a first dispatch, then ``slab``
    beside it, in the new order and the old: ``(new, old)`` by name."""
    B, sent = eng.max_batch, eng._page_sentinel
    copy = lambda t: jax.tree.map(jnp.copy, t)       # noqa: E731
    old = old_order_mixed_step(eng)
    first = [x.copy() for x in eng._slab_of(eng._blank_segments(), 1)]
    first[0][0, :5] = (5, 4, 3, 2, 1)
    first[1][0, 0] = 0
    first[3][0], first[4][0], first[5][0] = 5, 0, 5
    tables = np.full((B, eng._table_width), sent, np.int32)
    tables[0, :2] = (0, 1)
    key = jax.random.PRNGKey(1)
    z = jnp.zeros((B,), jnp.int32)
    pk, pv, lengths, last, *_ = old(
        eng.params, eng._pk, eng._pv, tuple(first), jnp.asarray(tables), z,
        z, jnp.zeros((B,), bool), key, jnp.int32(-1),
        jnp.asarray([0] * B, jnp.int32), eng.decode_block)
    assert int(lengths[0]) == 5
    for slot, pages in tables_too:
        tables[slot, :len(pages)] = pages
    args = (slab, jnp.asarray(tables), lengths, last,
            jnp.asarray([True] + [False] * (B - 1)), key, jnp.int32(eos),
            jnp.asarray(budget, jnp.int32), eng.decode_block)
    new = eng._mixed_step.inner(eng.params, *copy((pk, pv)), *args)
    ref = old(eng.params, pk, pv, *args)
    return dict(zip(_OUT, new)), dict(zip(_OUT, ref))


@pytest.fixture(scope="module")
def hand_engine(params):
    with mock.patch.object(ContinuousBatchingEngine, "_warm_mixed_variants",
                           lambda self: None):
        eng = ContinuousBatchingEngine(
            CFG, params, max_seq=96, max_batch=4, sampling=GREEDY,
            kv_block_tokens=8, prefill_chunk=8, decode_block=4,
            mixed_token_budget=24)
    with eng:
        yield eng


def test_a_final_installed_by_the_dispatch_joins_the_loop_a_step_late(
        hand_engine):
    """Token #1 + ``num_steps - 1``: the final's row takes no part in the
    step the slab carries (nobody reads column 0 of its row, and neither
    its table nor its length is touched by it) and gets the loop's three, which are
    the old order's first three; the riding row gets its four."""
    eng = hand_engine
    new, old = _one_riding_row(eng, _hand_packed(eng, 2), [16, 5, 0, 0],
                               tables_too=[(1, (4, 5))])
    assert int(new["steps"]) == int(old["steps"]) == 4
    assert np.asarray(new["lengths"])[:2].tolist() == [9, 9]
    assert np.asarray(old["lengths"])[:2].tolist() == [9, 10]
    toks, was = np.asarray(new["toks"]), np.asarray(old["toks"])
    assert (toks[0] == was[0]).all()
    assert (toks[1, 1:] == was[1, :3]).all()
    assert int(new["final_toks"][1]) == int(old["final_toks"][1])
    # the final's budget counts from the step it joins at: with two tokens
    # left it is done after the loop's second step, where the riding row
    # is done too, and the device says three steps ran
    new, old = _one_riding_row(eng, _hand_packed(eng, 2), [3, 2, 0, 0],
                               tables_too=[(1, (4, 5))])
    assert (int(new["steps"]), int(old["steps"])) == (3, 3)
    assert np.asarray(new["lengths"])[:2].tolist() == [8, 8]


@pytest.mark.parametrize("case", ["eos", "budget"])
def test_a_row_that_ends_in_the_carried_step_is_done_for_the_loop(
        hand_engine, case):
    """The carried step folds ``eos`` and ``budget`` into done as the
    loop's first iteration does: a riding row whose first token is ``eos``,
    or whose budget is one token, is done when the loop begins, and with
    no other row the loop runs no step: one step ran, as in the old
    order."""
    eng = hand_engine
    slab = _hand_packed(eng, 2)
    slab[4][:] = eng.max_batch          # chunks alone: nothing is installed
    free, _ = _one_riding_row(eng, slab, [16, 0, 0, 0])
    first = int(np.asarray(free["toks"])[0, 0])
    assert int(free["steps"]) == 4
    new, old = _one_riding_row(
        eng, slab, [16 if case == "eos" else 1, 0, 0, 0],
        eos=first if case == "eos" else -1)
    assert int(new["steps"]) == int(old["steps"]) == 1
    for out in (new, old):
        assert np.asarray(out["toks"])[0].tolist() == [first, 0, 0, 0]
        assert int(out["lengths"][0]) == 6
    for a, b in zip(jax.tree.leaves((new["pk"], new["pv"])),
                    jax.tree.leaves((old["pk"], old["pv"]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_the_program_without_a_slab_is_what_it_was(hand_engine):
    """``seg is None``: the decode loop alone, line for line the old
    composition's own lowering (``tests/test_program_pins.py`` holds every
    toy family's ``.decode`` program to its hash, which PR 61 left as it
    was), and an engine still launches ``n_seg + 1``
    variants before it is ready."""
    eng = hand_engine
    call = abstract_mixed_call(eng, slab=False)
    text = eng._mixed_step.inner.lower(*call).as_text()
    was = old_order_mixed_step(eng).lower(*call).as_text()
    strip = lambda t: "\n".join(                        # noqa: E731
        line for line in t.splitlines() if "module @jit" not in line)
    # (the old composition is not donated: its arguments carry no alias)
    assert strip(text).replace(
        " {tf.aliasing_output = 0 : i32}", "").replace(
        " {tf.aliasing_output = 1 : i32}", "") == strip(was)
    slab = eng._mixed_step.inner.lower(
        *abstract_mixed_call(eng, slab=True)).as_text()
    assert slab != text
    with ContinuousBatchingEngine(
            CFG, eng.params, max_seq=96, max_batch=4, sampling=GREEDY,
            kv_block_tokens=8, prefill_chunk=8, decode_block=4,
            mixed_token_budget=24) as warm:
        compiled = warm.stats()["compile"]["mixed_step"]
    assert compiled["cache_entries"] == compiled["variant_budget"] == \
        warm._mixed_seg_cap + 1 == 4


def _shapes_in(jaxpr, prim=None):
    """Every eqn's output shapes in a jaxpr and its sub-jaxprs (of the
    primitive named, its operands' instead)."""
    found = []
    for eqn in jaxpr.eqns:
        if prim is None:
            found += [v.aval.shape for v in eqn.outvars]
        elif eqn.primitive.name == prim:
            found += [v.aval.shape for v in eqn.invars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _shapes_in(sub, prim)
    return found


@pytest.mark.parametrize("tp", [1, 4])
def test_no_array_of_the_slab_s_every_position_by_the_vocabulary(tp):
    """PR 48, the guard: as traced, ``mixed_step`` with a full slab holds
    no array of ``r x C`` rows by ``V`` (or ``V / tp``) columns: the
    widest thing with the vocabulary's columns is ``[rows, 1, V]``, for
    the slab's ``r`` segments and for the decode loop's ``B`` slots (and
    since PR 61 ``[1, r + B, V]``: the slab's pass with the step it
    carries).
    Over a mesh of four (CPU) devices the vocab-parallel head's
    ``all_gather`` moves ``[1, r + B, V / 4]`` and ``[B, 1, V / 4]``."""
    from distributed_inference_demo_tpu.parallel.mesh import local_tp_mesh
    from distributed_inference_demo_tpu.runtime.engine import (
        shard_engine_params)
    # four kv heads shard over four chips; an untied head, so that no
    # weight is [H, V] transposed; V and V / 4 are no other width
    cfg = dataclasses.replace(CFG, num_kv_heads=4, vocab_size=320)
    mesh = local_tp_mesh(tp)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    with mock.patch.object(ContinuousBatchingEngine, "_warm_mixed_variants",
                           lambda self: None), ContinuousBatchingEngine(
            cfg, params, max_seq=96, max_batch=4, mesh=mesh,
            sampling=GREEDY, kv_block_tokens=8, prefill_chunk=8,
            decode_block=4, mixed_token_budget=24) as eng:
        if mesh is not None:
            eng.params = shard_engine_params(params, cfg, mesh)
        r, C, B, V = eng._mixed_seg_cap, 8, eng.max_batch, cfg.vocab_size
        call = abstract_mixed_call(eng, slab=True)
        jaxpr = jax.make_jaxpr(eng._mixed_step.inner, static_argnums=(11,))(
            *call).jaxpr
    wide = {s for s in _shapes_in(jaxpr) if s and s[-1] in (V, V // tp)}
    assert (r, 1, V) in wide and (B, 1, V) in wide
    # (the slab's pass carries the decoding rows' step: one head product
    # over its r segments' rows and the B slots')
    assert max(int(np.prod(s[:-1])) for s in wide) == r + B < r * C
    gathered = [s for s in _shapes_in(jaxpr, "all_gather")]
    if tp == 1:
        assert not gathered
    else:
        assert set(gathered) == {(1, r + B, V // 4), (B, 1, V // 4)}


@pytest.mark.parametrize("budget", [16, 24])
def test_every_variant_is_launched_before_the_first_request(params, budget):
    """``n_seg + 1`` compiled entries of ``mixed_step`` when the engine
    is ready (the decode loop alone, a slab of 1 .. ``n_seg`` segments),
    every one launched and none met by traffic first: a first dispatch
    of one, two (and on a three-segment budget, three) segments, and the
    decode-only dispatches between them, add no entry, and the idle
    launches left no dispatch record, no counter and no page behind."""
    n_seg = budget // 8
    with mixed_engine(params, mixed_token_budget=budget) as eng:
        ready = eng.stats()
        entry = ready["compile"]["mixed_step"]
        assert entry["variant_budget"] == n_seg + 1
        assert eng._mixed_step.inner._cache_size() == n_seg + 1 \
            == entry["cache_entries"]
        assert ready["dispatch_trace"]["seq"] == 0
        assert ready["mixed"]["dispatches"] == 0
        assert eng.kv_cache.used_blocks == 0
        assert (eng._tables == eng._page_sentinel).all()
        assert not np.asarray(eng._lengths).any()
        for r in range(1, n_seg + 1):
            # prompts that share no prefix: r - 1 chunks and a final
            eng.submit(list(range(50 * r, 50 * r + 8 * r - 2)),
                       6).wait(timeout=300)
        settle(eng)
        st = eng.stats()
        dt = st["dispatch_trace"]
        segs = [row[dt["fields"].index("segments")] for row in dt["recent"]]
        assert set(segs) == set(range(n_seg + 1)), segs
        assert eng._mixed_step.inner._cache_size() == n_seg + 1
        assert st["compile"]["mixed_step"]["compiles"] == entry["compiles"]
        # the rows the launched programs computed, against those that
        # held a token
        assert dt["slab_rows"] == 8 * sum(segs)
        assert dt["prefill_tokens"] == sum(8 * r - 2
                                           for r in range(1, n_seg + 1))


@pytest.mark.quick
def test_mixed_sampled_stream_bit_identical_to_serialized(params):
    """The rng contract: one split per packed final in pack order, one
    decode split per decoding dispatch — the serialized path's exact
    spend, so SAMPLED token streams match bit-for-bit across sequential
    requests.

    What is guaranteed for the reported LOG-PROBABILITIES is agreement
    to float32 rounding, not bit-identity: the two schedules run the
    same arithmetic through differently shaped XLA programs (a
    [n_seg, C] slab here, a bucket-wide prefill there), and XLA makes
    no promise that two programs reduce in the same order.  On jax 0.9
    they differ by 1-3 ulp; a wrong rng split, mask or page would move
    them by O(1) (and move the tokens, which stay pinned exactly).  The
    bound is ~100 ulp of an |lp| ~ 4 float32 — slack for accumulated
    rounding over the layer stack, nothing more."""
    samp = SamplingParams(greedy=False, temperature=0.9, top_k=40)

    def run(**kw):
        with ContinuousBatchingEngine(
                CFG, params, max_seq=96, max_batch=4, sampling=samp,
                seed=7, prompt_buckets=(16, 48), kv_block_tokens=8,
                prefill_chunk=8, decode_block=4, **kw) as eng:
            outs = []
            for p, n in ((list(range(3, 30)), 8), ([9, 8, 7, 6], 6)):
                r = eng.submit(p, n)
                outs.append((list(r.wait(timeout=300)), list(r.lps)))
            if kw:
                assert_both_kinds_ran(eng)
            return outs

    for (toks, lps), (m_toks, m_lps) in zip(run(),
                                            run(mixed_token_budget=24)):
        assert toks == m_toks
        np.testing.assert_allclose(lps, m_lps, rtol=1e-5, atol=0)


@pytest.mark.quick
def test_decode_fusion_survives_admission(params, oracle):
    """The acceptance headline: submit a chunk-streaming prompt while a
    row decodes — chunks pack INTO decode dispatches
    (interleaved_steps > 0) and dispatches/step stays ≈ 1/K instead of
    collapsing to per-token suppression."""
    K = 4
    with mixed_engine(params, max_batch=2) as eng:
        a = eng.submit([5, 4, 3, 2], 36)
        deadline = time.monotonic() + 60
        while len(a.tokens) < 2:
            assert time.monotonic() < deadline, "row A never started"
            time.sleep(0.002)
        b = eng.submit(list(range(1, 36)), 8)    # 4 chunks + final
        np.testing.assert_array_equal(a.wait(timeout=300),
                                      expected(oracle, [5, 4, 3, 2], 36))
        np.testing.assert_array_equal(
            b.wait(timeout=300), expected(oracle, list(range(1, 36)), 8))
        assert eng.chunk_stats["interleaved_steps"] >= 1
        ls = eng.loop_stats
        assert ls["device_loop_steps"] > 0
        ratio = ls["host_dispatches"] / ls["device_loop_steps"]
        # exact 1/K plus a margin for early-exit tail blocks at each
        # request's end; the suppressed path would measure ≈ 1.0
        assert ratio <= 1 / K + 0.12, ls


@pytest.mark.quick
def test_mixed_admission_failure_fails_request_not_engine(params, oracle):
    """A dispatch failure while admissions are packed fails THOSE
    requests (the serialized admission contract) and leaves the engine
    serving with zero leaked pages."""
    with mixed_engine(params, max_batch=2) as eng:
        orig = eng._mixed_step
        state = {"armed": True}

        def boom(*a, **k):
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("injected mixed failure")
            return orig(*a, **k)

        eng._mixed_step = boom
        b = eng.submit(list(range(1, 20)), 6)
        with pytest.raises(RuntimeError, match="injected mixed failure"):
            b.wait(timeout=300)
        assert b.error is not None
        c = eng.submit([8, 8, 1], 3)
        np.testing.assert_array_equal(c.wait(timeout=300),
                                      expected(oracle, [8, 8, 1], 3))
        assert eng.stats()["pending_prefill_tokens"] == 0
        assert_no_leak(eng)


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
@pytest.mark.parametrize("chunk,budget", [(4, 8), (8, 24), (16, 32)])
def test_mixed_matches_serialized_property_sweep(params, kv_dtype,
                                                 chunk, budget):
    """Property sweep (chunk sizes x budgets x eos-mid-decode x
    quantized pages): concurrent greedy streams out of the mixed loop
    are bit-identical to the serialized interleave — quantized pages
    included, because both modes write the SAME chunk values at the
    SAME page positions (quantization points coincide) — and every
    run ends leak-free."""
    prompts = [(list(range(3, 30)), 10), ([9, 8, 7, 6], 8),
               (list(range(50, 85)), 6)]

    def run(eos_id, mixed):
        kw = {"mixed_token_budget": budget} if mixed else {}
        with ContinuousBatchingEngine(
                CFG, params, max_seq=96, max_batch=4, sampling=GREEDY,
                seed=3, prompt_buckets=(16, 48), kv_block_tokens=8,
                prefill_chunk=chunk, decode_block=4, eos_id=eos_id,
                kv_dtype=kv_dtype, **kw) as eng:
            reqs = [eng.submit(p, n) for p, n in prompts]
            outs = [list(r.wait(timeout=300)) for r in reqs]
            assert_no_leak(eng)
            if mixed:
                assert_both_kinds_ran(eng)
            return outs

    base = run(None, mixed=False)
    assert run(None, mixed=True) == base
    # an eos taken from a real stream ends one request mid-decode while
    # the others still admit/decode — truncation points must coincide
    eos = int(base[0][4])
    assert run(eos, mixed=True) == run(eos, mixed=False)


# ---------------------------------------------------------------------------
# §19: the next dispatch prepared under the execution in flight
# ---------------------------------------------------------------------------
# (the scripted traffic is here, where ``tests/test_early_launch.py`` and
# the profiler's cases take it from; the whole contract, every script as it
# is and with every plan refused, is ``tests/test_mixed_order.py``'s)

KEEPER = list(range(2, 24))            # 22 tokens: two whole pages + 6
LONG40 = list(range(30, 70))           # five segments of eight
LONG72 = list(range(100, 172))         # nine: eight chunks and the final
# what reaches the scheduler, keyed by the call of ``mixed_step`` it
# follows: the hook below acts on the scheduler's own thread right after
# that call was enqueued, so each event lands DURING that execution
# whatever the machine's timing, in either order of an iteration.  An
# event ``*_planned`` lands later in the same execution: after the plan
# of the next dispatch was made under it, before the blocking read.
# ``raise_at``: that call of ``mixed_step`` raises instead.
SCRIPTS = {
    "base": {
        # shares the keeper's first two pages (adopted by the tree when
        # the keeper's final is drained: the arrival must find them)
        1: [("submit", "share", KEEPER[:16] + [77, 78, 79], 30)],
        # a final with nothing to decode, and a row whose budget ends
        # one step into a later block (token #1 + 4 + 1)
        2: [("submit", "one", [9, 2, 6], 1),
            ("submit", "mid", [5, 4, 3, 2], 6)],
        # the batch is full (keeper, share, mid): the final parks
        3: [("submit", "parked", list(range(40, 62)), 7)],
        7: [("cancel", "share")],
        9: [("submit", "late", [8, 8, 1], 5)],
        14: [("submit", "last", [7, 1, 7, 1, 7], 3)],
    },
    # a prompt of five segments admitted under a decoding row: two
    # segments a dispatch, the second and third of them prepared
    "long_prompt": {
        2: [("submit", "long", LONG40, 6)],
        9: [("submit", "tail", list(range(70, 97)), 4)],
    },
    # two admissions in flight, packed FIFO: the first's last chunk and
    # final before the second's first chunk
    "two_admissions": {
        2: [("submit", "first", list(range(30, 60)), 5),
            ("submit", "second", list(range(60, 87)), 5)],
    },
    # three slots, all decoding, a final parked for want of a slot; the
    # row ``brief`` ends inside an execution, and the plan made under it
    # would hand the parked final that row's slot, which the drain of
    # the execution has yet to clear
    "parked_final": {
        1: [("submit", "brief", [5, 4, 3, 2], 18),
            ("submit", "other", [9, 2, 6], 40)],
        3: [("submit", "parked", list(range(40, 62)), 7)],
    },
    # an admission cancelled mid-prefill: ``seen`` before the plan under
    # that execution is made, ``unseen`` after it (the plan holds its
    # next two chunks, and the validation must turn it away)
    "cancelled_admission": {
        2: [("submit", "seen", LONG40, 6)],
        3: [("cancel", "seen")],
        6: [("submit", "unseen", LONG40[::-1], 6)],
        8: [("cancel_planned", "unseen")],
        12: [("submit", "tail", [7, 1, 7, 1, 7], 3)],
    },
    # the launch of a prepared slab raises: its request fails, the rows
    # of the execution not yet drained get their tokens, the engine
    # serves on
    "failed_launch": {
        2: [("submit", "victim", LONG40, 6)],
        "raise_at": 4,
        7: [("submit", "tail", LONG40[::-1], 3)],
    },
    # two rows decoding (room for two segments) and a prompt of nine:
    # every slab but the gap's first and the final's is packed full under
    # its predecessor, and enqueued behind it (calls 5, 6, 7); a second
    # long prompt follows it through the same dispatches
    "full_slab": {
        1: [("submit", "row", [9, 2, 6], 40)],
        3: [("submit", "long", LONG72, 6),
            ("submit", "next", LONG72[::-1], 4)],
    },
}
RECORD_FIELDS = ("segments", "finals", "prefill_tokens", "active_rows",
                 "steps", "kv_tokens")


def settle(eng):
    """Wait until the scheduler has committed its last dispatch: a
    request's ``wait`` returns while that dispatch is still draining."""
    deadline = time.monotonic() + 10
    while (eng.dispatch_trace.seq != eng.chunk_stats["mixed_dispatches"]
           and time.monotonic() < deadline):
        time.sleep(0.005)


def scripted_run(params, sampling, eos_id, refuse, case="base",
                 sample_n=None):
    """The scripted traffic through one engine of three slots; with
    ``refuse`` every prepared dispatch is turned away by a patched
    validator, so every iteration runs in the old order.  With
    ``sample_n`` the engine has a profiler of its own that samples one
    dispatch in so many a signature (0: none), and from the moment the
    engine is ready ``jax.block_until_ready`` notes its callers and
    raises.  Returns what the two runs must agree on, and what each
    alone must show."""
    eng = mixed_engine(params, max_batch=3, sampling=sampling, seed=11,
                       eos_id=eos_id)
    synced = []
    with contextlib.ExitStack() as stack:
        if sample_n is not None:
            eng._prof = DispatchProfiler(sample_n=sample_n)

            def no_sync(*a, **kw):
                synced.append(sys._getframe(1).f_code.co_name)
                raise AssertionError("the mixed loop blocked on the device")

            stack.enter_context(
                mock.patch.object(jax, "block_until_ready", no_sync))
        out = _scripted_traffic(eng, eos_id, refuse, case)
    out["synced"] = synced
    out["profile"] = {sig: (st.samples, st.total_s)
                      for sig, st in eng._prof._stats.items()}
    return out


def _scripted_traffic(eng, eos_id, refuse, case):
    script = dict(SCRIPTS[case])
    raise_at = script.pop("raise_at", None)
    n_events = sum(map(len, script.values()))
    reqs, fired, touched, calls, raised = {}, [], [], [0], []
    inner, plan_ahead = eng._mixed_step, eng._plan_ahead

    def snap():
        dt = eng.dispatch_trace
        return (np.asarray(eng._rng).tobytes(),
                [(id(a["req"]), a["start"], len(a["suffix"]))
                 for a in eng._adms],
                eng._tables.tobytes(), dict(eng.chunk_stats),
                dt.queue_wait_count, dt.queue_wait_ms_sum,
                [r.first_seq for r in reqs.values()])

    def fire(planned):
        for kind, name, *rest in script.get(calls[0], ()):
            if kind.endswith("_planned") != planned:
                continue
            if kind == "submit":
                reqs[name] = eng.submit(*rest)
            else:
                reqs[name].cancel()
            fired.append(name)

    def hooked(*a):
        calls[0] += 1
        if calls[0] == raise_at:
            dt = eng.dispatch_trace
            # launched and not yet committed: this one, and the one it
            # was prepared under if that is still to be drained
            raised.append(dt.launched - dt.seq)
            raise RuntimeError("scripted launch failure")
        out = inner(*a)
        fire(False)
        return out

    def watched(flight):
        before = snap()
        out = plan_ahead(flight)
        if snap() != before:
            touched.append(calls[0])
        fire(True)
        return out

    eng._mixed_step, eng._plan_ahead = hooked, watched
    if refuse:
        eng._ahead_refusal = lambda flight: "other"
    with eng:
        reqs["keeper"] = eng.submit(KEEPER, 60)
        deadline = time.monotonic() + 300
        while (len(fired) < n_events
               or not all(r.done.is_set() for r in reqs.values())):
            if (all(r.done.is_set() for r in list(reqs.values()))
                    and eng.stats()["active_slots"] == 0):
                break                  # the keeper ended under the script
            assert time.monotonic() < deadline, (fired, calls)
            time.sleep(0.005)
        settle(eng)
        st = eng.stats()
        dt = st["dispatch_trace"]
        recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
        assert_no_leak(eng)
        return {
            "same": {
                "streams": {n: (list(r.tokens), list(r.lps), r.cancelled,
                                repr(r.error), r.first_seq, r.final_seq)
                            for n, r in reqs.items()},
                "records": [tuple(r[f] for f in RECORD_FIELDS)
                            for r in recs],
                "rng": np.asarray(eng._rng).tolist(),
                "chunk_stats": dict(eng.chunk_stats),
                "queue_waits": dt["queue_wait_count"],
                "fired": list(fired),
                "kv": (eng.kv_cache.used_blocks,
                       eng.kv_cache.tree.block_count),
                "tables": eng._tables.tolist(),
            },
            "script_done": len(fired) == n_events,
            "touched": touched, "trace": dt, "recs": recs,
            "compile": st["compile"]["mixed_step"], "raised": raised,
        }


_PROFILED = {}


def profiled_run(params, sampled, sample_n):
    """The base script under a profiler that samples one dispatch in
    ``sample_n`` a signature (0: none), one run a kind."""
    if (sampled, sample_n) not in _PROFILED:
        sampling = (SamplingParams(greedy=False, temperature=0.9, top_k=40)
                    if sampled else GREEDY)
        _PROFILED[sampled, sample_n] = scripted_run(
            params, sampling, None, False, sample_n=sample_n)
    return _PROFILED[sampled, sample_n]


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_a_profiler_sample_changes_no_schedule(params, sampled):
    """A dispatch the profiler samples is prepared under its predecessor
    like any other: with every dispatch sampled the script has the hits,
    the misses, the streams and the records it has with none."""
    every, none = (profiled_run(params, sampled, n) for n in (1, 0))
    assert every["script_done"] and none["script_done"]
    for key in none["same"]:
        assert every["same"][key] == none["same"][key], key
    for key in ("ahead_hits", "ahead_hits_slab", "ahead_misses",
                "ahead_first", "seq"):
        assert every["trace"][key] == none["trace"][key], key
    assert every["trace"]["ahead_hits"] >= 5
    assert none["profile"] == {}


def test_a_profiler_sample_is_the_dispatch_records_time(params):
    """With every dispatch sampled the profiler holds one sample a mixed
    dispatch, under ``mixed_step`` signatures alone, and their seconds
    are the records' own ``t_done - t_launch``: no second clock.  An
    early dispatch's sample starts when its predecessor returned: what
    it spent queued behind it is no time of its own."""
    sampling = GREEDY
    for case in ("base", "full_slab"):
        run = (profiled_run(params, False, 1) if case == "base" else
               scripted_run(params, sampling, None, False, case, sample_n=1))
        recs, profile = run["recs"], run["profile"]
        assert profile and all(sig.startswith("mixed_step|")
                               for sig in profile)
        assert sum(n for n, _ in profile.values()) == len(recs) == run[
            "trace"]["seq"]
        begun = [b["t_launch"] if not b["early"] else a["t_done"]
                 for a, b in zip([None] + recs, recs)]
        # a record's instants are rounded to 1e-5 s
        assert sum(t for _, t in profile.values()) == pytest.approx(
            sum(r["t_done"] - t0 for r, t0 in zip(recs, begun)),
            abs=2e-5 * len(recs))
        assert all(r["t_done"] > t0 >= r["t_launch"]
                   for r, t0 in zip(recs, begun))
    assert run["trace"]["ahead_early"] == 6 and run["synced"] == []


def test_the_mixed_loop_never_blocks_on_the_device(params):
    """Once the engine is ready nothing on the mixed path calls
    ``jax.block_until_ready``, a sampled dispatch's drain included: the
    script ran to its end with that call made to raise."""
    run = profiled_run(params, False, 1)
    assert run["synced"] == []
    assert run["script_done"]
    assert all(error == "None"
               for *_, error, _, _ in run["same"]["streams"].values())


def test_under_a_mesh_a_plan_lies_where_the_call_wants_it():
    """Over a tp mesh the call's small arguments are put on every chip of
    it (committed, replicated), on both orders alike, so a launch spreads
    nothing and a prepared dispatch adds no compiled entry to
    ``mixed_step``: the same count with every plan refused, the same
    tokens."""
    from distributed_inference_demo_tpu.models.loader import load_or_init
    from distributed_inference_demo_tpu.parallel.mesh import local_tp_mesh
    cfg = get_model_config("qwen2-test")
    seen = {}
    for refuse in (True, False):
        mesh = local_tp_mesh(2)
        eng = ContinuousBatchingEngine(
            cfg, load_or_init("qwen2-test", cfg, seed=0, mesh=mesh),
            max_seq=96, max_batch=4, mesh=mesh, sampling=GREEDY,
            kv_block_tokens=8, prefill_chunk=8, decode_block=4,
            mixed_token_budget=24)
        put, placed = eng._put_mixed, []

        def watched(plan, put=put, placed=placed):
            dev = put(plan)
            placed.extend(x.sharding for x in jax.tree.leaves(dev))
            return dev

        eng._put_mixed = watched
        if refuse:
            eng._ahead_refusal = lambda flight: "other"
        with eng:
            toks = [eng.submit(p, n).wait(timeout=300).tolist()
                    for p, n in ((KEEPER, 14), ([3, 14, 15], 9))]
            settle(eng)
            dt = eng.stats()["dispatch_trace"]
        assert all(sh.is_fully_replicated and len(sh.device_set) == 2
                   for sh in placed) and placed
        seen[refuse] = (toks, eng._mixed_step.inner._cache_size(),
                        dt["seq"])
        assert (dt["ahead_hits"] > 0) != refuse
    assert seen[True] == seen[False]
    # ... and that count is the variants', on a mesh as off it: the
    # rows' state and the pool are born sharded as a program returns them
    assert seen[True][1] == eng._mixed_seg_cap + 1 == 4


@pytest.mark.quick
def test_spec_mixed_engine_prepares_nothing(params):
    """The speculative mixed programs pack from what their drain learns
    (adaptive K, the proposer's host-side seeding): they keep the old
    order because of what the engine is, and count no hit."""
    with mixed_engine(params, **spec_kw("pld")) as eng:
        for p, n in (([3, 14, 15], 14), (list(range(2, 24)), 9)):
            eng.submit(p, n).wait(timeout=300)
        settle(eng)
        dt = eng.stats()["dispatch_trace"]
    assert dt["seq"] > 4 and dt["ahead_hits"] == 0
    # (under an execution they do one thing, as every engine does: hand
    # the streams what the gap recorded, directly behind the launch)
    spans = dt["spans"]
    assert spans["ahead_plan"]["n"] == spans["ahead_drain"]["n"] == 0
    assert dt["phase_s"]["ahead"] == pytest.approx(
        spans["deliver"]["wall_s"], abs=2e-6)
    assert dt["delivered_after_launch"] == dt["ahead_misses"]["other"]
    # ... and their full slab: three segments of eight a dispatch
    assert dt["slab_rows"] == 3 * 8 * dt["seq"]
    assert dt["ahead_misses"]["other"] + dt["ahead_first"] == dt["seq"]
    assert all(r[dt["fields"].index("ahead")] == 0 for r in dt["recent"])


# ---------------------------------------------------------------------------
# §22: speculation inside the mixed dispatch (docs/DESIGN.md §22)
# ---------------------------------------------------------------------------


def spec_kw(proposer, draft_params=None, num_draft=3, **extra):
    if proposer == "pld":
        kw = dict(prompt_lookup=True, num_draft=num_draft)
    else:
        kw = dict(draft_cfg=DRAFT_CFG, draft_params=draft_params,
                  num_draft=num_draft)
    kw.update(extra)
    return kw


def assert_spec_idle(eng):
    """§22 zero-leak extension: the draft scratch pool holds no pages
    when no request is in flight."""
    if eng._dmgr is not None:
        assert eng._dmgr.used_blocks == 0, eng._dmgr.used_blocks


@pytest.mark.quick
@pytest.mark.parametrize("proposer", [
    "pld",
    # tier-1 budget: the draft proposer keeps quick-lane coverage via
    # the sampled and adaptive-shrink tests; this greedy twin rides
    # the slow lane with the property sweep
    pytest.param("draft", marks=pytest.mark.slow),
])
def test_spec_mixed_greedy_parity_and_zero_leak(params, draft_params,
                                                oracle, proposer):
    """§22 headline at greedy: speculative rows packed into the SAME
    mixed dispatch as prefill chunks and plain decode, adaptive K live,
    concurrent submissions — and the streams are still bit-identical to
    the one-shot oracle.  Both proposers; draft scratch pool returns to
    zero pages at idle."""
    prompts = [[3, 14, 15], list(range(2, 24)), [9, 2, 6, 5, 3, 5]]
    ns = [10, 12, 8]
    with mixed_engine(params, **spec_kw(proposer, draft_params)) as eng:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, ns)]
        for p, n, r in zip(prompts, ns, reqs):
            np.testing.assert_array_equal(r.wait(timeout=300),
                                          expected(oracle, p, n))
        sp = eng.stats()["speculative"]
        assert sp["drafted"] > 0
        assert sp["adaptive"] is True
        assert eng.stats()["mixed"]["dispatches"] > 0
        assert_no_leak(eng)
        assert_spec_idle(eng)


@pytest.mark.quick
def test_spec_mixed_sampled_bit_identical_to_serialized(params,
                                                        draft_params):
    """§22 rng contract: the fused draft/verify dispatch spends rng
    exactly like the serialized spec schedule, so SAMPLED streams
    (tokens and logprobs) match bit-for-bit.  K_row is pinned — the
    adaptive controller feeds back measured wall-clock acceptance, which
    is not part of the schedule being compared."""
    samp = SamplingParams(greedy=False, temperature=0.9, top_k=40)

    def run(**kw):
        with ContinuousBatchingEngine(
                CFG, params, max_seq=96, max_batch=4, sampling=samp,
                seed=7, prompt_buckets=(16, 48), kv_block_tokens=8,
                prefill_chunk=8, decode_block=4, draft_cfg=DRAFT_CFG,
                draft_params=draft_params, num_draft=3,
                spec_adaptive=False, **kw) as eng:
            outs = []
            for p, n in ((list(range(3, 30)), 8), ([9, 8, 7, 6], 6)):
                r = eng.submit(p, n)
                outs.append((list(r.wait(timeout=300)), list(r.lps)))
            return outs

    assert run() == run(mixed_token_budget=24)


@pytest.mark.parametrize("kv_dtype", [
    # tier-1 budget: both quantized reps ride the slow lane — the
    # quick-lane bf16 greedy parity test pins the same fused-program
    # seam, and the §17 suite pins quantized-page exactness itself
    pytest.param("int8", marks=pytest.mark.slow),
    pytest.param("int4", marks=pytest.mark.slow),
])
def test_spec_mixed_quantized_greedy_matches_serialized(params, kv_dtype):
    """Quick quantized rep (the full cross product runs in the slow
    sweep): greedy spec x mixed over int8/int4 pages matches the
    serialized spec schedule on the SAME page dtype — verify reads and
    draft proposals see identically-quantized history in both modes."""

    def run(mixed):
        kw = {"mixed_token_budget": 24} if mixed else {}
        with ContinuousBatchingEngine(
                CFG, params, max_seq=96, max_batch=4, sampling=GREEDY,
                prompt_buckets=(16, 48), kv_block_tokens=8,
                prefill_chunk=8, decode_block=4, kv_dtype=kv_dtype,
                prompt_lookup=True, num_draft=3, **kw) as eng:
            reqs = [eng.submit(p, n)
                    for p, n in ((list(range(3, 24)), 8), ([9, 8, 7], 6))]
            outs = [list(r.wait(timeout=300)) for r in reqs]
            assert_no_leak(eng)
            return outs

    assert run(mixed=True) == run(mixed=False)


@pytest.mark.quick
def test_spec_dispatch_ratio_survives_admission(params, oracle):
    """§22 acceptance: dispatches/step stays ≈ 1/K with speculation
    armed WHILE a chunked prompt admits — the spec row keeps its fused
    cadence inside the packed program instead of being suppressed."""
    K = 4
    with mixed_engine(params, max_batch=2, prompt_lookup=True,
                      num_draft=3, mixed_token_budget=40) as eng:
        a = eng.submit([5, 4, 3, 2], 36)
        deadline = time.monotonic() + 60
        while len(a.tokens) < 2:
            assert time.monotonic() < deadline, "row A never started"
            time.sleep(0.002)
        b = eng.submit(list(range(1, 36)), 8)
        np.testing.assert_array_equal(a.wait(timeout=300),
                                      expected(oracle, [5, 4, 3, 2], 36))
        np.testing.assert_array_equal(
            b.wait(timeout=300), expected(oracle, list(range(1, 36)), 8))
        assert eng.chunk_stats["interleaved_steps"] >= 1
        sp = eng.stats()["speculative"]
        assert sp["drafted"] > 0
        ls = eng.loop_stats
        assert ls["device_loop_steps"] > 0
        ratio = ls["host_dispatches"] / ls["device_loop_steps"]
        # accepted drafts only push the ratio further BELOW the plain
        # fused bound; the suppressed path would measure ≈ 1.0
        assert ratio <= 1 / K + 0.12, ls
        assert_no_leak(eng)


@pytest.mark.quick
def test_spec_adaptive_k_shrinks_on_low_acceptance(params, draft_params,
                                                   oracle):
    """Adaptive K_row feedback: a draft model that disagrees with the
    target drives EWMA acceptance down, the controller walks the row to
    the smallest bucket (observable in k_row_buckets while the row is
    live), and the stream still equals plain greedy decode exactly —
    collapse degrades speculation, never correctness."""
    prompt, n = [7, 3, 11], 60
    with mixed_engine(params, max_batch=2,
                      **spec_kw("draft", draft_params)) as eng:
        r = eng.submit(prompt, n)
        saw_small = False
        deadline = time.monotonic() + 120
        while not r.done.is_set() and time.monotonic() < deadline:
            sp = eng.stats().get("speculative") or {}
            if (sp.get("k_row_buckets") or {}).get("1", 0) >= 1:
                saw_small = True
                break
            time.sleep(0.003)
        np.testing.assert_array_equal(r.wait(timeout=300),
                                      expected(oracle, prompt, n))
        sp = eng.stats()["speculative"]
        assert saw_small, sp
        assert sp["acceptance_rate"] is None or sp["acceptance_rate"] < 0.5
        assert_no_leak(eng)
        assert_spec_idle(eng)


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
@pytest.mark.parametrize("proposer", ["pld", "draft"])
def test_spec_mixed_matches_serialized_property_sweep(params, draft_params,
                                                      kv_dtype, proposer):
    """§22 property sweep (proposer x page dtype x eos-mid-decode):
    concurrent greedy spec streams out of the mixed loop are
    bit-identical to the serialized spec schedule, and every run ends
    with both pools leak-free."""
    prompts = [(list(range(3, 30)), 10), ([9, 8, 7, 6], 8),
               (list(range(50, 85)), 6)]

    def run(eos_id, mixed):
        kw = {"mixed_token_budget": 24} if mixed else {}
        kw.update(spec_kw(proposer, draft_params))
        with ContinuousBatchingEngine(
                CFG, params, max_seq=96, max_batch=4, sampling=GREEDY,
                seed=3, prompt_buckets=(16, 48), kv_block_tokens=8,
                prefill_chunk=8, decode_block=4, eos_id=eos_id,
                kv_dtype=kv_dtype, **kw) as eng:
            reqs = [eng.submit(p, n) for p, n in prompts]
            outs = [list(r.wait(timeout=300)) for r in reqs]
            assert_no_leak(eng)
            assert_spec_idle(eng)
            return outs

    base = run(None, mixed=False)
    assert run(None, mixed=True) == base
    eos = int(base[0][4])
    assert run(eos, mixed=True) == run(eos, mixed=False)
