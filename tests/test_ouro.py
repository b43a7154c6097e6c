"""The looped decoder (family ``ouro``: ``ModelConfig.ut_steps`` passes of
the layer stack a token, four norms a block, K/V planes of each pass's
own) at toy size on the CPU.

``ouro-test`` has 4 layers and 3 passes, so a count that took one for the
other shows.  The oracle is the benchmark's plain float32 reference
(``benchmark/families/ouro.py`` through ``benchmark/reference.py``): no
line of the program.  And a one-pass model is what it was: its dispatch
record and stats are the parent's (its program: ``tests/test_program_pins.py``).
"""

import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

from distributed_inference_demo_tpu.models import (        # noqa: E402
    KVCache, StageSpec, get_model_config)
from distributed_inference_demo_tpu.models.base import (   # noqa: E402
    slice_stage, split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (  # noqa: E402
    init_full_params, stage_forward)
from distributed_inference_demo_tpu.ops.quant import (     # noqa: E402
    alloc_kv_pool)
from distributed_inference_demo_tpu.ops.sampling import (  # noqa: E402
    SamplingParams)
from distributed_inference_demo_tpu.parallel.tensor import (  # noqa: E402
    make_paged_forward_seam)
from distributed_inference_demo_tpu.runtime.batching import (  # noqa: E402
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.telemetry.tracing import (  # noqa: E402
    DISPATCH_FIELDS, DISPATCH_LAST_FIELDS, LOOP_DISPATCH_FIELDS, LoopCounters)
from tests.test_mixed_batching import abstract_mixed_call  # noqa: E402

CFG = get_model_config("ouro-test")
L, T = CFG.num_layers, CFG.ut_steps
GREEDY = SamplingParams(temperature=0.0)
FIELDS = dataclasses.asdict(CFG)        # what the reference is given
PARENT = json.loads((ROOT / "tests" / "data" / "mixed_step_hlo.json")
                    .read_text())


@pytest.fixture(scope="module")
def params():
    """Seeded weights with the norm weights moved off one, so that a norm
    left out, or applied on the wrong side, changes the logits."""
    p = init_full_params(jax.random.PRNGKey(0), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for name in ("attn_norm_w", "attn_post_norm_w", "mlp_norm_w",
                 "mlp_post_norm_w"):
        p.layers[name] = 1.0 + 0.3 * jax.random.normal(
            next(keys), p.layers[name].shape)
    p.final_norm["w"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), p.final_norm["w"].shape)
    return p


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_seq", 96)
    kw.setdefault("max_batch", 4)
    kw.setdefault("kv_block_tokens", 8)
    kw.setdefault("kv_cache_blocks", 40)
    return ContinuousBatchingEngine(cfg, params, sampling=GREEDY, **kw)


MIXED = dict(prefill_chunk=8, decode_block=4, mixed_token_budget=24)


def _reference(params, prompt, tokens):
    import reference
    ids = [int(t) for t in prompt] + [int(t) for t in tokens]
    return reference.emitted_logprobs(params, FIELDS, ids, len(prompt))


def _settled(eng, section="loop"):
    """``/stats`` once the last dispatch's record is committed."""
    for _ in range(200):
        st = eng.stats()
        if st["dispatch_trace"]["seq"] == st[section]["dispatches"]:
            return st
        time.sleep(0.02)
    raise AssertionError("the last dispatch never committed")


# ----------------------------------------------- the config and its sizes

def test_registry_entry_is_the_published_config():
    cfg = get_model_config("ouro-2.6b")
    assert (cfg.family, cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size) == ("ouro", 48, 2048, 16, 16, 128, 5632, 49152)
    assert (cfg.ut_steps, cfg.sandwich_norm, cfg.tie_embeddings,
            cfg.rope_theta, cfg.norm_eps) == (4, True, False, 1e6, 1e-6)
    assert cfg.kv_planes == 192
    # a token's keys and values: 1.5 MiB
    assert cfg.kv_planes * 2 * 16 * 128 * 2 == 1_572_864
    assert get_model_config("llama-test").kv_planes == 4


def test_kv_planes_sizes_every_kv_structure(params):
    """Dense cache, page pool, the manager's block bytes, exported
    blocks: ``layers x passes`` planes, never ``layers``."""
    from distributed_inference_demo_tpu.runtime.kvcache import (
        PagedKVCacheManager, make_kv_backend)
    assert CFG.kv_planes == L * T == 12
    assert KVCache.create(CFG, L, 2, 32).keys.shape[0] == 12
    block = 2 * 12 * CFG.num_kv_heads * 8 * CFG.head_dim * 4
    assert PagedKVCacheManager.for_model(CFG, 4, 8).block_bytes == block
    backend = make_kv_backend(CFG, kv_cache_blocks=4, kv_block_tokens=8)
    assert backend._pk.shape[0] == 12
    with _engine(params, **MIXED) as eng:
        assert eng._pk.shape == (12, 40, CFG.num_kv_heads, 8, CFG.head_dim)
        assert eng.kv_cache.block_bytes == block
        st = eng.stats()["loop"]
        assert (st["ut_steps"], st["kv_planes"],
                st["kv_bytes_per_token"]) == (3, 12, block // 8)


# ------------------------------------- logits against the plain reference

@pytest.mark.parametrize("mode", ["mixed", "serialized", "chunked"])
def test_served_logprobs_equal_the_float32_reference(params, mode):
    """Prefill (in chunks through the mixed slab, in one bucket, or in
    serialized chunks), then decode through the page pool: every emitted
    token's log-probability against the family's reference, and the
    tokens the reference would have chosen."""
    kw = {"mixed": MIXED, "serialized": {},
          "chunked": dict(prefill_chunk=8)}[mode]
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, CFG.vocab_size, size=n).astype(np.int32)
               for n in (21, 7)]
    with _engine(params, **kw) as eng:
        reqs = [eng.submit(p, 9) for p in prompts]
        outs = [np.asarray(r.wait(timeout=300)) for r in reqs]
        lps = [list(r.lps) for r in reqs]
    for p, o, lp in zip(prompts, outs, lps):
        ref = _reference(params, p, o)
        assert lp == pytest.approx(ref["logprobs"], abs=2e-4)
        assert [int(t) for t in o] == ref["best_ids"]


def test_stage_forward_equals_the_reference_and_a_hand_unrolled_stack(params):
    """The scan over passes against the same layer applied T x L times by
    hand through ``stage_forward`` of a ONE-pass config (its final norm
    after each pass, its head once): the loop adds nothing and drops
    nothing."""
    ids = jnp.asarray([[(5 * i + 2) % CFG.vocab_size for i in range(18)]])
    pos = jnp.arange(18)[None]
    spec = StageSpec(0, 1, 0, L)
    logits, cache = stage_forward(params, CFG, spec, ids,
                                  KVCache.create(CFG, L, 1, 32), pos)
    assert cache.keys.shape[0] == L * T
    once = CFG.replace(ut_steps=1)
    body = StageSpec(0, 2, 0, L)          # no final norm, no head
    x = ids
    for t in range(T):
        x, c = stage_forward(params, once, body, x,
                             KVCache.create(once, L, 1, 32), pos)
        # pass t's planes hold what a one-pass run over its input wrote
        np.testing.assert_allclose(cache.keys[t * L:(t + 1) * L], c.keys,
                                   atol=1e-5)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + CFG.norm_eps) * params.final_norm["w"]
    by_hand = jnp.einsum("bsh,hv->bsv", x, params.lm_head["w"])
    np.testing.assert_allclose(logits, by_hand, atol=1e-4)
    ref = _reference(params, ids[0, :10], ids[0, 10:])
    lp = jax.nn.log_softmax(logits[0], -1)
    assert [float(lp[t - 1, ids[0, t]]) for t in range(10, 18)] == \
        pytest.approx(ref["logprobs"], abs=2e-4)


def test_a_scratch_cache_of_one_pass_serves_one_whole_sequence(params):
    """``L`` planes (what a scorer that knows nothing of passes builds):
    exact for one call from position 0; any other count is refused."""
    ids = jnp.asarray([[3, 9, 27, 81, 243 % 256, 5, 15]])
    pos, spec = jnp.arange(7)[None], StageSpec(0, 1, 0, L)
    full, _ = stage_forward(params, CFG, spec, ids,
                            KVCache.create(CFG, L, 1, 16), pos)
    z = jnp.zeros((L, 1, CFG.num_kv_heads, 16, CFG.head_dim))
    scratch, _ = stage_forward(params, CFG, spec, ids,
                               KVCache(z, z, jnp.int32(0)), pos)
    np.testing.assert_array_equal(full, scratch)
    z = jnp.zeros((L + 1, 1, CFG.num_kv_heads, 16, CFG.head_dim))
    with pytest.raises(ValueError, match="3 x 4 planes"):
        stage_forward(params, CFG, spec, ids, KVCache(z, z, jnp.int32(0)),
                      pos)


# ------------------------------------------------ passes own their planes

@pytest.mark.parametrize("t,l", [(0, 1), (1, 2), (2, 0), (2, 3)])
def test_poisoning_a_plane_changes_only_what_its_pass_reads(params, t, l):
    """Through the page pool.  After a clean prefill, plane ``t*L + l``
    is overwritten; one decode step then writes the new token's keys into
    every plane.  Planes up to and including the poisoned one get the
    clean run's keys (nothing before pass ``t``'s layer ``l`` read it:
    not layer ``l`` of another pass, not another layer of pass ``t``),
    every later plane differs, and so do the logits."""
    bt, W, n = 8, 3, 13
    fwd, bind, _ = make_paged_forward_seam(CFG, StageSpec(0, 1, 0, L), None,
                                           params, bt)
    pk, pv = alloc_kv_pool((CFG.kv_planes, W, CFG.num_kv_heads, bt,
                            CFG.head_dim), "bf16", CFG.dtype)
    tables = jnp.arange(W, dtype=jnp.int32)[None]
    ids = jnp.asarray([[(11 * i + 4) % CFG.vocab_size for i in range(n)]])

    def run(pk, pv, x, pos):
        bind(tables, "test")
        logits, c = fwd(params, x, KVCache(pk, pv, jnp.int32(0)), pos,
                        x.shape[1] - 1)
        return logits, c.keys, c.values

    _, pk, pv = run(pk, pv, ids, jnp.arange(n)[None])
    tok, pos = jnp.asarray([[7]]), jnp.asarray([[n]])
    clean, ck, _ = run(pk, pv, tok, pos)
    p = t * L + l
    bad, bk, _ = run(pk.at[p].add(0.5), pv.at[p].add(-0.5), tok, pos)
    page, col = n // bt, n % bt
    new_clean, new_bad = ck[:, page, :, col], bk[:, page, :, col]
    np.testing.assert_array_equal(new_clean[:p + 1], new_bad[:p + 1])
    later = np.abs(np.asarray(new_clean[p + 1:] - new_bad[p + 1:]))
    assert all(plane.max() > 1e-6 for plane in later)
    assert float(jnp.abs(clean - bad).max()) > 1e-4


# -------------------------------------- pool, radix tree, tier, migration

def test_prefix_sharing_gives_the_cold_prompt_s_logprobs(params):
    """A prompt whose first two pages are in the radix tree reads them in
    place, in every pass's planes: the same tokens and log-probabilities
    as the engine that never saw the prefix."""
    shared = list(range(5, 21))                       # two whole pages
    prompt = np.asarray(shared + [77, 78, 79], np.int32)
    with _engine(params, **MIXED) as cold:
        r = cold.submit(prompt, 8)
        want, want_lps = r.wait(timeout=300), list(r.lps)
    with _engine(params, **MIXED) as eng:
        eng.submit(np.asarray(shared + [99], np.int32), 3).wait(timeout=300)
        r = eng.submit(prompt, 8)
        got = r.wait(timeout=300)
        assert eng.stats()["kvcache"]["partial_hit_tokens"] == len(shared)
    np.testing.assert_array_equal(want, got)
    assert list(r.lps) == pytest.approx(want_lps, abs=1e-5)


def test_export_import_and_resume_round_trip(params):
    """A request frozen mid-decode ships ``[n, T x L, H, bt, D]`` blocks,
    lands in another engine's pool and finishes with the unmigrated
    run's tokens; ``submit_resumed`` re-derives a dead replica's
    delivered prefix the same way."""
    prompt = np.arange(3, 24, dtype=np.int32)
    with _engine(params, **MIXED) as src, _engine(params, **MIXED) as dst:
        ref = [int(t) for t in src.submit(prompt, 12).wait(timeout=300)]
        req = src.submit(prompt, 12, request_id="seam")
        for _ in range(2000):
            if len(req.tokens) >= 2:
                break
            time.sleep(0.005)
        ckpt = src.export_request("seam", detach=True)
        assert ckpt["k"].shape[1:] == (CFG.kv_planes, CFG.num_kv_heads, 8,
                                       CFG.head_dim)
        resumed = dst.import_request(ckpt)
        assert [int(t) for t in resumed.wait(60)] == ref
        again = dst.submit_resumed(prompt, 12, ref[:5])
        assert [int(t) for t in again.wait(60)] == ref
        # premigrated blocks of a one-pass shape are refused by shape
        thin = np.zeros((1, L, CFG.num_kv_heads, 8, CFG.head_dim),
                        np.float32)
        with pytest.raises(ValueError, match=r"\[n, 12, 4, 8, 16\]"):
            dst.submit_premigrated(prompt, 4, thin, thin)


def test_host_tier_blocks_hold_every_pass_s_planes(params):
    """A pool too small for two working sets demotes the first prompt's
    pages to the host tier whole (all ``T x L`` planes a block) and
    promotes them back: the same tokens as the cold run."""
    a = np.arange(1, 18, dtype=np.int32)
    b = np.arange(101, 118, dtype=np.int32)
    with _engine(params, kv_cache_blocks=7, kv_block_tokens=4,
                 kv_host_tier_bytes=1 << 22, **MIXED) as eng:
        tier = eng._kv_tier
        cold = eng.submit(a, 6).wait(timeout=300)
        eng.submit(b, 6).wait(timeout=300)            # evicts, so demotes
        assert tier.stats["demoted_blocks"] > 0
        assert tier.stats["demote_errors"] == 0
        block = 2 * CFG.kv_planes * CFG.num_kv_heads * 4 * CFG.head_dim * 4
        assert tier.host_resident_bytes % block == 0
        warm = eng.submit(a, 6).wait(timeout=300)
        assert tier.stats["promoted_blocks"] > 0
    np.testing.assert_array_equal(cold, warm)


# --------------------------------------------------- spans and counters

def test_stats_loop_sums_equal_the_dispatch_records(params):
    prompts = [np.arange(1, n, dtype=np.int32) for n in (30, 8, 19)]
    with _engine(params, **MIXED) as eng:
        for r in [eng.submit(p, 10) for p in prompts]:
            r.wait(timeout=300)
        st = _settled(eng)
    dt, loop = st["dispatch_trace"], st["loop"]
    assert dt["fields"] == list(DISPATCH_FIELDS + LOOP_DISPATCH_FIELDS
                                + DISPATCH_LAST_FIELDS)
    recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
    assert len(recs) == loop["dispatches"] == dt["seq"]
    for r in recs:
        # (a step that rode the slab's pass is no pass of its own)
        assert r["ut_passes"] == ((r["segments"] > 0) + r["steps"]
                                  - (r["slab_carried_step"] > 0)) * T
    assert any(r["slab_carried_step"] for r in recs)
    assert loop["slab_passes"] == T * sum(r["segments"] > 0 for r in recs)
    assert loop["decode_passes"] == T * sum(
        r["steps"] - (r["slab_carried_step"] > 0) for r in recs)
    assert sum(r["steps"] for r in recs) == st["device_loop"][
        "device_loop_steps"]
    assert loop["slab_passes"] + loop["decode_passes"] == sum(
        r["ut_passes"] for r in recs)


def test_loop_counters_by_hand():
    c = LoopCounters(4, 192, 1_572_864)
    assert c.add(slab=True, steps=4) == {"ut_passes": 20}
    assert c.add(slab=False, steps=3) == {"ut_passes": 12}
    assert c.snapshot() == {
        "ut_steps": 4, "kv_planes": 192, "kv_bytes_per_token": 1_572_864,
        "dispatches": 2, "slab_passes": 4, "decode_passes": 28}
    c.reset()
    assert c.snapshot()["decode_passes"] == 0


def test_each_pass_runs_under_the_ut_pass_scope(params):
    """In both halves of ``mixed_step`` (the compiled ops' names, which
    are what a device trace keeps), and one layer body however many
    passes: as many matmuls as a one-pass model's program."""
    def lowered(cfg, p):
        with _engine(p, cfg=cfg, **MIXED) as eng:
            return eng._mixed_step.inner.lower(
                *abstract_mixed_call(eng, slab=True))

    looped = lowered(CFG, params)
    names = set(re.findall(r'op_name="([^"]*)/ut_pass/',
                           looped.compile().as_text()))
    assert any(n.startswith("jit(mixed_step)/slab_body/") for n in names)
    assert any(n.startswith("jit(mixed_step)/decode_loop/") for n in names)
    once = CFG.replace(ut_steps=1)
    flat = lowered(once, init_full_params(jax.random.PRNGKey(0), once))
    assert '"ut_pass/' not in flat.as_text(debug_info=True)
    assert '"ut_pass/' in looped.as_text(debug_info=True)
    assert looped.as_text().count("dot_general") == \
        flat.as_text().count("dot_general")


# ------------------------------------------------------------- refusals

def _seeded(cfg=CFG):
    return init_full_params(jax.random.PRNGKey(0), cfg)


def _two_stage_forward():
    z = jnp.zeros((2, 1, CFG.num_kv_heads, 8, CFG.head_dim))
    stage_forward(_seeded(), CFG, StageSpec(0, 2, 0, 2),
                  jnp.zeros((1, 4), jnp.int32), KVCache(z, z, jnp.int32(0)),
                  jnp.arange(4)[None])


def _stage_worker():
    from distributed_inference_demo_tpu.runtime.distributed import (
        StageRuntime)
    StageRuntime(CFG, StageSpec(0, 2, 0, 2), None, 64)


def _circular_pipeline():
    from distributed_inference_demo_tpu.parallel.pipeline import (
        make_pipeline_generate_fn)
    make_pipeline_generate_fn(CFG, None, max_seq=32, num_new_tokens=4)


def _draft_in_the_slot_loop():
    target = get_model_config("llama-test")
    ContinuousBatchingEngine(target, _seeded(target), draft_cfg=CFG,
                             draft_params=_seeded(), sampling=GREEDY)


def _speculative_engine():
    from distributed_inference_demo_tpu.runtime.speculative import (
        SpeculativeEngine)
    SpeculativeEngine(get_model_config("llama-test"), None, CFG, None)


def _ring():
    from distributed_inference_demo_tpu.parallel.sequence import (
        _make_ring_cores)
    _make_ring_cores(CFG, StageSpec(0, 1, 0, L), 16, GREEDY, None)


def _ulysses():
    from distributed_inference_demo_tpu.parallel.ulysses import (
        _make_ulysses_cores)
    _make_ulysses_cores(CFG, 32, 2, GREEDY, None)


def _loader():
    from distributed_inference_demo_tpu.models.loader import (
        params_from_state_dict)
    params_from_state_dict({}, CFG)


LOOPED = "does not support a looped model"
REFUSALS = {
    "pipeline stages": (ValueError, LOOPED, lambda: slice_stage(
        _seeded(), CFG, split_layer_ranges(L, 2)[0])),
    "a stage of two": (ValueError, LOOPED, _two_stage_forward),
    "stage worker": (ValueError, LOOPED, _stage_worker),
    "circular pipeline": (ValueError, "circular pipeline does not",
                          _circular_pipeline),
    "draft": (ValueError, "draft side of speculation",
              _draft_in_the_slot_loop),
    "speculative engine": (ValueError, "draft side of speculation",
                           _speculative_engine),
    "ring sequence parallelism": (ValueError, "ring sequence parallelism",
                                  _ring),
    "ulysses": (ValueError, "Ulysses sequence", _ulysses),
    "loader": (NotImplementedError,
               "no state-dict mapper for family 'ouro'", _loader),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_visits_a_layer_once_refuses_a_looped_model(what):
    error, sentence, build = REFUSALS[what]
    with pytest.raises(error, match=sentence):
        build()


def test_serve_chain_refuses_a_looped_model_in_a_sentence(capsys):
    from distributed_inference_demo_tpu import cli
    assert cli.main(["serve", "--model", "ouro-test", "--chain",
                     "w1@127.0.0.1:1", "--device-id", "h"]) == 1
    assert "does not support a looped model" in capsys.readouterr().err


# ------------------------------------- a one-pass model is what it was

def _parent_engine(model):
    cfg = get_model_config(model)
    return ContinuousBatchingEngine(
        cfg, init_full_params(jax.random.PRNGKey(0), cfg), max_seq=96,
        max_batch=4, sampling=GREEDY, kv_block_tokens=8, prefill_chunk=8,
        decode_block=4, mixed_token_budget=24)


@pytest.mark.parametrize("model", ["qwen2-test", "bloom-test", "olmoe-test"])
def test_one_pass_record_and_stats_are_the_parent_s(model):
    with _parent_engine(model) as eng:
        eng.submit(np.arange(1, 20, dtype=np.int32), 4).wait(timeout=300)
        st = eng.stats()
    assert "loop" not in st
    # the parent's columns, and those every record got since (PR 35:
    # `ahead`, PR 41: `late` and `await`), after `kv_tokens` and before
    # a model's own columns
    fields = list(PARENT["fields"][model])
    at = fields.index("kv_tokens") + 1
    # (... and PR 61's after them: the rows that rode the slab's pass)
    fields[at:at] = ["ahead", "late", "await", "slab_carried_step"]
    # ... and PR 47's, what the prefill kernel's page loop walks
    at = fields.index("prefill_tokens") + 1
    fields[at:at] = ["prefill_pages_walked"]
    # ... and PR 48's, the rows the head ran over
    fields[at + 1:at + 1] = ["head_rows"]
    # ... and PR 54's, after a model's own columns: enqueued early or not
    fields.append("early")
    assert st["dispatch_trace"]["fields"] == fields
