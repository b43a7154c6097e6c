"""A model with a recurrent state (family ``solar_open2``, PR 56) in the
ENGINE: a row of the state pool a request beside its pages, leased at
admission and started from zero by the request's first segment; greedy
tokens against the dense forward whatever the neighbours, the fused
block, the slab's packing or the order of enqueueing; what a row that
holds no token, a padded position and a row past its budget leave alone;
the counters; and every refusal's sentence.  CPU, toy widths
(``solar-open2-test``); ``tests/test_solar_open2.py`` holds the model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import (KVCache, StageSpec,
                                                        require_no_state,
                                                        require_token_rows,
                                                        slice_stage)
from distributed_inference_demo_tpu.models.decoder import (init_full_params,
                                                           stage_forward)
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from test_mixed_batching import settle

CFG = get_model_config("solar-open2-test")
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
GREEDY = SamplingParams(temperature=0.0)
NEW = 10


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _engine(params, **kw):
    kw.setdefault("max_seq", 128)
    kw.setdefault("max_batch", 3)
    kw.setdefault("kv_block_tokens", 8)
    kw.setdefault("kv_cache_blocks", 48)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_block", 4)
    kw.setdefault("mixed_token_budget", 24)
    return ContinuousBatchingEngine(CFG, params, sampling=GREEDY, **kw)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


PROMPTS = [_prompt(n, i) for i, n in enumerate((21, 9, 40, 3, 16, 27))]


@jax.jit
def _dense_forward(params, ids, cache, start, last):
    pos = start + jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    cache = KVCache(cache.keys, cache.values, start)
    logits, cache = stage_forward(
        params, CFG, SPEC, ids, cache, pos, logits_at=last,
        valid=(jnp.arange(ids.shape[1]) <= last)[None])
    return jnp.argmax(logits[0, 0]), cache


def _dense(params, prompt, new):
    """``(greedy tokens, the dense cache after them)``: no page, no table,
    no row of a pool; the prompt padded to one length and told which
    positions hold a token, so two programs serve the whole file."""
    n = len(prompt)
    ids = jnp.asarray([list(prompt) + [0] * (48 - n)], jnp.int32)
    cache = KVCache.create(CFG, CFG.num_layers, 1, 128)
    tok, cache = _dense_forward(params, ids, cache, jnp.int32(0),
                                jnp.int32(n - 1))
    out = [int(tok)]
    for t in range(n, n + new - 1):
        tok, cache = _dense_forward(params, jnp.asarray([[out[-1]]]), cache,
                                    jnp.int32(t), jnp.int32(0))
        out.append(int(tok))
    return out, cache


@pytest.fixture(scope="module")
def want(params):
    return [_dense(params, p, NEW)[0] for p in PROMPTS]


def _serve(eng, prompts, new=NEW):
    reqs = [eng.submit(np.asarray(p, np.int32), new) for p in prompts]
    out = [r.wait(timeout=300).tolist() for r in reqs]
    settle(eng)
    return out, reqs


# ------------------------------------------------------------ the tokens

def test_six_requests_over_three_slots_are_the_dense_forward(params, want):
    """Rows of the state pool and slots are reused by later requests,
    prompts run as one segment, several, and a partial last one."""
    with _engine(params) as eng:
        got, _ = _serve(eng, PROMPTS)
        st = eng.stats()["kvcache"]["kinds"]["state"]
        fields = eng.stats()["dispatch_trace"]["fields"]
        rows = eng.stats()["dispatch_trace"]["recent"]
    assert got == want
    assert st["slots"] == 4 and st["held"] == 0 and st["held_peak"] <= 4
    assert st["bytes_per_slot"] == CFG.state_bytes_per_slot
    assert st["zeroed"] == len(PROMPTS)     # a first segment a request
    # the two columns sum to what the requests asked for: every prompt
    # token once through the chunk form, every later token a row-step
    at = {f: i for i, f in enumerate(fields)}
    assert sum(r[at["kda_chunk_tokens"]] for r in rows) == sum(
        len(p) for p in PROMPTS) == st["chunk_tokens"]
    assert sum(r[at["kda_row_steps"]] for r in rows) == len(PROMPTS) * (
        NEW - 1) == st["row_steps"]
    assert fields[-1] == "early"    # a model's own columns come before it


def test_a_slot_used_again_gives_a_fresh_engine_s_tokens(params, want):
    """One slot, one request after another: the second starts in the row
    and the slot the first left dirty."""
    with _engine(params, max_batch=1) as eng:
        first, _ = _serve(eng, PROMPTS[:1])
        second, reqs = _serve(eng, PROMPTS[2:3])
        assert reqs[0]._pkv["state_row"] in (0, 1)
    with _engine(params, max_batch=1) as fresh:
        alone, _ = _serve(fresh, PROMPTS[2:3])
    assert first == want[:1] and second == alone == want[2:3]


@pytest.mark.parametrize("kw", [
    dict(decode_block=1),
    dict(decode_block=8),
    dict(mixed_token_budget=8),             # one segment a dispatch
    dict(mixed_token_budget=48, max_batch=2),
    dict(max_batch=6),                      # every request beside the others
    dict(prefill_chunk=16, mixed_token_budget=32),
], ids=lambda kw: ",".join(f"{k}={v}" for k, v in kw.items()))
def test_tokens_do_not_depend_on_the_block_the_slab_or_the_neighbours(
        params, want, kw):
    with _engine(params, **kw) as eng:
        got, _ = _serve(eng, PROMPTS)
    assert got == want


def test_an_early_enqueue_changes_no_token(params, want, monkeypatch):
    """Dispatches enqueued behind their predecessor (a full slab, no
    ``eos``) against the same traffic with every one packed in the gap:
    the device orders them by the state arrays they hand on."""
    with _engine(params, mixed_token_budget=8) as eng:
        got, _ = _serve(eng, PROMPTS * 2)
        early = eng.stats()["dispatch_trace"]["ahead_early"]
    assert early > 0
    monkeypatch.setattr(ContinuousBatchingEngine, "_plan_ahead",
                        lambda self, flight: (None, "other"))
    with _engine(params, mixed_token_budget=8) as eng:
        old, _ = _serve(eng, PROMPTS * 2)
        assert eng.stats()["dispatch_trace"]["ahead_early"] == 0
    assert got == old == want * 2


def test_a_dispatch_hands_the_state_on_by_donation(params):
    """The state pool and the convolution tails are donated to
    ``mixed_step`` with the pages: the arrays a dispatch was given are
    gone when it is enqueued, and the next can only have its outputs."""
    with _engine(params) as eng:
        before = (eng._pk[-1], eng._pv[-1], eng._pk[0])
        assert before[0].shape == (6, 5, 4, 16, 16)
        assert before[0].dtype == jnp.float32
        assert before[1].shape == (6, 5, 3, 192)
        _serve(eng, PROMPTS[:1])
        assert all(a.is_deleted() for a in before)
        assert not eng._pk[-1].is_deleted()


# ------------------------------------------------- what is left alone

def test_idle_padded_and_over_budget_rows_leave_the_state_alone(params):
    """Request B asks for 2 tokens under a fused block of 8 beside a
    longer request: its row steps on for 6 steps past its budget.  Its
    state and tail are then what prompt + token #1 leave (11 = 8 + 3: the
    last segment padded by 5), and the rows nobody leased are zero to the
    bit."""
    with _engine(params, decode_block=8, max_batch=3) as eng:
        a = eng.submit(np.asarray(PROMPTS[0], np.int32), 24)
        b = eng.submit(np.asarray(_prompt(11, 9), np.int32), 2)
        toks_b = b.wait(timeout=300).tolist()
        a.wait(timeout=300)
        settle(eng)
        row_a, row_b = a._pkv["state_row"], b._pkv["state_row"]
        state, tails = np.asarray(eng._pk[-1]), np.asarray(eng._pv[-1])
    assert {row_a, row_b} == {0, 1}
    want_b, cache = _dense(params, _prompt(11, 9), 2)
    assert toks_b == want_b
    # the dense cache after prompt + token #1 (its last call fed token #1)
    np.testing.assert_allclose(state[:, row_b], np.asarray(cache.keys[-1])[:, 0],
                               atol=1e-5)
    np.testing.assert_allclose(tails[:, row_b],
                               np.asarray(cache.values[-1])[:, 0], atol=1e-5)
    # rows 2 and 3 were never leased (two requests): not one bit moved
    assert not state[:, 2:4].any() and not tails[:, 2:4].any()


def test_an_idle_engine_s_warm_up_moves_no_leased_row(params):
    """Every variant is launched before the engine is ready, on tables
    of sentinels: only the last row, nobody's, may hold anything."""
    with _engine(params) as eng:
        state = np.asarray(eng._pk[-1])
    assert not state[:, :-1].any()


# ------------------------------------------------------------ the records

def _sample(record):
    import base64
    return np.frombuffer(base64.b64decode(record["float32_b64"]),
                         "<f4").reshape(record["shape"])


def test_a_reply_with_log_probabilities_says_the_state_it_ended_in(params):
    """``generate(logprobs=True)`` carries, a sequence, a sample of its row
    of the state pool as the request left it: the dense forward's state
    after prompt + all emitted tokens but the last, whatever ran beside
    it and whichever row it held; a reply without log-probabilities
    carries none."""
    with _engine(params, decode_block=8) as eng:
        beside = eng.submit(np.asarray(PROMPTS[0], np.int32), 30)
        first = eng.generate(np.asarray(PROMPTS[2], np.int32), NEW,
                             logprobs=True)
        again = eng.generate(np.asarray(PROMPTS[4], np.int32), NEW,
                             logprobs=True)
        plain = eng.generate(np.asarray(PROMPTS[3], np.int32), NEW)
        beside.wait(timeout=300)
        settle(eng)
    assert plain.generation is None and plain.logprobs is None
    for res, prompt in ((first, PROMPTS[2]), (again, PROMPTS[4])):
        toks, cache = _dense(params, prompt, NEW)
        assert res.tokens[0].tolist() == toks
        (said,) = res.generation
        record = said["kda_state"]
        assert record["pool_dtype"] == "float32"
        assert record["heads"] == [0, 1, 2, 3] and record["keys"] == [0, 8]
        assert record["shape"] == [CFG.state_planes, 4, 2, 16]
        np.testing.assert_allclose(
            _sample(record), np.asarray(cache.keys[-1])[:, 0, :, ::8],
            atol=1e-4)      # segments of 8 against one of 48


def test_the_http_reply_carries_the_record(params):
    """``POST /generate`` with ``logprobs`` answers ``generation`` beside
    ``tokens`` and ``logprobs``, one entry a sequence, JSON as it is."""
    import json
    import urllib.request

    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)
    with _engine(params) as eng:
        srv = InferenceHTTPServer(eng, port=0)
        srv.start()
        try:
            def post(body):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/generate",
                    json.dumps(body).encode(),
                    {"Content-Type": "application/json"})
                return json.loads(urllib.request.urlopen(req, timeout=300)
                                  .read())
            out = post({"prompt_ids": [PROMPTS[1]], "max_new_tokens": 4,
                        "logprobs": True})
            bare = post({"prompt_ids": [PROMPTS[1]], "max_new_tokens": 4})
        finally:
            srv.shutdown()
    assert len(out["generation"]) == len(out["tokens"]) == 1
    assert _sample(out["generation"][0]["kda_state"]).shape == (
        CFG.state_planes, 4, 2, 16)
    assert "generation" not in bare and bare["tokens"] == out["tokens"]


def test_a_request_leases_its_row_with_its_pages(params):
    with _engine(params, max_batch=2) as eng:
        assert eng._table_cols == eng._table_width + 1
        reqs = [eng.submit(np.asarray(p, np.int32), 4) for p in PROMPTS[:5]]
        for r in reqs:
            r.wait(timeout=300)
        settle(eng)
        st = eng.stats()["kvcache"]["kinds"]["state"]
        # (the slots and one admission more: never a fourth row)
        assert st["slots"] == 3 and st["held_peak"] <= 3 and st["held"] == 0
        assert sorted(eng._state_free) == [0, 1, 2]
        assert all(r._pkv["table"][-1] == r._pkv["state_row"] for r in reqs)
        # prefix sharing is off: the same prompt again matches nothing
        eng.submit(np.asarray(PROMPTS[2], np.int32), 2).wait(timeout=300)
        assert eng.stats()["kvcache"]["hits"] == 0


# ------------------------------------------------------------ the refusals

def _refused(build):
    with pytest.raises(ValueError, match="recurrent state") as e:
        build()
    msg = str(e.value)
    assert "solar_open2" in msg and "Serve it on one chip" in msg
    return msg


@pytest.mark.parametrize("what,kw", [
    ("the serialized interleave", dict(mixed_token_budget=0)),
    ("speculation", dict(prompt_lookup=True)),
    ("a page pool of int8 pages", dict(kv_dtype="int8")),
    ("the host tier", dict(kv_host_tier_bytes=1 << 20)),
])
def test_the_engine_refuses_in_a_sentence(params, what, kw):
    assert what in _refused(lambda: _engine(params, **kw))


def test_a_draft_with_a_state_is_refused(params):
    llama = get_model_config("llama-test").replace(vocab_size=256)
    lp = init_full_params(jax.random.PRNGKey(1), llama)
    msg = _refused(lambda: ContinuousBatchingEngine(
        llama, lp, max_seq=64, max_batch=2, sampling=GREEDY,
        draft_cfg=CFG, draft_params=params))
    assert "the draft side of speculation" in msg


def test_migration_is_refused_in_a_sentence(params):
    with _engine(params) as eng:
        req = eng.submit(np.asarray(PROMPTS[0], np.int32), 4)
        assert "export_request" in _refused(
            lambda: eng.export_request(req.rid))
        assert "import_request" in _refused(
            lambda: eng.import_request({"tokens": [1], "length": 4}))
        req.wait(timeout=300)


@pytest.mark.parametrize("what", ["a pipeline of stages",
                                  "tensor parallelism (--tp)",
                                  "ring sequence parallelism",
                                  "Ulysses sequence parallelism"])
def test_what_splits_a_request_refuses_in_a_sentence(what):
    # one refusal for every cache that is not a row a token: whatever
    # asks for token rows refuses a state, by the state's own sentence
    msg = _refused(lambda: require_token_rows(CFG, what))
    assert msg.startswith(what) and "recurrent state" in msg
    assert msg == _refused(lambda: require_no_state(CFG, what))
    require_token_rows(get_model_config("laguna-test"), what)   # has none


def test_a_pipeline_and_the_loader_refuse(params):
    from distributed_inference_demo_tpu.models import loader
    _refused(lambda: slice_stage(params, CFG, StageSpec(0, 2, 0, 1)))
    with pytest.raises(NotImplementedError, match="solar_open2"):
        loader.params_from_state_dict({}, CFG)
