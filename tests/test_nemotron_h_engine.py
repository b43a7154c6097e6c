"""Blocks of ONE sublayer (family ``nemotron_h``, PR 66) in the ENGINE: a
period of which the ``E`` blocks hold no plane of any pool, the state pool
sized by the grouped ssd kind's shapes, the pages of the attention blocks
alone; prefill in the chunk form and decode in the step form against one
causal forward (tokens, log-probabilities and the state a request ends in);
the record's columns counted over the blocks that have them (four ``E``,
four ``M``); ``/stats.blocks``; a state fault far from the dense forward.
CPU, toy widths (``nemotron-h-test``); ``tests/test_nemotron_h.py`` holds
the model."""
import base64

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import KVCache, StageSpec
from distributed_inference_demo_tpu.models.decoder import (init_full_params,
                                                           stage_forward)
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops import kda, ssd
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from test_mixed_batching import settle

CFG = get_model_config("nemotron-h-test")
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
    lp = jax.nn.log_softmax(logits[0, 0].astype(jnp.float32))
    return jnp.argmax(lp), jnp.max(lp), cache


def _dense(params, prompt, new):
    """``(greedy tokens, their log-probabilities, the dense cache after
    them)``: no page, no table, no row of a pool; the prompt padded to one
    length and told which positions hold a token."""
    n = len(prompt)
    ids = jnp.asarray([list(prompt) + [0] * (48 - n)], jnp.int32)
    cache = KVCache.create(CFG, CFG.num_layers, 1, 128)
    tok, lp, cache = _dense_forward(params, ids, cache, jnp.int32(0),
                                    jnp.int32(n - 1))
    out, lps = [int(tok)], [float(lp)]
    for t in range(n, n + new - 1):
        tok, lp, cache = _dense_forward(params, jnp.asarray([[out[-1]]]),
                                        cache, jnp.int32(t), jnp.int32(0))
        out.append(int(tok))
        lps.append(float(lp))
    return out, lps, cache


@pytest.fixture(scope="module")
def want(params):
    return [_dense(params, p, NEW) for p in PROMPTS]


def _serve(eng, prompts, new=NEW):
    reqs = [eng.submit(np.asarray(p, np.int32), new) for p in prompts]
    out = [r.wait(timeout=300).tolist() for r in reqs]
    settle(eng)
    return out, reqs


def _sample(record):
    return np.frombuffer(base64.b64decode(record["float32_b64"]),
                         "<f4").reshape(record["shape"])


def _state_err(res, cache):
    """The reply's sample of the state against the dense cache's, over the
    latter's largest magnitude."""
    record = res.generation[0]["ssd_state"]
    # ... and the log-probabilities once more, for the family's own limit
    assert res.generation[0]["logprobs"] == res.logprobs[0].tolist()
    assert record["pool_dtype"] == "float32"
    assert record["heads"] == [0, 2, 4, 6] and record["keys"] == [0, 8]
    assert record["shape"] == [CFG.state_planes, 4, 2, 16] == [4, 4, 2, 16]
    dense = np.asarray(cache.keys[-1])[:, 0, ::2, ::8]
    return float(np.abs(_sample(record) - dense).max() / np.abs(dense).max())


# --------------------------------------------- tokens, state and the records

def test_six_requests_over_three_slots_are_the_dense_forward(params, want):
    """Rows of the state pool and slots are reused by later requests; the
    pools hold a plane a block that has a cache and none for an ``E``
    block; the counters count the blocks that have what they count."""
    with _engine(params) as eng:
        before = (eng._pk[-1], eng._pv[-1])
        # 4 M planes of 10 blocks; 2 attention planes; the E blocks none
        assert before[0].shape == (4, 5, 8, 16, 16)
        assert before[0].dtype == jnp.float32
        assert before[1].shape == (4, 5, 576)
        assert eng._pk[0].shape[0] == 2 and len(eng._pk) == 2
        assert eng._table_cols == eng._table_width + 1
        got, reqs = _serve(eng, PROMPTS)
        assert all(r._pkv["table"][-1] == r._pkv["state_row"] for r in reqs)
        stats = eng.stats()
        said = [eng.generate(np.asarray(PROMPTS[i], np.int32), NEW,
                             logprobs=True) for i in (2, 4)]
        paths = eng.stats()["attention_paths"]
        gmm_calls = eng.stats()["moe"]["gmm"]
    assert got == [w[0] for w in want]
    assert stats["blocks"] == {
        "kinds": {"ssd": 4, "mlp": 4, "full": 2},
        "planes": {"pages": [2], "state": 4}, "with_experts": 4}
    st = stats["kvcache"]["kinds"]["state"]
    assert st["bytes_per_slot"] == CFG.state_bytes_per_slot == 4 * (
        8 * 16 * 16 * 4 + 3 * 192 * 4)
    assert st["zeroed"] == len(PROMPTS)
    trace = stats["dispatch_trace"]
    at = {f: i for i, f in enumerate(trace["fields"])}
    rows = trace["recent"]
    tokens = sum(len(p) for p in PROMPTS)
    assert sum(r[at["ssd_chunk_tokens"]] for r in rows) == tokens
    assert sum(r[at["ssd_row_steps"]] for r in rows) == len(PROMPTS) * (
        NEW - 1) == st["row_steps"]
    # every token routed top-2 in each of the FOUR E blocks, not the ten
    moe = stats["moe"]
    assert moe["valid_rows"] == sum(r[at["moe_valid_rows"]] for r in rows)
    # (a row that finishes inside a fused block steps to the block's end)
    least = (tokens + len(PROMPTS) * (NEW - 1)) * 2 * 4
    assert least <= moe["valid_rows"] < 1.2 * least
    assert moe["valid_rows"] % (2 * 4) == 0
    assert moe["experts"] == 4 and moe["experts_routed"] == 8
    assert 0 < moe["rows"] < moe["valid_rows"]
    assert moe["rows_absent"] == moe["valid_rows"] - moe["rows"]
    assert moe["layer_calls"] % 4 == 0      # four E blocks a pass
    assert set(paths) == {"mixed_step/ssd", "mixed_step/full"}
    assert paths["mixed_step/ssd"] == {"chunk=1": "xla_ssd: platform cpu",
                                       "chunk=8": "xla_ssd: platform cpu"}
    assert gmm_calls == []      # width 24: under the lanes, no kernel form
    for res, i in zip(said, (2, 4)):
        toks, lps, cache = want[i]
        assert res.tokens[0].tolist() == toks
        np.testing.assert_allclose(res.logprobs[0], lps, atol=2e-5)
        assert _state_err(res, cache) < 1e-4


def test_a_prefill_chunk_of_two_scan_chunks_and_a_wide_block(params, want):
    with _engine(params, prefill_chunk=16, mixed_token_budget=32,
                 decode_block=8, max_batch=6) as eng:
        got, _ = _serve(eng, PROMPTS)
        res = eng.generate(np.asarray(PROMPTS[0], np.int32), NEW,
                           logprobs=True)
    assert got == [w[0] for w in want]
    np.testing.assert_allclose(res.logprobs[0], want[0][1], atol=2e-5)
    assert _state_err(res, want[0][2]) < 1e-4


@pytest.mark.parametrize("fault", ["not_carried", "tail_dropped"])
def test_a_state_fault_in_the_engine_is_far_from_the_dense_forward(
        params, want, fault, monkeypatch):
    """The controls: every segment's state started from zero, every
    segment's convolution from zeros (the sound engine: under 1e-4)."""
    if fault == "not_carried":
        inner = ssd.ssd_chunk
        monkeypatch.setattr(
            ssd, "ssd_chunk", lambda state, plane, row, fresh, *a, **k:
            inner(state, plane, row, jnp.bool_(True), *a, **k))
    else:
        conv = kda.causal_conv
        monkeypatch.setattr(
            kda, "causal_conv", lambda u, tail, w, ntok, bias=None: conv(
                u, jnp.zeros_like(tail) if u.shape[1] > 1 else tail, w,
                ntok, bias))
    with _engine(params) as eng:
        res = eng.generate(np.asarray(PROMPTS[2], np.int32), NEW,
                           logprobs=True)
    assert _state_err(res, want[2][2]) > 0.05
    assert np.abs(res.logprobs[0] - np.asarray(want[2][1])).max() > 1e-4
