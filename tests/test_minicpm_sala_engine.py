"""MiniCPM-SALA's two kinds of block (family ``minicpm_sala``, PR 69) in the
ENGINE: a period of which the sparse blocks hold pages and, beside them,
rows of the index plane, and the linear blocks a row of the state pool;
prefill in slabs and decode in fused blocks against the family's plain
reference (tokens, log-probabilities, the state a request ends in); rows
under and over ``dense_len`` in one dispatch; pages, index rows and state
rows leased again by later requests; the record's columns and
``/stats.sparse``; a selection fault far from the reference; what the
model does not have refused in a sentence.  CPU, toy widths
(``minicpm-sala-test``); ``tests/test_minicpm_sala.py`` holds the model."""
import base64
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import (require_no_state,
                                                        require_one_kind)
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.models.registry import get_model_config
from distributed_inference_demo_tpu.ops import sparse_attention as sa
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from test_mixed_batching import settle

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import model_parity  # noqa: E402  (tools/)

CFG = get_model_config("minicpm-sala-test")
FAM = families.load("minicpm_sala")
GREEDY = SamplingParams(temperature=0.0)
NEW = 10
SIZES = CFG.sparse_kind.sparse_sizes


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(3), CFG)


def _engine(params, **kw):
    kw.setdefault("max_seq", 320)
    kw.setdefault("max_batch", 3)
    kw.setdefault("kv_block_tokens", 16)
    kw.setdefault("kv_cache_blocks", 48)    # under six requests' 66 pages
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("decode_block", 4)
    kw.setdefault("mixed_token_budget", 76)
    return ContinuousBatchingEngine(CFG, params, sampling=GREEDY, **kw)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


# 30 and 40 stay under dense_len (48) to their last token, 45 crosses it
# while decoding, the others are past it one to five times over
PROMPTS = [_prompt(n, i) for i, n in enumerate((150, 40, 233, 97, 30, 45))]


@pytest.fixture(scope="module")
def want(params):
    """The family's reference over each prompt and the tokens the engine
    emitted for it (greedy both ways): filled in by the first test, read
    by the others."""
    return {}


def _reference(params, prompt, toks):
    ids = np.concatenate([prompt, np.asarray(toks, np.int32)])
    ref, _ = model_parity.reference_logprobs(CFG, params, ids, len(prompt))
    return ids, ref


def _sample(record):
    return np.frombuffer(base64.b64decode(record["float32_b64"]),
                         "<f4").reshape(record["shape"])


def test_six_requests_over_three_slots_are_the_family_s_reference(params):
    """Slots, pages with their index rows and rows of the state pool are
    leased again by later requests (48 pages for 66 pages' worth of
    requests); rows under and over ``dense_len`` share dispatches."""
    with _engine(params) as eng:
        reqs = [eng.submit(p, NEW) for p in PROMPTS]
        outs = [r.wait(timeout=600).tolist() for r in reqs]
        settle(eng)
        res = eng.generate(PROMPTS[2], NEW, logprobs=True)
        st = eng.stats()
    for p, out, r in zip(PROMPTS, outs, reqs):
        ids, ref = _reference(params, p, out)
        assert out == ref[:-1].argmax(-1).tolist()
        # float32 both ways: the sums' rounding alone (tests/
        # test_minicpm_sala.py reads 1e-6)
        np.testing.assert_allclose(
            r.lps, ref[np.arange(NEW), out], atol=2e-4)
    # the reply with log-probabilities carries the state it ended in
    assert res.tokens[0].tolist() == outs[2]
    record = res.generation[0]["lightning_state"]
    assert record["pool_dtype"] == "float32"
    assert record["shape"] == [CFG.state_planes, 4, 2, 16] == [4, 4, 2, 16]
    ids, _ = _reference(params, PROMPTS[2], outs[2])
    dense = model_parity.reference_states(CFG, params, ids[:-1])
    assert np.abs(_sample(record) - dense).max() / np.abs(dense).max() < 1e-4

    paths = st["attention_paths"]
    assert paths["mixed_step/sparse"] == {
        "chunk=1": "gather: backend=auto on platform=cpu",
        "chunk=32": "gather: backend=auto on platform=cpu"}
    assert paths["mixed_step/lightning"] == {
        "chunk=1": "xla_la: platform cpu", "chunk=32": "xla_la: platform cpu"}
    state = st["kvcache"]["kinds"]["state"]
    assert state["bytes_per_slot"] == CFG.state_bytes_per_slot
    assert state["chunk_tokens"] == sum(map(len, PROMPTS)) + len(PROMPTS[2])
    sp = st["sparse"]
    assert sp["kept_at_most"] == 1 + 2 + 3 and sp["block"] == 8
    assert sp["queries_dense"] > 0 and sp["queries_sparse"] > 0
    assert sp["blocks_kept"] < sp["blocks_live"]
    # by hand: every prompt token and every decoded position a query
    live = kept = rows = 0
    for p in PROMPTS + [PROMPTS[2]]:
        for t in range(len(p) + NEW - 1):
            a, b, c = sa.blocks_kept(t, SIZES)
            live, kept, rows = live + int(a), kept + int(b), rows + int(c)
    assert (sp["blocks_live"], sp["blocks_kept"], sp["index_rows"]) == (
        live, kept, rows)
    # ... and what the PROGRAMS' selections kept, counted on the device
    # where each mask is handed to its fold, is that arithmetic: the queries
    # that hold a token and no other, every kv head of every sparse block
    # alike, six blocks a query past dense_len
    assert sp["device_blocks_kept"] == kept
    assert sp["device_queries_sparse"] == sp["queries_sparse"]
    assert sp["device_kept_a_sparse_query"] == 6 == sp["kept_at_most"]
    trace = st["dispatch_trace"]
    at = {f: i for i, f in enumerate(trace["fields"])}
    recent = trace["recent"]
    for f in ("sparse_blocks_live", "sparse_blocks_kept",
              "sparse_index_rows", "sparse_decode_blocks_kept",
              "sparse_device_blocks_kept",
              "lightning_row_steps", "lightning_chunk_tokens"):
        assert f in at
    assert all(r[at["sparse_device_blocks_kept"]]
               == r[at["sparse_blocks_kept"]] for r in recent)
    assert all(r[at["sparse_blocks_kept"]] <= r[at["sparse_blocks_live"]]
               and r[at["sparse_decode_blocks_kept"]]
               <= r[at["sparse_blocks_kept"]] for r in recent)
    # a row past dense_len keeps six blocks a step
    decode_only = [r for r in recent if r[at["segments"]] == 0
                   and r[at["active_rows"]] == 1]
    assert decode_only and all(
        r[at["sparse_decode_blocks_kept"]] == 6 * r[at["steps"]]
        for r in decode_only)


def test_a_wide_block_and_another_page_size(params):
    """A fused block of 8 and pages of 32 under chunks of 64 (two pages a
    chunk, the budget of one segment beside the rows' steps)."""
    with _engine(params, kv_block_tokens=32, kv_cache_blocks=40,
                 prefill_chunk=64, mixed_token_budget=96,
                 decode_block=8, max_batch=4) as eng:
        reqs = [eng.submit(p, NEW) for p in PROMPTS[:4]]
        outs = [r.wait(timeout=600).tolist() for r in reqs]
    for p, out, r in zip(PROMPTS, outs, reqs):
        _, ref = _reference(params, p, out)
        assert out == ref[:-1].argmax(-1).tolist()
        np.testing.assert_allclose(
            r.lps, ref[np.arange(NEW), out], atol=2e-4)


@pytest.mark.parametrize("control", ["forced-only", "edge-dropped"])
def test_a_selection_fault_in_the_engine_is_far_from_the_reference(
        params, control, monkeypatch):
    """The tool's controls planted in the engine's programs
    (``model_parity.selection_control``); the device's own count shows the
    first: three forced blocks a query past dense_len where six are due."""
    for name in ("_choose", "_keys_before"):
        monkeypatch.setattr(sa, name, getattr(sa, name))
    model_parity.selection_control(control)
    with _engine(params) as eng:
        res = eng.generate(PROMPTS[2], NEW, logprobs=True)
        sp = eng.stats()["sparse"]
    toks = res.tokens[0].tolist()
    _, ref = _reference(params, PROMPTS[2], toks)
    assert np.abs(res.logprobs[0] - ref[np.arange(NEW), toks]).max() > 1e-4
    assert sp["device_kept_a_sparse_query"] == (
        3 if control == "forced-only" else 6)
    assert (sp["device_blocks_kept"] < sp["blocks_kept"]) == (
        control == "forced-only")


# ------------------------------------------------------------ the refusals

@pytest.mark.parametrize("what,kw", [
    ("the serialized interleave", dict(mixed_token_budget=0)),
    ("speculation", dict(prompt_lookup=True)),
    ("a page pool of int8 pages", dict(kv_dtype="int8")),
    ("the host tier", dict(kv_host_tier_bytes=1 << 20)),
])
def test_the_engine_refuses_in_a_sentence(params, what, kw):
    with pytest.raises(ValueError) as e:
        _engine(params, **kw)
    msg = str(e.value)
    assert what in msg and "minicpm_sala" in msg
    assert "Serve it on one chip" in msg


@pytest.mark.parametrize("what", ["a pipeline of stages",
                                  "tensor parallelism (--tp)"])
def test_what_splits_a_request_refuses_in_a_sentence(what):
    with pytest.raises(ValueError, match="4 lightning blocks"):
        require_no_state(CFG, what)
    with pytest.raises(ValueError, match="more than one kind of block"):
        require_one_kind(CFG, what)


def test_a_page_that_cuts_a_block_is_refused_before_any_program(params):
    with pytest.raises(ValueError, match="whole blocks"):
        _engine(params, kv_block_tokens=4)
