"""Device-resident K-token decode loop (docs/DESIGN.md §13).

Acceptance invariants pinned here:

- greedy output is BIT-IDENTICAL between the per-token path (K=1) and
  the device loop at every K — including mid-block eos and on-device
  stop-token cuts — for the streaming engine, the dense and paged fused
  batching blocks (their parity lives in test_batching/test_paged_
  batching; the early-exit accounting lives here), and the ring
  pipeline's fused tail;
- host dispatches per token ≈ 1/K on the streaming path (the host
  dispatch floor amortizes K-fold);
- an all-rows-done at step j < K ends the device loop after j steps —
  the remaining K−j steps are NOT executed (the device-reported step
  count proves it).
"""

import threading

import jax
import numpy as np
import pytest

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import (
    SamplingParams, match_stop_ids, pad_stop_ids)
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

CFG = get_model_config("llama-test")
GREEDY = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def make_engine(params, **kw):
    kw.setdefault("sampling", GREEDY)
    return InferenceEngine(CFG, params, max_seq=96, **kw)


def stream_tokens(engine, prompt, n, seed=0, logprobs=False):
    return list(engine.generate_stream(prompt, n, seed=seed,
                                       logprobs=logprobs))


PROMPT = np.asarray([[3, 14, 15, 92, 65], [7, 6, 5, 4, 3]], np.int32)


# ---------------------------------------------------------------- parity

@pytest.mark.parametrize("K", [
    4, pytest.param(16, marks=pytest.mark.slow)])
def test_stream_block_greedy_bit_identical(params, K):
    ref = stream_tokens(make_engine(params, stream_block=1), PROMPT, 24)
    got = stream_tokens(make_engine(params, stream_block=K), PROMPT, 24)
    assert len(got) == len(ref)
    np.testing.assert_array_equal(np.stack(ref, 1), np.stack(got, 1))


def test_stream_block_logprobs_bit_identical(params):
    ref = stream_tokens(make_engine(params, stream_block=1), PROMPT, 12,
                        logprobs=True)
    got = stream_tokens(make_engine(params, stream_block=8), PROMPT, 12,
                        logprobs=True)
    assert len(got) == len(ref)
    for (rt, rl), (gt, gl) in zip(ref, got):
        np.testing.assert_array_equal(rt, gt)
        np.testing.assert_array_equal(rl, gl)


# slow lane: sampled-stream twin — the rng-stream claim is pinned quick by
# test_mixed_sampled_stream_bit_identical_to_serialized (mixed dispatch)
@pytest.mark.slow
def test_stream_block_sampled_bit_identical(params):
    """K-fusion must not perturb the rng stream: the loop body splits
    the carried rng per step in decode_one's exact order, so SAMPLED
    streams (not just greedy) are bit-identical across K."""
    samp = SamplingParams(temperature=0.8, top_k=5)
    ref = stream_tokens(make_engine(params, sampling=samp,
                                    stream_block=1), PROMPT, 16, seed=11)
    got = stream_tokens(make_engine(params, sampling=samp,
                                    stream_block=4), PROMPT, 16, seed=11)
    np.testing.assert_array_equal(np.stack(ref, 1), np.stack(got, 1))


def test_generate_matches_stream_any_block(params):
    """The fused ``generate`` path runs the same device loop (one block
    of size max_new): parity with the streamed per-token path."""
    eng = make_engine(params, stream_block=1)
    fused = eng.generate(PROMPT, 10).tokens
    streamed = np.stack(stream_tokens(eng, PROMPT, 10), 1)
    np.testing.assert_array_equal(fused, streamed)


# ------------------------------------------------- dispatch accounting

@pytest.mark.quick
def test_dispatches_per_token_is_one_over_K(params):
    """THE headline invariant: with stream_block=K the host pays one
    dispatch per K tokens; K=1 pays one per token."""
    for K, want_dispatches in ((1, 16), (4, 4), (16, 1)):
        eng = make_engine(params, stream_block=K)
        toks = stream_tokens(eng, PROMPT, 16)
        assert len(toks) == 16
        # prefill is not a decode dispatch; only the loop counts
        assert eng.loop_stats["host_dispatches"] == want_dispatches, K
        assert eng.loop_stats["device_loop_steps"] == 16, K
        ratio = eng.loop_stats["host_dispatches"] / len(toks)
        assert abs(ratio - 1 / K) < 1e-9


def test_dwt_engine_series_feed(params):
    """The instance counters bridge to the dwt_engine_* catalog series
    (scraped dispatches-per-token is the §13 runbook signal)."""
    from distributed_inference_demo_tpu.telemetry.catalog import (
        ENGINE_DEVICE_LOOP_STEPS, ENGINE_HOST_DISPATCHES)

    def val(counter):
        return {key: v for _, key, v in counter.samples()}.get(
            ((("engine", "InferenceEngine"),)), 0.0)

    d0, s0 = val(ENGINE_HOST_DISPATCHES), val(ENGINE_DEVICE_LOOP_STEPS)
    eng = make_engine(params, stream_block=4)
    stream_tokens(eng, PROMPT, 8)
    assert val(ENGINE_HOST_DISPATCHES) - d0 == 2
    assert val(ENGINE_DEVICE_LOOP_STEPS) - s0 == 8


# ------------------------------------------------------ early exit

def _nth_greedy_token(params, n, prompt=None):
    """Token the greedy reference emits at step index n (row 0)."""
    toks = stream_tokens(make_engine(params),
                         PROMPT[:1] if prompt is None else prompt, n + 1)
    return int(toks[n][0])


@pytest.mark.slow
def test_all_rows_eos_ends_device_loop_early(params):
    """All-rows-EOS at step j < K must end the loop after j+1 steps —
    the remaining K−(j+1) steps are NOT run (device-reported count)."""
    eos = _nth_greedy_token(params, 2)
    eng = make_engine(params, stream_block=16)
    eng.eos_id = eos
    toks = stream_tokens(eng, PROMPT[:1], 12)
    assert len(toks) == 3 and int(toks[-1][0]) == eos
    assert eng.loop_stats["host_dispatches"] == 1
    assert eng.loop_stats["device_loop_steps"] == 3    # not 12, not 16
    # K=1 reference: same tokens, one dispatch each
    ref_eng = make_engine(params, stream_block=1)
    ref_eng.eos_id = eos
    ref = stream_tokens(ref_eng, PROMPT[:1], 12)
    np.testing.assert_array_equal(np.stack(ref, 1), np.stack(toks, 1))
    assert ref_eng.loop_stats["host_dispatches"] == 3


def test_fused_generate_early_exits_on_eos(params):
    """The non-streaming ``generate`` block exits at the eos step too
    (the old fixed-trip scan burned the full block), while its output
    keeps the deterministic eos padding contract."""
    eos = _nth_greedy_token(params, 2)
    eng = make_engine(params)
    eng.eos_id = eos
    res = eng.generate(PROMPT[:1], 10)
    assert res.tokens.shape == (1, 10)
    assert (res.tokens[0, 3:] == eos).all()
    assert eng.loop_stats["host_dispatches"] == 1
    assert eng.loop_stats["device_loop_steps"] == 3


# ------------------------------------------------- on-device stop ids

@pytest.mark.slow
def test_stop_token_ids_cut_matches_per_token_path(params):
    stop_tok = _nth_greedy_token(params, 3)
    outs = {}
    for K in (1, 8):
        eng = make_engine(params, stream_block=K,
                          stop_token_ids=[stop_tok, 9999])
        outs[K] = stream_tokens(eng, PROMPT[:1], 12)
        # the stop token is emitted (eos-include convention), then the
        # row is done: the stream ends at the cut on both paths
        assert len(outs[K]) == 4
        assert int(outs[K][-1][0]) == stop_tok
    np.testing.assert_array_equal(np.stack(outs[1], 1),
                                  np.stack(outs[8], 1))


def test_stop_token_ids_early_exit_accounting(params):
    # the stop token must be one the greedy stream has NOT emitted
    # before step n, or the cut lands earlier than n: which step first
    # shows a fresh token depends on the seeded weights' numerics (on
    # jax 0.9 steps 0 and 1 emit the same token, and the old pin on
    # "the token of step 1" cut the stream at step 0 — the program was
    # right, the pin was not)
    ref = [int(t[0]) for t in stream_tokens(make_engine(params),
                                            PROMPT[:1], 8)]
    n = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eng = make_engine(params, stream_block=16,
                      stop_token_ids=[ref[n]])
    toks = stream_tokens(eng, PROMPT[:1], 12)
    assert [int(t[0]) for t in toks] == ref[:n + 1]
    assert eng.loop_stats == {"host_dispatches": 1,
                              "device_loop_steps": n + 1}


def test_stop_id_helpers():
    np.testing.assert_array_equal(np.asarray(pad_stop_ids(None)), [-1])
    np.testing.assert_array_equal(np.asarray(pad_stop_ids([7, 3, 7])),
                                  [3, 7])
    with pytest.raises(ValueError, match="stop_token_ids"):
        pad_stop_ids([-2])
    import jax.numpy as jnp
    got = match_stop_ids(jnp.asarray([3, 7, 5]), pad_stop_ids([3, 5]))
    np.testing.assert_array_equal(np.asarray(got), [True, False, True])
    # the empty sentinel can never match a real (non-negative) token
    got = match_stop_ids(jnp.asarray([0, 1]), pad_stop_ids(None))
    assert not np.asarray(got).any()


def test_stream_block_validation(params):
    with pytest.raises(ValueError, match="stream_block"):
        make_engine(params, stream_block=0)


def test_stream_block_env_knob(params, monkeypatch):
    monkeypatch.setenv("DWT_STREAM_BLOCK", "4")
    eng = make_engine(params)           # stream_block=None -> env
    assert eng.stream_block == 4
    stream_tokens(eng, PROMPT[:1], 8)
    assert eng.loop_stats["host_dispatches"] == 2


# ------------------------------------- batching fused-block early exit

def test_batching_fused_block_reports_actual_steps(params):
    """The dense fused block's on-device active count: a block whose
    rows all exhaust their budget at step j < decode_block runs j
    steps, and the drain sees the device-reported count."""
    oracle = make_engine(params)
    with ContinuousBatchingEngine(CFG, params, max_seq=96, max_batch=4,
                                  sampling=GREEDY, prompt_buckets=(16,),
                                  decode_block=16) as eng:
        got = eng.submit([3, 14, 15, 92, 65], 5).wait(timeout=300)
        want = oracle.generate(np.asarray([[3, 14, 15, 92, 65]]),
                               5).tokens[0]
        np.testing.assert_array_equal(got, want)
        stats = eng.loop_stats.copy()
    # token #1 comes from prefill; the 4 decode tokens need at most ONE
    # 16-step fused block that early-exits on the budget — without the
    # exit the block would burn 16 steps into stale positions
    assert stats["device_loop_steps"] < 16
    assert stats["device_loop_steps"] >= 4


@pytest.mark.slow
def test_paged_fused_block_reports_actual_steps(params):
    oracle = make_engine(params)
    with ContinuousBatchingEngine(CFG, params, max_seq=96, max_batch=4,
                                  sampling=GREEDY, prompt_buckets=(16,),
                                  decode_block=16) as eng:
        got = eng.submit([3, 14, 15, 92, 65], 5).wait(timeout=300)
        want = oracle.generate(np.asarray([[3, 14, 15, 92, 65]]),
                               5).tokens[0]
        np.testing.assert_array_equal(got, want)
        stats = eng.loop_stats.copy()
    assert stats["device_loop_steps"] < 16
    assert stats["device_loop_steps"] >= 4


# slow lane: eos-mid-block twin; test_fused_generate_early_exits_on_eos,
# test_stop_token_ids_early_exit_accounting and the batching-level
# test_decode_block_eos_mid_block keep the seam quick
@pytest.mark.slow
def test_batching_eos_mid_block_early_exit(params):
    """An all-rows-EOS inside the fused block ends it on device: parity
    plus the step count proves the remaining rounds never ran."""
    oracle = make_engine(params)
    prompt = [3, 14, 15, 92, 65]
    ref = oracle.generate(np.asarray([prompt]), 8).tokens[0]
    eos = int(ref[2])
    with ContinuousBatchingEngine(CFG, params, max_seq=96, max_batch=4,
                                  sampling=GREEDY, prompt_buckets=(16,),
                                  decode_block=16, eos_id=eos) as eng:
        got = eng.submit(prompt, 30).wait(timeout=300)
        stats = eng.loop_stats.copy()
    np.testing.assert_array_equal(got, ref[:list(ref).index(eos) + 1])
    assert stats["device_loop_steps"] < 30


# ----------------------------------------------------- ring fused tail

def _run_ring(model, fused: bool, monkeypatch):
    from tests.test_distributed import PROMPT as RING_PROMPT
    from tests.test_distributed import build_pipeline
    monkeypatch.setenv("DWT_RING_FUSED_TAIL", "1" if fused else "0")
    header, threads = build_pipeline(model, 2)
    try:
        toks = header.generate(RING_PROMPT, 10)
    finally:
        header.shutdown_pipeline()
        for t in threads:
            t.join(timeout=30)
    return toks


# tier-1 budget: dispatches-per-token + stream-block parity keep the
# quick-lane fused-loop reps; the ring-mesh tail twin rides slow
@pytest.mark.slow
def test_ring_fused_tail_parity(params, monkeypatch):
    """The tail's fused forward+sample program must emit bit-identical
    tokens to the split forward-then-sample pair it replaces (same rng
    fold_in stream by construction; this pins it)."""
    split = _run_ring("llama-test", False, monkeypatch)
    fused = _run_ring("llama-test", True, monkeypatch)
    np.testing.assert_array_equal(split, fused)


@pytest.mark.slow
def test_ring_fused_tail_halves_tail_dispatches(monkeypatch):
    """Tail dispatch accounting: the fused tail pays 1 host dispatch
    per token where the split pair paid 2."""
    from distributed_inference_demo_tpu.comm.transport import (
        LoopbackNetwork, LoopbackTransport)
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.base import (
        slice_stage, split_layer_ranges)
    from distributed_inference_demo_tpu.runtime.distributed import (
        PipelineHeader, PipelineWorker, StageRuntime)

    counts = {}
    for fused in (False, True):
        monkeypatch.setenv("DWT_RING_FUSED_TAIL", "1" if fused else "0")
        cfg = get_model_config("llama-test")
        full = init_full_params(jax.random.PRNGKey(0), cfg)
        specs = split_layer_ranges(cfg.num_layers, 2)
        net = LoopbackNetwork()
        t0, t1 = (LoopbackTransport(d, net) for d in ("s0", "s1"))
        header = PipelineHeader(
            StageRuntime(cfg, specs[0],
                         slice_stage(full, cfg, specs[0]), 64, GREEDY),
            t0, next_id="s1", step_timeout=60)
        worker = PipelineWorker(
            StageRuntime(cfg, specs[1],
                         slice_stage(full, cfg, specs[1]), 64, GREEDY),
            t1, next_id=None, header_id="s0", step_timeout=60)
        th = threading.Thread(target=worker.serve_forever, daemon=True)
        th.start()
        try:
            header.generate(np.asarray([[5, 17, 42, 7]], np.int32), 8)
        finally:
            header.shutdown_pipeline()
            th.join(timeout=30)
        counts[fused] = worker.tail_dispatches
    assert counts[True] * 2 == counts[False]
    assert counts[True] > 0


def test_cli_stream_block_mode_rules(capsys):
    """--stream-block is honored by the plain engine path and REJECTED
    (never silently ignored) by modes with their own fusion unit."""
    from distributed_inference_demo_tpu import cli
    assert cli.main(["serve", "--model", "llama-test",
                     "--batch-slots", "2", "--stream-block", "4"]) == 1
    assert "--stream-block" in capsys.readouterr().err
