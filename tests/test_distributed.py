"""Distributed pipeline tests: multi-stage parity with the single-process
engine, in-flight request interleaving, and a real multi-process run.

The parity property: an N-stage pipeline over any transport must produce
token-for-token identical greedy output to the single-stage InferenceEngine
(the reference has no such test — or any test; SURVEY.md §4)."""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

from distributed_inference_demo_tpu.comm.transport import (
    LoopbackNetwork, LoopbackTransport)
from distributed_inference_demo_tpu.models import StageSpec, get_model_config
from distributed_inference_demo_tpu.models.base import slice_stage, \
    split_layer_ranges
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.distributed import (
    PipelineHeader, PipelineWorker, StageRuntime)

GREEDY = SamplingParams(greedy=True)


def reference_tokens(model, prompt, max_new):
    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(cfg, params, max_seq=128, sampling=GREEDY)
    return engine.generate(prompt, max_new).tokens


def reference_classify(model, prompt, label_token_ids):
    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(cfg, params, max_seq=128, sampling=GREEDY)
    return engine.classify(prompt, label_token_ids)


def build_pipeline(model, num_stages, max_seq=128):
    """In-process pipeline over loopback: header + workers on threads."""
    cfg = get_model_config(model)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, num_stages)
    net = LoopbackNetwork()
    ids = [f"s{i}" for i in range(num_stages)]
    transports = [LoopbackTransport(d, net) for d in ids]

    header = PipelineHeader(
        StageRuntime(cfg, specs[0], slice_stage(full, cfg, specs[0]),
                     max_seq, GREEDY),
        transports[0], next_id=ids[1], step_timeout=60)
    workers = []
    for i in range(1, num_stages):
        rt = StageRuntime(cfg, specs[i], slice_stage(full, cfg, specs[i]),
                          max_seq, GREEDY)
        workers.append(PipelineWorker(
            rt, transports[i],
            next_id=ids[i + 1] if i + 1 < num_stages else None,
            header_id=ids[0], step_timeout=60))
    threads = [threading.Thread(target=w.serve_forever, daemon=True)
               for w in workers]
    for t in threads:
        t.start()
    return header, threads


PROMPT = np.array([[5, 17, 42, 7, 99, 3, 12, 56]], dtype=np.int32)


@pytest.mark.parametrize("model,num_stages", [
    ("llama-test", 2),          # BASELINE config #1 shape: 2-way split
    # 3-way split twin — slow lane: middle-stage (no-embed/no-head)
    # handling stays quick via the 3-stage chaos/elastic loopbacks
    pytest.param("llama-test", 3, marks=pytest.mark.slow),
    # bloom 2-way twin — slow lane: the split math is model-agnostic
    # (llama 2-way rep stays); bloom family parity stays quick via
    # hf_parity + test_models kv-cache decode
    pytest.param("bloom-test", 2, marks=pytest.mark.slow),
    # MoE across the cut — slow lane: test_expert pins EP-stage parity
    pytest.param("mixtral-test", 2, marks=pytest.mark.slow),
])
def test_pipeline_matches_single_engine(model, num_stages):
    want = reference_tokens(model, PROMPT, 12)
    header, threads = build_pipeline(model, num_stages)
    got = header.generate(PROMPT, 12)
    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    np.testing.assert_array_equal(got, want)


# tier-1 budget: pipeline_eos_early_stop and the quick
# pipeline_matches_single_engine params are the quick-lane reps
@pytest.mark.slow
def test_pipeline_interleaved_requests_match():
    """pool_size=2: two requests share the pipeline; results must equal the
    sequential single-engine output for each prompt."""
    p0 = PROMPT
    p1 = np.array([[9, 8, 7, 6, 5, 4, 3, 2]], dtype=np.int32)
    want0 = reference_tokens("llama-test", p0, 10)
    want1 = reference_tokens("llama-test", p1, 10)

    header, threads = build_pipeline("llama-test", 2)
    got = header.generate_many([p0, p1], 10, pool_size=2)
    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)
    np.testing.assert_array_equal(got[0], want0)
    np.testing.assert_array_equal(got[1], want1)


@pytest.mark.quick
def test_pipeline_eos_early_stop():
    """EOS: the header must stop a request early and release the stages."""
    cfg = get_model_config("llama-test")
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    want = reference_tokens("llama-test", PROMPT, 12)
    eos = int(want[0, 3])  # pretend this token value is EOS
    stop_at = int(np.argmax(want[0] == eos)) + 1  # first occurrence + 1

    net = LoopbackNetwork()
    specs = split_layer_ranges(cfg.num_layers, 2)
    t0, t1 = LoopbackTransport("s0", net), LoopbackTransport("s1", net)
    header = PipelineHeader(
        StageRuntime(cfg, specs[0], slice_stage(full, cfg, specs[0]),
                     128, GREEDY),
        t0, next_id="s1", eos_id=eos, step_timeout=60)
    worker = PipelineWorker(
        StageRuntime(cfg, specs[1], slice_stage(full, cfg, specs[1]),
                     128, GREEDY),
        t1, next_id=None, header_id="s0", step_timeout=60)
    th = threading.Thread(target=worker.serve_forever, daemon=True)
    th.start()
    got = header.generate(PROMPT, 12)
    header.shutdown_pipeline()
    th.join(timeout=30)
    assert got.shape[1] == stop_at                # stopped at EOS
    np.testing.assert_array_equal(got[0], want[0, :stop_at])
    assert not worker.rt._tables                   # end:{rid} freed the slot


def test_capacity_checked_before_launch():
    header, threads = build_pipeline("llama-test", 2, max_seq=16)
    with pytest.raises(ValueError, match="exceeds KV capacity"):
        header.generate(PROMPT, 100)
    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)


@pytest.mark.slow
def test_two_process_pipeline_over_sockets(tmp_path):
    """BASELINE config #1 shape: TinyLlama-arch model split across two OS
    processes on localhost, sockets in between (the reference's 2-device
    bloom560m demo, ``server.py:26-27``, done as a real test)."""
    from distributed_inference_demo_tpu.comm.transport import ZmqTransport

    model = "llama-test"
    cfg = get_model_config(model)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, 2)
    want = reference_tokens(model, PROMPT, 8)

    header_transport = ZmqTransport("header")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "distributed_inference_demo_tpu.runtime.worker_main",
         "--model", model, "--stage-id", "1", "--num-stages", "2",
         "--layer-start", str(specs[1].layer_start),
         "--layer-end", str(specs[1].layer_end),
         "--device-id", "w1", "--port", "0",
         "--header", f"header@{header_transport.address}",
         "--max-seq", "128", "--greedy"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("WORKER_READY w1 "), line
        worker_addr = line.split()[-1]
        header_transport.connect("w1", worker_addr)
        header = PipelineHeader(
            StageRuntime(cfg, specs[0], slice_stage(full, cfg, specs[0]),
                         128, GREEDY),
            header_transport, next_id="w1", step_timeout=120)
        got = header.generate(PROMPT, 8)
        np.testing.assert_array_equal(got, want)
        header.shutdown_pipeline()
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        header_transport.close()


@pytest.mark.slow
def test_two_process_pipeline_worker_tp(tmp_path):
    """Pipeline x tensor parallelism: the worker process runs its stage
    tp=2-sharded over virtual devices while the header stays single-
    device — greedy tokens must still match the plain engine (the wire
    carries replicated [b, s, H] either way)."""
    from distributed_inference_demo_tpu.comm.transport import ZmqTransport

    model = "llama-test"
    cfg = get_model_config(model)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, 2)
    want = reference_tokens(model, PROMPT, 8)

    header_transport = ZmqTransport("header")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "distributed_inference_demo_tpu.runtime.worker_main",
         "--model", model, "--stage-id", "1", "--num-stages", "2",
         "--layer-start", str(specs[1].layer_start),
         "--layer-end", str(specs[1].layer_end),
         "--device-id", "w1", "--port", "0",
         "--header", f"header@{header_transport.address}",
         "--max-seq", "128", "--greedy", "--tp", "2"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("WORKER_READY w1 "), line
        header_transport.connect("w1", line.split()[-1])
        header = PipelineHeader(
            StageRuntime(cfg, specs[0], slice_stage(full, cfg, specs[0]),
                         128, GREEDY),
            header_transport, next_id="w1", step_timeout=120)
        got = header.generate(PROMPT, 8)
        np.testing.assert_array_equal(got, want)
        header.shutdown_pipeline()
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        header_transport.close()


@pytest.mark.slow
def test_pipeline_fp8_kv_cache_matches_fp8_engine():
    """--chain --kv-cache-dtype: every stage stores its own layers' K/V
    at fp8 with the engine's insert-cast/read-upcast contract, so the
    pipeline must match the single fp8 engine bit-exactly."""
    from distributed_inference_demo_tpu.runtime import InferenceEngine

    cfg = get_model_config("llama-test")
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    oracle = InferenceEngine(cfg, full, max_seq=128, sampling=GREEDY,
                             kv_cache_dtype="float8_e4m3fn")
    want = oracle.generate(PROMPT, 12).tokens

    specs = split_layer_ranges(cfg.num_layers, 2)
    net = LoopbackNetwork()
    transports = [LoopbackTransport(d, net) for d in ("s0", "s1")]
    header = PipelineHeader(
        StageRuntime(cfg, specs[0], slice_stage(full, cfg, specs[0]),
                     128, GREEDY, kv_cache_dtype="float8_e4m3fn"),
        transports[0], next_id="s1", step_timeout=60)
    worker = PipelineWorker(
        StageRuntime(cfg, specs[1], slice_stage(full, cfg, specs[1]),
                     128, GREEDY, kv_cache_dtype="float8_e4m3fn"),
        transports[1], next_id=None, header_id="s0", step_timeout=60)
    t = threading.Thread(target=worker.serve_forever, daemon=True)
    t.start()
    got = header.generate(PROMPT, 12)
    header.shutdown_pipeline()
    t.join(timeout=30)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# dynamic batching over the pipeline (serve --chain --pool-size)


@pytest.mark.slow
def test_dynamic_batching_backend_concurrent_parity():
    """Concurrent requests with DIFFERENT lengths group into
    generate_many windows and each comes out bit-exact; stats/classify
    commands execute between windows on the one transport consumer."""
    from distributed_inference_demo_tpu.runtime.dynamic_batch import (
        DynamicBatchingHeaderBackend)

    header, threads = build_pipeline("llama-test", 2)
    backend = DynamicBatchingHeaderBackend(header, max_seq=128,
                                           num_stages=2, pool_size=2)
    try:
        prompts = [np.array([[5, 17, 42, 7]], dtype=np.int32),
                   np.array([[9, 8, 7]], dtype=np.int32),
                   np.array([[1, 2]], dtype=np.int32)]
        ns = [10, 6, 8]
        wants = [reference_tokens("llama-test", p, n)
                 for p, n in zip(prompts, ns)]

        results = {}

        def run(i):
            results[i] = backend.generate(prompts[i], ns[i]).tokens

        ts = [threading.Thread(target=run, args=(i,))
              for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        for i, want in enumerate(wants):
            np.testing.assert_array_equal(results[i], want)

        # streaming yields per-step [b] arrays matching the blocking path
        steps = list(backend.generate_stream(prompts[0], 5))
        np.testing.assert_array_equal(np.stack(steps, axis=1), wants[0][:, :5])

        # control ops ride the scheduler thread between windows
        stats = backend.stats()
        assert {s["role"] for s in stats["stages"]} == {"header", "tail"}
        labels = [7, 42, 99]
        want_cls = reference_classify("llama-test", prompts[0], labels)
        assert backend.classify(prompts[0], labels).tolist() == \
            want_cls.tolist()
    finally:
        backend.close()
        header.shutdown_pipeline()
        for t in threads:
            t.join(timeout=30)


def test_dynamic_batching_backend_close_drains_waiters():
    """close() must fail queued waiters with a clear error instead of
    hanging them, and reject post-close submissions."""
    from distributed_inference_demo_tpu.runtime.dynamic_batch import (
        DynamicBatchingHeaderBackend)

    header, threads = build_pipeline("llama-test", 2)
    backend = DynamicBatchingHeaderBackend(header, max_seq=128,
                                           num_stages=2, pool_size=2)
    prompt = np.array([[5, 17, 42]], dtype=np.int32)
    # one request completes normally first (proves the loop was live)
    assert backend.generate(prompt, 4).tokens.shape == (1, 4)
    backend.close()
    with pytest.raises(RuntimeError, match="closed"):
        backend.generate(prompt, 4)
    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)
