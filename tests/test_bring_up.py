"""Bring-up invariants (PR 21): the chip cannot be hidden.

Everything here runs on the CPU and is cheap.  What it pins:

- ``chip_smoke.py`` fails, naming the missing TPU, where JAX has none —
  and its request script passes against ``serve --model qwen2-test`` on
  the CPU, so the command is debugged before chip time is spent on it;
- ``benchmark/run.py`` refuses to run without a TPU and prints no result
  line (only ``--rehearse-cpu`` walks it on the CPU, and that prints none);
- the compile-cache rule: ``JAX_COMPILATION_CACHE_DIR`` set → the code
  sets nothing; unset → ``<checkout>/.jax_cache``, whatever the cwd;
- the attention-path record: ``gather`` (with the reason) on the CPU,
  the kernel names under a forced ``"pallas"`` backend, also per shard
  under a tp mesh; an explicit ``"pallas"`` on a shape no kernel takes
  raises instead of gathering unseen;
- ``/health`` carries platform / device_kind / device count and its
  status follows the scheduler thread;
- seeded weights and the page pool are born sharded under a tp mesh
  with the values of the unsharded init;
- the native library is keyed on a hash of its sources, and a failed
  build is an error;
- every kernel specialisation gets through Mosaic for a TPU v5e, checked
  here by compiling ahead of time against a device-less v5e topology
  (skipped where libtpu offers none).
"""

import argparse
import math
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.base import KVCache, StageSpec
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops import paged_attention as pa
from distributed_inference_demo_tpu.ops.quant import (
    alloc_kv_pages, alloc_kv_pool)
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.parallel.mesh import local_tp_mesh
from distributed_inference_demo_tpu.parallel.tensor import (
    make_paged_forward_seam)
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


# -------------------------------------------------- smoke and benchmark

def test_chip_smoke_fails_without_a_tpu():
    """In a sandbox like this one the smoke must fail: its children ask
    for the ``tpu`` platform, so JAX errors out instead of falling back."""
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=CPU_ENV, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 1
    assert "JAX found no TPU" in proc.stdout
    assert '"ok"' not in proc.stdout         # no result line


def test_chip_smoke_outside_a_checkout(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=CPU_ENV, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode == 2 and proc.stdout == ""
    assert "not a checkout" in proc.stderr


def test_smoke_request_script_passes_on_cpu(tmp_path):
    """The same gateway → serve --batch-slots command and the same
    requests, against the tiny qwen2 config on the CPU: every check of
    the script holds, and the attention paths say gather and why."""
    try:
        ph = chip_smoke.serving_phase("cpu", "qwen2-test", 256, "cpu",
                                      tmp_path, ready_timeout=300)
    finally:
        chip_smoke.stop_all_children()
    assert ph["health"]["platform"] == "cpu"
    assert ph["health"]["device_count"] >= 1
    assert ph["stats"]["attention_paths"]["mixed_step"] == \
        chip_smoke.expected_paths("cpu")
    assert ph["tokens"]["long"] == ph["tokens"]["long_again"]
    assert ph["tokens"]["prefix_b"] == ph["tokens"]["prefix_b_again"]
    assert ph["stats"]["kvcache"]["hits"] >= 1


def test_smoke_stats_check_catches_a_hidden_gather():
    """A program that should have taken a kernel and gathered fails the
    smoke: the TPU expectation is both kernel names."""
    stats = {"kvcache": {"hits": 1, "partial_hit_tokens": 16},
             "device_loop": {"host_dispatches": 2, "device_loop_steps": 8},
             "mixed": {"dispatches": 3, "prefill_tokens": 64},
             "chunked_prefill": {"chunks": 2},
             "attention_paths": {"mixed_step": {
                 "chunk=1": "pallas_decode",
                 "chunk=64": "gather: chunk 64 x group 9 = 576 query rows "
                             "> PREFILL_KERNEL_MAX_ROWS=512"}}}
    with pytest.raises(chip_smoke.SmokeFailure, match="attention paths"):
        chip_smoke.check_stats(stats, "tpu")
    stats["attention_paths"]["mixed_step"]["chunk=64"] = "pallas_prefill"
    chip_smoke.check_stats(stats, "tpu")


def test_benchmark_refuses_to_run_without_a_tpu():
    """The promise of ``benchmark/run.py``'s docstring: the children start
    under ``JAX_PLATFORMS=tpu``, so where there is no TPU the run ends
    non-zero and stdout, where the result line would go, stays empty."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload",
         "qwen2.5-7b-int8.chat", "--seconds", "2"],
        env=CPU_ENV, capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, 3)     # 3 is the rehearsal's
    assert proc.stdout.strip() == ""         # no result line
    assert "JAX found no TPU" in proc.stderr


# ------------------------------------------------------- compile cache

def test_compile_cache_rule(monkeypatch, tmp_path):
    from distributed_inference_demo_tpu import cli

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert cli.configure_compile_cache() == str(tmp_path / "c")
    assert calls == []                       # env set: code sets nothing

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(REPO / ".jax_cache")
    assert cli.configure_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]

    # a second process, another cwd: the same directory
    env = {k: v for k, v in CPU_ENV.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from distributed_inference_demo_tpu.cli import "
         "configure_compile_cache as c; print(c()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.stdout.split() == [want, want], out.stderr


# ---------------------------------------------------- attention routing

def _pages(kind, bt=16):
    return alloc_kv_pages((4, 2, bt, 8), kind, jnp.float32)


@pytest.mark.parametrize("backend,platform,kind,bt,chunk,groups,want", [
    ("auto", "cpu", "bf16", 16, 1, 7, "gather: backend=auto on platform=cpu"),
    ("xla", "tpu", "bf16", 16, 1, 7, "gather: backend=xla"),
    ("auto", "tpu", "bf16", 16, 1, 7, "pallas_decode"),
    ("auto", "tpu", "bf16", 16, 64, 7, "pallas_prefill"),
    ("auto", "tpu", "int8", 32, 64, 7, "pallas_prefill"),
    ("auto", "tpu", "int8", 16, 1, 7, "gather: int8 pages need"),
    ("auto", "tpu", "int4", 32, 1, 7, "gather: int4 pages have no kernel"),
    ("auto", "tpu", "bf16", 12, 1, 7, "gather: block_tokens=12"),
    # the README's documented --prefill-chunk 256: 1792 rows at group 7
    ("auto", "tpu", "bf16", 16, 256, 7, "gather: chunk 256 x group 7 = 1792"),
    ("pallas", "cpu", "int8", 16, 1, 7, "pallas_decode"),   # interpret runs
])
def test_route_paged_attention(backend, platform, kind, bt, chunk, groups,
                               want):
    path, why = pa.route_paged_attention(backend, platform, _pages(kind, bt),
                                         chunk, groups)
    assert (path if not why else f"{path}: {why}").startswith(want)


def test_forced_pallas_raises_where_no_kernel_fits():
    for kind, bt, chunk in (("int4", 32, 1), ("bf16", 12, 1),
                            ("bf16", 16, 256)):
        with pytest.raises(ValueError, match="cannot take this shape"):
            pa.route_paged_attention("pallas", "cpu", _pages(kind, bt),
                                     chunk, 7)


def _seam_programs(cfg, params, mesh, backend, record, bt=8, W=4, N=16):
    """A decode step and a prefill slab over one paged seam — the two
    attention shapes a mixed dispatch holds."""
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    fwd, bind, pool_sharding = make_paged_forward_seam(
        cfg, spec, mesh, params, bt, backend=backend, interpret=True,
        record=record)
    pk, pv = alloc_kv_pool((cfg.num_layers, N, cfg.num_kv_heads, bt,
                            cfg.head_dim), "bf16", cfg.dtype, pool_sharding)
    tables = jnp.arange(2 * W, dtype=jnp.int32).reshape(2, W)

    @jax.jit
    def slab(params, pk, pv, ids):
        bind(tables, "slab")
        pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        logits, cache = fwd(params, ids, KVCache(pk, pv, jnp.int32(0)),
                            pos, None)
        return logits, cache.keys, cache.values

    @jax.jit
    def step(params, pk, pv, tok, lengths):
        bind(tables, "step")
        logits, _ = fwd(params, tok[:, None], KVCache(pk, pv, jnp.int32(0)),
                        lengths[:, None], 0)
        return logits

    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 16)), jnp.int32)
    logits, pk, pv = slab(params, pk, pv, ids)
    return logits, step(params, pk, pv, ids[:, -1],
                        jnp.asarray([16, 16], jnp.int32))


@pytest.mark.parametrize("tp", [1, 2])
def test_attention_path_record_names_kernels_under_forced_pallas(tp):
    """Forced "pallas" (interpreted here) runs both kernels, the record
    names them per program, and the logits match the gather path — also
    INSIDE a tp shard_map, where each shard's kernel sees nkv / tp kv
    heads (the seam used to hard-code the gather there)."""
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    mesh = local_tp_mesh(tp)
    if mesh is not None:
        from distributed_inference_demo_tpu.runtime.engine import (
            shard_engine_params)
        params = shard_engine_params(params, cfg, mesh)
    got, want = {}, {}
    for backend, out in (("pallas", got), ("xla", want)):
        out["record"] = pa.AttnPathRecord()
        out["slab"], out["step"] = _seam_programs(cfg, params, mesh,
                                                  backend, out["record"])
    assert got["record"].snapshot() == {
        "slab": {"chunk=16": "pallas_prefill"},
        "step": {"chunk=1": "pallas_decode"}}
    assert want["record"].snapshot() == {
        "slab": {"chunk=16": "gather: backend=xla"},
        "step": {"chunk=1": "gather: backend=xla"}}
    # f32 model: the kernel's online softmax vs the gather's one-shot
    # softmax differ by reduction order only
    np.testing.assert_allclose(got["slab"], want["slab"], atol=2e-5)
    np.testing.assert_allclose(got["step"], want["step"], atol=2e-5)


# ------------------------------------------------ engine, /stats, /health

def _get(port, path):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_engine_reports_gather_on_cpu_and_health_follows_scheduler():
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)

    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(
        cfg, params, max_seq=64, max_batch=2,
        sampling=SamplingParams(greedy=True), decode_block=2,
        prefill_chunk=8, mixed_token_budget=16)
    server = InferenceHTTPServer(eng, model_name="llama-test")
    server.start()
    try:
        eng.submit(list(range(1, 20)), 4).wait(timeout=120)
        why = "gather: backend=auto on platform=cpu"
        assert eng.stats()["attention_paths"] == {
            "mixed_step": {"chunk=8": why, "chunk=1": why}}

        status, health = _get(server.port, "/health")
        dev = jax.devices()[0]
        assert status == 200 and health["status"] == "ok"
        assert (health["platform"], health["device_kind"],
                health["device_count"]) == (dev.platform, dev.device_kind,
                                            len(jax.devices()))
        assert [d["id"] for d in health["devices"]] == \
            [d.id for d in jax.devices()]
        # the cores the replica shares with whatever serves beside it
        assert health["host_cpus"] == len(os.sched_getaffinity(0)) >= 1

        # a pure-decode dispatch failure kills the scheduler thread: it
        # drains every request with the error — and /health says so
        def boom(*a, **k):
            raise RuntimeError("device lost")
        row = eng.submit([5, 4, 3], 40)
        deadline = time.monotonic() + 60
        while len(row.tokens) < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        eng._mixed_step = boom
        with pytest.raises(RuntimeError, match="device lost"):
            row.wait(timeout=60)
        status, health = _get(server.port, "/health")
        assert status == 503 and health["status"] == "scheduler_dead"
        assert "device lost" in health["error"]
    finally:
        server.shutdown()
        eng.close()


def test_attention_paths_whichever_variant_is_traced_first():
    """``mixed_step`` has a variant with no slab (PR 33) and one a number
    of packed segments (PR 39), and the engine launches them all before
    it is ready, the one with no slab FIRST (served traffic always starts
    with a prefill): ``attention_paths`` names one program with both
    chunk shapes, as the benchmark's configurations state it, before any
    request, and ``compile`` counts the variants its budget allows (two
    segments: three); a decode-only dispatch by hand over the idle engine
    and a request through the scheduler add nothing to either."""
    from distributed_inference_demo_tpu.telemetry import profiling
    cfg = get_model_config("llama-test")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    B = 2
    profiling.reset_observatory()   # the compile ledger is the process's
    eng = ContinuousBatchingEngine(
        cfg, params, max_seq=64, max_batch=B,
        sampling=SamplingParams(greedy=True), decode_block=2,
        prefill_chunk=8, mixed_token_budget=16)
    try:
        why = "gather: backend=auto on platform=cpu"
        both = {"mixed_step": {"chunk=1": why, "chunk=8": why}}
        ready = eng.stats()
        assert ready["attention_paths"] == both
        assert ready["compile"]["mixed_step"]["compiles"] == \
            ready["compile"]["mixed_step"]["variant_budget"] == 3
        out = eng._mixed_step(
            eng.params, eng._pk, eng._pv, None, jnp.asarray(eng._tables),
            eng._lengths, eng._last_tok, jnp.zeros((B,), bool),
            jax.random.PRNGKey(0), eng._eos_scalar(),
            jnp.zeros((B,), jnp.int32), eng.decode_block)
        eng._pk, eng._pv = out[0], out[1]
        assert int(out[8]) == 0              # no row active: no step ran
        want = eng.submit(list(range(1, 20)), 6).wait(timeout=120)
        stats = eng.stats()
        assert stats["attention_paths"] == both
        assert stats["compile"]["mixed_step"] == ready["compile"][
            "mixed_step"]
        assert stats["dispatch_trace"]["decode_only"] > 0
    finally:
        eng.close()
        profiling.reset_observatory()
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    oracle = InferenceEngine(cfg, params, max_seq=64,
                             sampling=SamplingParams(greedy=True))
    np.testing.assert_array_equal(
        want, oracle.generate(np.arange(1, 20)[None, :], 6).tokens[0])


# --------------------------------------------------- born-sharded state

def test_seeded_weights_and_pool_are_born_sharded():
    from distributed_inference_demo_tpu.models.loader import load_or_init

    # int8: the layer-by-layer quantizing init is the one with structure
    # to lose under an outer jit; its q and scale leaves both shard
    mesh = local_tp_mesh(2)
    name = "qwen2-test-int8"
    cfg = get_model_config(name)
    whole = load_or_init(name, cfg, seed=3)
    sharded = load_or_init(name, cfg, seed=3, mesh=mesh)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    wq = sharded.layers["wq"].q
    assert wq.sharding.spec == jax.sharding.PartitionSpec(None, None, "tp")
    assert len(wq.addressable_shards) == 2
    assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 2

    from distributed_inference_demo_tpu.parallel.tensor import (
        tp_cache_sharding)
    pk, pv = alloc_kv_pool((2, 8, 2, 8, 16), "int8", jnp.bfloat16,
                           tp_cache_sharding(mesh))
    for leaf in jax.tree.leaves((pk, pv)):     # data AND scale sidecars
        assert leaf.sharding.spec[2] == "tp"
        assert leaf.addressable_shards[0].data.shape[2] == 1
        assert not np.asarray(leaf).any()


# -------------------------------------------------------- native build

def test_native_library_is_keyed_on_its_sources(tmp_path, monkeypatch):
    from distributed_inference_demo_tpu.comm.native import build as nb

    # a one-function stand-in for the real sources keeps g++ quick
    monkeypatch.setattr(nb, "SOURCES", ["one.cc"])
    (tmp_path / "one.cc").write_text('extern "C" int one() { return 1; }\n')
    monkeypatch.setattr(nb, "_DIR", tmp_path)
    first = nb.build()
    assert first.exists() and first == nb.lib_path()
    assert nb.build() == first               # nothing to do

    # other sources, other name: a stale binary cannot be picked up, and
    # the old one is swept once the new one exists
    with open(tmp_path / nb.SOURCES[0], "a") as f:
        f.write("\n// edited\n")
    second = nb.lib_path()
    assert second != first and not second.exists()
    assert nb.build() == second and not first.exists()

    # a compiler that is there and fails is an error, not a fallback
    (tmp_path / nb.SOURCES[0]).write_text("this is not C++\n")
    with pytest.raises(nb.NativeBuildError, match="failed"):
        nb.build()
    assert not list(tmp_path.glob("*.tmp"))

    # no compiler at all: the one case the Python codec may serve
    monkeypatch.setattr(nb.shutil, "which", lambda _: None)
    with pytest.raises(nb.NativeUnavailable):
        nb.build()


# ------------------------------------------------- Mosaic, ahead of time

@pytest.fixture(scope="module")
def v5e():
    """One device of a device-less TPU v5e topology: libtpu compiles for
    it (Mosaic included) without a chip being there."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:                   # no libtpu, or no such target
        pytest.skip(f"no ahead-of-time TPU compiler here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compile_for(sharding, fn, *shapes):
    args = [jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        x) for x in shapes]
    jax.jit(fn).lower(*args).compile()       # raises on a Mosaic refusal


QWEN, BLOOM560M, BLOOM7B1 = (28, 4, 128), (16, 16, 64), (32, 32, 128)
MOSAIC_CASES = {
    # (heads, page kind, page tokens, slots, table width, pool pages)
    "qwen-bf16-p16": (QWEN, "bf16", 16, 8, 8, 64),
    "qwen-int8-p32": (QWEN, "int8", 32, 8, 8, 64),
    "bloom560m-bf16-p16": (BLOOM560M, "bf16", 16, 8, 8, 64),
    "bloom560m-int8-p32": (BLOOM560M, "int8", 32, 8, 8, 64),
    # the benchmark's cells as they are served (PERF.md §4), so that a
    # refusal is found here and not on the chip
    "cell-qwen2.5-7b": (QWEN, "bf16", 128, 32, 32, 416),
    "cell-bloom7b1": (BLOOM7B1, "bf16", 128, 8, 16, 46),
    "cell-tp4-shard": ((7, 1, 128), "bf16", 128, 64, 32, 2048),
    "qwen-int8-p128": (QWEN, "int8", 128, 32, 32, 416),
    "bloom560m-bf16-p128": (BLOOM560M, "bf16", 128, 8, 16, 512),
}


@pytest.mark.parametrize("case", MOSAIC_CASES)
def test_paged_kernels_get_through_mosaic(v5e, case):
    """qwen2.5-7b heads (28 q / 4 kv x 128, one kv head a chip under
    tp4) without ALiBi, bloom heads (16 / 16 x 64, 32 / 32 x 128) with:
    decode and prefill kernel, bf16 and int8 pages."""
    (nh, nkv, hd), kind, bt, b, W, N = MOSAIC_CASES[case]
    alibi = nh == nkv
    S = jax.ShapeDtypeStruct
    pages = jax.eval_shape(
        lambda: alloc_kv_pages((N, nkv, bt, hd), kind, jnp.bfloat16))
    slopes = (S((nh,), jnp.float32),) if alibi else ()
    _compile_for(
        v5e, lambda q, pk, pv, t, n, *s: pa.paged_flash_attention(
            q, pk, pv, t, n, *s),
        S((b, 1, nh, hd), jnp.bfloat16), pages, pages,
        S((b, W), jnp.int32), S((b,), jnp.int32), *slopes)
    chunk = 64 if nh // nkv * 64 <= 512 else 32
    _compile_for(
        v5e, lambda q, pk, pv, t, p, *s: pa.paged_prefill_attention(
            q, pk, pv, t, p, *s),
        S((2, chunk, nh, hd), jnp.bfloat16), pages, pages,
        S((2, W), jnp.int32), S((2, chunk), jnp.int32), *slopes)


# the cells' prefill calls as the slab makes them (PERF.md §4): (query
# heads, kv heads, ALiBi, rows of the call, tokens a tile, table width,
# pool pages, window); a tile is a chunk of 256 tokens, or the sub-chunk
# a period model's kind cuts it into (6 and 9 query heads a kv head)
PREFILL_LOOP_CASES = {
    "laguna-full": (48, 8, False, 8, 64, 200, 3328, 0),
    "laguna-window": (72, 8, False, 16, 32, 200, 255, 512),
    "bloom7b1": (32, 32, True, 2, 256, 16, 46, 0),
    "olmoe-1b-7b": (16, 16, False, 2, 256, 32, 224, 0),
    "ouro-2.6b": (16, 16, False, 2, 256, 16, 44, 0),
    "tp4-shard": (7, 1, False, 2, 64, 32, 2048, 0),
    # no cell serves int8 pages; at 128 tokens they take the loop too
    "int8-p128": (16, 16, False, 2, 256, 32, 224, 0),
}


@pytest.mark.parametrize("case", PREFILL_LOOP_CASES)
def test_prefill_page_loop_gets_through_mosaic(v5e, case):
    """The prefill kernel's page loop at the shapes the cells serve
    (laguna's full kind 8 x 384 rows and window kind 8 x 288 at a table
    of 200, bloom 32 x 256 with ALiBi, olmoe and ouro 16 x 256, one kv
    head of a four-chip shard), compiled for the chip under both jitted
    names; the compiled call is the loop (its pools stay in HBM, no
    block a page), not the grid kernel."""
    nh, nkv, alibi, b, tile, W, N, window = PREFILL_LOOP_CASES[case]
    S = jax.ShapeDtypeStruct
    pages = jax.eval_shape(lambda: alloc_kv_pages(
        (N, nkv, 128, 128), "int8" if case.startswith("int8") else "bf16",
        jnp.bfloat16))
    assert pa._page_loop_covers(pages)
    slopes = (S((nh,), jnp.float32),) if alibi else ()
    kw = {"window": window} if window else {}
    args = [jax.tree.map(
        lambda s: S(s.shape, s.dtype, sharding=v5e), x) for x in (
            S((b, tile, nh, 128), jnp.bfloat16), pages, pages,
            S((b, W), jnp.int32), S((b, tile), jnp.int32), *slopes)]
    text = jax.jit(
        lambda q, pk, pv, t, p, *s: pa.paged_prefill_attention(
            q, pk, pv, t, p, *s, **kw)).lower(*args).compile().as_text()
    assert ("_paged_prefill_call_window." if window
            else "_paged_prefill_call.") in text


@pytest.mark.parametrize("case,chunk,how", [
    ("cell-qwen2.5-7b", 1, "kernel write"),
    ("cell-qwen2.5-7b", 64, "kernel write"),
    ("cell-bloom7b1", 1, "kernel write"),
    ("cell-bloom7b1", 64, "kernel write"),
    ("cell-tp4-shard", 1, "kernel write"),
    ("cell-tp4-shard", 64, "kernel write"),
    # what the Pallas write does not take: a chunk that is not whole
    # tile groups, int8 pages (their sidecars), heads of 64
    ("cell-qwen2.5-7b", 24, "plane"),
    ("qwen-int8-p128", 1, "plane"),
    ("qwen-int8-p128", 64, "plane"),
    ("bloom560m-bf16-p128", 1, "plane"),
    ("bloom560m-bf16-p128", 64, "plane")])
def test_pool_is_addressed_in_place_on_the_chip(v5e, case, chunk, how):
    """A layer call of the paged hook (KV write, then the kernel) over
    the STACKED pool, the layer picked by index, compiled for the chip.
    Where the write is the Pallas one (the benchmark's cells), the
    optimized program makes nothing as large as one layer's plane: no
    plane and no pool is copied.  Everywhere else the hook goes through
    the layer's plane, and then nothing of the POOL's shape is made but
    the plane put back in place: the stacked scatter, and a custom call
    on a pool of narrow heads (which lies in HBM in a layout that pads
    less than the one a custom call takes), each cost copies of the whole
    pool, a layer call."""
    from distributed_inference_demo_tpu.ops.stacked import LayerOf
    sys.path.insert(0, str(REPO / "tools"))
    from aot_mixed_step import large_ops
    (nh, nkv, hd), kind, bt, b, W, N = MOSAIC_CASES[case]
    L = 8
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=v5e)  # noqa: E731
    record = pa.AttnPathRecord()
    impl, bind = pa.make_paged_attn_impl(bt, record=record)
    slopes = (S((nh,), jnp.float32),) if nh == nkv else ()

    def layer_call(q, k, v, pk, pv, tables, pos, li, *slopes):
        bind(tables, "layer")
        out, pk, pv = impl(q, k, v, LayerOf(pk, li), LayerOf(pv, li), pos,
                           jnp.int32(0), *(slopes or (None,)))
        return out, pk.stack, pv.stack

    pool = jax.tree.map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: alloc_kv_pages((L, N, nkv, bt, hd), kind, jnp.bfloat16)))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        hlo = jax.jit(layer_call, donate_argnums=(3, 4)).lower(
            S((b, chunk, nh, hd), jnp.bfloat16),
            S((b, chunk, nkv, hd), jnp.bfloat16),
            S((b, chunk, nkv, hd), jnp.bfloat16), pool, pool,
            S((b, W), jnp.int32), S((b, chunk), jnp.int32),
            S((), jnp.int32), *slopes).compile().as_text()
    assert record.addressing() == {"layer": {f"chunk={chunk}": how}}
    assert ("kv_page_write" in hlo) == (how == "kernel write")
    leaves = jax.tree.leaves(pool)
    plane = min(a.dtype.itemsize * math.prod(a.shape[1:]) for a in leaves)
    large = large_ops(hlo, plane)
    if how == "kernel write":
        assert not large, large
    else:
        whole = {str(list(a.shape)) for a in leaves}
        made = [op for op in large if op[2].lstrip("bfsu0123456789") in whole]
        assert made and all("dynamic-update-slice" in op[0] for op in made), (
            made)


@pytest.mark.parametrize("slab", [2, 1, 0],
                         ids=["slab", "one-segment", "nothing-packed"])
@pytest.mark.parametrize("model,blocks", [("qwen2.5-7b-int8", 416),
                                          ("olmoe-1b-7b-int8", 224)])
def test_each_variant_of_mixed_step_leaves_the_pool_in_place(
        v5e, model, blocks, slab):
    """Every variant of ``mixed_step`` at a budget of two segments (a
    dispatch that packed two or one: a slab of as many + the decode loop;
    one that packed none: the decode loop alone; the slab's pass carries
    the decoding rows' first step), compiled whole for the
    chip through ``tools/aot_mixed_step``
    at a dense and the expert cell's flags (PERF.md section 4).  None
    makes anything of the pool's or of a plane's shape, and the variant
    without a slab holds no prefill attention and one KV write."""
    sys.path.insert(0, str(REPO / "tools"))
    from aot_mixed_step import compile_mixed_step, large_ops
    flags = argparse.Namespace(
        batch_slots=32, prefill_chunk=256, decode_block=4,
        mixed_token_budget=640, max_seq=4096, kv_block_tokens=128)
    compiled, eng = compile_mixed_step(model, blocks, flags, slab)
    hlo = compiled.as_text()
    leaf = jax.tree.leaves(eng._pk)[0]
    plane = leaf.dtype.itemsize * math.prod(leaf.shape[1:])
    pool_shapes = {str(list(leaf.shape)), str(list(leaf.shape[1:]))}
    large = large_ops(hlo, plane)
    assert not [op for op in large
                if op[2].lstrip("bfsu0123456789") in pool_shapes], large
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < plane * leaf.shape[0]      # far under one pool
    chunks = {"chunk=256", "chunk=1"} if slab else {"chunk=1"}
    assert eng.attn_paths.addressing() == {
        "mixed_step": dict.fromkeys(chunks, "kernel write")}
    assert set(eng.attn_paths.snapshot()["mixed_step"]) == chunks
    assert eng.attn_paths.snapshot()["mixed_step"]["chunk=1"] == \
        "pallas_decode"
    calls = set(re.findall(
        r"%([\w.-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call", hlo))
    writes = {c for c in calls if c.startswith("kv_page_write")}
    # a slab's pass writes its own rows and the rows of the step it
    # carries; then the decode loop's
    assert len(writes) == (3 if slab else 1), calls
    if not slab:
        assert not any("prefill" in c for c in calls), calls
        # the experts' three projections, once: the decode step's
        assert len([c for c in calls if c.startswith("moe_gmm")]) == (
            3 if "olmoe" in model else 0), calls


@pytest.mark.parametrize("slab", [2, 0], ids=["slab", "nothing-packed"])
@pytest.mark.parametrize("model,blocks,slots,budget,max_seq", [
    ("qwen2.5-7b-int8", 416, 32, 640, 4096),
    ("bloom7b1-int8", 46, 8, 544, 2048),
    ("ouro-2.6b", 44, 8, 544, 2048)],      # served at its own dtype, bf16
    ids=["qwen2.5-7b-int8", "bloom7b1-int8", "ouro-2.6b-bf16"])
def test_qkv_projections_read_their_weights_where_they_lie(
        v5e, model, blocks, slots, budget, max_seq, slab):
    """``mixed_step`` at three cells' flags (PERF.md section 4), compiled
    whole for the chip.  The q, k and v projections are plain matmuls over
    the stored ``[H, D]`` matrices: no instruction makes anything of the
    shape and dtype of a leaf of ``params.layers`` (a ``copy`` of a
    whole wq / wk / wv stack into another layout, once an execution), and
    the head reshape has not been folded into the dot (a convolution
    windowed over heads, which reads ``[heads, hd, H]`` and so has each
    layer's matrix written out first).  A window of 1 is the slab's
    segment axis, a plain matmul.  The decode-only program holds no
    temporary of a stack's size."""
    sys.path.insert(0, str(REPO / "tools"))
    from aot_mixed_step import compile_mixed_step, copied_weight_leaves
    flags = argparse.Namespace(
        batch_slots=slots, prefill_chunk=256, decode_block=4,
        mixed_token_budget=budget, max_seq=max_seq, kv_block_tokens=128)
    compiled, eng = compile_mixed_step(model, blocks, flags, slab)
    hlo = compiled.as_text()
    assert copied_weight_leaves(hlo, eng.params.layers) == []
    dots = [line for line in hlo.splitlines() if " convolution(" in line
            and 'bsh,hd->bsd/dot_general"' in line]
    assert len(dots) >= 3           # the scan still finds the projections
    windows = [int(n) for line in dots
               for size in re.findall(r"window=\{size=([\dx]+)", line)
               for n in size.split("x")]
    assert all(n == 1 for n in windows), windows
    if not slab:
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_flash_kernel_with_alibi_gets_through_mosaic(v5e):
    """MHA + ALiBi at a 256-token chunk: Mosaic refused the in-kernel
    ``jnp.tile`` of the slope vector ("Input offsets outside of the
    first tile"); the per-row slope column is now built outside."""
    from distributed_inference_demo_tpu.ops.flash_attention import (
        flash_attention)
    S = jax.ShapeDtypeStruct
    _compile_for(
        v5e, lambda q, k, v, a, b, s: flash_attention(q, k, v, a, b, s),
        S((2, 256, 16, 64), jnp.bfloat16),
        S((2, 16, 1024, 64), jnp.bfloat16),
        S((2, 16, 1024, 64), jnp.bfloat16),
        S((), jnp.int32), S((), jnp.int32), S((16,), jnp.float32))


# layers, experts here, experts routed over, hidden, intermediate; the
# token-expert rows of the cell's decode step and of its slab
_GMM_WIDTHS = {"olmoe": (16, 64, 64, 2048, 1024),
               "granite": (2, 36, 72, 4096, 768),
               "xing": (2, 64, 64, 3584, 1024),
               "solar": (2, 40, 320, 4096, 1280)}
_GMM_ROWS = {"granite": (320, 13120), "xing": (64, 2112),
             "solar": (512, 4608)}


@pytest.mark.parametrize("model, rows, quant", [
    *(("olmoe", rows, quant) for quant in ("int8", "bf16")
      for rows in (256, 4096)),
    *((model, rows, "bf16") for model, both in _GMM_ROWS.items()
      for rows in both)])
def test_grouped_matmul_gets_through_mosaic(v5e, model, rows, quant):
    """The experts' grouped matmul at the olmoe configuration's two
    shapes (256 token-expert rows: a decode step at 32 slots; 4,096: the
    512-token slab) and at granite's, xing's and solar's widths and rows
    (granite's slab, 13,120 rows, has groups that fill the widest row
    tile and is no multiple of it), both
    projections, the layer picked out of the whole stack by index.  The
    contraction is one tile of 3 to 7 MiB and Mosaic accepts it under the
    limit the call declares.  The compiler's temporaries stay under a
    MiB: no copy of a 128 MiB expert stack (sliced out, or widened from
    int8) is written to HBM, and no padded copy of the rows."""
    from distributed_inference_demo_tpu.ops.grouped_matmul import (
        LayerOf, grouped_matmul, tiling)
    from distributed_inference_demo_tpu.ops.quant import QuantizedArray
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=v5e)  # noqa: E731
    L, E, routed, H, I = _GMM_WIDTHS[model]
    for k, n in ((H, I), (I, H)):
        assert tiling(rows, k, n, 1 if quant == "int8" else 2, routed)[1] == k
        stack = (QuantizedArray(q=S((L, E, k, n), jnp.int8),
                                scale=S((L, E, 1, n), jnp.float32))
                 if quant == "int8" else S((L, E, k, n), jnp.bfloat16))
        compiled = jax.jit(
            lambda x, w, g, i: grouped_matmul(x, LayerOf(w, i), g,
                                              routed=routed,
                                              backend="pallas")
        ).lower(S((rows, k), jnp.bfloat16), stack, S((E,), jnp.int32),
                S((), jnp.int32)).compile()
        assert "moe_gmm" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("T, k, H", [(1312, 10, 4096), (576, 8, 4096)],
                         ids=["granite-slab", "solar-slab"])
def test_the_experts_combine_is_one_gather_and_one_fusion(v5e, T, k, H):
    """PR 65: ``decoder._combine`` at a slab's rows, compiled for the
    chip.  What stands between the down projection's bf16 rows and the
    float32 ``[T, H]`` sum is the un-sort gather, in bf16, and ONE fusion
    that masks, widens, weights and sums: the compiler's temporaries are
    the gathered rows and nothing else (the form before held a float32
    ``[T k, H]`` twice and a ``[T, k, H]`` with ``k`` padded to the
    sublanes: five times as much), and no instruction outside a fusion
    makes a float32 array of ``T k H`` elements."""
    from distributed_inference_demo_tpu.models.decoder import _combine
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=v5e)  # noqa: E731
    compiled = jax.jit(_combine).lower(
        S((k * T, H), jnp.bfloat16), S((k * T,), jnp.int32),
        S((T, k), jnp.float32), S((), jnp.int32)).compile()
    # (at most: the compiler may keep solar's 36 MiB in fast memory)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * 2 * k * T * H
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    wide = re.findall(r"= f32\[([\d,]+)\]\S* (?!parameter)[\w-]+\(", entry)
    assert wide and not [
        d for d in wide if np.prod([int(n) for n in d.split(",")]) >= k * T * H]
    assert f"bf16[{k * T},{H}]" in entry          # the gathered rows


@pytest.mark.parametrize("b,chunk", [(16, 1), (2, 256)],
                         ids=["decode", "slab"])
def test_latent_kernels_get_through_mosaic(v5e, b, chunk):
    """The latent (MLA) page write and the page-walking kernel at the
    kanana cell's shapes (8 planes of 2,048 pages of 128 tokens x 640
    lanes, 32 heads, rank 512): 16 decoding slots and a two-segment slab,
    each with the group of pages and the ring its shapes derive (both
    fold 4 pages an iteration through 3 slots: a decode step's group is
    held by a slot's bytes, a slab tile's by its float32 scores) and
    their scratch inside the kernel's VMEM limit.
    The calls carry the names the trace readers match by prefix, the pool
    is aliased through the write (temporaries far under a plane), and a
    576-wide row, the width as published, is refused by Mosaic's DMA
    slicing, which is why the page is lane-padded."""
    from distributed_inference_demo_tpu.ops import latent_attention as la
    from distributed_inference_demo_tpu.ops.stacked import LayerOf
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=v5e)  # noqa: E731
    L, N, bt, nh, rank = 8, 2048, 128, 32, 512
    rows = la.latent_tile_tokens(chunk, nh) * nh
    group, ring = la.latent_fold(rows, bt, 640, 2, 96)
    assert (group, ring) == (4, 3)
    # the ring, the float32 state (output, maximum, sum), an iteration's
    # float32 scores with the weights made of them (the probabilities and
    # their two bf16 terms), the pipeline's two copies of the query and
    # output tiles
    keys = group * bt
    scratch = (ring * keys * 640 * 2 + rows * (rank + 2 * 128) * 4
               + rows * keys * (4 + 4 + 2 * 2) + 2 * rows * (640 + rank) * 2)
    assert scratch < la._VMEM_LIMIT, scratch

    def step(q, pool, li, tables, pos, row):
        pages = la.write_latent_pages(LayerOf(pool, li), row, tables, pos,
                                      form=la.WRITE_KERNEL)
        out = la.latent_paged_attention(q, pages, tables, pos, rank,
                                        192 ** -0.5)
        return out, pages.stack

    def lower(width):
        return jax.jit(step, donate_argnums=(1,)).lower(
            S((b, chunk, nh, width), jnp.bfloat16),
            S((L, N, 1, bt, width), jnp.bfloat16), S((), jnp.int32),
            S((b, 96), jnp.int32), S((b, chunk), jnp.int32),
            S((b, chunk, width), jnp.bfloat16))

    compiled = lower(640).compile()
    text = compiled.as_text()
    assert "kv_page_write" in text
    assert ("_paged_call_latent" if chunk == 1
            else "_paged_prefill_call_latent") in text
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20
    if chunk == 1:
        with pytest.raises(Exception, match="aligned to tiling"):
            lower(576).compile()
