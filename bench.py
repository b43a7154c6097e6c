"""Benchmark harness: north-star metrics on the real TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Headline metric (BASELINE.md config #1): decode tokens/sec on
TinyLlama-1.1B, single chip, vs the measured 2-process CPU socket-pipeline
baseline of the SAME model/batch (``tools/cpu_baseline.py`` →
``tools/cpu_baseline.json``).  North-star target: >= 10x.

Extra legs (each reported inside the same JSON object):

- ``headline_int8``: int8 TinyLlama decode (half the HBM bytes/step —
  decode is bandwidth-bound, so this is the throughput configuration);
- ``sweep``: batch sweep 8/32/64 at bf16 and int8, each with achieved
  HBM GB/s (= weights_bytes x steps/s) so the roofline gap is visible;
- ``flagship_int8`` / ``flagship_bf16``: Llama-3-8B single-chip decode —
  BASELINE.md's flagship model (bf16 weights exceed a 16 GB chip: the leg
  reports "does not fit" from a host-side precheck instead of OOMing);
- ``pipeline``: inter-shard activation latency p50/p95 across a live
  2-process socket pipeline (device header + CPU worker — BASELINE
  config #2's heterogeneous shape), from the hot-loop stats
  (``runtime/stats.py``; reference timers ``Communication.java:859-896``);
- ``prefill_long``: long-prompt prefill, Pallas flash kernel vs jnp
  attention, 2k-8k tokens;
- ``speculative``: draft/verify decoding vs plain decode on the same
  workload (draft = int8 quantization of the same seed weights), with
  acceptance rate and speedup;
- ``prompt_lookup``: draft-free n-gram speculation at batch 1 on a
  repetitive prompt, vs plain decode;
- ``batching``: continuous-batching aggregate throughput (24 requests
  into 8 slots) vs sequential plain batches, plus the block KV cache's
  hit/reuse counters on a shared-prefix workload;
- ``prefix_reuse``: the block KV cache (runtime/kvcache) on a
  repeated-shared-prefix workload — hit rate, reused tokens, and
  measured prefill-seconds saved (cache-off vs cache-on wall delta);
- ``tiered_prefix``: the §21 host-RAM/disk KV tier vs re-prefill when
  the shared-prefix working set exceeds the device pool — revisit TTFT
  p95, promotion h2d bytes, per-tier hit rates, greedy bit-identity,
  and the three-tier zero-leak check;
- ``paged_decode``: paged vs dense KV layout on the batching engine —
  decode tok/s ratio, reserved-vs-actually-allocated cache HBM at a
  serving-realistic max_seq, and the primed phase's h2d_bytes == 0
  zero-copy-prefix-hit check (docs/DESIGN.md §11);
- ``long_context``: 32k-token single-chip generation via chunked prefill
  + flash attention (prefill and decode tok/s at full context).

**Process isolation:** every leg runs in a fresh subprocess (`--leg` mode)
with its own TPU context, so one leg's allocations or failure can never
poison the next (the round-2 bench lost all three flagship legs to exactly
that).  The parent process never initializes JAX.
"""

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
BASELINE_PATH = REPO / "tools" / "cpu_baseline.json"

def _load_baseline() -> dict:
    if BASELINE_PATH.exists():
        data = json.loads(BASELINE_PATH.read_text())
        data["source"] = "tools/cpu_baseline.json"
        return data
    return {"tokens_per_sec": None, "source": "missing"}


def _device_kind():
    import jax
    return jax.devices()[0].device_kind


def _hbm_limit_bytes():
    """Per-device HBM capacity if the backend exposes it, else None."""
    import jax
    try:
        stats = jax.devices()[0].memory_stats()
        return stats.get("bytes_limit")
    except Exception:
        return None


def _with_bandwidth(result: dict, weights_bytes: int, device: str) -> dict:
    """Annotate a decode result with achieved HBM GB/s and roofline frac.

    Decode is weight-streaming-bound: every step reads all weights once,
    so achieved_gbs = weights_bytes * steps/s is a lower bound on HBM
    traffic actually sustained (cache reads add more)."""
    tps = result.get("decode_tokens_per_sec")
    batch = result.get("batch")
    if not tps or not batch:
        return result
    steps_per_sec = tps / batch
    gbs = weights_bytes * steps_per_sec / 1e9
    result["weights_gb"] = round(weights_bytes / 1e9, 3)
    result["achieved_gbs"] = round(gbs, 1)
    # the repo's ONE peaks table, keyed by device_kind; a kind it lacks
    # (the CPU of the structure tests) gets no ratio, and says so
    from distributed_inference_demo_tpu.telemetry.profiling import (
        device_peaks)
    try:
        peaks = device_peaks(device)
    except KeyError as e:
        result["hbm_roofline_frac"] = None
        result["hbm_peak_note"] = e.args[0]
        return result
    result["hbm_roofline_frac"] = round(gbs / peaks.hbm_gbs, 3)
    result["hbm_gbs_published"] = peaks.hbm_gbs
    result["hbm_peak_source"] = peaks.source
    return result


def _bench_engine(model: str, batch: int, prompt_len: int, new_tokens: int,
                  quant=False, latency: bool = False) -> dict:
    """Single-chip decode + prefill throughput via InferenceEngine.
    ``quant``: False | True (int8) | "int8" | "int4".  ``latency`` adds
    per-request TTFT/TPOT percentiles (one extra compiled program — the
    streamed step — so only the headline legs pay for it)."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine

    mode = "int8" if quant is True else quant
    name = model + (f"-{mode}" if mode else "")
    cfg = get_model_config(name)
    # layer-chunked init+quantize: peak HBM stays near the int8 footprint
    # instead of materializing the float tree first (which would OOM exactly
    # the chips int8 exists to fit on) — models/decoder.py:_init_quantized
    params = init_full_params(jax.random.PRNGKey(0), cfg,
                              quantize=bool(mode))
    engine = InferenceEngine(
        cfg, params, max_seq=prompt_len + new_tokens,
        sampling=SamplingParams(temperature=0.7, top_k=7))  # ref defaults

    prompt = (np.arange(batch * prompt_len).reshape(batch, prompt_len)
              % 1000).astype(np.int32)
    engine.generate(prompt, new_tokens, seed=0)           # compile warmup
    result = engine.generate(prompt, new_tokens, seed=0)  # steady state
    decode_tps = result.tokens_per_second

    # prefill throughput: best of 3 single dispatches on fresh caches;
    # the per-round list keeps one slow sample visible in the artifact
    rounds = []
    for _ in range(3):
        cache = engine.new_cache(batch)           # fresh, outside timing
        t0 = time.perf_counter()
        logits, cache = engine._prefill(engine.params, prompt, cache)
        jax.block_until_ready(logits)
        rounds.append(time.perf_counter() - t0)
    prefill_tps = batch * prompt_len / min(rounds)

    out = {
        "model": name,
        "decode_tokens_per_sec": round(decode_tps, 2),
        # per decode STEP (the fused scan advances the whole batch one
        # position per step, so steps/s = tok/s / batch) — the number the
        # large-batch roofline-erosion analysis decomposes: cache-read
        # bytes grow with batch while weight bytes stay fixed
        "decode_step_ms": round(1000.0 * batch / decode_tps, 3),
        "prefill_tokens_per_sec": round(prefill_tps, 2),
        "prefill_round_ms": [round(r * 1000, 1) for r in rounds],
        "batch": batch, "prompt_len": prompt_len, "new_tokens": new_tokens,
        "dtype": mode if mode else cfg.dtype_name,
    }
    if latency:
        out["latency"] = _latency_percentiles(engine, prompt[:1],
                                              min(new_tokens, 16))
    out = _with_bandwidth(out, params.nbytes(), _device_kind())
    # cache-READ traffic estimate per second: each decode step attends
    # the whole valid context, so cache bytes grow linearly with batch
    # while the weight stream stays fixed — the decomposition behind the
    # large-batch roofline erosion (achieved_gbs counts weights only)
    kv_bytes_per_pos = (cfg.num_layers * 2 * cfg.num_kv_heads
                        * cfg.head_dim
                        * (engine.kv_cache_dtype or cfg.dtype).itemsize)
    avg_ctx = prompt_len + new_tokens / 2
    steps_per_sec = decode_tps / batch
    out["cache_read_gbs_est"] = round(
        batch * avg_ctx * kv_bytes_per_pos * steps_per_sec / 1e9, 1)
    if out.get("achieved_gbs"):
        out["total_gbs_est"] = round(
            out["achieved_gbs"] + out["cache_read_gbs_est"], 1)
    return out


def _latency_percentiles(engine, prompt, new_tokens: int,
                         requests: int = 8) -> dict:
    """Per-request TTFT/TPOT p50/p95/p99 over ``requests`` sequential
    single-row STREAMED generations (the SLO view of the same engine the
    throughput numbers describe: TTFT = prefill + first streamed step,
    TPOT = mean inter-token gap per request), so latency regressions
    show up per PR, not just tok/s."""
    from distributed_inference_demo_tpu.runtime.stats import _percentile

    ttfts, tpots = [], []
    for i in range(requests):
        t0 = time.perf_counter()
        t_first = t_last = None
        n = 0
        for _ in engine.generate_stream(prompt, new_tokens, seed=i):
            t_last = time.perf_counter()
            if t_first is None:
                t_first = t_last
            n += 1
        if t_first is None:
            continue
        if i == 0:
            # first request compiles the streamed step: warmup, not data
            continue
        ttfts.append(t_first - t0)
        if n > 1:
            tpots.append((t_last - t_first) / (n - 1))
    out = {"requests": len(ttfts), "new_tokens": new_tokens}
    for name, xs in (("ttft", ttfts), ("tpot", tpots)):
        xs = sorted(xs)
        for q in (50, 95, 99):
            out[f"{name}_p{q}_ms"] = (
                round(_percentile(xs, q) * 1e3, 3) if xs else None)
    return out


def _weights_bytes_estimate(model: str) -> int:
    """Host-side parameter-count estimate (no device allocation)."""
    from distributed_inference_demo_tpu.models import get_model_config
    cfg = get_model_config(model)
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = H * nh * hd + 2 * H * nkv * hd + nh * hd * H
    mlp = 3 * H * I if cfg.family != "bloom" else 2 * H * I
    if cfg.num_experts:
        mlp *= cfg.num_experts
    per_layer = attn + mlp
    embed = cfg.vocab_size * H * (1 if cfg.tie_embeddings else 2)
    if cfg.quantization == "int8":
        bpp = 1.0
    elif cfg.quantization == "int4":
        # 2 weights/byte + f32 group scales (ops/quant.DEFAULT_INT4_GROUP)
        bpp = 0.5 + 4.0 / 64
    else:
        bpp = jnp_bytes(cfg.dtype_name)
    # embeddings/head stay at the model dtype even under quantization
    return int(L * per_layer * bpp) + embed * jnp_bytes(cfg.dtype_name)


def jnp_bytes(dtype_name: str) -> int:
    import numpy as np
    return np.dtype(dtype_name if dtype_name != "bfloat16" else "uint16").itemsize


def _leg_flagship(model: str, batch: int, prompt_len: int, new_tokens: int,
                  quant) -> dict:
    mode = "int8" if quant is True else quant
    name = model + (f"-{mode}" if mode else "")
    need = _weights_bytes_estimate(name)
    # what the device's own allocator reports; a backend without
    # memory_stats (the CPU) gets no fit check rather than an assumed size
    limit = _hbm_limit_bytes()
    if limit and need > limit * 0.92:  # leave room for cache + compiled code
        return {"model": name,
                "skipped": f"does not fit: ~{need / 1e9:.1f} GB weights vs "
                           f"{limit / 1e9:.1f} GB HBM"}
    return _bench_engine(model, batch, prompt_len, new_tokens, quant=quant)


def _bench_batching_kv(model: str, batch: int, prompt_len: int,
                       new_tokens: int, quant=False,
                       kv_dtype: str = "bf16") -> dict:
    """One (weight-dtype x kv-dtype) sweep point on the paged-native
    batching engine.  The kv-dtype axis can only be measured HERE: the
    plain engine's dense working cache never touches the page pool, so
    threading ``kv_dtype`` through ``_bench_engine`` would time a no-op
    (docs/DESIGN.md §17)."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    mode = "int8" if quant is True else quant
    name = model + (f"-{mode}" if mode else "")
    cfg = get_model_config(name)
    params = init_full_params(jax.random.PRNGKey(0), cfg,
                              quantize=bool(mode))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 1000, size=(prompt_len,)).astype(np.int32)
               for _ in range(batch)]
    with ContinuousBatchingEngine(
            cfg, params, max_seq=prompt_len + new_tokens, max_batch=batch,
            sampling=SamplingParams(temperature=0.7, top_k=7),
            kv_layout="paged", kv_dtype=kv_dtype) as eng:
        eng.submit(prompts[0], 4).wait(timeout=600)       # compile warmup
        eng.submit(prompts[-1], 4).wait(timeout=600)
        t0 = time.perf_counter()
        reqs = [eng.submit(p, new_tokens) for p in prompts]
        for r in reqs:
            r.wait(timeout=900)
        dt = time.perf_counter() - t0
        mgr = eng.kv_cache
        return {
            "model": name, "engine": "batching-paged",
            "kv_dtype": kv_dtype, "batch": batch,
            "prompt_len": prompt_len, "new_tokens": new_tokens,
            "decode_tokens_per_sec": round(batch * new_tokens / dt, 2),
            "block_bytes": int(mgr.block_bytes),
            "pool_capacity_bytes": int(eng._pk.nbytes + eng._pv.nbytes),
        }


def _leg_sweep(model: str, prompt_len: int, new_tokens: int,
               quants=(False, True), batches=(32, 64),
               kv_dtypes=()) -> dict:
    """Batch sweep at bf16 and int8 with achieved GB/s per point.
    Points are isolated: one OOMing batch size must not discard the rest.
    (b=8 is omitted — the headline/headline_int8 legs already cover it —
    to keep total bench wall-clock inside the driver's window.)

    ``kv_dtypes`` adds the §17 weight-dtype x kv-dtype cross at the
    largest batch, measured on the paged batching engine (the only
    engine whose decode reads the page pool): one point per
    (quant, kv_dtype) pair in ``kv_points``."""
    points = []
    for quant in quants:
        for batch in batches:
            try:
                points.append(_bench_engine(model, batch, prompt_len,
                                            new_tokens, quant=quant))
            except Exception as e:
                points.append({"model": model, "batch": batch,
                               "dtype": "int8" if quant else "bf16",
                               "error": f"{type(e).__name__}: {e}"})
    out = {"points": points}
    if kv_dtypes:
        kv_points = []
        batch = max(batches)
        for quant in quants:
            for kvd in kv_dtypes:
                try:
                    kv_points.append(_bench_batching_kv(
                        model, batch, prompt_len, new_tokens,
                        quant=quant, kv_dtype=kvd))
                except Exception as e:
                    mode = "int8" if quant is True else quant
                    kv_points.append({
                        "model": model + (f"-{mode}" if mode else ""),
                        "batch": batch, "kv_dtype": kvd,
                        "error": f"{type(e).__name__}: {e}"})
        out["kv_points"] = kv_points
    return out


def _leg_roofline_probe(reps: int = 32, rounds_n: int = 3) -> dict:
    """Two probes of THIS chip (one dispatch each, loops on device):

    - ``hbm_read_gbs``: pure-HBM read bandwidth (1 GiB reduce x32).
    - ``dispatch_floor_ms``: per-call dispatch latency (tiny op).

    They are context for the decode legs, not a ceiling: ratios are
    taken against the published peak of the device kind
    (telemetry/profiling.DEVICE_PEAKS), never against a number this
    harness measured itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    big = jnp.ones((1 << 29,), jnp.bfloat16)   # 1 GiB

    @jax.jit
    def red_many(x):
        # the scan input feeds each read so the reduce is NOT
        # loop-invariant (LICM would otherwise hoist it and inflate the
        # reported bandwidth 32x)
        def rep(acc, j):
            return acc + jnp.sum((x + j).astype(jnp.float32)), None
        acc, _ = jax.lax.scan(rep, 0.0, jnp.arange(reps, dtype=x.dtype))
        return acc

    float(red_many(big))                        # compile
    # best of rounds_n, with the spread reported beside it
    rounds = []
    for _ in range(rounds_n):
        t0 = time.perf_counter()
        s = red_many(big)
        float(s)
        rounds.append(big.nbytes * reps / (time.perf_counter() - t0) / 1e9)
    hbm = max(rounds)
    ordered = sorted(rounds)
    median = ordered[len(ordered) // 2]

    @jax.jit
    def tiny(x):
        return x + 1.0

    float(tiny(jnp.float32(0)))
    t0 = time.perf_counter()
    for _ in range(8):
        y = tiny(jnp.float32(0))
    float(y)
    floor_ms = (time.perf_counter() - t0) / 8 * 1000

    return {"hbm_read_gbs": round(hbm, 1),
            "hbm_read_gbs_min": round(min(rounds), 1),
            "hbm_read_gbs_median": round(median, 1),
            "hbm_read_gbs_rounds": [round(r, 1) for r in rounds],
            "dispatch_floor_ms": round(floor_ms, 2)}


def _leg_prefill_long(model: str, seqs=(2048, 8192)) -> dict:
    """Long-prompt prefill: Pallas flash kernel vs jnp attention.

    >= 100k tokens of work per measurement; this is where the L1 kernel
    story must show up in an artifact (decode chunks route to the XLA path
    by design — make_flash_attn_impl min_chunk)."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.runtime import InferenceEngine

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    out = {"model": model, "points": []}
    # 4096 omitted: a point between the two endpoints
    for seq in seqs:
        # small batch x long prompt: the long-context serving shape (and
        # where flash's causal block-skipping matters); reps make up the
        # >=128k tokens of measured work
        batch = 8
        point = {"prompt_len": seq, "batch": batch}
        for backend in ("flash", "jnp"):
            try:
                engine = InferenceEngine(cfg, params, max_seq=seq,
                                         attn_backend=backend)
                prompt = (np.arange(batch * seq).reshape(batch, seq)
                          % 1000).astype(np.int32)
                cache = engine.new_cache(batch)
                logits, _ = engine._prefill(engine.params, prompt, cache)
                jax.block_until_ready(logits)  # compile warmup
                reps = max(2, 131072 // (batch * seq))
                t0 = time.perf_counter()
                for _ in range(reps):
                    cache = engine.new_cache(batch)
                    logits, cache = engine._prefill(engine.params, prompt,
                                                    cache)
                jax.block_until_ready(logits)
                dt = (time.perf_counter() - t0) / reps
                point[backend + "_tokens_per_sec"] = round(
                    batch * seq / dt, 1)
            except Exception as e:  # per-point, per-backend isolation
                point[backend + "_error"] = (
                    f"{type(e).__name__}: {e}"[:300])
        if ("flash_tokens_per_sec" in point
                and "jnp_tokens_per_sec" in point):
            point["flash_speedup"] = round(
                point["flash_tokens_per_sec"]
                / point["jnp_tokens_per_sec"], 3)
        out["points"].append(point)
    return out


def _leg_long_context(model: str) -> dict:
    """Single-chip long-context generation at 32k tokens: chunked prefill
    (ONE compiled 2048-token chunk shape regardless of prompt length,
    bounding activation memory) + flash attention + KV-cached decode at
    full context.  The sequence-parallel strategies (ring / Ulysses)
    cover contexts beyond one chip and are certified by the multichip
    dryrun's engine-parity checks; this leg is the real-hardware
    long-context number (SURVEY §5.7 — absent in the reference, whose
    max_length was 40)."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    ctx = int(os.environ.get("BENCH_LONG_CTX", "32768"))
    new, chunk = 64, min(2048, ctx // 2)
    plen = ctx - new
    engine = InferenceEngine(cfg, params, max_seq=ctx,
                             sampling=SamplingParams(greedy=True),
                             prefill_chunk=chunk)
    prompt = (np.arange(plen) % 1000).astype(np.int32)[None, :]

    import jax.numpy as jnp

    engine.generate(prompt, new, seed=0)            # compile warmup
    cache = engine.new_cache(1)
    t0 = time.perf_counter()
    logits, cache = engine._run_prefill(jnp.asarray(prompt), cache)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    toks, _, _ = engine._decode(engine.params, logits, cache,
                                jax.random.PRNGKey(0),
                                engine._eos_scalar(), new, False)
    np.asarray(toks)
    decode_s = time.perf_counter() - t0
    return {
        "model": model, "batch": 1, "context": ctx, "prompt_len": plen,
        "new_tokens": new, "prefill_chunk": chunk,
        "attn_backend": engine.attn_backend,
        "prefill_tokens_per_sec": round(plen / prefill_s, 1),
        "decode_tokens_per_sec": round(new / decode_s, 2),
    }


def _leg_decode_fused(model: str, prompt_len: int, new_tokens: int,
                      batches=(1, 8), blocks=(1, 4, 16)) -> dict:
    """The device-resident decode loop (docs/DESIGN.md §13): streamed
    decode tok/s + MEASURED host dispatches/token at batch x
    stream_block K.  K=1 is the per-token path — its dispatches/token
    is exactly 1 and its tok/s exposes the host dispatch floor; the
    K>1 points show the floor amortizing as dispatches/token ≈ 1/K.
    Greedy-bit-identity across K is pinned by tier-1 tests; this leg
    measures only speed."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    out = {"model": model, "prompt_len": prompt_len,
           "new_tokens": new_tokens, "points": []}
    for batch in batches:
        prompt = (np.arange(batch * prompt_len).reshape(batch, prompt_len)
                  % 1000).astype(np.int32)
        for K in blocks:
            try:
                engine = InferenceEngine(
                    cfg, params, max_seq=prompt_len + new_tokens,
                    sampling=SamplingParams(temperature=0.7, top_k=7),
                    stream_block=K)
                for _ in engine.generate_stream(prompt, new_tokens,
                                                seed=0):
                    pass                        # compile warmup
                engine.loop_stats = {"host_dispatches": 0,
                                     "device_loop_steps": 0}
                t_first = t_last = None
                n = 0
                for _ in engine.generate_stream(prompt, new_tokens,
                                                seed=0):
                    t_last = time.perf_counter()
                    if t_first is None:
                        t_first = t_last
                    n += 1
                point = {"batch": batch, "stream_block": K, "tokens": n,
                         **engine.loop_stats}
                point["dispatches_per_token"] = round(
                    engine.loop_stats["host_dispatches"] / max(n, 1), 4)
                if n > 1:
                    point["decode_tokens_per_sec"] = round(
                        batch * (n - 1) / (t_last - t_first), 2)
                out["points"].append(point)
            except Exception as e:   # per-point isolation
                out["points"].append({"batch": batch, "stream_block": K,
                                      "error": f"{type(e).__name__}: "
                                               f"{e}"[:300]})
    best = [p.get("decode_tokens_per_sec") for p in out["points"]
            if p.get("decode_tokens_per_sec")]
    if best:
        out["best_decode_tokens_per_sec"] = max(best)
    return out


def _leg_pipeline(model: str, batch: int, prompt_len: int,
                  new_tokens: int) -> dict:
    """2-process socket pipeline: this process (default backend — the TPU
    when present) is the header, a spawned CPU process is the tail.
    Inter-shard activation latency is derived per token as
    ``(ring RTT - tail compute p50) / 2`` — the RTT covers exactly two
    socket hops (hidden out, token back) around the tail's compute."""
    import numpy as np
    import jax
    from distributed_inference_demo_tpu.comm.transport import ZmqTransport
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.base import (
        slice_stage, split_layer_ranges)
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.distributed import (
        PipelineHeader, StageRuntime)

    cfg = get_model_config(model)
    specs = split_layer_ranges(cfg.num_layers, 2)
    max_seq = prompt_len + new_tokens
    sampling = SamplingParams(temperature=0.7, top_k=7)

    header_transport = ZmqTransport("header")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "distributed_inference_demo_tpu.runtime.worker_main",
         "--model", model, "--stage-id", "1", "--num-stages", "2",
         "--layer-start", str(specs[1].layer_start),
         "--layer-end", str(specs[1].layer_end),
         "--device-id", "w1", "--port", "0",
         "--header", f"header@{header_transport.address}",
         "--max-seq", str(max_seq), "--dtype", "float32",
         "--temperature", "0.7", "--top-k", "7",
         "--step-timeout", "600"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True, cwd=str(REPO))
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("WORKER_READY w1 "), line
        header_transport.connect("w1", line.split()[-1])

        full = init_full_params(jax.random.PRNGKey(0), cfg)
        header = PipelineHeader(
            StageRuntime(cfg, specs[0], slice_stage(full, cfg, specs[0]),
                         max_seq, sampling),
            header_transport, next_id="w1", step_timeout=600)
        prompt = (np.arange(batch * prompt_len).reshape(batch, prompt_len)
                  % 1000).astype(np.int32)
        header.generate(prompt, 4)          # warmup/compile
        header.reset_stats()
        t0 = time.perf_counter()
        header.generate(prompt, new_tokens)
        dt = time.perf_counter() - t0
        stats = header.collect_stats(num_stages=2, timeout=30)
        # dynamic-batching phase: the same 4 requests serialized vs
        # interleaved (pool_size rids in flight — the serve --pool-size
        # capability measured on the live 2-process pipeline; prompt
        # shapes match the warmup so no new compiles)
        pool_pts = {}
        for pool in (1, 4):
            t1 = time.perf_counter()
            header.generate_many([prompt] * 4, new_tokens, pool_size=pool)
            pool_pts[f"pool{pool}_tokens_per_sec"] = round(
                4 * batch * new_tokens / (time.perf_counter() - t1), 2)
        header.shutdown_pipeline()
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
        header_transport.close()

    h = stats[0]
    tail = stats[1] if len(stats) > 1 else {}
    tail_p50 = tail.get("compute_p50_ms", 0.0)
    out = {
        "model": model, "batch": batch, "num_stages": 2,
        # one host dispatch per ring step bounds this tok/s; the
        # framework's own cost is the activation_hop percentiles below
        # (BASELINE config #2's metric)
        "note": "tokens_per_sec is bounded by one host dispatch per ring "
                "step; activation_hop_* is the framework metric",
        "pipeline_tokens_per_sec": round(batch * new_tokens / dt, 2),
        "dynamic_batching_4req": dict(
            pool_pts,
            speedup=round(pool_pts["pool4_tokens_per_sec"]
                          / pool_pts["pool1_tokens_per_sec"], 3)),
        "ring_rtt_p50_ms": h.get("ring_rtt_p50_ms"),
        "ring_rtt_p95_ms": h.get("ring_rtt_p95_ms"),
        "tail_compute_p50_ms": tail_p50,
        "stage_stats": stats,
    }
    _paired_hop_percentiles(h, tail, out)
    return out


class _LineReader:
    """Reads a subprocess's stdout on a daemon thread into a queue, so
    waits can time out reliably.  (select() on the pipe fd is wrong with a
    buffered TextIOWrapper: readline() may pull several lines into the
    Python buffer, leaving the fd empty while the awaited line sits
    buffered; blocking readline() can't time out at all.)"""

    _EOF = object()

    def __init__(self, proc):
        import queue
        import threading
        self.proc = proc
        self.q: "queue.Queue" = queue.Queue()

        def pump():
            for line in proc.stdout:
                self.q.put(line)
            self.q.put(self._EOF)   # death declared only past this marker

        threading.Thread(target=pump, daemon=True).start()

    def read_until(self, prefix: str, timeout: float = 300.0) -> str:
        import queue
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise RuntimeError(f"{prefix!r} not seen within {timeout}s")
            try:
                line = self.q.get(timeout=min(left, 0.5))
            except queue.Empty:
                continue
            if line is self._EOF:
                # the pump drained every line the process ever wrote (no
                # poll/queue race): it is gone and the line never came
                raise RuntimeError(
                    f"process exited (rc={self.proc.poll()}) without "
                    f"printing {prefix!r}")
            line = line.strip()
            if line.startswith(prefix):
                return line


def _paired_hop_percentiles(header_stats: dict, tail_stats: dict,
                            out: dict) -> None:
    """Per-hop activation latency from PAIRED per-step samples: with one
    request in flight, header rtt sample i and tail compute sample i are
    the same token step, so (rtt_i - compute_i)/2 cancels the tail's
    compute variance (aggregate p50s can't — a slow CPU tail's jitter
    swamps the hop and clamps the estimate to 0)."""
    rtts = header_stats.get("rtt_samples_ms") or []
    comps = tail_stats.get("compute_samples_ms") or []
    n = min(len(rtts), len(comps))
    if n:
        hops = sorted(max(0.0, (r - c) / 2)
                      for r, c in zip(rtts[-n:], comps[-n:]))
        out["activation_hop_p50_ms"] = round(hops[n // 2], 3)
        out["activation_hop_p95_ms"] = round(
            hops[min(n - 1, int(0.95 * n))], 3)


def _leg_speculative(model: str, batch: int, prompt_len: int,
                     new_tokens: int) -> dict:
    """Speculative decoding vs plain decode on the SAME workload.

    Without real weights, the draft is the int8 quantization of the SAME
    seed-init target (identical PRNGKey -> identical float tree ->
    quantized): a faithful cheap approximation of the target, so greedy
    acceptance measures real argmax agreement and the draft's cost is
    genuinely about half the target's HBM stream.  Acceptance on real
    checkpoints is a weights property; this leg pins the MECHANICS
    (round cost, speedup at the measured acceptance)."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import (InferenceEngine,
                                                        SpeculativeEngine)
    from distributed_inference_demo_tpu.runtime.speculative import stats_json

    cfg = get_model_config(model)
    draft_cfg = get_model_config(model + "-int8")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    draft_params = init_full_params(jax.random.PRNGKey(0), draft_cfg,
                                    quantize=True)
    sampling = SamplingParams(greedy=True)
    max_seq = prompt_len + new_tokens
    prompt = (np.arange(batch * prompt_len).reshape(batch, prompt_len)
              % 1000).astype(np.int32)

    engine = InferenceEngine(cfg, params, max_seq=max_seq, sampling=sampling)
    engine.generate(prompt, new_tokens, seed=0)            # compile
    plain = engine.generate(prompt, new_tokens, seed=0)

    num_draft = 4
    spec = SpeculativeEngine(cfg, params, draft_cfg, draft_params,
                             max_seq=max_seq, sampling=sampling,
                             num_draft=num_draft)
    spec.generate(prompt, new_tokens, seed=0)              # compile
    res, stats = spec.generate(prompt, new_tokens, seed=0)

    return {
        "model": model, "draft": model + "-int8 (same seed weights)",
        "batch": batch, "prompt_len": prompt_len, "new_tokens": new_tokens,
        "sampling": "greedy",
        "plain_tokens_per_sec": round(plain.tokens_per_second, 2),
        "spec_tokens_per_sec": round(res.tokens_per_second, 2),
        "speedup": round(res.tokens_per_second
                         / plain.tokens_per_second, 3),
        "spec_stats": stats_json(stats, num_draft),
    }


def _leg_prompt_lookup(model: str, new_tokens: int) -> dict:
    """Prompt-lookup (draft-free) speculation vs plain decode, batch 1.

    The prompt is a REPEATED n-gram block — the shape PLD exists for
    (quotes, code identifiers, summarization).  Whether the model's
    greedy continuation re-uses context spans is a weights property;
    seed-init weights are adversarial for acceptance, so the leg's
    value is the mechanics cost (rounds/s, speedup at the measured
    acceptance), not an acceptance ceiling."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    from distributed_inference_demo_tpu.runtime.prompt_lookup import (
        PromptLookupEngine)
    from distributed_inference_demo_tpu.runtime.speculative import stats_json

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    sampling = SamplingParams(greedy=True)
    prompt_len = 128
    max_seq = prompt_len + new_tokens
    block = np.arange(16) * 37 % 1000              # one 16-token motif
    prompt = np.tile(block, prompt_len // 16)[None, :].astype(np.int32)

    engine = InferenceEngine(cfg, params, max_seq=max_seq, sampling=sampling)
    engine.generate(prompt, new_tokens, seed=0)            # compile
    plain = engine.generate(prompt, new_tokens, seed=0)

    num_draft = 4
    pld = PromptLookupEngine(cfg, params, max_seq=max_seq,
                             sampling=sampling, num_draft=num_draft)
    pld.generate(prompt, new_tokens, seed=0)               # compile
    res, stats = pld.generate(prompt, new_tokens, seed=0)

    return {
        "model": model, "batch": 1, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "sampling": "greedy",
        "prompt_shape": "16-token motif tiled x8",
        "plain_tokens_per_sec": round(plain.tokens_per_second, 2),
        "pld_tokens_per_sec": round(res.tokens_per_second, 2),
        "speedup": round(res.tokens_per_second
                         / plain.tokens_per_second, 3),
        "spec_stats": stats_json(stats, num_draft),
    }


def _leg_batching(model: str, prompt_len: int, new_tokens: int) -> dict:
    """Continuous batching aggregate throughput + automatic prefix cache.

    Phase A: 24 distinct-prompt requests submitted at once into 8 slots
    (aggregate tok/s with slot churn — admissions interleave with decode
    steps).  The plain-engine comparison runs the same 24 requests as 3
    sequential batch-8 ``generate`` calls on the same weights.
    Phase B: 8 requests sharing a long prefix — reports the prefix
    cache's hit/reuse counters and its aggregate tok/s."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    sampling = SamplingParams(temperature=0.7, top_k=7)
    slots, n_req = 8, 24
    # covers phase B's 128-token prompts even when BENCH_PROMPT is small
    max_seq = max(prompt_len, 128) + new_tokens
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 1000, size=(n_req, prompt_len)).astype(
        np.int32)

    plain = InferenceEngine(cfg, params, max_seq=max_seq, sampling=sampling)
    plain.generate(prompts[:slots], new_tokens, seed=0)    # compile
    t0 = time.perf_counter()
    for i in range(0, n_req, slots):
        plain.generate(prompts[i:i + slots], new_tokens, seed=0)
    plain_dt = time.perf_counter() - t0
    plain_tps = n_req * new_tokens / plain_dt

    out = {"model": model, "slots": slots, "requests": n_req,
           "prompt_len": prompt_len, "new_tokens": new_tokens,
           "plain_sequential_tokens_per_sec": round(plain_tps, 2)}

    with ContinuousBatchingEngine(
            cfg, params, max_seq=max_seq, max_batch=slots,
            sampling=sampling,
            # default pool (B x table_width): the dense-era explicit 64
            # blocks sized a PREFIX cache; on the paged-native scheduler
            # the pool IS the decode cache and 64 blocks would make page
            # pressure, not batching, the measured bottleneck
            kv_block_tokens=16) as eng:
        # warmups cover EVERY compile either timed phase can reach:
        # (a) sub-16-token prompt: step + admit + zero_row + bucket 32,
        #     without polluting the block cache (below one block);
        # (b) a 128-token throwaway: bucket 128 (also stores its blocks);
        # (c) (b)'s prefix + fresh tail: the block-HIT path
        #     (_load_prefix + suffix bucket) — phase B's steady state
        warm = rng.integers(0, 1000, size=(128,)).astype(np.int32)
        eng.submit(warm[:8], 4).wait(timeout=600)
        eng.submit(warm, 4).wait(timeout=600)
        eng.submit(np.concatenate([
            warm[:96], rng.integers(0, 1000, size=(32,))]).astype(np.int32),
            4).wait(timeout=600)
        # (d) a phase-A-shaped prompt, so ITS bucket is compiled even when
        #     BENCH_PROMPT lands past 128 (stores one random prompt's
        #     blocks; phase A's random prompts can't hit them — the
        #     common prefix stays below one block)
        eng.submit(rng.integers(0, 1000, size=(prompt_len,)).astype(
            np.int32), 4).wait(timeout=600)
        t0 = time.perf_counter()
        reqs = [eng.submit(p, new_tokens) for p in prompts]
        for r in reqs:
            r.wait(timeout=900)
        dt = time.perf_counter() - t0
        out["batching_tokens_per_sec"] = round(n_req * new_tokens / dt, 2)
        out["vs_plain_sequential"] = round(
            (n_req * new_tokens / dt) / plain_tps, 3)

        # Phase B: shared 96-token prefix (6 whole 16-token blocks),
        # distinct 32-token tails (the bucket layout keeps prompt_len
        # at 128)
        base = dict(eng.kv_cache.stats)
        shared = rng.integers(0, 1000, size=(96,))
        pre_prompts = [np.concatenate([
            shared, rng.integers(0, 1000, size=(32,))]).astype(np.int32)
            for _ in range(slots)]
        t0 = time.perf_counter()
        reqs = [eng.submit(p, new_tokens) for p in pre_prompts]
        for r in reqs:
            r.wait(timeout=900)
        dt = time.perf_counter() - t0
        out["prefix_phase_tokens_per_sec"] = round(
            slots * new_tokens / dt, 2)
        out["kvcache_stats"] = {
            k: eng.kv_cache.stats[k] - base.get(k, 0)
            for k in eng.kv_cache.stats}

    # Phase B2: the fused decode-block throughput mode (one host sync
    # per 8 steps) on the phase-A workload — on a high-dispatch-latency
    # device this is where batching stops being dispatch-bound
    try:
        with ContinuousBatchingEngine(
                cfg, params, max_seq=max_seq, max_batch=slots,
                sampling=sampling, kv_cache_blocks=0,
                decode_block=8) as eng:
            eng.submit(prompts[0][:8], 4).wait(timeout=600)   # warm 32
            eng.submit(prompts[0], 4).wait(timeout=600)       # warm 128
            t0 = time.perf_counter()
            reqs = [eng.submit(p, new_tokens) for p in prompts]
            for r in reqs:
                r.wait(timeout=900)
            dt = time.perf_counter() - t0
            out["decode_block8_tokens_per_sec"] = round(
                n_req * new_tokens / dt, 2)
    except Exception as e:   # phase isolation
        out["decode_block8_error"] = f"{type(e).__name__}: {e}"

    # Phase C: the composed serving shape — speculative decoding inside
    # the slot loop (int8 self-draft, as in the speculative leg), same
    # phase-A workload, greedy (the composition's parity mode)
    try:
        draft_cfg = get_model_config(model + "-int8")
        draft_params = init_full_params(jax.random.PRNGKey(0), draft_cfg,
                                        quantize=True)
        with ContinuousBatchingEngine(
                cfg, params, max_seq=max_seq, max_batch=slots,
                sampling=SamplingParams(greedy=True), kv_cache_blocks=0,
                draft_cfg=draft_cfg, draft_params=draft_params,
                num_draft=4) as eng:
            eng.submit(prompts[0][:8], 4).wait(timeout=600)   # warm 32
            eng.submit(prompts[0], 4).wait(timeout=600)       # warm 128
            eng.reset_stats()     # warmup rounds out of the measurement
            t0 = time.perf_counter()
            reqs = [eng.submit(p, new_tokens) for p in prompts]
            for r in reqs:
                r.wait(timeout=900)
            dt = time.perf_counter() - t0
            st = eng.stats()["speculative"]
            out["spec_batching"] = {
                "draft": model + "-int8 (same seed weights)",
                "sampling": "greedy",
                "tokens_per_sec": round(n_req * new_tokens / dt, 2),
                "num_draft": st["num_draft"], "rounds": st["rounds"],
                "acceptance_rate": st["acceptance_rate"],
            }
    except Exception as e:   # phase isolation: A/B numbers survive
        out["spec_batching"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def _leg_mixed_batching(model: str, prompt_len: int = 256,
                        new_tokens: int = 48, slots: int = 8,
                        n_req: int = 24, prefill_chunk: int = 32,
                        decode_block: int = 4,
                        token_budget: int = 0,
                        arrival_s: float = 0.02,
                        block_tokens: int = 16) -> dict:
    """Mixed token-budget dispatch vs the alternating baseline
    (docs/DESIGN.md §19) under a fixed arrival load.

    Both modes serve the SAME schedule: ``slots - 1`` long-decode
    background rows pin the batch, then ``n_req`` chunk-heavy prompts
    arrive at a fixed interval.  The baseline is the serialized
    interleave this repo shipped pre-§19 (chunk dispatches alternating
    with decode steps, fused-loop suppression while an admission is in
    flight); mixed packs the chunks INTO the fused decode dispatches
    under the token budget.  Reported per mode: aggregate tok/s over
    the measured window (arrival-stream tokens PLUS the background
    rows' tokens produced inside it — the baseline's suppression
    stalls the background decode during every admission, and that
    stalled decode is exactly the cost §19 removes), TTFT p95 (engine
    reservoir, background rows excluded by the post-warmup reset),
    and dispatches/step — the 1/K-vs-1 structural signature the §19
    acceptance pins."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    sampling = SamplingParams(greedy=True)
    budget = token_budget or slots * decode_block + 2 * prefill_chunk
    bg_rows = max(1, slots - 1)
    # background rows must outlive the arrival stream; they are
    # cancelled once the measured requests finish
    bg_new = max(64, n_req * new_tokens)
    max_seq = max(prompt_len + new_tokens, 8 + bg_new)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 1000, size=(n_req, prompt_len)).astype(
        np.int32)
    warm = rng.integers(0, 1000, size=(2, prompt_len)).astype(np.int32)

    def run(mixed: bool) -> dict:
        kw = {"mixed_token_budget": budget} if mixed else {}
        with ContinuousBatchingEngine(
                cfg, params, max_seq=max_seq, max_batch=slots,
                sampling=sampling, prefill_chunk=prefill_chunk,
                decode_block=decode_block, kv_block_tokens=block_tokens,
                **kw) as eng:
            # compile pass 1: a full-shape admission on an idle engine
            eng.submit(warm[0], 2).wait(timeout=600)
            bg = [eng.submit(np.asarray([7, i + 1, 3], np.int32), bg_new)
                  for i in range(bg_rows)]
            deadline = time.monotonic() + 600
            for r in bg:               # every background row decoding
                while not r.tokens:
                    if time.monotonic() > deadline:
                        raise TimeoutError("background rows never "
                                           "admitted")
                    time.sleep(0.002)
            # compile pass 2: an admission UNDER decode load — the
            # baseline's suppressed per-token step and the mixed
            # engine's no-finals slab variant both compile here, not
            # inside the measured window
            eng.submit(warm[1], 2).wait(timeout=600)
            eng.reset_stats()
            bg_before = sum(len(r.tokens) for r in bg)
            t0 = time.perf_counter()
            reqs = []
            for p in prompts:
                reqs.append(eng.submit(p, new_tokens))
                if arrival_s:
                    time.sleep(arrival_s)
            for r in reqs:
                r.wait(timeout=900)
            dt = time.perf_counter() - t0
            bg_tokens = sum(len(r.tokens) for r in bg) - bg_before
            st = eng.stats()
            ls = dict(eng.loop_stats)
            for r in bg:
                r.cancel()
            for r in bg:
                try:
                    r.wait(timeout=600)
                except Exception:
                    pass
            out = {
                "tokens_per_sec": round(
                    (n_req * new_tokens + bg_tokens) / dt, 2),
                "stream_tokens_per_sec": round(
                    n_req * new_tokens / dt, 2),
                "background_tokens": bg_tokens,
                "ttft_p95_ms": st["latency"].get("ttft_p95_ms"),
                "host_dispatches": ls["host_dispatches"],
                "device_loop_steps": ls["device_loop_steps"],
                "dispatches_per_step": round(
                    ls["host_dispatches"]
                    / max(1, ls["device_loop_steps"]), 4),
            }
            if mixed:
                out["mixed_dispatches"] = st["mixed"]["dispatches"]
                out["prefill_tokens"] = st["mixed"]["prefill_tokens"]
                out["budget_utilization"] = st["mixed"][
                    "budget_utilization"]
            mgr = eng.kv_cache
            out["leaked_blocks"] = (mgr.used_blocks
                                    - mgr.tree.block_count)
            return out

    baseline = run(mixed=False)
    mixed = run(mixed=True)
    return {
        "model": model, "slots": slots, "requests": n_req,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "prefill_chunk": prefill_chunk, "decode_block": decode_block,
        "token_budget": budget, "arrival_s": arrival_s,
        "background_rows": bg_rows,
        "baseline": baseline, "mixed": mixed,
        "mixed_wins_tokens_per_sec": (
            mixed["tokens_per_sec"] > baseline["tokens_per_sec"]),
        "mixed_ttft_p95_le_baseline": (
            mixed["ttft_p95_ms"] is not None
            and baseline["ttft_p95_ms"] is not None
            and mixed["ttft_p95_ms"] <= baseline["ttft_p95_ms"]),
    }


def _leg_spec_mixed(model: str, prompt_len: int = 192,
                    new_tokens: int = 32, slots: int = 8,
                    n_req: int = 16, prefill_chunk: int = 32,
                    decode_block: int = 4, num_draft: int = 4,
                    token_budget: int = 0,
                    arrival_s: float = 0.02,
                    block_tokens: int = 16,
                    bg_prompt_len: int = 32) -> dict:
    """Speculation INSIDE the mixed dispatch (docs/DESIGN.md §22) vs the
    two single-feature configurations it fuses.

    One schedule, three engines: ``slots - 1`` long-decode background
    rows pin the batch while ``n_req`` chunk-heavy motif-tiled prompts
    arrive at a fixed interval.  All prompts are tiled 16-token motifs —
    the n-gram shape prompt-lookup speculation exists for — so the
    proposer has real lookup structure; measured acceptance on
    seed-init weights stays a weights property (adversarial for
    agreement), so the leg's value is the MECHANICS: what fusing
    draft/verify into the packed dispatch does to aggregate tok/s,
    TTFT p95, and dispatches/step on the same arrival load.

    - ``spec_only``: prompt-lookup speculation with serialized chunked
      prefill (the pre-§22 shipping configuration — every arriving
      chunk is its own dispatch between speculative rounds).
    - ``mixed_only``: §19 token-budget packing, no speculation.
    - ``spec_mixed``: ONE program carries prefill segments + decode +
      draft/verify, adaptive per-row K (§22).

    Gates: ``spec_mixed_wins_tokens_per_sec`` (beats BOTH baselines)
    and ``ttft_p95_le_mixed_only`` (fusing speculation must not buy
    throughput with arrival latency).  The spec arms also report the
    §22 shrink observables (``k_row_buckets``, acceptance)."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    sampling = SamplingParams(greedy=True)
    # the default §19 budget (slots * decode_block + 2 chunks) prices a
    # DECODE row at decode_block tokens; §22 prices a spec row at
    # (K_row + 1) * decode_block, so a budget sized for plain decode
    # leaves no prefill room once the batch speculates — size it for
    # the spec pricing and give every arm the same knob
    budget = token_budget or (slots * (num_draft + 1) * decode_block
                              + 2 * prefill_chunk)
    bg_rows = max(1, slots - 1)
    # background rows must OUTLIVE the arrival stream in every mode: a
    # speculating row emits up to K+1 tokens per round, and a row that
    # finishes mid-window both zeroes its arm's background tokens and
    # dumps its pre-window TTFT (submit-to-first-token spans the warmup
    # compiles) into the measured latency reservoir.  Budget from the
    # worst case: the window is bounded by the per-request dispatch
    # count (decode blocks + prefill chunks + admission slack) and a
    # spec row emits at most (K+1) * decode_block tokens per dispatch.
    per_req_dispatches = ((new_tokens + decode_block - 1) // decode_block
                          + (prompt_len + prefill_chunk - 1)
                          // prefill_chunk + 8)
    bg_new = ((num_draft + 1) * decode_block
              * n_req * per_req_dispatches)
    max_seq = max(prompt_len + new_tokens, bg_prompt_len + bg_new)
    rng = np.random.default_rng(0)

    def motif_prompt(length):
        # per-request DISTINCT motif (identical prompts would let the
        # block cache collapse the prefill work the leg measures)
        motif = rng.integers(0, 1000, size=(16,))
        return np.tile(motif, max(1, length // 16))[:length].astype(
            np.int32)

    prompts = [motif_prompt(prompt_len) for _ in range(n_req)]
    # background prompts are RANDOM (no n-gram structure): their
    # near-zero lookup acceptance is the §22 shrink workload — the
    # adaptive controller walks their K_row toward bucket 1, which is
    # exactly the ``k_row_buckets`` observable the spec arms report
    bg_prompts = [rng.integers(0, 1000, size=(bg_prompt_len,)).astype(
        np.int32) for _ in range(bg_rows)]
    warm = [motif_prompt(prompt_len) for _ in range(2)]

    def run(mode: str) -> dict:
        kw = {}
        if mode != "spec_only":
            kw["mixed_token_budget"] = budget
        if mode != "mixed_only":
            kw.update(prompt_lookup=True, num_draft=num_draft)
        with ContinuousBatchingEngine(
                cfg, params, max_seq=max_seq, max_batch=slots,
                sampling=sampling, prefill_chunk=prefill_chunk,
                decode_block=decode_block, kv_block_tokens=block_tokens,
                **kw) as eng:
            # compile pass 1: a full-shape admission on an idle engine
            eng.submit(warm[0], 2).wait(timeout=600)
            bg = [eng.submit(p, bg_new) for p in bg_prompts]
            deadline = time.monotonic() + 600
            for r in bg:               # every background row decoding
                while not r.tokens:
                    if time.monotonic() > deadline:
                        raise TimeoutError("background rows never "
                                           "admitted")
                    time.sleep(0.002)
            # compile pass 2: an admission UNDER decode/spec load — the
            # packed-with-rounds and no-finals program variants both
            # compile here, not inside the measured window
            eng.submit(warm[1], 2).wait(timeout=600)
            eng.reset_stats()
            bg_before = sum(len(r.tokens) for r in bg)
            t0 = time.perf_counter()
            reqs = []
            for p in prompts:
                reqs.append(eng.submit(p, new_tokens))
                if arrival_s:
                    time.sleep(arrival_s)
            for r in reqs:
                r.wait(timeout=900)
            dt = time.perf_counter() - t0
            bg_tokens = sum(len(r.tokens) for r in bg) - bg_before
            st = eng.stats()
            ls = dict(eng.loop_stats)
            for r in bg:
                r.cancel()
            for r in bg:
                try:
                    r.wait(timeout=600)
                except Exception:
                    pass
            out = {
                "tokens_per_sec": round(
                    (n_req * new_tokens + bg_tokens) / dt, 2),
                "stream_tokens_per_sec": round(
                    n_req * new_tokens / dt, 2),
                "background_tokens": bg_tokens,
                "ttft_p95_ms": st["latency"].get("ttft_p95_ms"),
                "host_dispatches": ls["host_dispatches"],
                "device_loop_steps": ls["device_loop_steps"],
                "dispatches_per_step": round(
                    ls["host_dispatches"]
                    / max(1, ls["device_loop_steps"]), 4),
            }
            if mode != "spec_only":
                out["mixed_dispatches"] = st["mixed"]["dispatches"]
                out["prefill_tokens"] = st["mixed"]["prefill_tokens"]
                out["budget_utilization"] = st["mixed"][
                    "budget_utilization"]
            if mode != "mixed_only":
                sp = st["speculative"]
                # the §22 shrink observables: per-bucket occupancy of
                # the active rows' K_row + measured acceptance
                out["spec"] = {
                    "drafted": sp["drafted"],
                    "accepted": sp["accepted"],
                    "acceptance_rate": sp["acceptance_rate"],
                    "adaptive": sp["adaptive"],
                    "k_row_buckets": sp["k_row_buckets"],
                }
            mgr = eng.kv_cache
            out["leaked_blocks"] = (mgr.used_blocks
                                    - mgr.tree.block_count)
            return out

    spec_only = run("spec_only")
    mixed_only = run("mixed_only")
    spec_mixed = run("spec_mixed")
    return {
        "model": model, "slots": slots, "requests": n_req,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "prefill_chunk": prefill_chunk, "decode_block": decode_block,
        "num_draft": num_draft, "token_budget": budget,
        "arrival_s": arrival_s, "background_rows": bg_rows,
        "prompt_shape": "16-token motif tiled, distinct per request",
        "spec_only": spec_only, "mixed_only": mixed_only,
        "spec_mixed": spec_mixed,
        # the §22 acceptance gates: the fused program must beat BOTH
        # single-feature configurations on aggregate throughput without
        # regressing arrival TTFT vs the mixed-only packer
        "spec_mixed_wins_tokens_per_sec": (
            spec_mixed["tokens_per_sec"] > spec_only["tokens_per_sec"]
            and spec_mixed["tokens_per_sec"]
            > mixed_only["tokens_per_sec"]),
        "ttft_p95_le_mixed_only": (
            spec_mixed["ttft_p95_ms"] is not None
            and mixed_only["ttft_p95_ms"] is not None
            and spec_mixed["ttft_p95_ms"] <= mixed_only["ttft_p95_ms"]),
    }


def _leg_prefix_reuse(model: str, new_tokens: int, slots: int = 8,
                      n_req: int = 16, shared_len: int = 96,
                      tail_len: int = 32, block_tokens: int = 16,
                      kv_blocks: int = 0) -> dict:
    """Block-level KV cache (runtime/kvcache) on a repeated-shared-prefix
    workload: hit rate, reused tokens, and prefill seconds SAVED — the
    prefill-amortization number shared-prefix serving (chat system
    prompts, few-shot templates) turns on.

    The same workload runs twice through the batching engine — cache OFF
    then cache ON — after identical warmup and a priming request, so
    ``prefill_seconds_saved`` is a measured wall delta on identical
    decode work, not an estimate from token counts."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    sampling = SamplingParams(temperature=0.7, top_k=7)
    max_seq = shared_len + tail_len + new_tokens
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 1000, size=(shared_len,))

    def prompt():
        return np.concatenate(
            [shared, rng.integers(0, 1000, size=(tail_len,))]
        ).astype(np.int32)

    prime = prompt()
    prompts = [prompt() for _ in range(n_req)]
    # the no-reuse baseline: same shape, all-distinct prefixes — random
    # prompts share no whole block, so every admission prefills in full.
    # (The paged-native scheduler has no cache-off mode to compare
    # against: the pool IS the decode cache, so "off" is modeled by a
    # workload that cannot hit, not by a disabled subsystem.)
    cold_prompts = [rng.integers(0, 1000,
                                 size=(shared_len + tail_len,)).astype(
                                     np.int32) for _ in range(n_req)]

    def run(wave):
        with ContinuousBatchingEngine(
                cfg, params, max_seq=max_seq, max_batch=slots,
                sampling=sampling, kv_cache_blocks=kv_blocks,
                kv_block_tokens=block_tokens) as eng:
            # identical warmup both runs: the priming request stores the
            # shared blocks and compiles the cold admission path; the
            # second covers the hit path (warm wave) / re-admission
            # (cold wave) so neither timed wave pays a compile the
            # other didn't
            eng.submit(prime, 4).wait(timeout=600)
            eng.submit(prompts[0], 4).wait(timeout=600)
            eng.reset_stats()
            t0 = time.perf_counter()
            reqs = [eng.submit(p, new_tokens) for p in wave]
            for r in reqs:
                r.wait(timeout=900)
            dt = time.perf_counter() - t0
            return dt, eng.kv_cache.snapshot()

    cold_dt, _ = run(cold_prompts)
    warm_dt, snap = run(prompts)
    lookups = snap["hits"] + snap["misses"]
    return {
        "model": model, "slots": slots, "requests": n_req,
        "shared_prefix_tokens": shared_len, "tail_tokens": tail_len,
        "new_tokens": new_tokens, "block_tokens": block_tokens,
        "kv_blocks": kv_blocks,
        "hit_rate": round(snap["hits"] / lookups, 3) if lookups else None,
        "reused_tokens": snap["partial_hit_tokens"],
        "cold_seconds": round(cold_dt, 3),
        "warm_seconds": round(warm_dt, 3),
        "prefill_seconds_saved": round(cold_dt - warm_dt, 3),
        "tokens_per_sec_cold": round(n_req * new_tokens / cold_dt, 2),
        "tokens_per_sec_warm": round(n_req * new_tokens / warm_dt, 2),
        "blocks_resident": snap["blocks_used"],
        "evicted_blocks": snap["evicted_blocks"],
    }


def _leg_tiered_prefix(model: str, new_tokens: int, slots: int = 2,
                       groups: int = 6, revisits: int = 3,
                       shared_len: int = 96, tail_len: int = 16,
                       block_tokens: int = 16, kv_blocks: int = 24,
                       host_groups: int = 3) -> dict:
    """Tiered KV (docs/DESIGN.md §21) vs re-prefill on a
    working-set-over-HBM workload: ``groups`` distinct shared prefixes
    whose trees cannot all stay resident in a ``kv_blocks``-block device
    pool, revisited after eviction.

    Phase A (tiering OFF) pays a full re-prefill on every revisit of an
    evicted prefix.  Phase B (tiering ON, host ring sized to hold
    ``host_groups`` of the ``groups`` prefixes so the REST spill to the
    disk segment) promotes the demoted pages back through the staged
    adopt seam instead.  Same prompts, same greedy sampling, same pool:
    the gates are

    - ``tiered_wins_ttft_p95``: revisit TTFT p95 with tiering beats
      re-prefill;
    - ``promote_h2d_bytes`` > 0: the promotion path actually moved
      bytes (phase A's h2d stays 0 — nothing else may touch the host
      bounce);
    - ``bit_identical``: greedy revisit tokens match across phases —
      a promoted prefix is the SAME cache state, not an approximation;
    - ``three_tier_zero_leak``: at leg end the device pool's used
      blocks equal tree-owned blocks and the host/disk ledgers pass
      :meth:`TieredKVStore.check` (host XOR disk, exact byte sums,
      consistent disk free list).
    """
    import tempfile

    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.stats import _percentile

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    greedy = SamplingParams(greedy=True)
    new_tokens = min(new_tokens, 16)
    max_seq = shared_len + tail_len + new_tokens + block_tokens
    rng = np.random.default_rng(7)
    shared = [rng.integers(2, cfg.vocab_size - 1, size=(shared_len,))
              .astype(np.int32) for _ in range(groups)]
    # revisit tails fixed up front so BOTH phases replay the identical
    # prompt sequence (the bit-identity gate compares token-for-token)
    tails = [[rng.integers(2, cfg.vocab_size - 1, size=(tail_len,))
              .astype(np.int32) for _ in range(revisits)]
             for _ in range(groups)]
    # the warm prompt has the SAME shape as a group prompt so the
    # promote-path warmup below compiles the same adopt-scatter block
    # count the measured revisits dispatch
    warm = rng.integers(2, cfg.vocab_size - 1,
                        size=(shared_len + tail_len,)).astype(np.int32)
    blocks_per_group = -(-(shared_len + tail_len + new_tokens)
                         // block_tokens)

    def run(tier_kwargs):
        with ContinuousBatchingEngine(
                cfg, params, max_seq=max_seq, max_batch=slots,
                sampling=greedy, kv_cache_blocks=kv_blocks,
                kv_block_tokens=block_tokens, **tier_kwargs) as eng:
            # compile the admission/prefill/decode programs before
            # timing; the warm blocks sit in-tree identically in both
            # phases (oldest, so they evict first either way)
            eng.submit(warm, new_tokens).wait(timeout=600)
            eng.kv_cache.reset_stats()
            # round 1: touch every group once; the small pool evicts
            # older groups as later ones admit (demoting in phase B)
            for g in range(groups):
                eng.submit(np.concatenate([shared[g], tails[g][0]]),
                           new_tokens).wait(timeout=900)
            # promote-path warmup, symmetric across phases: the warm
            # prefix was evicted by round 1, so resubmitting it here
            # compiles the adopt-scatter programs (phase B) / replays a
            # re-prefill (phase A) OUTSIDE the measured wave — same
            # discipline as warming prefill before timing it
            eng.submit(warm, new_tokens).wait(timeout=900)
            # round 2: revisit every group — evicted prefixes re-prefill
            # (phase A) or promote from the tier (phase B).  Revisit
            # round 0 is the steady-state round: it flushes out the
            # remaining demote/promote compile variants (the export and
            # adopt scatters bucket to powers of two, but a leaf size
            # class first seen mid-wave would still stall one TTFT on a
            # compile); rounds >= 1 are the measured ones.  Tokens from
            # EVERY round feed the bit-identity gate.
            ttfts, toks = [], []
            for rv in range(revisits):
                for g in range(groups):
                    r = eng.submit(
                        np.concatenate([shared[g], tails[g][rv]]),
                        new_tokens)
                    r.wait(timeout=900)
                    if rv >= 1:
                        ttfts.append(r.t_first - r.t_submit)
                    toks.append(list(r.tokens))
            snap = eng.kv_cache.snapshot()
            leaked = snap["blocks_used"] - snap["tree_blocks"]
            tier_ok = True
            if eng.kv_cache.tier is not None:
                try:
                    eng.kv_cache.tier.check()
                except AssertionError:
                    tier_ok = False
            return {"ttfts": ttfts, "tokens": toks, "snap": snap,
                    "leaked_blocks": leaked, "tier_ledger_ok": tier_ok}

    cold = run({})
    # size the host ring off the REAL pool geometry (quantized pools
    # carry scale sidecars; 1.25x covers them at int4's worst ratio)
    per_block = cold["snap"]["capacity_bytes"] // max(kv_blocks, 1)
    host_bytes = int(per_block * blocks_per_group * host_groups * 1.25)
    disk_bytes = int(per_block * blocks_per_group * groups * 1.5)
    with tempfile.TemporaryDirectory(prefix="dwt-tier-") as td:
        tiered = run({"kv_host_tier_bytes": host_bytes,
                      "kv_disk_tier_path": os.path.join(td, "kv.seg"),
                      "kv_disk_tier_bytes": disk_bytes})

    def pcts(xs):
        xs = sorted(xs)
        return {"requests": len(xs),
                "ttft_p50_ms": round(_percentile(xs, 50) * 1e3, 2),
                "ttft_p95_ms": round(_percentile(xs, 95) * 1e3, 2)}

    a, b = pcts(cold["ttfts"]), pcts(tiered["ttfts"])
    frag = tiered["snap"].get("tier") or {}
    hits = frag.get("host_hits", 0) + frag.get("disk_hits", 0)
    out = {
        "model": model, "slots": slots, "groups": groups,
        "revisits": revisits, "shared_prefix_tokens": shared_len,
        "tail_tokens": tail_len, "new_tokens": new_tokens,
        "block_tokens": block_tokens, "kv_blocks": kv_blocks,
        "host_tier_bytes": host_bytes, "disk_tier_bytes": disk_bytes,
        "reprefill": a, "tiered": b,
        "tiered_wins_ttft_p95": b["ttft_p95_ms"] < a["ttft_p95_ms"],
        "ttft_p95_speedup": round(a["ttft_p95_ms"] / b["ttft_p95_ms"], 3)
        if b["ttft_p95_ms"] else None,
        "promote_h2d_bytes": tiered["snap"]["h2d_bytes"],
        "reprefill_h2d_bytes": cold["snap"]["h2d_bytes"],
        "demoted_blocks": frag.get("demoted_blocks", 0),
        "promoted_blocks": frag.get("promoted_blocks", 0),
        "spilled_blocks": frag.get("spilled_blocks", 0),
        "dropped_blocks": frag.get("dropped_blocks", 0),
        "tier_hits": {"host": frag.get("host_hits", 0),
                      "disk": frag.get("disk_hits", 0)},
        # which tier the promoted blocks came from (host ring vs the
        # disk segment below it)
        "tier_hit_share": ({
            "host": round(frag.get("host_hits", 0) / hits, 3),
            "disk": round(frag.get("disk_hits", 0) / hits, 3)}
            if hits else None),
        "bit_identical": cold["tokens"] == tiered["tokens"],
        "three_tier_zero_leak": (cold["leaked_blocks"] == 0
                                 and tiered["leaked_blocks"] == 0
                                 and tiered["tier_ledger_ok"]),
        "leaked_blocks": {"reprefill": cold["leaked_blocks"],
                          "tiered": tiered["leaked_blocks"]},
    }
    return out


def _leg_paged_decode(model: str, new_tokens: int, slots: int = 8,
                      prompt_len: int = 64, max_seq: int = 1024,
                      block_tokens: int = 16, n_req: int = 0,
                      shared_len: int = 48,
                      kv_dtypes=("int8", "int4")) -> dict:
    """Paged KV on the (paged-native) batching engine vs dense-layout
    reservation (docs/DESIGN.md §11/§14): decode tok/s parity AND the
    HBM story the paged layout exists for — at a serving-realistic
    ``max_seq`` a dense cache reserves ``B x max_seq`` rows up front
    while the paged engine allocates blocks per request actually in
    flight.

    Phases, one workload shape (distinct prompts, then a shared-prefix
    wave on the paged engine):

    - dense reference: the plain InferenceEngine at batch = slots —
      its working cache is dense ``B x max_seq`` rows (the dense pool
      layout is deleted; the working cache shape is the reference),
      so its cache bytes are measured off the real buffers, not
      estimated;
    - paged: tok/s + pool capacity + PEAK blocks/bytes in use (polled
      while the wave decodes) + the analytic max-concurrent-sequences
      at the dense reference's HBM budget;
    - admissible: at the dense reservation byte budget, the max
      admissible batch at 4k/8k/32k sequence budgets — dense reserves
      the full row per request, paged reserves the blocks the workload
      shape actually touches (strictly larger batches, the §14
      acceptance gate);
    - paged primed: radix hits on the paged path — ``h2d_bytes`` must
      stay 0 (hits are block-table references, nothing crosses the
      host boundary);
    - kv-dtype axis (docs/DESIGN.md §17): the same wave on int8/int4
      page pools — tok/s plus the per-dtype admissible table, whose
      narrower ``block_bytes`` (scale sidecar included) must admit a
      STRICTLY larger batch than bf16 at the same fixed byte budget."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.base import (
        pad_cache_capacity)
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import InferenceEngine
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    sampling = SamplingParams(temperature=0.7, top_k=7)
    n_req = n_req or slots * 2
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 1000, size=(prompt_len,)).astype(np.int32)
               for _ in range(n_req)]
    shared = rng.integers(0, 1000, size=(shared_len,))

    def shared_prompt():
        tail = rng.integers(0, 1000, size=(prompt_len - shared_len,))
        return np.concatenate([shared, tail]).astype(np.int32)

    def run_wave(eng, wave):
        """Submit a wave, poll block occupancy while it decodes (the
        peak is the honest 'blocks actually allocated' number — after
        the wave only tree-cached blocks remain)."""
        eng.reset_stats()
        peak_blocks = 0
        t0 = time.perf_counter()
        reqs = [eng.submit(p, new_tokens) for p in wave]
        while not all(r.done.is_set() for r in reqs):
            if eng.kv_cache is not None:
                peak_blocks = max(peak_blocks,
                                  eng.kv_cache.snapshot()["blocks_used"])
            time.sleep(0.02)
        for r in reqs:
            r.wait(timeout=900)
        dt = time.perf_counter() - t0
        return dt, peak_blocks

    out = {"model": model, "slots": slots, "requests": n_req,
           "prompt_len": prompt_len, "new_tokens": new_tokens,
           "max_seq": max_seq, "block_tokens": block_tokens}

    # phase 1: the dense-reservation reference — the plain engine's
    # working cache is dense B x max_seq rows regardless of pool
    # layout (the dense pool layout itself is deleted), so its real
    # buffers at batch = slots ARE the dense reservation, measured not
    # estimated
    dense_eng = InferenceEngine(cfg, params, max_seq=max_seq,
                                sampling=sampling)
    batch_prompts = np.stack(prompts[:slots])
    dense_eng.generate(batch_prompts, new_tokens, seed=0)     # compile
    dense_cache = dense_eng.new_cache(slots)
    dense_bytes = int(dense_cache.keys.nbytes + dense_cache.values.nbytes)
    del dense_cache
    t0 = time.perf_counter()
    for i in range(0, n_req, slots):
        dense_eng.generate(np.stack(prompts[i:i + slots]), new_tokens,
                           seed=0)
    dense_dt = time.perf_counter() - t0
    del dense_eng
    out["dense"] = {
        "engine": "InferenceEngine dense-row working cache (reference)",
        "tokens_per_sec": round(n_req * new_tokens / dense_dt, 2),
        "cache_reserved_bytes": dense_bytes,
        "reserved_tokens": slots * max_seq,
    }

    # phase 2 + 3: paged (pool sized to the dense-equivalent budget)
    with ContinuousBatchingEngine(
            cfg, params, max_seq=max_seq, max_batch=slots,
            sampling=sampling, kv_layout="paged",
            kv_block_tokens=block_tokens) as eng:
        eng.submit(prompts[0], 4).wait(timeout=600)      # compile warmup
        eng.submit(prompts[1], 4).wait(timeout=600)
        dt, peak_blocks = run_wave(eng, prompts)
        mgr = eng.kv_cache
        blocks_per_req = -(-(prompt_len + new_tokens) // block_tokens)
        out["paged"] = {
            "tokens_per_sec": round(n_req * new_tokens / dt, 2),
            "pool_capacity_bytes": int(eng._pk.nbytes + eng._pv.nbytes),
            "pool_blocks": mgr.num_blocks,
            "block_bytes": int(mgr.block_bytes),
            "peak_blocks_in_use": int(peak_blocks),
            "peak_bytes_in_use": int(peak_blocks * mgr.block_bytes),
            "blocks_per_request": blocks_per_req,
            # at the dense run's HBM budget, how many sequences of THIS
            # shape fit: dense pins max_batch rows; paged packs blocks
            "max_seqs_at_dense_budget": int(
                dense_bytes // (blocks_per_req * mgr.block_bytes)),
            "dense_max_seqs": slots,
        }
        out["paged_vs_dense_decode"] = round(
            out["paged"]["tokens_per_sec"]
            / out["dense"]["tokens_per_sec"], 3)
        out["cache_bytes_ratio"] = round(
            out["paged"]["peak_bytes_in_use"] / dense_bytes, 3)

        # the §14 acceptance table: at the dense reservation's byte
        # budget, the max admissible batch per sequence budget — dense
        # pins a padded max_seq row per request; paged pins only the
        # blocks this workload shape (prompt + new) actually touches.
        # Parameterized on block_bytes so the §17 kv-dtype phase below
        # reuses the same arithmetic with its narrower pages.
        itemsize = np.dtype(cfg.dtype).itemsize
        kv_row_unit = 2 * cfg.num_layers * cfg.num_kv_heads \
            * cfg.head_dim * itemsize
        used_tokens = prompt_len + new_tokens

        def admissible_table(blk_bytes):
            tbl = {}
            for seq in (4096, 8192, 32768):
                dense_row = kv_row_unit * pad_cache_capacity(seq)
                paged_req = -(-used_tokens // block_tokens) * blk_bytes
                tbl[str(seq)] = {
                    "budget_bytes": dense_bytes,
                    "dense_max_batch": int(dense_bytes // dense_row),
                    "paged_max_batch": int(dense_bytes // paged_req),
                    "workload_tokens_per_request": used_tokens,
                }
            return tbl

        out["admissible"] = admissible_table(mgr.block_bytes)

        # phase 3: primed — shared-prefix wave; hits must move 0 bytes
        # through the host (the acceptance gate for the paged path)
        eng.submit(shared_prompt(), 4).wait(timeout=600)   # prime+compile
        dt, _ = run_wave(eng, [shared_prompt() for _ in range(n_req)])
        snap = mgr.snapshot()
        lookups = snap["hits"] + snap["misses"]
        out["paged_primed"] = {
            "tokens_per_sec": round(n_req * new_tokens / dt, 2),
            "hit_rate": (round(snap["hits"] / lookups, 3)
                         if lookups else None),
            "reused_tokens": snap["partial_hit_tokens"],
            "h2d_bytes": snap["h2d_bytes"],
        }

    # phase 4: the §17 kv-dtype axis — the same cold wave on quantized
    # page pools.  Each dtype's admissible table reuses the bf16 dense
    # budget, so paged_max_batch growing strictly with narrowing width
    # IS the byte-budget claim measured, not asserted.  (Each engine is
    # opened after the bf16 one closed: pools never coexist, so the leg
    # fits the same HBM the bf16 phase needed.)
    out["kv_dtype"] = {}
    for d in kv_dtypes:
        with ContinuousBatchingEngine(
                cfg, params, max_seq=max_seq, max_batch=slots,
                sampling=sampling, kv_layout="paged",
                kv_block_tokens=block_tokens, kv_dtype=d) as qeng:
            qeng.submit(prompts[0], 4).wait(timeout=600)  # compile warmup
            qeng.submit(prompts[1], 4).wait(timeout=600)
            dt, peak_q = run_wave(qeng, prompts)
            qmgr = qeng.kv_cache
            out["kv_dtype"][d] = {
                "tokens_per_sec": round(n_req * new_tokens / dt, 2),
                "vs_bf16_paged": round(
                    (n_req * new_tokens / dt)
                    / out["paged"]["tokens_per_sec"], 3),
                "block_bytes": int(qmgr.block_bytes),
                "scale_block_bytes": int(qmgr.scale_block_bytes),
                "pool_capacity_bytes": int(qeng._pk.nbytes
                                           + qeng._pv.nbytes),
                "peak_blocks_in_use": int(peak_q),
                "peak_bytes_in_use": int(peak_q * qmgr.block_bytes),
                "admissible": admissible_table(qmgr.block_bytes),
            }
    return out


def _leg_serving_relative(model: str, batch: int, prompt_len: int,
                          new_tokens: int, slots: int = 4,
                          n_req: int = 8) -> dict:
    """CPU-relative serving evidence: the
    serving-stack RATIOS that survive a hardware change — speculative
    speedup vs plain, prompt-lookup acceptance rate, batching aggregate
    throughput-per-slot vs the plain engine — measured wherever the leg
    runs and stamped with the platform.  Absolute tok/s here are NOT
    comparable to the TPU legs and the stamp says so
    (``relative_only``); what transfers is the mechanics: acceptance is
    an argmax-agreement property, per-slot scaling a scheduler
    property."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime import (InferenceEngine,
                                                        SpeculativeEngine)
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.prompt_lookup import (
        PromptLookupEngine)
    from distributed_inference_demo_tpu.runtime.speculative import stats_json

    cfg = get_model_config(model)
    draft_cfg = get_model_config(model + "-int8")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    draft_params = init_full_params(jax.random.PRNGKey(0), draft_cfg,
                                    quantize=True)
    greedy = SamplingParams(greedy=True)
    max_seq = max(prompt_len, 64) + new_tokens
    prompt = (np.arange(batch * prompt_len).reshape(batch, prompt_len)
              % 1000).astype(np.int32)
    out = {"platform": jax.default_backend(), "relative_only": True,
           "model": model, "batch": batch, "prompt_len": prompt_len,
           "new_tokens": new_tokens}

    plain = InferenceEngine(cfg, params, max_seq=max_seq, sampling=greedy)
    plain.generate(prompt, new_tokens, seed=0)             # compile
    base = plain.generate(prompt, new_tokens, seed=0)
    out["plain_tokens_per_sec"] = round(base.tokens_per_second, 2)

    num_draft = 4
    spec = SpeculativeEngine(cfg, params, draft_cfg, draft_params,
                             max_seq=max_seq, sampling=greedy,
                             num_draft=num_draft)
    spec.generate(prompt, new_tokens, seed=0)              # compile
    sres, sstats = spec.generate(prompt, new_tokens, seed=0)
    out["speculative"] = dict(
        stats_json(sstats, num_draft),
        tokens_per_sec=round(sres.tokens_per_second, 2),
        speedup_vs_plain=round(sres.tokens_per_second
                               / base.tokens_per_second, 3))

    # prompt lookup on its natural shape: a repeated motif (acceptance
    # is what transfers; seed weights are adversarial for it)
    motif = (np.arange(16) * 37 % 1000).astype(np.int32)
    pl_len = max(32, min(prompt_len, max_seq - new_tokens) // 16 * 16)
    pl_prompt = np.tile(motif, pl_len // 16)[None, :]
    pld = PromptLookupEngine(cfg, params, max_seq=max_seq,
                             sampling=greedy, num_draft=num_draft)
    pld.generate(pl_prompt, new_tokens, seed=0)            # compile
    pres, pstats = pld.generate(pl_prompt, new_tokens, seed=0)
    out["prompt_lookup"] = dict(
        stats_json(pstats, num_draft),
        tokens_per_sec=round(pres.tokens_per_second, 2))

    # batching: aggregate throughput per slot vs one plain stream
    rng = np.random.default_rng(0)
    reqs_p = rng.integers(0, 1000, size=(n_req, prompt_len)).astype(
        np.int32)
    plain.generate(prompt[:1], new_tokens, seed=0)   # compile [1, plen]
    single = plain.generate(prompt[:1], new_tokens, seed=0)
    with ContinuousBatchingEngine(cfg, params, max_seq=max_seq,
                                  max_batch=slots,
                                  sampling=greedy) as eng:
        eng.submit(reqs_p[0], 2).wait(timeout=600)         # compile
        t0 = time.perf_counter()
        rs = [eng.submit(p, new_tokens) for p in reqs_p]
        for r in rs:
            r.wait(timeout=900)
        agg_tps = n_req * new_tokens / (time.perf_counter() - t0)
    out["batching"] = {
        "slots": slots, "requests": n_req,
        "aggregate_tokens_per_sec": round(agg_tps, 2),
        "throughput_per_slot": round(agg_tps / slots, 2),
        "per_slot_vs_plain_single": round(
            (agg_tps / slots) / single.tokens_per_second, 3),
    }
    return out


def _long_context_sp_points(model: str, new: int = 8) -> list:
    """>= 32k-context points for BOTH sp strategies (ring / Ulysses) at
    micro budget — the carried sweep satellite: the sequence-parallel
    long-context shape banks at least a micro number per strategy in
    the first healthy window.  Needs >= 2 local devices; stamps a skip
    otherwise.  Per-strategy isolation: one failing build (e.g. a head
    count Ulysses can't divide) must not lose the other point."""
    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.parallel.mesh import local_sp_mesh

    ctx = int(os.environ.get("BENCH_LONG_CTX_SP", "32768"))
    if len(jax.devices()) < 2:
        return [{"skipped": "sequence parallelism needs >= 2 devices",
                 "context": ctx}]
    sp = 2
    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    plen = (ctx - new) // sp * sp
    prompt = (np.arange(plen) % 1000).astype(np.int32)[None, :]
    points = []
    for strategy in ("ring", "ulysses"):
        point = {"strategy": strategy, "sp": sp, "context": ctx,
                 "prompt_len": plen, "new_tokens": new}
        try:
            if strategy == "ring":
                from distributed_inference_demo_tpu.parallel.sequence import (
                    make_sp_generate_fn)
                gen = make_sp_generate_fn(
                    cfg, local_sp_mesh(sp), max_seq=ctx,
                    num_new_tokens=new,
                    sampling=SamplingParams(greedy=True))
            else:
                from distributed_inference_demo_tpu.parallel.ulysses import (
                    make_ulysses_generate_fn)
                gen = make_ulysses_generate_fn(
                    cfg, local_sp_mesh(sp), max_seq=ctx,
                    num_new_tokens=new,
                    sampling=SamplingParams(greedy=True))
            mesh = local_sp_mesh(sp)
            with mesh:
                toks = np.asarray(gen(params, prompt,
                                      jax.random.PRNGKey(0)))  # compile
            t0 = time.perf_counter()
            with mesh:
                toks = np.asarray(gen(params, prompt,
                                      jax.random.PRNGKey(0)))
            dt = time.perf_counter() - t0
            point["tokens_per_sec"] = round(toks.size / dt, 2)
        except Exception as e:
            point["error"] = f"{type(e).__name__}: {e}"[:300]
        points.append(point)
    return points


def _leg_planner_pipeline(model: str, batch: int, prompt_len: int,
                          new_tokens: int) -> dict:
    """BASELINE config #2 measured through the COMPOSED product: the
    ``server`` app (collect window → monitor round → cost-model plan →
    artifact weight distribution) plus a bare ``worker --auto`` — not a
    hand-wired harness.  The server/header runs on this host's default
    backend (the TPU when present); the worker is a CPU process that
    knows only the registry address.  Reports the planner's layer ranges
    next to the measured throughput."""
    import json as _json
    import urllib.request

    env_worker = dict(os.environ, JAX_PLATFORMS="cpu",
                      XLA_FLAGS="--xla_force_host_platform_device_count=1")
    max_seq = prompt_len + new_tokens
    server = subprocess.Popen(
        [sys.executable, "-m", "distributed_inference_demo_tpu", "server",
         "--model", model, "--num-workers", "1",
         "--max-seq", str(max_seq), "--max-new-tokens", str(new_tokens),
         "--temperature", "0.7", "--top-k", "7",
         "--collect-timeout", "600", "--monitor-timeout", "600",
         "--step-timeout", "600"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=str(REPO))
    worker = None
    reader = _LineReader(server)
    try:
        registry = reader.read_until("SERVER_REGISTRY").split()[1]
        worker = subprocess.Popen(
            [sys.executable, "-m", "distributed_inference_demo_tpu",
             "worker", "--auto", "--registry", registry,
             "--device-id", "w1", "--step-timeout", "600"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=env_worker, text=True, cwd=str(REPO))
        plan_line = reader.read_until("SERVER_PLAN", timeout=600)
        ranges = _json.loads(plan_line.split(" ", 1)[1])
        http = reader.read_until("HTTP_READY", timeout=600).split()[1]

        import numpy as np
        prompt = (np.arange(batch * prompt_len).reshape(batch, prompt_len)
                  % 1000).astype(int).tolist()

        def post(path, body, timeout=900):
            req = urllib.request.Request(
                http + path, data=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return _json.loads(r.read())

        post("/generate", {"prompt_ids": prompt, "max_new_tokens": 2})
        post("/stats/reset", {})
        t0 = time.perf_counter()
        post("/generate", {"prompt_ids": prompt,
                           "max_new_tokens": new_tokens})
        dt = time.perf_counter() - t0
        with urllib.request.urlopen(http + "/stats", timeout=120) as r:
            stages = _json.loads(r.read())["stages"]
    finally:
        server.kill()
        if worker is not None:
            worker.kill()

    h = next((s for s in stages if s.get("role") == "header"), {})
    tail = next((s for s in stages if s.get("role") == "tail"), {})
    out = {
        "model": model, "batch": batch,
        # the leg process must NOT touch the TPU (the server subprocess
        # owns it — the device is exclusive), so no _device_kind() here
        "device": "server subprocess (default backend) + 1 CPU worker",
        "planner_layer_ranges": ranges,
        "pipeline_tokens_per_sec": round(batch * new_tokens / dt, 2),
        "ring_rtt_p50_ms": h.get("ring_rtt_p50_ms"),
        "tail_compute_p50_ms": tail.get("compute_p50_ms"),
    }
    _paired_hop_percentiles(h, tail, out)
    return out


# ---------------------------------------------------------------------------
# Leg dispatch (subprocess entry) + orchestrator
def _leg_int4(model: str, flagship: str, batch: int, prompt_len: int,
              new_tokens: int) -> dict:
    """Weight-only int4 decode (ops/quant.QuantizedArray4): nibble-packed
    weights at 2/byte + group-wise f32 scales = ~0.56 bytes/weight.
    Decode streams every weight byte once per step, so at the
    bandwidth-bound batch sizes int4 is the throughput configuration
    ABOVE int8 — the ratio vs the headline_int8/flagship_int8 legs (same
    shapes) is the packing payoff net of the in-feed unpack cost.
    Reference analog: the -int8 export variants (data/Data.kt:19-33);
    the reference has no int4 story."""
    out = {"headline_int4": _bench_engine(model, batch, prompt_len,
                                          new_tokens, quant="int4")}
    out["flagship_int4"] = _leg_flagship(flagship, batch, prompt_len,
                                         min(new_tokens, 64), quant="int4")
    return out


def _leg_moe(batch: int, prompt_len: int, new_tokens: int,
             moe_model: str = "mixtral-tpu-1b",
             dense_model: str = "mixtral-tpu-1b-dense") -> dict:
    """MoE decode on one chip (BASELINE config #4 at a chip-fitting
    scale, ~0.8 B params bf16).

    mixtral-tpu-1b (8 experts, top-2) against its dense FLOPs-matched
    twin (dense intermediate = 2x expert intermediate, i.e. the SAME
    active compute per token): the tok/s ratio isolates routing +
    dispatch cost.  The single-chip MoE layer computes all experts
    batched on the MXU and combines by gate weight
    (models/decoder.py:201-230), so the MoE side also streams ~4x the
    active expert weights per step — achieved_gbs shows how much of
    that the chip absorbs.  int8 is the throughput configuration."""
    moe = _bench_engine(moe_model, batch, prompt_len, new_tokens)
    moe_int8 = _bench_engine(moe_model, batch, prompt_len,
                             new_tokens, quant=True)
    dense = _bench_engine(dense_model, batch, prompt_len, new_tokens)
    out = {"moe_bf16": moe, "moe_int8": moe_int8,
           "dense_equal_active_flops_bf16": dense}
    if moe.get("decode_tokens_per_sec") and dense.get(
            "decode_tokens_per_sec"):
        out["moe_vs_dense_decode"] = round(
            moe["decode_tokens_per_sec"] / dense["decode_tokens_per_sec"],
            3)
    return out


def _leg_multimodal(batch: int, new_tokens: int,
                    scale: str = "llava15",
                    decoder_model: str = "tinyllama-1.1b") -> dict:
    """LLaVA-stage throughput (BASELINE config #5).

    Two measures: (a) the vision encoder alone at llava-1.5 scale
    (336px / patch 14 / hidden 1024 / 24 layers, bf16) in images/s —
    the edge-client stage's capacity; (b) e2e image+text generation on
    MultimodalEngine with a tinyllama-class decoder — vision prefix +
    combined prefill + fused decode, in decode tok/s.  The reference
    has no vision path (its closest concept is per-device module
    placement, server.py:831-832); SURVEY lists multimodal as a
    framework goal."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    from distributed_inference_demo_tpu.models.vision import (
        VisionConfig, init_vision_params, vision_forward)
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.multimodal import (
        MultimodalEngine)

    # (a) llava-1.5-scale tower alone ("tiny" keeps the same code path
    # runnable on CPU for the leg's smoke test)
    if scale == "llava15":
        vcfg = VisionConfig(image_size=336, patch_size=14,
                            hidden_size=1024, num_layers=24, num_heads=16,
                            intermediate_size=4096,
                            dtype_name="bfloat16")
    else:
        vcfg = VisionConfig(image_size=32, patch_size=16, hidden_size=32,
                            num_layers=2, num_heads=2,
                            intermediate_size=64, dtype_name="float32")
    dcfg = get_model_config(decoder_model)
    rng = jax.random.PRNGKey(0)
    vparams = init_vision_params(rng, vcfg,
                                 decoder_hidden=dcfg.hidden_size)
    fwd = jax.jit(lambda p, img: vision_forward(p, vcfg, img))
    images = jnp.ones((batch, vcfg.image_size, vcfg.image_size, 3),
                      vcfg.dtype)
    jax.block_until_ready(fwd(vparams, images))       # compile
    t0 = time.perf_counter()
    rounds = 4
    for _ in range(rounds):
        out_h = fwd(vparams, images)
    jax.block_until_ready(out_h)
    enc_s = (time.perf_counter() - t0) / rounds
    encoder = {
        "images_per_sec": round(batch / enc_s, 2),
        "batch": batch, "image_size": vcfg.image_size,
        "patches_per_image": vcfg.num_patches,
        "vit_layers": vcfg.num_layers, "dtype": vcfg.dtype_name,
        "projector_out_dim": dcfg.hidden_size,
    }

    # (b) e2e: small tower + a real decoder
    dparams = init_full_params(jax.random.PRNGKey(1), dcfg)
    if scale == "llava15":
        small_v = VisionConfig(image_size=224, patch_size=14,
                               hidden_size=256, num_layers=6, num_heads=8,
                               intermediate_size=1024,
                               dtype_name="bfloat16")
    else:
        small_v = vcfg
    svp = init_vision_params(jax.random.PRNGKey(2), small_v,
                             decoder_hidden=dcfg.hidden_size)
    b2 = min(batch, 4)
    n_img = small_v.num_patches
    text_len = min(32, dcfg.max_seq_len // 4)
    eng = MultimodalEngine(dcfg, dparams, small_v, svp,
                           max_seq=n_img + text_len + new_tokens,
                           sampling=SamplingParams(temperature=0.7,
                                                   top_k=7))
    side = small_v.image_size
    imgs = np.ones((b2, side, side, 3), np.float32)
    text = (np.arange(b2 * text_len).reshape(b2, text_len)
            % dcfg.vocab_size).astype(np.int32)
    eng.generate(imgs, text, new_tokens, seed=0)      # compile
    res = eng.generate(imgs, text, new_tokens, seed=0)
    e2e = {
        "decode_tokens_per_sec": round(res.tokens_per_second, 2),
        "batch": b2, "image_tokens": n_img, "text_tokens": text_len,
        "new_tokens": new_tokens, "decoder": decoder_model,
    }
    return {"vision_encoder_llava15_scale": encoder,
            "e2e_image_text_generate": e2e}


def _leg_fault_recovery(model: str, new_tokens: int = 24,
                        prompt_len: int = 8, max_seq: int = 64,
                        crash_after_msgs: int = 6,
                        num_stages: int = 3) -> dict:
    """Elastic recovery under an injected worker crash (comm/faults):
    a 3-stage loopback pipeline loses its middle stage mid-generation
    via a seeded ``crash_after`` fault plan; the leg measures the
    recovery path end to end — reshard latency, time from crash to the
    first post-recovery token, and the token streams' bit-identity with
    a fault-free run (the §12 chaos invariant, timed).

    Loopback on purpose: the number under test is the FRAMEWORK's
    detect→reshard→drain/resume cost, not socket noise; it is the same
    path a socket deployment runs (tests/test_chaos.py drives it under
    messier plans)."""
    import threading

    import jax
    import numpy as np
    from distributed_inference_demo_tpu.comm.faults import (
        FaultPlan, FaultRule, FaultyTransport, InjectedCrash)
    from distributed_inference_demo_tpu.comm.transport import (
        LoopbackNetwork, LoopbackTransport)
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.base import split_layer_ranges
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.elastic import (
        ElasticHeader, ElasticStageRuntime, ElasticWorker)

    cfg = get_model_config(model)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, num_stages)
    greedy = SamplingParams(greedy=True)
    prompt = (np.arange(prompt_len)[None, :] % 97).astype(np.int32)
    ids = [f"s{i}" for i in range(num_stages)]

    def build(plan):
        net = LoopbackNetwork()
        transports = [LoopbackTransport(d, net) for d in ids]
        if plan is not None:
            # the crash plan wraps the MIDDLE stage's transport: the
            # n_msgs-th message through it raises InjectedCrash and the
            # serve thread dies like a real worker crash
            transports[1] = FaultyTransport(transports[1], plan)
        header = ElasticHeader(
            ElasticStageRuntime(cfg, specs[0], full, max_seq, greedy),
            transports[0], chain=list(ids), step_timeout=60,
            poll_interval=0.05)
        workers = [
            ElasticWorker(
                ElasticStageRuntime(cfg, specs[i], full, max_seq, greedy),
                transports[i],
                next_id=ids[i + 1] if i + 1 < num_stages else None,
                header_id=ids[0], step_timeout=60)
            for i in range(1, num_stages)]
        threads = []
        for w in workers:
            def serve(w=w):
                try:
                    w.serve_forever(30)
                except InjectedCrash:
                    pass          # the injected death IS the scenario
            t = threading.Thread(target=serve, daemon=True)
            t.start()
            threads.append(t)
        return header, workers, threads

    # -- fault-free reference run (also the compile warmup) ----------------
    header, _, threads = build(None)
    header.generate(prompt, 4)               # compile
    t0 = time.perf_counter()
    want = header.generate(prompt, new_tokens)
    clean_dt = time.perf_counter() - t0
    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)

    # -- chaos run: s1 crashes after crash_after_msgs messages -------------
    plan = FaultPlan(seed=1234, rules=[
        FaultRule(kind="crash_after", n_msgs=crash_after_msgs)])
    header, workers, threads = build(plan)
    token_times = []
    t_crash = [None]
    t_signal = [None]
    reshard_s = [None]
    orig_reshard = header.reshard

    def timed_reshard(chain, in_flight=None, dead=()):
        r0 = time.perf_counter()
        orig_reshard(chain, in_flight, dead=dead)
        reshard_s[0] = time.perf_counter() - r0
    header.reshard = timed_reshard

    def supervise():
        # stands in for the heartbeat sweeper: the dead serve thread IS
        # the missed heartbeat (test_elastic wires the real sweeper)
        threads[0].join()
        t_crash[0] = time.perf_counter()
        header.signal_failure(ids[1])
        t_signal[0] = time.perf_counter()
    sup = threading.Thread(target=supervise, daemon=True)
    sup.start()

    t0 = time.perf_counter()
    got = header.generate_many(
        [prompt], new_tokens,
        on_token=lambda i, step, toks: token_times.append(
            (step, time.perf_counter())))[0]
    chaos_dt = time.perf_counter() - t0
    header.shutdown_pipeline()
    for t in threads[1:]:
        t.join(timeout=30)
    sup.join(timeout=30)

    identical = bool(np.array_equal(got, want))
    post = [ts for _, ts in token_times
            if t_crash[0] is not None and ts > t_crash[0]]
    recovery_s = (post[0] - t_crash[0]
                  if post and t_crash[0] is not None else None)
    tokens_after = len(post)
    return {
        "model": model, "num_stages": num_stages,
        "new_tokens": new_tokens, "crash_after_msgs": crash_after_msgs,
        "plan_seed": plan.seed,
        "injected_events": [e["kind"] for e in plan.events],
        "tokens_bit_identical_after_recovery": identical,
        "clean_seconds": round(clean_dt, 3),
        "chaos_seconds": round(chaos_dt, 3),
        "reshard_seconds": (round(reshard_s[0], 4)
                            if reshard_s[0] is not None else None),
        "crash_to_first_token_seconds": (round(recovery_s, 4)
                                         if recovery_s is not None
                                         else None),
        "tokens_to_recovery": (new_tokens - tokens_after
                               if t_crash[0] is not None else None),
        "recovery_overhead_seconds": round(chaos_dt - clean_dt, 3),
        "surviving_chain": list(header.chain),
    }


def _leg_disagg(model: str, slots: int = 8, bg: int = 7,
                n_req: int = 5, prompt_len: int = 256,
                prefill_chunk: int = 16, new_tokens: int = 4,
                bg_new: int = 4096, max_seq: int = 4096,
                block_tokens: int = 16,
                n_prefill_workers: int = 2) -> dict:
    """Disaggregated prefill/decode vs the colocated engine, measured
    where the split matters: **TTFT under concurrent decode load**
    (docs/DESIGN.md §15).

    Both phases run the same decode substrate — ``slots`` continuous-
    batching slots with ``bg`` of them pinned by long-running decode
    requests — and then admit ``n_req`` long-prompt requests:

    - *colocated*: the requests chunk-prefill on the SAME engine; every
      chunk interleaves one decode step of the busy batch (the §5
      chunked-admission contract), so TTFT pays the batch's decode for
      every chunk, serially per request.
    - *disaggregated*: the requests hand off to dedicated prefill
      workers (loopback transport), which chunk-prefill concurrently
      and stream KV pages to the decode worker as each chunk lands;
      the decode engine only runs the adopt + one suffix prefill.

    Loopback on purpose (same rationale as fault_recovery): the number
    under test is the scheduling structure, not socket noise.  The leg
    also reports the §15 acceptance gates: decode-side
    ``dwt_kvcache_h2d_bytes_total`` staying 0 for migrated pages, the
    page-leak invariant on both pools, and migrated/adopted page
    parity."""
    import threading

    import jax
    import numpy as np
    from distributed_inference_demo_tpu.comm.transport import (
        LoopbackNetwork, LoopbackTransport)
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.disagg import (
        DecodeWorker, DisaggCoordinator, PrefillWorker)
    from distributed_inference_demo_tpu.runtime.stats import _percentile

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    greedy = SamplingParams(greedy=True)
    rng = np.random.default_rng(0)
    bg_prompt = (np.arange(24) % 89 + 2).astype(np.int32)
    bg_new = min(bg_new, max_seq - len(bg_prompt))
    # distinct long prompts: no radix hit may shortcut the prefill;
    # one extra prompt warms the compile caches WITHOUT seeding the
    # radix tree with a measured prompt's blocks
    prompts = [rng.integers(2, cfg.vocab_size - 1, prompt_len)
               .astype(np.int32) for _ in range(n_req + 1)]
    warm_prompt, prompts = prompts[0], prompts[1:]

    def pcts(ttfts):
        xs = sorted(ttfts)
        return {"requests": len(xs),
                "ttft_p50_ms": round(_percentile(xs, 50) * 1e3, 2),
                "ttft_p95_ms": round(_percentile(xs, 95) * 1e3, 2)}

    def engine_kwargs(chunk):
        return dict(max_seq=max_seq, max_batch=slots, sampling=greedy,
                    kv_cache_blocks=0, kv_block_tokens=block_tokens,
                    prefill_chunk=chunk)

    # -- colocated: prefill chunks interleave with the busy batch ----------
    eng = ContinuousBatchingEngine(cfg, params, **engine_kwargs(
        prefill_chunk))
    bg_reqs = [eng.submit(bg_prompt, bg_new) for _ in range(bg)]
    # warm the admission/prefill programs before timing (compile noise
    # would otherwise dominate the first request's TTFT)
    eng.submit(warm_prompt, 2).wait(timeout=600)
    reqs = [eng.submit(p, new_tokens) for p in prompts]
    for r in reqs:
        r.wait(timeout=600)
    colocated = pcts([r.t_first - r.t_submit for r in reqs])
    for r in bg_reqs:
        r.cancel()
    steps_colocated = eng.stats()["steps"]
    eng.close()

    # -- disaggregated: same decode load, prefill on its own workers -------
    net = LoopbackNetwork()
    tc = LoopbackTransport("coord", net)
    pids = [f"p{i}" for i in range(n_prefill_workers)]
    tps = [LoopbackTransport(pid, net) for pid in pids]
    td = LoopbackTransport("d0", net)
    # the decode engine needs no prefill_chunk: its longest admission
    # is a migrated request's <= one-block suffix
    deng = ContinuousBatchingEngine(cfg, params, **engine_kwargs(None))
    pws = [PrefillWorker(cfg, params, t, max_seq=max_seq,
                         prefill_chunk=prefill_chunk,
                         kv_block_tokens=block_tokens)
           for t in tps]
    dw = DecodeWorker(deng, td)
    threads = [threading.Thread(target=w.serve_forever, daemon=True)
               for w in pws + [dw]]
    for t in threads:
        t.start()
    coord = DisaggCoordinator(tc, pids, "d0")
    bg_reqs = [deng.submit(bg_prompt, bg_new) for _ in range(bg)]
    # warm EVERY prefill worker (each has its own jit caches) with the
    # off-tree warm prompt before timing — round robin lands one each
    for wr in [coord.submit(warm_prompt, 2)
               for _ in range(n_prefill_workers)]:
        wr.wait(timeout=600)
    dreqs = [coord.submit(p, new_tokens) for p in prompts]
    for r in dreqs:
        r.wait(timeout=600)
    disagg = pcts([r.ttft_s for r in dreqs])
    for r in bg_reqs:
        r.cancel()
    for r in bg_reqs:
        try:
            r.wait(timeout=600)
        except Exception:
            pass
    time.sleep(0.2)            # let completions release their pages
    dsnap = deng.kv_cache.snapshot()
    psnaps = [pw.kv_cache.snapshot() for pw in pws]
    migrated = sum(pw.stats["migrated_pages"] for pw in pws)
    migration_ms = [pw.stats["last_migration_ms"] for pw in pws]
    disagg.update({
        "migrated_pages": migrated,
        "migrated_bytes": sum(pw.stats["migrated_bytes"]
                              for pw in pws),
        "adopted_pages": dw.stats["adopted_pages"],
        "retransmitted_frames": sum(pw.stats["retransmitted_frames"]
                                    for pw in pws),
        "last_migration_ms": max((m for m in migration_ms
                                  if m is not None), default=None),
        # the §15 zero-host-bounce gate: migrated pages join as
        # block-table references, never a dense-row H2D seed
        "decode_h2d_bytes": dsnap["h2d_bytes"],
        # leak invariants, both pools: idle used == tree-owned
        "decode_pool_leaked_blocks": (dsnap["blocks_used"]
                                      - dsnap["tree_blocks"]),
        "prefill_pool_leaked_blocks": sum(
            s["blocks_used"] - s["tree_blocks"] for s in psnaps),
    })
    for w in pws + [dw]:
        w.stop()
    coord.close()
    deng.close()

    return {
        "model": model, "slots": slots, "background_decodes": bg,
        "prompt_len": prompt_len, "prefill_chunk": prefill_chunk,
        "prefill_workers": n_prefill_workers,
        "colocated": dict(colocated, steps=steps_colocated),
        "disagg": disagg,
        "disagg_wins_ttft_p95": (disagg["ttft_p95_ms"]
                                 < colocated["ttft_p95_ms"]),
        "ttft_p95_speedup": round(
            colocated["ttft_p95_ms"] / disagg["ttft_p95_ms"], 3)
        if disagg["ttft_p95_ms"] else None,
    }


def _leg_gateway_routing(model: str, n_replicas: int = 3, groups: int = 6,
                         per_group: int = 6, prefix_len: int = 96,
                         suffix_len: int = 8, new_tokens: int = 16,
                         slots: int = 4, max_seq: int = 512,
                         block_tokens: int = 16,
                         kill_requests: int = 12) -> dict:
    """Cache-aware gateway routing vs round-robin over N loopback
    replicas, measured where the router matters (docs/DESIGN.md §16):
    **prefix reuse and TTFT under a grouped shared-prefix workload**.

    Three phases over the SAME replica fleet (real HTTP all the way —
    client → gateway → replica — so both policies pay the same proxy
    hop):

    - *round_robin*: the gateway's router is overridden to cycle
      through replicas, the classic L4 answer.  Group members scatter,
      so most requests re-prefill a prefix some OTHER replica already
      holds.
    - *cache_aware*: the real PrefixAwareRouter.  The first member of
      a group lands by rendezvous hash; every later member follows the
      routing-history index to the replica that already holds the
      prefix, paying only the suffix prefill.
    - *kill*: re-issue cache-aware-phase prompts while one replica
      drains away mid-soak.  Gates: every request completes
      bit-identically to its phase-2 answer or sheds as 503 — never a
      hang, never divergent tokens — and the eviction debounce moves
      ``dwt_gateway_replica_down_total``.

    Two more phases exercise LIVE MIGRATION (docs/DESIGN.md §18) over
    the two surviving replicas:

    - *live_rebalance*: a 2*slots burst lands entirely on one replica
      (maximal skew); the same burst re-runs with a rebalancer moving
      rows hot → light mid-decode, so the queued tail admits a wave
      early.  Gates: TTFT p95 strictly beats the no-migration run
      (completion p95 is reported as context — both replicas share
      one host's compute in this harness) and every stream is
      bit-identical.
    - *drain*: :class:`MigrationController` over the LIVE registry
      marks the hot replica draining and drives it empty.  Gate: every
      in-flight request completes off the drained replica,
      bit-identically.

    Phases use DISJOINT prompt groups (fresh prefixes per phase) so
    phase order cannot lend one policy the other's warm cache."""
    import threading
    from concurrent.futures import ThreadPoolExecutor
    from http.client import HTTPConnection

    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.gateway import (
        GatewayHTTPServer, PrefixAwareRouter, ReplicaRegistry, RouteDecision)
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)
    from distributed_inference_demo_tpu.runtime.overload import (
        GatewayOverloaded)
    from distributed_inference_demo_tpu.runtime.stats import _percentile

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    greedy = SamplingParams(greedy=True)
    rng = np.random.default_rng(11)
    min_prefix = min(block_tokens, prefix_len)

    def make_workload():
        """``groups`` shared prefixes x ``per_group`` unique suffixes,
        interleaved across groups (g0r0, g1r0, ..., g0r1, ...) — the
        order that maximally punishes a router that forgets where a
        group's prefix lives."""
        per = []
        for _ in range(groups):
            prefix = rng.integers(2, cfg.vocab_size - 1, prefix_len)
            per.append([np.concatenate([
                prefix, rng.integers(2, cfg.vocab_size - 1, suffix_len)])
                .astype(np.int32) for _ in range(per_group)])
        return [per[g][i] for i in range(per_group)
                for g in range(groups)]

    def send(host, port, prompt, timeout=600):
        """One streaming /generate; returns status, client-side TTFT,
        and the decoded row (None on non-200 / severed stream)."""
        conn = HTTPConnection(host, port, timeout=timeout)
        try:
            t0 = time.perf_counter()
            conn.request("POST", "/generate", body=json.dumps(
                {"prompt_ids": [prompt.tolist()],
                 "max_new_tokens": new_tokens, "stream": True}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                resp.read()
                return resp.status, None, None
            toks, ttft, severed = [], None, False
            while True:
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line:
                    continue
                if ttft is None:
                    ttft = time.perf_counter() - t0
                d = json.loads(line)
                if "error" in d:
                    severed = True
                    break
                tl = d.get("tokens")   # flat: one entry per batch row
                if tl:
                    toks.append(tl[0])
            return resp.status, ttft, None if severed else toks
        except Exception:
            return -1, None, None
        finally:
            conn.close()

    def kv_totals():
        out = {"partial_hit_tokens": 0, "hits": 0, "misses": 0}
        for eng in engines:
            kv = eng.stats()["kvcache"]
            for k in out:
                out[k] += kv[k]
        return out

    def phase_metrics(before, after, ttfts, prompt_tokens):
        d = {k: after[k] - before[k] for k in before}
        lookups = d["hits"] + d["misses"]
        xs = sorted(t for t in ttfts if t is not None)
        return {
            "requests": len(ttfts),
            "ttft_p50_ms": round(_percentile(xs, 50) * 1e3, 2),
            "ttft_p95_ms": round(_percentile(xs, 95) * 1e3, 2),
            # fraction of submitted prompt tokens served from a warm
            # radix tree (full hits won't happen — suffixes are unique
            # — so reused tokens ARE the prefix-routing signal)
            "prefix_hit_rate": round(
                d["partial_hit_tokens"] / prompt_tokens, 4)
            if prompt_tokens else 0.0,
            "reused_prefix_tokens": d["partial_hit_tokens"],
            "radix_lookups": lookups,
        }

    def scrape_counter(gw, name):
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        try:
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
        finally:
            conn.close()
        for ln in text.splitlines():
            if ln.startswith(name + " ") or ln.startswith(name + "{"):
                return float(ln.rsplit(" ", 1)[1])
        return 0.0

    engines = [ContinuousBatchingEngine(
        cfg, params, max_seq=max_seq, max_batch=slots, sampling=greedy,
        kv_cache_blocks=0, kv_block_tokens=block_tokens)
        for _ in range(n_replicas)]
    servers = []
    for eng in engines:
        srv = InferenceHTTPServer(eng, port=0, model_name=model)
        srv.start()
        servers.append(srv)

    # warm every replica's compile caches on BOTH admission shapes the
    # measured phases hit — the full-prompt bucket and the suffix-only
    # bucket behind a prefix hit — with an off-workload prefix
    warm_prefix = rng.integers(2, cfg.vocab_size - 1, prefix_len)
    for srv in servers:
        for _ in range(2):     # second send takes the prefix-hit path
            suffix = rng.integers(2, cfg.vocab_size - 1, suffix_len)
            warm = np.concatenate([warm_prefix, suffix]).astype(np.int32)
            st, _, _ = send(srv.host, srv.port, warm)
            if st != 200:
                raise RuntimeError(f"warmup failed on {srv.host}:"
                                   f"{srv.port} (status {st})")

    registry = ReplicaRegistry(
        [(s.host, s.port) for s in servers], sustain=2,
        readmit_cooldown_s=60.0, probe_interval_s=0.3)

    class _RoundRobinRouter(PrefixAwareRouter):
        """The baseline: same gateway, same proxy, zero cache sense."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self._rr = 0

        def route(self, tokens):
            ups = sorted(self.registry.up_replicas())
            if not ups:
                raise GatewayOverloaded("no replica up", retry_after_s=2.0)
            rid = ups[self._rr % len(ups)]
            self._rr += 1
            return RouteDecision(rid, "hash", 0,
                                 [r for r in ups if r != rid])

    n_tok = groups * per_group * (prefix_len + suffix_len)
    results = {}

    # -- phase 1: round-robin baseline -------------------------------------
    gw = GatewayHTTPServer(registry, _RoundRobinRouter(
        registry, min_prefix_tokens=min_prefix,
        block_tokens=block_tokens), port=0)
    gw.start()
    before = kv_totals()
    ttfts = [send(gw.host, gw.port, p)[1] for p in make_workload()]
    results["round_robin"] = phase_metrics(before, kv_totals(), ttfts,
                                           n_tok)
    gw.shutdown()

    # -- phase 2: cache-aware (fresh prefixes) -----------------------------
    router = PrefixAwareRouter(registry, min_prefix_tokens=min_prefix,
                               block_tokens=block_tokens)
    gw = GatewayHTTPServer(registry, router, port=0, retry_limit=2)
    gw.start()
    aware_prompts = make_workload()
    before = kv_totals()
    aware = [send(gw.host, gw.port, p) for p in aware_prompts]
    results["cache_aware"] = phase_metrics(
        before, kv_totals(), [t for _, t, _ in aware], n_tok)

    # -- phase 3: kill one replica mid-soak (same gateway) -----------------
    down_before = scrape_counter(gw, "dwt_gateway_replica_down_total")
    expected = {tuple(p.tolist()): toks
                for p, (st, _, toks) in zip(aware_prompts, aware)
                if st == 200 and toks}
    replay = [p for p in aware_prompts
              if tuple(p.tolist()) in expected][:kill_requests]
    victim = servers[0]
    kill_after = max(1, len(replay) // 3)
    done = []

    def one(i, p):
        if i == kill_after:
            victim.shutdown()    # drain: in-flight finish, connects die
        st, _, toks = send(gw.host, gw.port, p)
        done.append((tuple(p.tolist()), st, toks))

    with ThreadPoolExecutor(max_workers=3) as ex:
        list(ex.map(lambda a: one(*a), enumerate(replay)))
    completed = sum(1 for _, st, _ in done if st == 200)
    shed = sum(1 for _, st, _ in done if st in (503, 429))
    hung_or_failed = len(done) - completed - shed
    identical = all(toks == expected[key]
                    for key, st, toks in done if st == 200)
    # the debounce is asynchronous (background probes, sustain strikes):
    # a short replay can outrun it, so wait for the prober to strike the
    # dead victim out before reading the eviction counter — bounded, so
    # a wedged prober fails the gate instead of hanging the leg
    victim_rid = f"{victim.host}:{victim.port}"
    deadline = time.perf_counter() + 15.0
    while registry.is_up(victim_rid) and time.perf_counter() < deadline:
        time.sleep(0.05)
    down_moved = (scrape_counter(gw, "dwt_gateway_replica_down_total")
                  - down_before) >= 1
    results["kill"] = {
        "requests": len(done), "completed": completed, "shed_503": shed,
        "hung_or_failed": hung_or_failed,
        "bit_identical": bool(identical),
        "replica_down_moved": bool(down_moved),
        "survivors": registry.up_replicas(),
    }

    # -- phase 4: live rebalance under skewed load (docs/DESIGN.md §18) ----
    # The two SURVIVOR replicas at the engine seam.  A burst of
    # 2*slots requests all lands on one replica ("hot") while the
    # other idles — the worst skew the router can hand the fleet.  The
    # baseline decodes the burst in serial admission waves; the
    # rebalance run moves rows hot → light MID-DECODE over the §18
    # migration protocol, so the queued tail admits a wave early.
    # Gates: completion-latency p95 strictly improves AND every stream
    # stays bit-identical to the unmigrated run.
    from distributed_inference_demo_tpu.comm.transport import (
        LoopbackNetwork, LoopbackTransport)
    from distributed_inference_demo_tpu.runtime.disagg import MigrationError
    from distributed_inference_demo_tpu.runtime.migration import (
        MigrationController, MigrationWorker)

    hot_srv, light_srv = servers[1], servers[2]
    hot_e, light_e = engines[1], engines[2]
    mnet = LoopbackNetwork()
    hot_w = MigrationWorker(hot_e, LoopbackTransport("hot", mnet),
                            ack_timeout=2.0)
    light_w = MigrationWorker(light_e, LoopbackTransport("light", mnet),
                              ack_timeout=2.0)
    mthreads = [threading.Thread(target=w.serve_forever, daemon=True)
                for w in (hot_w, light_w)]
    for t in mthreads:
        t.start()

    # fresh prompts, with a decode runway long enough that one
    # admission wave costs SEVERAL handoffs (~100ms each on loopback)
    # — below that ratio the protocol cannot pay for itself on any
    # fabric.  2*slots deep: every queued row can admit via a freed
    # slot, so the TTFT tail is handoff-bound, not wave-bound.
    mig_new = min(448, max_seq - 64)
    mig_prompts = [rng.integers(2, cfg.vocab_size - 1, 32)
                   .astype(np.int32) for _ in range(2 * slots)]

    def settle_idle(timeout=10.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if (not hot_e.active_requests()
                    and not light_e.active_requests()):
                return
            time.sleep(0.02)

    # warm the migration path itself: the first export/adopt pays jit
    # on both replicas (~100ms+ on CPU) that the timed runs must not
    def _warm_migration():
        req = hot_e.submit(rng.integers(2, cfg.vocab_size - 1, 32)
                           .astype(np.int32), mig_new)
        deadline = time.perf_counter() + 5.0
        while (not hot_w.pick_migratable(1)
               and time.perf_counter() < deadline):
            time.sleep(0.002)
        for r in hot_w.pick_migratable(1):
            try:
                hot_w.migrate_out(r, "light")
            except (KeyError, MigrationError):
                pass
        req.wait(600)
        settle_idle()

    _warm_migration()

    def run_burst(migrate):
        t0 = time.perf_counter()
        reqs = [hot_e.submit(p, mig_new) for p in mig_prompts]
        stop = threading.Event()
        claim = {"moved": 0, "inflight": 0}
        picked, clock = set(), threading.Lock()

        def rebalancer():
            # move rows while hot still has a QUEUE (the signal that
            # skew is costing whole admission waves) and light has a
            # free slot: each handoff frees a hot slot so a queued row
            # admits handoff-early instead of wave-late.  Skip rows
            # past 2/3 of their budget (the handoff would cost more
            # than the tail it frees); at most ``slots`` total moves.
            # Two movers run this loop so handoffs overlap — the claim
            # set keeps them off the same rid.
            while not stop.is_set():
                if hot_e.stats()["queue_depth"] == 0:
                    return       # burst fully admitted: skew resolved
                with clock:
                    if claim["moved"] + claim["inflight"] >= slots:
                        return
                    rid = None
                    if (len(light_e.active_requests())
                            + claim["inflight"]) < slots:
                        cands = [r for r in hot_w.pick_migratable(
                            slots, min_remaining=max(32, mig_new // 3))
                            if r not in picked]
                        if cands:
                            rid = cands[0]
                            picked.add(rid)
                            claim["inflight"] += 1
                if rid is None:
                    time.sleep(0.005)
                    continue
                ok = False
                try:
                    ok = hot_w.migrate_out(rid, "light")
                except (KeyError, MigrationError):
                    pass         # resolved locally first / target hiccup
                with clock:
                    claim["inflight"] -= 1
                    if ok:
                        claim["moved"] += 1

        movers = []
        if migrate:
            movers = [threading.Thread(target=rebalancer, daemon=True)
                      for _ in range(2)]
            for m in movers:
                m.start()
        ttft_at = [None] * len(reqs)
        done_at, errs = [None] * len(reqs), [None] * len(reqs)

        def waiter(i, r):
            try:
                while not r.tokens and not r.done.is_set():
                    time.sleep(0.002)
                ttft_at[i] = time.perf_counter()
                r.wait(600)
            except Exception as e:
                errs[i] = e
            done_at[i] = time.perf_counter()

        ws = [threading.Thread(target=waiter, args=(i, r), daemon=True)
              for i, r in enumerate(reqs)]
        for w in ws:
            w.start()
        for w in ws:
            w.join(timeout=600)
        stop.set()
        for m in movers:
            m.join(timeout=5)
        settle_idle()
        return ([t - t0 for t in ttft_at if t is not None],
                [d - t0 for d in done_at],
                [[int(t) for t in r.tokens] for r in reqs],
                claim["moved"], [e for e in errs if e is not None])

    base = run_burst(migrate=False)
    mig = run_burst(migrate=True)
    base_ttfts, base_lats, base_streams, _, base_errs = base
    mig_ttfts, mig_lats, mig_streams, n_moved, mig_errs = mig
    results["live_rebalance"] = {
        "requests": len(mig_prompts),
        "moved": n_moved,
        "errors": len(base_errs) + len(mig_errs),
        # the §18 gate is TTFT p95 — the queued tail admitting a wave
        # early is migration's win, and it survives this harness's one
        # confound: both replicas share ONE host's compute here, so
        # total decode throughput (hence completion p95, reported
        # below as context) cannot improve the way it does when the
        # replicas are separate machines
        "ttft_p95_no_migration_ms": round(
            _percentile(sorted(base_ttfts), 95) * 1e3, 2),
        "ttft_p95_migration_ms": round(
            _percentile(sorted(mig_ttfts), 95) * 1e3, 2),
        "completion_p95_no_migration_ms": round(
            _percentile(sorted(base_lats), 95) * 1e3, 2),
        "completion_p95_migration_ms": round(
            _percentile(sorted(mig_lats), 95) * 1e3, 2),
        "bit_identical": mig_streams == base_streams,
    }

    # -- phase 5: graceful drain (docs/DESIGN.md §18) -----------------------
    # The real control path end to end: MigrationController over the
    # live gateway registry marks hot DRAINING (no new routes, no
    # eviction strike) and drives it empty via the same migrate_out
    # mechanism.  Gate: every in-flight request completes off the
    # drained replica, streams still bit-identical.
    hot_rid = f"{hot_srv.host}:{hot_srv.port}"
    light_rid = f"{light_srv.host}:{light_srv.port}"
    workers, peers = {hot_rid: hot_w}, {light_rid: "light"}

    def mover(src, dst, n):
        w, to = workers.get(src), peers.get(dst)
        if w is None or to is None:
            return 0
        m = 0
        for r in w.pick_migratable(n):
            try:
                if w.migrate_out(r, to):
                    m += 1
            except (KeyError, MigrationError):
                pass
        return m

    ctrl = MigrationController(registry, mover, load_gap=2,
                               max_moves_per_round=slots)
    drain_reqs = [hot_e.submit(p, mig_new) for p in mig_prompts[:slots]]
    # let the registry's async load view catch up before draining, or
    # the drain loop can read a stale pre-burst zero and return early
    deadline = time.perf_counter() + 10.0
    while ctrl.load(hot_rid) == 0 and time.perf_counter() < deadline:
        time.sleep(0.05)
    drain_moved = ctrl.drain(hot_rid, deadline_s=60.0)
    drain_completed, drain_streams = 0, []
    for r in drain_reqs:
        try:
            toks = [int(t) for t in r.wait(600)]
            drain_completed += 1
        except Exception:
            toks = None
        drain_streams.append(toks)
    settle_idle()
    results["drain"] = {
        "inflight": len(drain_reqs),
        "moved": drain_moved,
        "completed": drain_completed,
        "bit_identical": drain_streams == base_streams[:slots],
        "hot_idle_after": not hot_e.active_requests(),
        "draining_flag": bool(registry.is_draining(hot_rid)),
    }

    hot_w.stop()
    light_w.stop()
    for t in mthreads:
        t.join(timeout=2)

    gw.shutdown()
    for srv, eng in zip(servers, engines):
        if srv is not victim:
            srv.shutdown()
        eng.close()

    rr, aw, kl = (results["round_robin"], results["cache_aware"],
                  results["kill"])
    lr, dr = results["live_rebalance"], results["drain"]
    return {
        "model": model, "replicas": n_replicas, "groups": groups,
        "per_group": per_group, "prefix_len": prefix_len,
        "suffix_len": suffix_len, "new_tokens": new_tokens, **results,
        # the §16 acceptance gates
        "cache_aware_wins_hit_rate": (aw["prefix_hit_rate"]
                                      > rr["prefix_hit_rate"]),
        "cache_aware_wins_ttft_p95": (aw["ttft_p95_ms"]
                                      < rr["ttft_p95_ms"]),
        "kill_zero_hangs": kl["hung_or_failed"] == 0,
        "kill_bit_identical": kl["bit_identical"],
        "kill_replica_down_moved": kl["replica_down_moved"],
        # the §18 acceptance gates
        "rebalance_p95_wins": (lr["moved"] >= 1
                               and lr["ttft_p95_migration_ms"]
                               < lr["ttft_p95_no_migration_ms"]),
        "rebalance_bit_identical": (lr["bit_identical"]
                                    and lr["errors"] == 0),
        "drain_all_completed": (dr["completed"] == dr["inflight"]
                                and dr["hot_idle_after"]
                                and dr["bit_identical"]),
    }


def _leg_stream_failover(model: str, n_req: int = 8, prompt_len: int = 96,
                         new_tokens: int = 24, slots: int = 4,
                         max_seq: int = 512, block_tokens: int = 8,
                         crash_after: int = 6,
                         seed_victim: int = 3) -> dict:
    """Zero-loss streams (docs/DESIGN.md §23): kill a replica mid-soak
    and measure the resume path end to end — real HTTP client →
    gateway → replica, greedy so bit-identity is checkable.

    Three phases over the SAME two-replica fleet:

    - *reference*: the unfailed run.  Every prompt streams to
      completion with both replicas healthy; the recorded streams are
      the bit-identity oracle for everything after.
    - *failover*: the victim replica is armed to die ``crash_after``
      tokens into every stream it serves (the §23 mid-stream error
      seam), ``seed_victim`` prompts are pinned to it via the routing
      index, and the soak re-runs with ``resume_limit=1``.  Gates:
      100% completion, zero error lines, every stream bit-identical to
      the reference with contiguous steps, resume attempts == resume
      successes, the SLO ledger books each replay as a resume pause
      with the timeline decomposition still summing exactly, and the
      registry strikes the victim out.  Reported: TTF-resumed-token
      p95 (detect → route → re-POST → replay) interpolated from the
      gateway's own ``dwt_gateway_resume_ttf_seconds`` histogram.
    - *documented_loss*: one pinned prompt through a fresh gateway
      with ``resume_limit=0``: the pre-§23 contract — delivered
      prefix + error line, never a hang — stays reachable and
      documented.

    Zero-leak gates close the leg on BOTH paths: the survivor (served
    every resume) and the victim (its crashed streams must return
    their pages, as a restarted process would want them)."""
    import threading
    from concurrent.futures import ThreadPoolExecutor
    from http.client import HTTPConnection

    import jax
    import numpy as np
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.gateway import (
        GatewayHTTPServer, PrefixAwareRouter, ReplicaRegistry)
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)
    from distributed_inference_demo_tpu.runtime.stats import _percentile
    from distributed_inference_demo_tpu.telemetry.slo import (
        SloLedger, set_slo_ledger)

    cfg = get_model_config(model)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    greedy = SamplingParams(greedy=True)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(2, cfg.vocab_size - 1, prompt_len)
               .astype(np.int32) for _ in range(n_req)]

    class _DyingBackend:
        """The victim: while armed, every stream dies ``crash_after``
        tokens in — the engine generator is closed eagerly so the dead
        path's pages come back the way a crashed process's restart
        would reclaim them."""

        def __init__(self, inner):
            self._inner = inner
            self.armed = False

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def generate_stream(self, *a, **kw):
            gen = self._inner.generate_stream(*a, **kw)
            try:
                for i, item in enumerate(gen):
                    if self.armed and i >= crash_after:
                        raise RuntimeError(
                            f"injected replica death after {i} tokens")
                    yield item
            finally:
                gen.close()

    def send(host, port, prompt):
        """One streaming /generate; returns (status, token list or
        None if an error line arrived, delivered-before-error count,
        step list)."""
        conn = HTTPConnection(host, port, timeout=600)
        try:
            conn.request("POST", "/generate", body=json.dumps(
                {"prompt_ids": [prompt.tolist()],
                 "max_new_tokens": new_tokens, "stream": True}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                resp.read()
                return resp.status, None, 0, []
            toks, steps, errored = [], [], False
            while True:
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "error" in d:
                    errored = True
                    break
                tl = d.get("tokens")
                if tl:
                    toks.append(tl[0])
                    steps.append(d.get("step"))
            return (resp.status, None if errored else toks, len(toks),
                    steps)
        except Exception:
            return -1, None, 0, []
        finally:
            conn.close()

    def scrape(gw):
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        try:
            conn.request("GET", "/metrics")
            return conn.getresponse().read().decode()
        finally:
            conn.close()

    def counter_val(text, name):
        for ln in text.splitlines():
            if ln.startswith(name + " ") or ln.startswith(name + "{"):
                return float(ln.rsplit(" ", 1)[1])
        return 0.0

    def hist_p95(text, name):
        """PromQL-style histogram_quantile over the text exposition:
        cumulative le buckets, linear interpolation inside the bucket
        the 95th observation lands in."""
        pts = []
        for ln in text.splitlines():
            if ln.startswith(name + "_bucket{"):
                le = ln.split('le="', 1)[1].split('"', 1)[0]
                pts.append((float("inf") if le == "+Inf" else float(le),
                            float(ln.rsplit(" ", 1)[1])))
        pts.sort()
        total = pts[-1][1] if pts else 0.0
        if total <= 0:
            return None
        rank = 0.95 * total
        lo_b, lo_c = 0.0, 0.0
        for b, c in pts:
            if c >= rank:
                if b == float("inf"):
                    return round(lo_b * 1e3, 2)
                frac = (rank - lo_c) / max(c - lo_c, 1e-12)
                return round((lo_b + (b - lo_b) * frac) * 1e3, 2)
            lo_b, lo_c = b, c
        return None

    def settle_idle(timeout=30.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if not any(e.active_requests() for e in engines):
                return
            time.sleep(0.02)

    def no_leak(eng):
        mgr = eng.kv_cache
        return (mgr.used_blocks == mgr.tree.block_count
                and mgr.debug_state()["leased_nodes"] == 0)

    engines = [ContinuousBatchingEngine(
        cfg, params, max_seq=max_seq, max_batch=slots, sampling=greedy,
        kv_cache_blocks=0, kv_block_tokens=block_tokens)
        for _ in range(2)]
    victim_backend = _DyingBackend(engines[0])
    servers = []
    for backend in (victim_backend, engines[1]):
        srv = InferenceHTTPServer(backend, port=0, model_name=model)
        srv.start()
        servers.append(srv)
    victim_rid = f"{servers[0].host}:{servers[0].port}"

    # warm both replicas' compile caches off-workload, including the
    # resume admission shape (prompt + delivered prefix re-prefill)
    warm = rng.integers(2, cfg.vocab_size - 1, prompt_len) \
        .astype(np.int32)
    for srv in servers:
        st, _, _, _ = send(srv.host, srv.port, warm)
        if st != 200:
            raise RuntimeError(f"warmup failed on {srv.host}:{srv.port} "
                               f"(status {st})")

    def fresh_gateway(resume_limit):
        registry = ReplicaRegistry(
            [(s.host, s.port) for s in servers], sustain=2,
            readmit_cooldown_s=60.0, probe_interval_s=0.3)
        router = PrefixAwareRouter(registry,
                                   min_prefix_tokens=block_tokens,
                                   block_tokens=block_tokens)
        gw = GatewayHTTPServer(registry, router, port=0,
                               resume_limit=resume_limit)
        gw.start()
        return gw, registry, router

    results = {}

    # -- phase 1: reference (unfailed) --------------------------------------
    gw, registry, router = fresh_gateway(resume_limit=1)
    ref = [send(gw.host, gw.port, p) for p in prompts]
    gw.shutdown()
    settle_idle()
    if any(st != 200 or toks is None or len(toks) != new_tokens
           for st, toks, _, _ in ref):
        raise RuntimeError("reference phase did not complete cleanly")
    ref_streams = [toks for _, toks, _, _ in ref]
    results["reference"] = {"requests": n_req, "completed": n_req}

    # -- phase 2: failover soak (resume_limit=1, victim dies) ---------------
    led = SloLedger(ttft_slo_ms=60_000, tpot_slo_ms=60_000)
    set_slo_ledger(led)
    try:
        gw, registry, router = fresh_gateway(resume_limit=1)
        # pin a slice of the soak to the victim so streams are
        # guaranteed to be mid-flight on it when it starts dying
        for p in prompts[:seed_victim]:
            router.record(victim_rid, p.tolist())
        before = scrape(gw)
        victim_backend.armed = True
        out = [None] * n_req

        def one(i):
            out[i] = send(gw.host, gw.port, prompts[i])

        with ThreadPoolExecutor(max_workers=3) as ex:
            list(ex.map(one, range(n_req)))
        after = scrape(gw)
        settle_idle()
        victim_backend.armed = False

        completed = sum(1 for st, toks, _, _ in out
                        if st == 200 and toks is not None)
        identical = all(
            st == 200 and toks == ref_streams[i]
            for i, (st, toks, _, _) in enumerate(out))
        steps_contiguous = all(
            steps == list(range(len(toks or [])))
            for _, toks, _, steps in out)
        d = {name: counter_val(after, name) - counter_val(before, name)
             for name in ("dwt_gateway_resume_attempts_total",
                          "dwt_gateway_resume_succeeded_total",
                          "dwt_gateway_resume_exhausted_requests_total")}
        resumed_recs = [r for r in led.recent(4 * n_req)
                        if r.get("resumed")]
        decomposed = all(
            abs(r["ttft_s"] + r["per_token_s"] * (r["tokens"] - 1)
                + r["migration_pause_s"] + r["resume_pause_s"]
                - r["e2e_s"]) <= 1e-6 * max(r["e2e_s"], 1.0)
            for r in resumed_recs)
        results["failover"] = {
            "requests": n_req,
            "completed": completed,
            "bit_identical": bool(identical),
            "steps_contiguous": bool(steps_contiguous),
            "resume_attempts": int(d["dwt_gateway_resume_attempts_total"]),
            "resume_succeeded": int(
                d["dwt_gateway_resume_succeeded_total"]),
            "resume_exhausted": int(
                d["dwt_gateway_resume_exhausted_requests_total"]),
            "resume_ttf_p95_ms": hist_p95(
                after, "dwt_gateway_resume_ttf_seconds"),
            "slo_resumed_requests": len(resumed_recs),
            "slo_resume_pause_p95_ms": round(_percentile(
                sorted(r["resume_pause_s"] for r in resumed_recs), 95)
                * 1e3, 2) if resumed_recs else None,
            "slo_decomposition_exact": bool(decomposed),
            "victim_struck": not registry.is_up(victim_rid),
        }
        gw.shutdown()
    finally:
        set_slo_ledger(None)
        victim_backend.armed = False

    # -- phase 3: documented loss at resume_limit=0 -------------------------
    gw, registry, router = fresh_gateway(resume_limit=0)
    router.record(victim_rid, prompts[0].tolist())
    victim_backend.armed = True
    st, toks, delivered, _ = send(gw.host, gw.port, prompts[0])
    victim_backend.armed = False
    gw.shutdown()
    settle_idle()
    results["documented_loss"] = {
        "status": st,
        "error_line": toks is None,
        "delivered_before_error": delivered,
    }

    for srv in servers:
        srv.shutdown()
    leak_free = {"survivor": no_leak(engines[1]),
                 "victim": no_leak(engines[0])}
    for eng in engines:
        eng.close()

    fo, dl = results["failover"], results["documented_loss"]
    return {
        "model": model, "requests": n_req, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "crash_after": crash_after,
        **results,
        # the §23 acceptance gates
        "failover_completed_100pct": fo["completed"] == n_req,
        "failover_bit_identical": (fo["bit_identical"]
                                   and fo["steps_contiguous"]),
        "resume_all_succeeded": (fo["resume_attempts"] >= 1
                                 and fo["resume_succeeded"]
                                 == fo["resume_attempts"]
                                 and fo["resume_exhausted"] == 0),
        "slo_books_resume": (fo["slo_resumed_requests"]
                             == fo["resume_succeeded"]
                             and fo["slo_decomposition_exact"]),
        "loss_documented_at_limit_0": (dl["status"] == 200
                                       and dl["error_line"]
                                       and 1 <= dl[
                                           "delivered_before_error"]
                                       < new_tokens),
        "zero_leak_survivor": leak_free["survivor"],
        "zero_leak_victim": leak_free["victim"],
    }


# ---------------------------------------------------------------------------

def micro_shape(p: dict) -> dict:
    """The micro shape: the SAME model and leg structure at the
    smallest meaningful scale — 1 round, tiny token budgets.  It is the
    size tests/test_bench_legs.py runs the legs at on a CPU, where they
    gate structure and counts, never speed."""
    return dict(p, batch=min(p["batch"], 2),
                prompt_len=min(p["prompt_len"], 32),
                new_tokens=min(p["new_tokens"], 8))


# headline-order legs that stamp the §20 cost-observatory block into
# their artifact: per-signature p50/p95 from the
# sampled dispatch profiler plus the compile ledger.  Each leg runs in
# a fresh subprocess (_spawn_leg), so the process-global observatory
# snapshot IS that leg's own dispatches — no cross-leg bleed.
_PROFILED_LEGS = {"headline", "headline_int8", "flagship_bf16",
                  "flagship_int8", "decode_fused", "batching",
                  "mixed_batching", "spec_mixed", "tiered_prefix"}


def _dispatch_profile_extras() -> dict:
    """The ``dispatch_profile`` artifact block: per-signature p50/p95
    (+ achieved GB/s where attributed) and compile counts, from this
    process's cost observatory.  Empty dict when nothing was profiled
    (DWT_PROFILE_SAMPLE_N=0, or a leg that never dispatched a tracked
    program) — the block is then omitted rather than stamped hollow."""
    try:
        from distributed_inference_demo_tpu.telemetry import profiling
        prof = profiling.get_profiler()
        sigs = prof.snapshot()
        comp = profiling.get_compile_tracker().snapshot()
    except Exception:
        return {}
    if not sigs and not comp:
        return {}
    return {"sample_n": prof.sample_n, "signatures": sigs,
            "compile": comp}


def run_leg(name: str, p: dict, micro: bool = False) -> dict:
    if micro:
        p = micro_shape(p)
    model, batch = p["model"], p["batch"]
    prompt_len, new_tokens = p["prompt_len"], p["new_tokens"]
    flagship = p["flagship"]
    try:
        if name == "headline":
            out = _bench_engine(model, batch, prompt_len, new_tokens,
                                latency=not micro)
        elif name == "headline_int8":
            out = _bench_engine(model, batch, prompt_len, new_tokens,
                                quant=True, latency=not micro)
        elif name == "sweep":
            # the FULL b8/32/64 x {bf16,int8,int4} grid at BOTH budgets
            # (carried satellite, promoted): the micro prepass banks
            # coarse numbers for every shape in the first healthy
            # window, and the full-budget pass now measures the same
            # grid properly — the narrower b32/64 x {bf16,int8} grid
            # left the b8 points and the int4 column micro-only for
            # two rounds running
            out = _leg_sweep(model, prompt_len, new_tokens,
                             quants=(False, True, "int4"),
                             batches=(8, 32, 64),
                             kv_dtypes=("bf16", "int8", "int4"))
        elif name == "flagship_int8":
            out = _leg_flagship(flagship, batch, prompt_len,
                                min(new_tokens, 64), quant=True)
        elif name == "flagship_bf16":
            out = _leg_flagship(flagship, batch, prompt_len,
                                min(new_tokens, 64), quant=False)
        elif name == "speculative":
            out = _leg_speculative(model, batch, prompt_len, new_tokens)
        elif name == "prompt_lookup":
            out = _leg_prompt_lookup(model, new_tokens)
        elif name == "batching":
            out = _leg_batching(model, prompt_len, min(new_tokens, 64))
        elif name == "mixed_batching":
            # the micro shape keeps the §19 gate structural on CPU:
            # 12-chunk prompts over 4 slots with 3 pinned decode rows,
            # all arrivals at once — the serialized baseline pays one
            # suppressed per-token dispatch per step PLUS one dispatch
            # per chunk, mixed pays ~1 per decode_block with the
            # chunks riding along
            out = (_leg_mixed_batching(model, prompt_len=96,
                                       new_tokens=16, slots=4, n_req=8,
                                       prefill_chunk=8, decode_block=4,
                                       arrival_s=0.0, block_tokens=8)
                   if micro else _leg_mixed_batching(model))
        elif name == "spec_mixed":
            # the micro shape keeps the §22 comparison structural on
            # CPU: motif-tiled chunky prompts over 4 slots with 3
            # pinned background rows, all arrivals at once, K=2 — the
            # three engine builds and the packed-with-rounds program
            # variants all exercise at the smallest meaningful scale
            out = (_leg_spec_mixed(model, prompt_len=96, new_tokens=8,
                                   slots=4, n_req=6, prefill_chunk=8,
                                   decode_block=4, num_draft=2,
                                   arrival_s=0.0, block_tokens=8)
                   if micro else _leg_spec_mixed(model))
        elif name == "prefix_reuse":
            out = _leg_prefix_reuse(model, min(new_tokens, 64))
        elif name == "tiered_prefix":
            # the micro shape keeps the §21 gate structural on CPU: a
            # 14-block pool under a 4-group working set (8 blocks per
            # group) thrashes every revisit, the 2-group host ring
            # forces the rest through the disk segment
            out = (_leg_tiered_prefix(model, min(new_tokens, 8),
                                      groups=4, revisits=2,
                                      shared_len=48, tail_len=8,
                                      block_tokens=8, kv_blocks=14,
                                      host_groups=2) if micro
                   else _leg_tiered_prefix(model, new_tokens))
        elif name == "paged_decode":
            out = _leg_paged_decode(model, new_tokens)
        elif name == "serving_relative":
            out = (_leg_serving_relative(model, batch, prompt_len,
                                         new_tokens, slots=2, n_req=4)
                   if micro else
                   _leg_serving_relative(model, batch, prompt_len,
                                         new_tokens))
        elif name == "decode_fused":
            out = (_leg_decode_fused(model, prompt_len, new_tokens,
                                     batches=(1,), blocks=(1, 4))
                   if micro else
                   _leg_decode_fused(model, prompt_len, new_tokens))
        elif name == "pipeline":
            out = _leg_pipeline(model, batch, prompt_len,
                                min(new_tokens, 32))
        elif name == "fault_recovery":
            out = (_leg_fault_recovery(model, new_tokens=8) if micro
                   else _leg_fault_recovery(model))
        elif name == "disagg":
            # the micro shape keeps decode SATURATED (7 of 8 slots
            # pinned): the interleaved-step stall the split removes is
            # only visible under real concurrent decode load
            out = (_leg_disagg(model, n_req=3, prompt_len=128,
                               prefill_chunk=8, max_seq=1024,
                               block_tokens=8) if micro
                   else _leg_disagg(model))
        elif name == "gateway_routing":
            # the micro shape keeps the structure (3 replicas, grouped
            # shared prefixes, a drained replica) at the smallest scale
            # where the TTFT-p95 gate stays structural: enough requests
            # per group that cache-aware's full prefills sit below the
            # percentile while round-robin's sit above it
            out = (_leg_gateway_routing(model, groups=2, per_group=20,
                                        prefix_len=300, suffix_len=8,
                                        new_tokens=4, slots=2,
                                        max_seq=512, block_tokens=16,
                                        kill_requests=4) if micro
                   else _leg_gateway_routing(model))
        elif name == "stream_failover":
            # the micro shape keeps the §23 gates structural on CPU:
            # two replicas, a 4-stream soak with 2 streams pinned to
            # the dying victim, death 2 tokens in — enough to cover
            # detect → re-route → replay → bit-identical suffix
            out = (_leg_stream_failover(model, n_req=4, prompt_len=32,
                                        new_tokens=8, slots=2,
                                        max_seq=256, block_tokens=8,
                                        crash_after=2, seed_victim=2)
                   if micro else _leg_stream_failover(model))
        elif name == "planner_pipeline":
            out = _leg_planner_pipeline(model, batch, prompt_len,
                                        min(new_tokens, 8))
        elif name == "prefill_long":
            out = (_leg_prefill_long(model, seqs=(512,)) if micro
                   else _leg_prefill_long(model))
        elif name == "long_context_sp":
            # the carried >=32k sequence-parallel satellite PROMOTED to
            # a full-budget headline-order leg: ring AND ulysses points
            # at >= 32k context (BENCH_LONG_CTX_SP overrides for CPU
            # structure tests), not just the micro prepass
            out = {"points": _long_context_sp_points(
                model, new=8 if micro else 64)}
            errs = [p for p in out["points"] if "error" in p]
            if errs and len(errs) == len(out["points"]):
                out["error"] = errs[0]["error"]
        elif name == "long_context":
            if micro:
                # one chunk-multiple context that still exercises the
                # chunked-prefill + full-context-decode structure; the
                # >= 32k sp strategy points ride the micro prepass too
                # (carried satellite) so both strategies bank a number
                # in the first healthy window
                os.environ.setdefault("BENCH_LONG_CTX", "4096")
            out = _leg_long_context(model)
            if micro:
                out["sp_points"] = _long_context_sp_points(model)
        elif name in ("roofline_probe", "roofline_probe_rerun"):
            # the rerun executes the SAME probe immediately after the
            # headline leg: two readings bracket it
            out = (_leg_roofline_probe(reps=8, rounds_n=1) if micro
                   else _leg_roofline_probe())
        elif name == "moe":
            out = _leg_moe(batch, prompt_len, min(new_tokens, 64))
        elif name == "multimodal":
            out = _leg_multimodal(batch, min(new_tokens, 64))
        elif name == "int4":
            out = _leg_int4(model, flagship, batch, prompt_len,
                            new_tokens)
        else:
            raise SystemExit(f"unknown leg {name!r}")
    except Exception as e:         # structured error, not a dead process
        out = {"error": f"{type(e).__name__}: {e}"}
    if name in _PROFILED_LEGS and "error" not in out:
        dp = _dispatch_profile_extras()
        if dp:
            out["dispatch_profile"] = dp
    if micro:
        # stamped so a micro number can never masquerade as a
        # full-budget measurement in the artifact
        out["micro"] = True
        out["micro_shape"] = {k: p[k] for k in ("batch", "prompt_len",
                                                "new_tokens")}
    if "device" not in out:
        # guarded + lazy: the planner leg sets its own device string (its
        # subprocess owns the exclusive TPU), and an error path must not
        # die here trying to init a backend
        try:
            out["device"] = _device_kind()
        except Exception:
            pass
    return out


def headline_summary(headline: dict, params: dict, device: str) -> dict:
    """The artifact's top-level metric/value/vs_baseline/baseline block —
    ONE owner for the comparability caveats.

    Only a same-model/batch/prompt/new-tokens comparison is meaningful;
    anything else reports null rather than a mislabeled multiplier.  The
    one stated asymmetry is dtype: CPU runs f32 (its native dtype — bf16
    is emulated and slower there), TPU runs bf16."""
    baseline = _load_baseline()
    tps = headline.get("decode_tokens_per_sec")
    base_tps = baseline.get("tokens_per_sec")
    comparable = all(
        baseline.get(k) == params[k]
        for k in ("model", "batch", "prompt_len", "new_tokens"))
    vs = (round(tps / base_tps, 2)
          if tps is not None and base_tps and comparable else None)
    return {
        "metric": f"decode tokens/sec ({params['model']}, "
                  f"{headline.get('dtype', '?')}, batch={params['batch']}, "
                  f"prompt={params['prompt_len']}, "
                  f"new={params['new_tokens']}, "
                  f"device={device}) vs measured 2-process CPU "
                  f"socket-pipeline baseline (same model/batch/prompt/new; "
                  f"CPU at f32, its native dtype)",
        "value": tps,
        "vs_baseline": vs,
        "baseline": {k: baseline.get(k) for k in
                     ("tokens_per_sec", "model", "dtype", "batch", "host",
                      "cpu", "measured_at", "source")},
    }


def _run_group_killable(cmd, timeout: int):
    """Run ``cmd`` in its own process GROUP; on timeout kill the whole
    group (children included — e.g. the planner leg's server/worker hold
    the exclusive TPU and ports).  Returns
    (returncode_or_None_on_timeout, stdout, stderr)."""
    import signal

    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            cwd=str(REPO), start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
        return proc.returncode, stdout, stderr
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass   # unkillable child: report and move on anyway
        return None, "", ""


def _spawn_leg(name: str, params: dict, timeout: int = 900,
               micro: bool = False) -> dict:
    """Run one leg in a fresh process; parse the last stdout line as JSON."""
    rc, stdout, stderr = _run_group_killable(
        [sys.executable, str(REPO / "bench.py"), "--leg", name,
         "--params", json.dumps(params)]
        + (["--micro"] if micro else []), timeout)
    if rc is None:
        return {"error": f"leg timed out after {timeout}s"}
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    if rc != 0 or not lines:
        tail = (stderr or "").strip().splitlines()[-8:]
        return {"error": f"leg exited rc={rc}", "stderr_tail": tail}
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        return {"error": f"unparseable leg output: {lines[-1][:200]}"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--leg")
    ap.add_argument("--params")
    ap.add_argument("--micro", action="store_true",
                    help="run the leg's micro variant (1 round, smallest "
                         "meaningful shape — the CPU structure tests' "
                         "size)")
    ap.add_argument("--run-log", default=os.environ.get("BENCH_RUN_LOG",
                                                        ""),
                    help="append structured JSONL run-log events "
                         "(telemetry/runlog) for this bench run; leg "
                         "subprocesses inherit it via DWT_RUN_LOG and "
                         "write per-pid siblings")
    args = ap.parse_args()

    from distributed_inference_demo_tpu.telemetry.runlog import (
        NULL, RunLog, set_run_log)
    if args.run_log:
        runlog = RunLog(args.run_log)
        set_run_log(runlog)
        # engines inside leg subprocesses log their per-request
        # summaries next to ours (runlog suffixes the path per pid)
        os.environ["DWT_RUN_LOG"] = args.run_log
    else:
        # don't install NULL: a leg subprocess must keep get_run_log()'s
        # lazy DWT_RUN_LOG resolution (set by the orchestrator above)
        runlog = NULL

    params = {
        "model": os.environ.get("BENCH_MODEL", "tinyllama-1.1b"),
        "batch": int(os.environ.get("BENCH_BATCH", "8")),
        "prompt_len": int(os.environ.get("BENCH_PROMPT", "64")),
        "new_tokens": int(os.environ.get("BENCH_NEW_TOKENS", "128")),
        "flagship": os.environ.get("BENCH_FLAGSHIP", "llama-3-8b"),
    }
    if args.leg:  # subprocess mode: one leg, one JSON line
        if args.params:
            params.update(json.loads(args.params))
        # the package's one compile-cache rule, before any backend exists
        from distributed_inference_demo_tpu.cli import (
            configure_compile_cache)
        configure_compile_cache()
        print(json.dumps(run_leg(args.leg, params, micro=args.micro)))
        return

    # priority order: never-measured evidence first (speculative /
    # prompt_lookup / planner_pipeline / long_context), then the flagship
    # headline re-measurement, THEN the expensive multi-engine batching
    # leg (its 1500s budget must not starve the flagship under the
    # driver's deadline), then the already-proven tails
    legs = ["roofline_probe", "headline", "roofline_probe_rerun",
            "headline_int8", "decode_fused", "speculative",
            "prompt_lookup", "planner_pipeline", "long_context",
            "long_context_sp", "disagg", "gateway_routing",
            "stream_failover",
            "flagship_int8", "batching", "mixed_batching",
            "spec_mixed", "prefix_reuse", "tiered_prefix", "paged_decode",
            "serving_relative", "sweep", "flagship_bf16", "pipeline",
            "fault_recovery", "prefill_long", "moe", "multimodal",
            "int4"]
    for skip_var, leg_names in (
            ("BENCH_SKIP_FLAGSHIP", ["flagship_int8", "flagship_bf16"]),
            ("BENCH_SKIP_PIPELINE", ["pipeline", "planner_pipeline",
                                     "fault_recovery"]),
            ("BENCH_SKIP_SWEEP", ["sweep"]),
            ("BENCH_SKIP_SERVING", ["speculative", "prompt_lookup",
                                    "batching", "mixed_batching",
                                    "spec_mixed",
                                    "prefix_reuse", "tiered_prefix",
                                    "paged_decode",
                                    "serving_relative", "disagg",
                                    "gateway_routing",
                                    "stream_failover"]),
            ("BENCH_SKIP_LONGCTX", ["long_context", "long_context_sp"]),
            ("BENCH_SKIP_PREFILL", ["prefill_long"]),
            ("BENCH_SKIP_MOE_MM", ["moe", "multimodal"]),
            ("BENCH_SKIP_INT4", ["int4"])):
        if os.environ.get(skip_var, "") == "1":
            legs = [l for l in legs if l not in leg_names]
    only = os.environ.get("BENCH_ONLY")
    if only:
        legs = [l for l in legs if l in only.split(",")]

    # device probe in a child (this parent never touches JAX: the legs
    # need the chip).  A benchmark number comes from a TPU or not at all:
    # no TPU, no legs, exit code 1 — and nothing from an earlier run
    rc, p_out, p_err = _run_group_killable(
        [sys.executable, "-c",
         "import jax, json; d = jax.devices(); "
         "print(json.dumps({'platform': d[0].platform, "
         "'device_kind': d[0].device_kind, 'count': len(d)}))"],
        timeout=180)
    probe, reason = None, None
    if rc is None:
        reason = "the device backend did not answer a 180s probe"
    elif rc != 0:
        last = ((p_err or "").strip().splitlines() or ["?"])[-1]
        reason = f"device probe exited rc={rc}: {last}"
    else:
        probe = json.loads(p_out.strip().splitlines()[-1])
        if probe["platform"] != "tpu":
            reason = (f"JAX found no TPU (platform={probe['platform']!r}, "
                      f"device_kind={probe['device_kind']!r}); a CPU run "
                      "measures nothing this benchmark reports")
    if reason is not None:
        runlog.event("bench_abort", reason=reason)
        print(f"bench.py: no leg attempted: {reason}", file=sys.stderr)
        sys.exit(1)

    # global deadline: one JSON line MUST still be printed — remaining
    # legs are skipped, never the report
    deadline = time.monotonic() + int(
        os.environ.get("BENCH_DEADLINE_S", "2700"))
    # the batching leg builds several engine instances (plain compare +
    # slot/decode-block/speculative phases), each with its own compiles —
    # give it more rope than the single-engine legs
    # paged_decode keeps the acceptance shape (new=128, unclamped) and
    # builds two engines + three waves — budget it like batching
    # gateway_routing runs three replica engines through three phases
    # (two routed soaks + the drain) — multi-engine, budget it likewise
    # tiered_prefix builds two engines (re-prefill reference + tiered)
    # and runs two routed rounds each — budget it like prefix_reuse
    # spec_mixed builds THREE engines (spec-only, mixed-only, fused)
    # over the same arrival stream — budget it like batching
    # stream_failover runs two replica engines through three routed
    # phases (reference soak, failover soak, documented loss) — budget
    # it like gateway_routing
    leg_timeouts = {"batching": 1500, "mixed_batching": 1500,
                    "spec_mixed": 1500,
                    "prefix_reuse": 1200, "tiered_prefix": 1200,
                    "paged_decode": 1500, "serving_relative": 1500,
                    "gateway_routing": 1500, "stream_failover": 1500}
    runlog.event("bench_start", params=params, legs=legs)
    results = {}
    for leg in legs:
        left = deadline - time.monotonic()
        if left <= 120:    # a leg needs real budget (compiles alone are ~2m)
            results[leg] = {"error": "skipped: bench deadline reached"}
            runlog.event("bench_leg", leg=leg, skipped=True,
                         error=results[leg]["error"])
            continue
        t0 = time.perf_counter()
        results[leg] = _spawn_leg(leg, params,
                                  timeout=min(leg_timeouts.get(leg, 900),
                                              int(left)))
        if isinstance(results[leg], dict):
            results[leg]["leg_seconds"] = round(time.perf_counter() - t0, 1)
        runlog.event("bench_leg", leg=leg,
                     seconds=round(time.perf_counter() - t0, 1),
                     error=(results[leg].get("error")
                            if isinstance(results[leg], dict) else None))

    headline = results.get("headline", {})
    # headline may have errored; any leg that reached the device knows it
    # (planner_pipeline excluded: its device field is a topology
    # description, not a chip identity)
    device = headline.get("device") or next(
        (r["device"] for name, r in results.items()
         if name != "planner_pipeline"
         and isinstance(r, dict) and r.get("device")), "unknown")
    summary = headline_summary(headline, params, device)

    extras = {"device": device, "probe": probe,
              "baseline": summary["baseline"]}
    extras.update({k: v for k, v in results.items() if k != "headline"})

    # the two HBM probe legs bracket the headline; their spread is
    # context, never a ceiling (ratios use the published peak of the
    # device kind — _with_bandwidth)
    all_rounds = sorted(
        (results.get("roofline_probe", {}) or {}).get(
            "hbm_read_gbs_rounds", [])
        + (results.get("roofline_probe_rerun", {}) or {}).get(
            "hbm_read_gbs_rounds", []))
    if all_rounds:
        extras["probe_spread_gbs"] = {
            "n": len(all_rounds),
            "min": round(all_rounds[0], 1),
            "median": round(all_rounds[len(all_rounds) // 2], 1),
            "max": round(all_rounds[-1], 1)}

    runlog.event("bench_done", value=summary["value"],
                 vs_baseline=summary["vs_baseline"],
                 errored_legs=[k for k, v in results.items()
                               if isinstance(v, dict) and "error" in v])
    runlog.close()
    print(json.dumps({
        "metric": summary["metric"],
        "value": summary["value"],
        "unit": "tokens/sec",
        "vs_baseline": summary["vs_baseline"],
        "headline": headline,
        "extras": extras,
    }))


if __name__ == "__main__":
    main()
