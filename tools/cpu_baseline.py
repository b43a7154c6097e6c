"""Measure the 2-node CPU baseline (BASELINE.json config #1 at true scale).

TinyLlama-1.1B split into 2 layer ranges across 2 localhost OS processes —
the reference's 2-device demo shape (``server.py:26-27``) with the header in
this process and stage 1 in a spawned worker, ZMQ sockets in between.  The
result is the denominator of bench.py's ``vs_baseline`` (north star:
TPU >= 10x this number).

Writes ``tools/cpu_baseline.json``; run on the bench host:

    python tools/cpu_baseline.py            # full TinyLlama-1.1B (~minutes)
    BENCH_MODEL=llama-test python tools/cpu_baseline.py   # smoke

Weights are random (seed-derived in both processes) — throughput does not
depend on weight values.  fp32 is used on CPU (its native dtype; bf16 is
emulated and slower there, and a handicapped baseline would overstate
``vs_baseline``).
"""

import json
import os
import platform
import subprocess
import sys
import time
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"     # a CPU baseline, whatever the machine has

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

OUT_PATH = Path(__file__).resolve().parent / "cpu_baseline.json"


def main() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from distributed_inference_demo_tpu.comm.transport import ZmqTransport
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.base import (
        slice_stage, split_layer_ranges)
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.distributed import (
        PipelineHeader, StageRuntime)

    model = os.environ.get("BENCH_MODEL", "tinyllama-1.1b")
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    prompt_len = int(os.environ.get("BENCH_PROMPT", "64"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "32"))
    max_seq = prompt_len + new_tokens

    cfg = get_model_config(model).replace(dtype_name="float32")
    specs = split_layer_ranges(cfg.num_layers, 2)
    sampling = SamplingParams(temperature=0.7, top_k=7)  # reference defaults

    print(f"[cpu_baseline] {model} fp32, batch={batch}, "
          f"prompt={prompt_len}, new={new_tokens}, split="
          f"{[(s.layer_start, s.layer_end) for s in specs]}", file=sys.stderr)

    header_transport = ZmqTransport("header")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_DTYPE="float32")
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
         # generous: the header's own init/compile can take minutes on a
         # small CPU host, and the worker must not idle out meanwhile
         "--step-timeout", "1800"],
        stdout=subprocess.PIPE, stderr=sys.stderr, env=env,
        text=True, cwd=str(REPO))
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("WORKER_READY w1 "), line
        header_transport.connect("w1", line.split()[-1])

        print("[cpu_baseline] initializing header stage...", file=sys.stderr)
        full = init_full_params(jax.random.PRNGKey(0), cfg)
        header = PipelineHeader(
            StageRuntime(cfg, specs[0], slice_stage(full, cfg, specs[0]),
                         max_seq, sampling),
            header_transport, next_id="w1", step_timeout=600)

        prompt = (np.arange(batch * prompt_len, dtype=np.int64)
                  .reshape(batch, prompt_len) % 1000).astype(np.int32)

        print("[cpu_baseline] warmup (compiles both stages)...",
              file=sys.stderr)
        header.generate(prompt, 4)
        header.reset_stats()

        print("[cpu_baseline] timed run...", file=sys.stderr)
        t0 = time.perf_counter()
        toks = header.generate(prompt, new_tokens)
        dt = time.perf_counter() - t0
        assert toks.shape == (batch, new_tokens)
        tps = batch * new_tokens / dt

        stage_stats = header.collect_stats(num_stages=2, timeout=30)
        header.shutdown_pipeline()
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
        header_transport.close()

    result = {
        "tokens_per_sec": round(tps, 3),
        "seconds": round(dt, 3),
        "model": model,
        "dtype": "float32",
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "num_stages": 2,
        "transport": "zmq tcp localhost",
        "host": platform.node(),
        "cpu": platform.processor() or platform.machine(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "stage_stats": stage_stats,
    }
    OUT_PATH.write_text(json.dumps(result, indent=2) + "\n")
    print(f"[cpu_baseline] {tps:.2f} tok/s -> {OUT_PATH}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
