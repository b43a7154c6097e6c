"""Integrated root-server app: the full register → profile → plan →
distribute → run → serve composition (VERDICT r1 item 3; reference
``server.py:583-1052``).

The workers are *bare*: they get only the registry address and a device id
— no topology, no layer ranges, and no weights seed.  Stage weights arrive
through the lifecycle artifact channel from the server's parameter set, so
token-level parity with a local engine proves the whole chain.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine

MODEL = "llama-test"
SEED = 123      # distinctive: workers must NOT be able to derive weights
PROMPT = [[5, 17, 42, 7, 99, 3, 12, 56]]


def _cpu_env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=1")


def _read_until(proc, prefix, timeout=180.0, sink=None):
    """Read stdout lines until one starts with ``prefix``; returns it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            assert proc.poll() is None, \
                f"process died waiting for {prefix!r} (rc={proc.returncode})"
            time.sleep(0.05)
            continue
        line = line.strip()
        if sink is not None:
            sink.append(line)
        if line.startswith(prefix):
            return line
    raise AssertionError(f"{prefix!r} not seen within {timeout}s "
                         f"(saw {sink})")


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", ["", "float8_e4m3fn"])
def test_server_with_bare_workers_end_to_end(tmp_path, kv_dtype):
    """The composed server e2e; the fp8 variant proves --kv-cache-dtype
    rides the OPEN RunConfig to every auto worker's stage cache (greedy
    parity vs a ref engine with the SAME cache dtype) AND runs the HTTP
    surface through the dynamic-batching backend (--pool-size 2:
    generate + stats + classify all ride the scheduler thread)."""
    cfg = get_model_config(MODEL)
    ref_engine = InferenceEngine(
        cfg, init_full_params(jax.random.PRNGKey(SEED), cfg),
        max_seq=64, sampling=SamplingParams(greedy=True),
        kv_cache_dtype=kv_dtype or None)
    want = ref_engine.generate(np.asarray(PROMPT, np.int32), 8).tokens

    env = _cpu_env()
    server = subprocess.Popen(
        [sys.executable, "-m", "distributed_inference_demo_tpu", "server",
         "--model", MODEL, "--num-workers", "2", "--max-seq", "64",
         "--max-new-tokens", "8", "--greedy", "--weights-seed", str(SEED),
         "--collect-timeout", "300", "--monitor-timeout", "300",
         "--step-timeout", "300"]
        + (["--kv-cache-dtype", kv_dtype, "--pool-size", "2"]
           if kv_dtype else []),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    workers = []
    log = []
    try:
        registry = _read_until(server, "SERVER_REGISTRY", sink=log).split()[1]
        for wid in ("w1", "w2"):
            workers.append(subprocess.Popen(
                [sys.executable, "-m", "distributed_inference_demo_tpu",
                 "worker", "--auto", "--registry", registry,
                 "--device-id", wid, "--step-timeout", "300"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env, text=True))

        plan_line = _read_until(server, "SERVER_PLAN", timeout=300, sink=log)
        ranges = json.loads(plan_line.split(" ", 1)[1])
        assert set(ranges) == {"header", "w1", "w2"}
        covered = sorted(tuple(r) for r in ranges.values())
        assert covered[0][0] == 0 and covered[-1][1] == cfg.num_layers

        http = _read_until(server, "HTTP_READY", timeout=300,
                           sink=log).split()[1]

        body = json.dumps({"prompt_ids": PROMPT,
                           "max_new_tokens": 8}).encode()
        req = urllib.request.Request(
            http + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            tokens = np.asarray(json.loads(r.read())["tokens"])
        np.testing.assert_array_equal(tokens, want)

        # hot-loop stats flow across all three stages
        with urllib.request.urlopen(http + "/stats", timeout=60) as r:
            stats = json.loads(r.read())
        assert len(stats["stages"]) == 3
        assert {s["role"] for s in stats["stages"]} == \
            {"header", "worker", "tail"}

        # classification rides the same composed pipeline (task_type
        # "classification" implemented end to end, VERDICT r2 item 7):
        # bare workers speak the c:/ctok: protocol natively
        labels = [7, 42, 99]
        want_cls = ref_engine.classify(np.asarray(PROMPT, np.int32), labels)
        body = json.dumps({"prompt_ids": PROMPT,
                           "label_token_ids": labels}).encode()
        req = urllib.request.Request(
            http + "/classify", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            got_cls = json.loads(r.read())["labels"]
        assert got_cls == want_cls.tolist()
    finally:
        server.kill()
        for w in workers:
            w.kill()
