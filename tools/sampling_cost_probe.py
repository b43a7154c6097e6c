"""Probe: how much of a decode step does SAMPLING eat at large batch?

An early batch sweep (a record since deleted with the harness that took
it) showed achieved GB/s falling as batch grows.  Weights traffic is
batch-invariant,
so the extra per-step time is activation work — and top-k over [b, 32000]
logits (lax.top_k sorts) is a prime suspect.  This times the SAME decode
loop under greedy / top-k=7 / top-p sampling to isolate that cost.

Run on the real device: ``python tools/sampling_cost_probe.py``.
"""

import time

import jax
import numpy as np

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.telemetry.profiling import \
    dispatch_signature

try:        # `python tools/sampling_cost_probe.py` vs `-m tools....`
    from probe_artifact import emit_signatures
except ImportError:
    from tools.probe_artifact import emit_signatures


def main():
    cfg = get_model_config("tinyllama-1.1b")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    variants = [
        ("greedy", SamplingParams(greedy=True)),
        ("topk7", SamplingParams(temperature=0.7, top_k=7)),
        ("topp95", SamplingParams(temperature=0.7, top_k=0, top_p=0.95)),
    ]
    rows = []
    for batch in (8, 64):
        for name, samp in variants:
            eng = InferenceEngine(cfg, params, max_seq=192, sampling=samp)
            prompt = (np.arange(batch * 64).reshape(batch, 64)
                      % 1000).astype(np.int32)
            eng.generate(prompt, 128, seed=0)            # compile
            r = eng.generate(prompt, 128, seed=0)
            steps = 128
            ms = r.seconds / steps * 1000
            print(f"b={batch:3d} {name:7s} {r.tokens_per_second:9.1f} tok/s"
                  f"  {ms:6.2f} ms/step", flush=True)
            rows.append((dispatch_signature(f"probe_sampling_{name}",
                                            batch=batch, chunk=steps),
                         {"mean_ms": ms,
                          "tokens_per_sec": r.tokens_per_second}))
    # observatory artifact: signature-keyed, mergeable (§20)
    emit_signatures(rows, extra={"probe": "sampling_cost"})


if __name__ == "__main__":
    main()
