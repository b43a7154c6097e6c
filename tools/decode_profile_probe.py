"""Probe: decompose the large-batch decode step on the real chip.

An early batch sweep (a record since deleted with the harness that took
it) showed achieved weights-GB/s collapsing with batch at tiny context,
where cache reads are ~12% of weight traffic — so the erosion is per-row
ACTIVATION work, not HBM streaming.  This probe separates the suspects:

1. **Batch scaling law**: per-step time at b in {1, 8, 32, 64} under
   greedy (forward + argmax only).  A linear fit t(b) = floor + slope*b
   gives the weight-stream floor (should approach weights_bytes /
   measured HBM GB/s) and the per-row marginal cost.
2. **Sampling tax**: the same step under top-k=7 — the delta vs greedy is
   pure sampling (filtered_logits + categorical).  After the
   approx_max_k change (ops/sampling.py), this should be flat-ish in
   batch; if it still grows, the next suspect is `jax.random.categorical`
   's [b, vocab] gumbel draw.
3. **kth-value microbench in isolation**: lax.top_k's sort vs the
   iterative argmax-and-mask path (ops.sampling.kth_largest) vs a bare
   argmax on [b, 32000] f32 logits — the direct on-chip comparison
   behind the filtered_logits small-k gate.

Run on the real device: ``python tools/decode_profile_probe.py``.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.telemetry.profiling import \
    dispatch_signature

try:        # `python tools/decode_profile_probe.py` vs `-m tools....`
    from probe_artifact import emit_signatures
except ImportError:
    from tools.probe_artifact import emit_signatures

BATCHES = (1, 8, 32, 64)
NEW = 128


def step_ms(engine, batch: int) -> float:
    """Decode-ONLY per-step ms: prefill runs outside the timed region so
    the batch-scaling fit isolates the decode step (whole-generate /
    NEW would fold per-batch prefill cost into the slope)."""
    prompt = (np.arange(batch * 64).reshape(batch, 64) % 1000).astype(
        np.int32)
    engine.generate(prompt, NEW, seed=0)               # compile both jits
    cache = engine.new_cache(batch)
    logits, cache = engine._run_prefill(jnp.asarray(prompt), cache)
    np.asarray(logits)                                 # fence
    t0 = time.perf_counter()
    toks, _, _ = engine._decode(engine.params, logits, cache,
                                jax.random.PRNGKey(0),
                                engine._eos_scalar(), NEW, False)
    np.asarray(toks)                                   # fence
    return (time.perf_counter() - t0) / NEW * 1000


def main():
    cfg = get_model_config("tinyllama-1.1b")
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    weights_gb = params.nbytes() / 1e9

    print(f"== decode step decomposition (tinyllama bf16, "
          f"weights {weights_gb:.2f} GB, new={NEW}) ==", flush=True)
    rows = {}
    for name, samp in (("greedy", SamplingParams(greedy=True)),
                       ("topk7", SamplingParams(temperature=0.7, top_k=7))):
        eng = InferenceEngine(cfg, params, max_seq=192, sampling=samp)
        for b in BATCHES:
            ms = step_ms(eng, b)
            rows[(name, b)] = ms
            gbs = weights_gb / (ms / 1000)
            print(f"b={b:3d} {name:7s} {ms:7.2f} ms/step  "
                  f"weights-GB/s={gbs:6.1f}", flush=True)

    # linear fit of the greedy curve: floor + slope*b
    bs = np.asarray(BATCHES, np.float64)
    ts = np.asarray([rows[("greedy", b)] for b in BATCHES])
    slope, floor = np.polyfit(bs, ts, 1)
    print(f"greedy fit: floor={floor:.2f} ms (weight stream => "
          f"{weights_gb / (floor / 1000):.0f} GB/s), "
          f"slope={slope * 1000:.1f} us/row", flush=True)
    for b in BATCHES:
        tax = rows[("topk7", b)] - rows[("greedy", b)]
        print(f"b={b:3d} sampling tax {tax:+.2f} ms/step", flush=True)

    # observatory artifact: the same numbers keyed by dispatch
    # signature (mergeable with /debugz snapshots + bench extras)
    emit_signatures(
        [(dispatch_signature(f"probe_decode_{name}", batch=b, chunk=NEW),
          {"mean_ms": ms,
           "weights_gbs": weights_gb / (ms / 1000)})
         for (name, b), ms in sorted(rows.items())],
        extra={"probe": "decode_profile", "weights_gb": weights_gb})

    print("== kth-value microbench on [b, 32000] f32 ==", flush=True)

    def bench(fn, logits, reps=50):
        fn(logits).block_until_ready()
        out = None
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(logits)
        np.asarray(out)          # fence
        return (time.perf_counter() - t0) / reps * 1000

    from distributed_inference_demo_tpu.ops.sampling import (
        SamplingParams as SP, filtered_logits, kth_largest, sample_logits,
        topk_vals_idx)
    key = jax.random.PRNGKey(0)
    samp7 = SP(temperature=0.7, top_k=7)

    def full_vocab_draw(k, x):
        # the pre-r04 sampler: mask the vocab, gumbel over [b, V]
        return jax.random.categorical(k, filtered_logits(x, samp7), axis=-1)

    def fused_draw(k, x):
        # the r04 sampler: k argmax passes -> categorical over [b, k]
        return sample_logits(x, k, samp7)

    variants = {
        "top_k": jax.jit(lambda x: jax.lax.top_k(x, 7)[0][..., -1]),
        "iter_kth": jax.jit(lambda x: kth_largest(x, 7)[..., 0]),
        "iter_topk_vi": jax.jit(lambda x: topk_vals_idx(x, 7)[0]),
        "argmax": jax.jit(lambda x: jnp.argmax(x, -1)),
        # the OTHER half of the sampling tax: the [b, vocab] gumbel draw
        # (the key rides in as an argument — a baked constant key would
        # let XLA constant-fold the whole noise tensor out of the timing)
        "categorical": (lambda f: lambda x: f(key, x))(jax.jit(
            lambda k, x: jax.random.categorical(k, x, axis=-1))),
        # end-to-end samplers, old vs new (same distribution, different
        # draw shape: [b, V] gumbel vs [b, 7])
        "full_draw": (lambda f: lambda x: f(key, x))(jax.jit(
            full_vocab_draw)),
        "fused_draw": (lambda f: lambda x: f(key, x))(jax.jit(fused_draw)),
    }
    for b in BATCHES:
        logits = jax.random.normal(jax.random.PRNGKey(1), (b, 32000),
                                   jnp.float32)
        line = " ".join(f"{name}={bench(fn, logits):6.3f}ms"
                        for name, fn in variants.items())
        print(f"b={b:3d} {line}", flush=True)


if __name__ == "__main__":
    main()
