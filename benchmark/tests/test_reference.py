"""The plain reference against the program's own forward pass, at toy
size on the CPU, with int8 layers where the configuration has them, for
every configuration whose family generates one token a pass, left to
right.  (On the chip the same comparison runs inside every benchmark run,
at published width.)  A family with a ``replay`` generates otherwise: the
program's one causal forward is not what served its tokens, and its
comparison lives in ``tests/test_<family>_family.py``."""
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))


def toy_of(name):
    return json.loads(
        (BENCH / "configs" / f"{name}.json").read_text())["rehearsal"]


LEFT_TO_RIGHT = sorted(
    p.stem for p in (BENCH / "configs").glob("*.json")
    if not hasattr(families.load(toy_of(p.stem)["model_config"]["family"]),
                   "replay"))


@pytest.mark.parametrize("name", LEFT_TO_RIGHT)
def test_reference_agrees_with_the_program_at_toy_size(name):
    import jax
    import jax.numpy as jnp

    import reference
    from distributed_inference_demo_tpu.models.base import (KVCache,
                                                            ModelConfig,
                                                            StageSpec)
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params, stage_forward)

    toy = toy_of(name)
    fields = toy["model_config"]
    quant = "int8" if toy["serve_model"].endswith("-int8") else "none"
    cfg = ModelConfig(**fields, quantization=quant)
    params = init_full_params(jax.random.PRNGKey(3), cfg, quantize=True)
    ids = [(7 * i + 3) % cfg.vocab_size for i in range(40)]
    n_prompt = 24
    cache = KVCache(
        jnp.zeros((cfg.num_layers, 1, cfg.num_kv_heads, 64, cfg.head_dim),
                  cfg.dtype),
        jnp.zeros((cfg.num_layers, 1, cfg.num_kv_heads, 64, cfg.head_dim),
                  cfg.dtype), jnp.zeros((), jnp.int32))
    logits, _ = stage_forward(
        params, cfg, StageSpec(0, 1, 0, cfg.num_layers),
        jnp.asarray([ids], jnp.int32), cache,
        jnp.arange(len(ids), dtype=jnp.int32)[None])
    lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
    want = [float(lp[t - 1, ids[t]]) for t in range(n_prompt, len(ids))]
    got = reference.emitted_logprobs(params, fields, ids, n_prompt)
    assert got["logprobs"] == pytest.approx(want, abs=2e-4)
    assert got["best_ids"] == [int(lp[t - 1].argmax())
                               for t in range(n_prompt, len(ids))]


def test_alibi_slopes_by_hand():
    import reference
    assert reference.alibi_slopes(4) == pytest.approx([0.25, 0.0625,
                                                       0.015625, 0.00390625])
    assert len(reference.alibi_slopes(6)) == 6
