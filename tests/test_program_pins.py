"""Every toy family's ``mixed_step`` is the recorded program.

A change that adds a family, or a branch only some families take, leaves
every other family's program as it was: the new code is Python their
traces never take.  That is held here, once, for every family: the
pre-optimisation text of both variants of ``mixed_step`` is the one whose
hash ``tests/data/mixed_step_hlo.json`` keeps.  A new family adds its two
lines to the record; a change that is meant to alter a program re-pins it
there (the failing case shows the new hash) and says so.
"""

import hashlib
import json
from pathlib import Path
from unittest import mock

import pytest

import jax

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from test_mixed_batching import abstract_mixed_call

RECORD = json.loads(
    (Path(__file__).parent / "data" / "mixed_step_hlo.json").read_text())


@pytest.mark.parametrize("key", list(RECORD["sha256"]))
def test_mixed_step_lowers_to_the_recorded_program(key):
    """Character for character, by the hash.  The text is this JAX's;
    under another version the kept hashes say nothing."""
    if jax.__version__ != RECORD["jax"]:
        pytest.skip(f"hashes were made under jax {RECORD['jax']}")
    model, variant = key.split(".")
    cfg = get_model_config(model)
    # (an engine that launches nothing before it is ready: only the text
    # of the program is read, and warming its variants is most of a case)
    with mock.patch.object(ContinuousBatchingEngine, "_warm_mixed_variants",
                           lambda self: None), ContinuousBatchingEngine(
            cfg, init_full_params(jax.random.PRNGKey(0), cfg), max_seq=96,
            max_batch=4, sampling=SamplingParams(temperature=0.0),
            kv_block_tokens=8, prefill_chunk=8, decode_block=4,
            mixed_token_budget=24) as eng:
        text = eng._mixed_step.inner.lower(
            *abstract_mixed_call(eng, variant == "slab")).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == RECORD["sha256"][key]
