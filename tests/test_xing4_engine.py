"""A model with four residual streams a token (family ``xing4_0``, PR 60)
in the ENGINE: chunked prefill through the mixed slab and decode through
the paged latent pool, two requests of unlike length and one that joins
mid-way, every emitted token's log-probability against the reference's one
forward; the residual path's counters and the probe that says the Sinkhorn
iterations ran.  CPU, toy widths (``xing-bench-test``);
``tests/test_xing4.py`` holds the model."""
import dataclasses
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

from distributed_inference_demo_tpu.models.decoder import (  # noqa: E402
    init_full_params)
from distributed_inference_demo_tpu.models.registry import (  # noqa: E402
    get_model_config)
from distributed_inference_demo_tpu.ops import (        # noqa: E402
    hyper_connection as hc)
from distributed_inference_demo_tpu.ops.sampling import (  # noqa: E402
    SamplingParams)
from distributed_inference_demo_tpu.runtime.batching import (  # noqa: E402
    ContinuousBatchingEngine)

CFG = get_model_config("xing-bench-test")
FIELDS = dataclasses.asdict(CFG)
GREEDY = SamplingParams(temperature=0.0)
CHUNK, SLOTS = 8, 3


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_seq", 128)
    kw.setdefault("max_batch", SLOTS)
    kw.setdefault("kv_block_tokens", 8)
    kw.setdefault("kv_cache_blocks", 48)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("decode_block", 4)
    kw.setdefault("mixed_token_budget", 16)
    return ContinuousBatchingEngine(cfg, params, sampling=GREEDY, **kw)


@pytest.fixture(scope="module")
def served(params):
    """One engine as configured for the cases that serve through it (its
    three variants and the probe are most of such a case), and what
    ``/stats`` said before its first request."""
    with _engine(params) as eng:
        yield eng, eng.stats()


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


def _reference(params, prompt, tokens, fields=FIELDS):
    import reference
    ids = [int(t) for t in prompt] + [int(t) for t in tokens]
    # (a sound reading of the served maps, which the family's check also
    # holds: ``test_the_residual_path_s_counters`` reads the engine's own)
    return reference.emitted_logprobs(params, fields, ids, len(prompt),
                                      {"hc_sinkhorn_residual": 0.0})


def _settled(eng):
    for _ in range(200):
        st = eng.stats()
        if st["dispatch_trace"]["seq"] == st["mixed"]["dispatches"]:
            return st
        time.sleep(0.02)
    raise AssertionError("the last dispatch never committed")


def test_served_logprobs_equal_the_float32_reference(params, served):
    """A long prompt (six chunks) and a short one start together; a third
    joins while they decode.  Each emitted token's log-probability is the
    reference's, and the tokens are the ones it would have chosen."""
    prompts = [_prompt(45, 0), _prompt(7, 1), _prompt(19, 2)]
    eng, _ = served
    first = [eng.submit(p, 12) for p in prompts[:2]]
    while len(first[1].tokens) < 3:         # the short one decodes
        time.sleep(0.01)
    late = eng.submit(prompts[2], 9)
    reqs = first + [late]
    outs = [np.asarray(r.wait(timeout=300)) for r in reqs]
    lps = [list(r.lps) for r in reqs]
    st = _settled(eng)
    for p, o, lp in zip(prompts, outs, lps):
        ref = _reference(params, p, o)
        assert lp == pytest.approx(ref["logprobs"], abs=2e-4)
        assert [int(t) for t in o] == ref["best_ids"]
    assert st["attention_paths"]["mixed_step"].keys() == {"chunk=1",
                                                          f"chunk={CHUNK}"}
    # the streams' two ops say which path they compiled onto, beside the
    # attention's: off the chip the plain one, with the reason
    assert st["attention_paths"]["mixed_step/hc"] == {
        "chunk=1": "xla_hc: platform cpu",
        f"chunk={CHUNK}": "xla_hc: platform cpu"}
    assert "mixed_step/hc" not in st["pool_addressing"]


def test_one_sinkhorn_step_is_seen_through_the_engine(params):
    """The control of the comparison above, through the same path: a
    served model that stopped after one Sinkhorn step reads far from the
    reference of the model as configured."""
    p = _prompt(21, 3)
    with _engine(params, CFG.replace(hc_sinkhorn_iters=1)) as eng:
        r = eng.submit(p, 8)
        out = np.asarray(r.wait(timeout=300))
        lps = list(r.lps)
        st = eng.stats()
    ref = _reference(params, p, out)
    assert max(abs(a - b) for a, b in zip(lps, ref["logprobs"])) > 2e-3
    assert st["hc"]["sinkhorn_residual_max"] > 1e-2
    assert st["hc"]["sinkhorn_iters"] == 1


def test_the_residual_path_s_counters(served):
    """``/stats.hc``: the rows both kernels computed (a slab's rows and
    every slot of every decode step, by the dispatch records' own column)
    and the start-up probe's reading, which a reply with
    log-probabilities repeats; bridged onto the catalog's series."""
    from distributed_inference_demo_tpu.telemetry import catalog
    eng, fresh = served
    before = fresh["hc"]
    assert before["rows"] == 0
    # the start-up probe has run: 20 iterations reach float32's floor
    assert 0 < before["sinkhorn_residual_max"] < 1e-5
    was = _settled(eng)         # (whatever another case served before)
    out = eng.generate(_prompt(30, 4), 6, logprobs=True)
    assert out.logprobs.shape == (1, 6)
    assert out.generation == [
        {"hc_sinkhorn_residual": before["sinkhorn_residual_max"]}]
    assert eng.generate(_prompt(9, 5), 2).generation is None
    st = _settled(eng)
    hcs, trace = st["hc"], st["dispatch_trace"]
    col = trace["fields"].index("hc_rows")
    seq, seg, steps, rode = (trace["fields"].index(k) for k in (
        "seq", "segments", "steps", "slab_carried_step"))
    mine = [r for r in trace["recent"] if r[seq] > was["dispatch_trace"]["seq"]]
    # a slab's pass holds the slots' rows too (the step it carries, PR 61),
    # padded to the kernels' whole tiles; a step that rode it is no call
    # of its own
    assert [r[col] for r in mine] == [
        r[steps] * SLOTS + (hc.whole_tiles(r[seg] * CHUNK + SLOTS)
                            - (r[rode] > 0) * SLOTS if r[seg] else 0)
        for r in mine]
    assert hcs["rows"] - was["hc"]["rows"] == sum(r[col] for r in mine) > 0
    assert (hcs["streams"], hcs["sinkhorn_iters"]) == (4, 20)
    # read once: no request moves it
    assert hcs["sinkhorn_residual_max"] == before["sinkhorn_residual_max"]
    catalog.update_batching_series(st)
    read = lambda m: next(iter(m.samples()))[2]
    assert read(catalog.BATCH_HC_ROWS) == hcs["rows"]
    assert read(catalog.BATCH_HC_SINKHORN_RESIDUAL) == pytest.approx(
        hcs["sinkhorn_residual_max"])


def test_a_one_stream_model_has_no_such_section():
    cfg = get_model_config("kanana-test")
    with _engine(init_full_params(jax.random.PRNGKey(0), cfg), cfg) as eng:
        st = eng.stats()
    assert "hc" not in st
    assert "hc_rows" not in st["dispatch_trace"]["fields"]


def test_serve_refuses_tp_in_a_sentence(capsys):
    """``serve --model xing-bench-test --tp 2`` ends in one sentence, the
    streams' (the engine asks before anything is sharded)."""
    from distributed_inference_demo_tpu import cli
    rc = cli.main(["serve", "--model", "xing-bench-test", "--batch-slots",
                   "2", "--prefill-chunk", "8", "--mixed-token-budget", "16",
                   "--max-seq", "64", "--tp", "2", "--http-port", "0"])
    assert rc == 1
    assert ("tensor parallelism (--tp) does not support a model with 4 "
            "residual streams") in capsys.readouterr().err
