"""A period model (family ``laguna``, PR 46) in the ENGINE: served through
the mixed dispatch over a pool a kind of block, window pages given back
while a request runs, prefix sharing off; and what is built for one kind
of block refusing in a sentence.
``tests/test_laguna.py`` holds the model, the kernels and the share."""
import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_demo_tpu.models.base import (BlockKind, KVCache,
                                                        ModelConfig,
                                                        StageSpec,
                                                        slice_stage,
                                                        split_layer_ranges)
from distributed_inference_demo_tpu.models.decoder import (_moe_routed,
                                                           init_full_params,
                                                           stage_forward)
from distributed_inference_demo_tpu.models.registry import (MODEL_REGISTRY,
                                                            get_model_config)
from distributed_inference_demo_tpu.ops import rope
from distributed_inference_demo_tpu.ops.paged_attention import (
    paged_flash_attention, paged_gather_attention, paged_prefill_attention,
    sub_chunk, window_tables)
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.ops.stacked import LayerOf
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)

ROOT = Path(__file__).resolve().parent.parent
for extra in ("benchmark", "tools"):
    if str(ROOT / extra) not in sys.path:
        sys.path.insert(0, str(ROOT / extra))

import families  # noqa: E402  (benchmark/)
import model_parity  # noqa: E402  (tools/)

CFG = get_model_config("laguna-test")
MC = dataclasses.asdict(CFG)
SPEC = StageSpec(0, 1, 0, CFG.num_layers)
GREEDY = SamplingParams(temperature=0.0)
MIXED = dict(prefill_chunk=8, decode_block=4, mixed_token_budget=24)
FAM = families.load("laguna")


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_seq", 200)
    kw.setdefault("max_batch", 3)
    kw.setdefault("kv_block_tokens", 4)
    return ContinuousBatchingEngine(cfg, params, sampling=GREEDY, **kw)


# ------------------------------------------------------------ the engine

def _dense_greedy(params, prompt, out):
    """The dense path's choice after ``prompt`` and after each token of
    ``out`` but the last, in ONE forward over ``prompt + out[:-1]``: it is
    ``out`` exactly where greedy decoding through the dense path, a call
    a token, gives ``out`` (by induction over its tokens)."""
    ids = list(prompt) + list(out[:-1])
    cache = KVCache.create(CFG, CFG.num_layers, 1, len(ids) + 8)
    logits, _ = stage_forward(params, CFG, SPEC, jnp.asarray([ids]), cache,
                              jnp.arange(len(ids))[None])
    return [int(t) for t in logits[0, len(prompt) - 1:].argmax(-1)]


def test_engine_serves_the_dense_path_s_tokens_and_returns_its_pages(params):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n) for n in (37, 70, 9, 50)]
    with _engine(params, **MIXED) as eng:
        reqs = [eng.submit(p, 24) for p in prompts]
        outs = [list(r.wait(300)) for r in reqs]
        st = eng.stats()
        assert eng._wmgr.used_blocks == 0 and eng._window_reserved == 0
        assert eng.kv_cache.used_blocks == 0
    for p, out in zip(prompts, outs):
        assert len(out) == 24 and out == _dense_greedy(params, list(p), out)
    kinds = st["kvcache"]["kinds"]
    assert kinds["window"]["pages_returned"] > 0
    assert kinds["full"]["blocks_total"] == st["kvcache"]["blocks_total"]
    assert st["kvcache"]["bytes_per_token"] == 3 * 2 * 2 * 16 * 4
    assert kinds["window"]["bytes_per_token"] == 6 * 2 * 2 * 16 * 4
    assert set(st["attention_paths"]) == {"mixed_step/full",
                                          "mixed_step/window"}
    moe = st["moe"]
    assert (moe["experts"], moe["experts_routed"]) == (4, 16)
    assert len(moe["expert_rows"]) == 4
    assert moe["rows"] + moe["rows_absent"] == moe["valid_rows"]
    assert 0.1 < moe["rows"] / moe["valid_rows"] < 0.45
    fields = st["dispatch_trace"]["fields"]
    assert {"kv_window_tokens", "prefill_window_pairs"} <= set(fields)


def test_the_record_s_pages_walked_are_the_kernels_loop_bounds(params):
    """``prefill_pages_walked`` (the full kind's) and
    ``prefill_window_pages_walked`` of every packed slab are the sums of
    the loop bounds the prefill kernel computes from the same starts
    (``_walk_bounds``: chunk 8, pages of 4, a table of 50, window 8), a
    dispatch without a slab walks none, and /stats holds the totals
    beside the grid's tiles x width.  Host arithmetic: the plan is read
    as packed, before anything of it reaches the device."""
    from distributed_inference_demo_tpu.ops.paged_attention import (
        _walk_bounds)
    plans = []
    with _engine(params, **MIXED) as eng:
        pack = eng._pack_mixed

        def spy(*a, **kw):
            plans.append(pack(*a, **kw))
            return plans[-1]

        eng._pack_mixed = spy
        assert eng._prefill_tiles == ((8, 0), (8, 8))
        rng = np.random.default_rng(5)
        for r in [eng.submit(rng.integers(0, 256, size=n), 6)
                  for n in (61, 30)]:
            r.wait(300)
        deadline = time.monotonic() + 10
        while (eng.stats()["dispatch_trace"]["seq"]
               != eng.stats()["mixed"]["dispatches"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        dt = eng.stats()["dispatch_trace"]
    assert any(p.packed for p in plans) and not all(p.packed for p in plans)
    for plan in plans:
        starts = np.asarray([int(plan.seg[2][r]) for r, *_ in plan.packed],
                            np.int32)
        want = []
        for window in (0, 8):
            first, end = _walk_bounds(starts, 8, 4, 50, window)
            want.append(int(np.sum(np.asarray(end) - np.asarray(first))))
        assert plan.prefill_pages_walked == want
        # a tile of 8 under a window of 8 meets at most 5 pages of 4
        assert want[1] <= 5 * len(starts)
    recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
    assert dt["prefill_pages_walked"] == sum(
        r["prefill_pages_walked"] for r in recs) > 0
    assert dt["prefill_pages_grid"] == 50 * sum(r["segments"] for r in recs)
    for r in recs:
        assert (r["prefill_pages_walked"] > 0) == (r["segments"] > 0)
        assert (r["prefill_window_pages_walked"] > 0) == (r["segments"] > 0)
        assert r["prefill_window_pages_walked"] <= r["prefill_pages_walked"]


def test_a_request_of_twenty_windows_holds_its_window_and_a_dispatch(params):
    """160 tokens under a window of 8, pages of 4: the window kind never
    holds more than its quota (the window's pages, two dispatches' tokens
    and one), where no window would hold 40; and the pool's free count is
    back where it started."""
    prompt = np.random.default_rng(1).integers(0, 256, size=100)
    with _engine(params, **MIXED) as eng:
        start = eng._wmgr.free_blocks
        quota = eng._window_quota
        assert quota == -(-(8 + 2 * 24) // 4) + 1
        out = eng.submit(prompt, 60).wait(300)
        assert len(out) == 60
        ws = dict(eng.window_stats)
        assert eng._wmgr.free_blocks == start
    assert 3 <= ws["pages_held_peak"] <= quota
    assert ws["pages_unwindowed_peak"] >= 39
    assert ws["pages_returned"] >= 35


def test_prefix_sharing_is_off_under_a_window(params):
    """Two requests with one prompt: no hit, no store, the same tokens."""
    prompt = np.random.default_rng(2).integers(0, 256, size=48)
    with _engine(params, **MIXED) as eng:
        a = list(eng.submit(prompt, 8).wait(300))
        b = list(eng.submit(prompt, 8).wait(300))
        kv = eng.stats()["kvcache"]
    assert a == b
    assert kv["hits"] == 0 and kv["stores"] == 0 and kv["tree_blocks"] == 0


# --------------------------------------------- what refuses, in a sentence

def _draft(params):
    llama = get_model_config("llama-test")
    ContinuousBatchingEngine(
        llama, init_full_params(jax.random.PRNGKey(0), llama), max_seq=64,
        max_batch=2, draft_cfg=CFG, draft_params=params, num_draft=2)


def _tp(params):
    from distributed_inference_demo_tpu.parallel.mesh import (MeshConfig,
                                                              make_mesh)
    from distributed_inference_demo_tpu.parallel.tensor import validate_tp
    validate_tp(CFG, make_mesh(MeshConfig(tp=2)))


def _export(params):
    with _engine(params, **MIXED) as eng:
        eng.export_request("nobody")


def _import(params):
    with _engine(params, **MIXED) as eng:
        eng.import_request({"tokens": [1], "length": 3})


def _premigrated(params):
    with _engine(params, **MIXED) as eng:
        z = np.zeros((1, 4, 2, 4, 16), np.float32)
        eng.submit_premigrated(np.arange(1, 12, dtype=np.int32), 2, z, z)


def _ring(params):
    from distributed_inference_demo_tpu.parallel.sequence import (
        _make_ring_cores)
    _make_ring_cores(CFG, SPEC, 16, GREEDY, None)


def _ulysses(params):
    from distributed_inference_demo_tpu.parallel.ulysses import (
        _make_ulysses_cores)
    _make_ulysses_cores(CFG, 32, 2, GREEDY, None)


def _one_kind_hook(params):
    hook = lambda *a: None
    stage_forward(params, CFG, SPEC, jnp.asarray([[1, 2]]),
                  KVCache.create(CFG, CFG.num_layers, 1, 8),
                  jnp.arange(2)[None], attn_impl=hook)


def _load(params):
    from distributed_inference_demo_tpu.models.loader import (
        params_from_state_dict)
    params_from_state_dict({}, CFG)


KINDS = "does not support a model of more than one kind of block"
REFUSALS = {
    "int8 pages": (ValueError, "a page pool of int8 pages " + KINDS,
                   lambda p: _engine(p, kv_dtype="int8", **MIXED)),
    "int4 pages": (ValueError, "a page pool of int4 pages " + KINDS,
                   lambda p: _engine(p, kv_dtype="int4", **MIXED)),
    "host tier": (ValueError, "the host tier of the KV cache " + KINDS,
                  lambda p: _engine(p, kv_host_tier_bytes=1 << 20, **MIXED)),
    "export_request": (ValueError, r"export_request \(migration\) " + KINDS,
                       _export),
    "import_request": (ValueError, r"import_request \(migration\) " + KINDS,
                       _import),
    "premigrated prefill": (ValueError, "a premigrated prefill .* " + KINDS,
                            _premigrated),
    "draft": (ValueError, "the draft side of speculation " + KINDS, _draft),
    "prompt lookup": (ValueError, "speculation .* " + KINDS,
                      lambda p: _engine(p, prompt_lookup=True, **MIXED)),
    "serialized interleave": (ValueError, "the serialized interleave .* "
                              + KINDS, lambda p: _engine(p, prefill_chunk=8)),
    "manual TP": (ValueError, r"tensor parallelism \(--tp\) " + KINDS, _tp),
    "pipeline stages": (ValueError, "a pipeline of stages " + KINDS,
                        lambda p: slice_stage(p, CFG,
                                              split_layer_ranges(2, 2)[0])),
    "ring sequence parallelism": (ValueError,
                                  "ring sequence parallelism " + KINDS,
                                  _ring),
    "ulysses": (ValueError, "Ulysses sequence parallelism " + KINDS,
                _ulysses),
    "a hook for one kind": (ValueError, "needs an attention hook that knows "
                            "its pools", _one_kind_hook),
    "a checkpoint": (NotImplementedError, "no state-dict mapper for family "
                     "'laguna'", _load),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_built_for_one_kind_refuses_in_a_sentence(what, params):
    error, sentence, build = REFUSALS[what]
    with pytest.raises(error, match=sentence):
        build(params)


def test_serve_chain_refuses_a_period_model_in_a_sentence(capsys):
    from distributed_inference_demo_tpu import cli
    assert cli.main(["serve", "--model", "laguna-test", "--chain",
                     "w1@127.0.0.1:1", "--device-id", "h"]) == 1
    assert KINDS in capsys.readouterr().err


def test_a_share_of_the_experts_and_tensor_parallelism_do_not_compose():
    cfg = CFG.of_kind(CFG.period[0])
    from distributed_inference_demo_tpu.models.decoder import (
        init_layer_params)
    lp = jax.tree.map(lambda a: a[0], init_layer_params(
        jax.random.PRNGKey(5), cfg, 1))
    with pytest.raises(ValueError, match="both cut the expert stacks"):
        _moe_routed(cfg, lp, jnp.zeros((1, 2, 64)), tp_axis="tp")
