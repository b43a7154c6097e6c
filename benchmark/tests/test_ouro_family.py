"""``families/ouro.py``: the looped decoder's reference against the
program at toy size (float32 and int8 leaves), its T passes against a
hand-unrolled stack, its shape arithmetic pinned by hand at the published
sizes, and the three ``loop_*`` readers on a synthetic run context."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
CONF = json.loads((BENCH / "configs" / "ouro-2.6b-bf16.json").read_text())
MC = CONF["model_config"]
TOY = CONF["rehearsal"]["model_config"]
FAM = families.load("ouro")
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632      # one layer, one pass
CELL = "ouro-2.6b-bf16.reason-sat"


def _program(quant):
    import jax
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    cfg = ModelConfig(**TOY, quantization=quant)
    params = init_full_params(jax.random.PRNGKey(5), cfg,
                              quantize=quant != "none")
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 8))
    for name in ("attn_norm_w", "attn_post_norm_w", "mlp_norm_w",
                 "mlp_post_norm_w"):         # norms that are not all ones
        params.layers[name] = 1.0 + 0.3 * jax.random.normal(
            next(keys), params.layers[name].shape)
    params.final_norm["w"] = 1.0 + 0.3 * jax.random.normal(
        next(keys), params.final_norm["w"].shape)
    return cfg, params


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_family_agrees_with_the_program_at_toy_size(quant):
    """Through a cache of the program's own size (``kv_planes`` planes):
    prefill, then the rest token by token, so every pass reads back its
    own planes."""
    import jax
    import jax.numpy as jnp

    import reference
    from distributed_inference_demo_tpu.models.base import KVCache, StageSpec
    from distributed_inference_demo_tpu.models.decoder import stage_forward
    cfg, params = _program(quant)
    spec = StageSpec(0, 1, 0, cfg.num_layers)
    ids = [(7 * i + 3) % cfg.vocab_size for i in range(36)]
    n_prompt = 24
    cache = KVCache.create(cfg, cfg.num_layers, 1, 64)
    assert cache.keys.shape[0] == cfg.num_layers * TOY["ut_steps"] == 12
    logits, cache = stage_forward(
        params, cfg, spec, jnp.asarray([ids[:n_prompt]], jnp.int32), cache,
        jnp.arange(n_prompt, dtype=jnp.int32)[None])
    rows = [logits[0, -1]]
    for t in range(n_prompt, len(ids) - 1):
        step, cache = stage_forward(
            params, cfg, spec, jnp.asarray([[ids[t]]], jnp.int32), cache,
            jnp.asarray([[t]], jnp.int32))
        rows.append(step[0, 0])
    lp = jax.nn.log_softmax(jnp.stack(rows).astype(jnp.float32), -1)
    want = [float(lp[i, ids[n_prompt + i]]) for i in range(len(rows))]
    got = reference.emitted_logprobs(params, TOY, ids, n_prompt)
    assert got["logprobs"] == pytest.approx(want, abs=2e-4)
    assert got["best_ids"] == [int(r.argmax()) for r in lp]


def test_embed_holds_every_pass_but_the_last_against_a_hand_unrolled_stack():
    """``equations(mc).embed`` = embedding, then T - 1 times (the L
    layers, then the final norm); ``reference.py``'s loop is the last
    pass.  Unrolled here layer by layer with the family's own ``layer``."""
    import jax
    import jax.numpy as jnp

    import reference
    _, params = _program("none")
    embed, layer, final_norm = FAM.equations(TOY)
    ids = jnp.asarray([(3 * i + 1) % 256 for i in range(12)], jnp.int32)

    def leaves(i):
        return {k: reference._f32(v[i]) for k, v in params.layers.items()}

    with jax.default_matmul_precision("highest"):
        x = params.embed["tokens"][ids].astype(jnp.float32)
        by_pass = []
        for _ in range(TOY["ut_steps"]):
            for i in range(TOY["num_layers"]):
                x = layer(leaves(i), x)
            by_pass.append(x)
            x = final_norm(params, x)
        got = embed(params, ids)
        # what enters the last pass: the normed output of the one before
        want = final_norm(params, by_pass[-2])
    assert jnp.allclose(got, want, atol=1e-5)
    assert not jnp.allclose(got, final_norm(params, by_pass[0]), atol=1e-2)
    # with one pass the family is a plain stack: embed is the embedding
    one = dict(TOY, ut_steps=1)
    assert jnp.array_equal(FAM.equations(one)[0](params, ids),
                           params.embed["tokens"][ids])


def test_bytes_by_hand_at_the_published_sizes():
    assert LAYER == 51_380_224
    # a decode step or a slab pass of the program reads a layer 4 times
    assert B.layer_matrix_elements(MC) == 4 * LAYER
    assert B.layer_scale_elements(MC) == 4 * (4 * 2048 + 2 * 5632 + 2048)
    assert B.kv_bytes_per_token(MC) == 192 * 2 * 16 * 128 * 2 == 1_572_864
    assert B.kv_bytes_per_token(MC) == CONF["pool"]["bytes_per_token"]
    head = 49152 * 2048 * 2
    assert B.weight_bytes_per_pass(MC) == 48 * 4 * LAYER * 2 + head
    assert B.weight_bytes_per_pass(MC) == pytest.approx(19.93e9, rel=0.001)
    assert FAM.decode_step_bytes(MC, 0) == B.weight_bytes_per_pass(MC)
    assert FAM.decode_step_bytes(MC, 3200) == \
        B.weight_bytes_per_pass(MC) + 3200 * 1_572_864
    # all of it: 2.668 B parameters = 4.97 GiB of bf16
    params = 48 * LAYER + 2 * 49152 * 2048
    assert params == 2_667_577_344
    assert params * 2 / 2 ** 30 == pytest.approx(4.97, abs=0.005)
    # the pool the cell serves holds its six longest requests
    pool = CONF["pool"]
    assert pool["blocks"] >= 6 * -(-(512 + 384) // pool["block_tokens"])


def test_the_manifest_holds_the_new_entries_and_the_cell_s_traffic():
    m = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b-bf16", "reason-sat", 1)
    entry = next(c for c in m["configs"] if c["name"] == "ouro-2.6b-bf16")
    assert entry["reduced"] == [] == CONF["reduced"]
    for name in ("loop_pass_ms_p50", "loop_pass_hbm_pct",
                 "loop_kv_read_share_pct"):
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tpot_p50_ms"
    mix = json.loads((BENCH / "traffic" / "reason-sat.json").read_text())
    load = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    assert load == {"clients": 6}
    assert (mix["generator"], mix["loop"]) == ("closed_loop", "closed")
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 512}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 192,
                                    "max": 384}
    assert (mix["stagger_s"], mix["ramp_s"], mix["drain_s"]) == (0.05, 5, 30)
    # the source's own keys, whole
    assert (CONF["total_ut_steps"], CONF["early_exit_threshold"],
            CONF["num_hidden_layers"], len(CONF["layer_types"])) == (
        4, 1, 48, 48)


def _ctx(loop_open, loop_close, pairs=(), token_steps=(0, 0)):
    def stats(loop, ts):
        out = {"dispatch_trace": {"kv_token_steps": ts}}
        if loop:
            out["loop"] = loop
        return out
    return {"config": CONF, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_open": stats(loop_open, token_steps[0]),
            "stats_close": stats(loop_close, token_steps[1]),
            "marks": {}, "trace": {},
            "_dispatch_join": {"pairs": list(pairs), "share": 1.0}}


def _loop(decode_passes):
    return {"ut_steps": 4, "kv_planes": 192, "kv_bytes_per_token": 1_572_864,
            "dispatches": 1, "slab_passes": 0,
            "decode_passes": decode_passes}


def test_loop_readers_on_a_synthetic_run():
    from layer_metrics import (loop_kv_read_share_pct, loop_pass_hbm_pct,
                               loop_pass_ms_p50)
    step_bytes = FAM.decode_step_bytes(MC, 3000)
    at_peak_ns = 4 * step_bytes / 819e9 * 1e9     # four steps at HBM's peak
    alone = {"segments": 0, "steps": 4, "kv_tokens": 3000, "ut_passes": 16}
    slab = {"segments": 1, "steps": 4, "kv_tokens": 3000, "ut_passes": 20}
    pairs = [(i, 2 * at_peak_ns, alone) for i in range(6)]
    pairs.append((9, 10 * at_peak_ns, slab))      # not a decode-only one
    ctx = _ctx(_loop(0), _loop(160), pairs, token_steps=(0, 40 * 3000))
    assert loop_pass_hbm_pct.read(ctx) == pytest.approx(50.0)
    assert loop_pass_ms_p50.read(ctx) == pytest.approx(
        2 * at_peak_ns / 16 / 1e6)
    # 40 steps: KV 40 x 3000 tokens x 1.5 MiB against 40 x the weights
    kv, w = 3000 * 1_572_864, B.weight_bytes_per_pass(MC)
    assert loop_kv_read_share_pct.read(ctx) == pytest.approx(
        100 * kv / (kv + w))
    assert 18 < loop_kv_read_share_pct.read(ctx) < 20
    # a one-pass program (no loop section, no ut_passes): nothing to read
    old = _ctx(None, None, [(i, 1e6, {"segments": 0, "steps": 4,
                                      "kv_tokens": 9}) for i in range(6)])
    assert loop_pass_ms_p50.read(old) is None
    assert loop_pass_hbm_pct.read(old) is None
    assert loop_kv_read_share_pct.read(old) is None
    # fewer than five decode-only executions: no median
    assert loop_pass_ms_p50.read(_ctx(_loop(0), _loop(16), pairs[:3])) is None


def test_rehearsal_walks_the_new_cell():
    """``run.py --rehearse-cpu`` through the gateway with the toy looped
    model: exit 3, every part of ``correct``, and the loop's counter
    metric on the line a chip run would print."""
    import os
    import subprocess
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seconds", "4", "--trace", "1", "--rehearse-cpu", "--seed",
         "2147483999", "--out", str(BENCH / "out" / "test_rehearsal")],
        cwd=BENCH.parent, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("[rehearsal] the result line"))
    assert '"correct": true' in line and '"failed": 0' in line
    assert "loop_kv_read_share_pct" in line
    checks = next(ln for ln in proc.stdout.splitlines()
                  if ln.startswith("[checks]"))
    assert "false" not in checks
