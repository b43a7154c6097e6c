"""``families/xing4_0.py``: the equations against the program at toy size,
every block exactly once, the shape arithmetic pinned by hand, the
reference check's hold on the served maps' reading, the four readers of
the residual path's metrics on a synthetic run, and the rehearsal of the
new cell."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
CELL = "xing4.0-29b-a4b-bf16.longdoc-sat"
CONF = json.loads((BENCH / "configs" / "xing4.0-29b-a4b-bf16.json")
                  .read_text())
MC = CONF["model_config"]
FAM = families.load("xing4_0")
M = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
# a reply's reading of the served maps, sound: what the family's check
# holds beside the log-probabilities (``replay``)
SOUND = {"hc_sinkhorn_residual": 1.2e-6}


# ------------------------------------------------------- shape arithmetic

def test_layer_elements_by_hand():
    # q_a 3584x768, q_b 768x6144, kv_a 3584x576, kv_b 512x8192, o 4096x3584
    assert FAM.attention_elements(MC) == (2_752_512 + 4_718_592 + 2_064_384
                                          + 4_194_304 + 14_680_064) \
        == 28_409_856
    # two maps of 14336 x 24 a block
    assert FAM.hc_elements(MC) == 2 * 14336 * 24 == 688_128
    expert = 3 * 3584 * 1024
    assert expert == 11_010_048
    # attention + maps + 1 shared + router 3584x64 + 64 routed
    assert FAM.expert_layer_matrix_elements(MC) == (
        28_409_856 + 688_128 + 11_010_048 + 229_376 + 704_643_072) \
        == 744_980_480
    assert FAM.lead_layer_matrix_elements(MC) == (
        28_409_856 + 688_128 + 3 * 3584 * 9216) == 128_188_416
    assert MC["num_layers"] * B.layer_matrix_elements(MC) == pytest.approx(
        5 * 744_980_480 + 2 * 128_188_416)


def test_weight_and_pool_bytes_by_hand():
    # 2 leading + 5 expert blocks + embedding + untied head
    params = 5 * 744_980_480 + 2 * 128_188_416 + 2 * 131072 * 3584
    assert params == 4_920_803_328
    assert params * 2 / 1e9 == pytest.approx(9.84, abs=0.005)
    assert params * 2 / 2 ** 30 == pytest.approx(9.17, abs=0.005)
    # one latent row a token a block: 576 values held in 640 lanes
    assert FAM.page_width(MC) == 640
    assert B.kv_bytes_per_token(MC) == 7 * 640 * 2 == 8_960 \
        == CONF["pool"]["bytes_per_token"]
    assert (CONF["pool"]["blocks"] * CONF["pool"]["block_tokens"] * 8960
            / 2 ** 30) == pytest.approx(2.19, abs=0.005)


def test_residual_path_bytes_and_ops_by_hand():
    # a sublayer reads 4 H and writes H, then reads 5 H and writes 4 H:
    # what each call moves, the write call's re-read of the streams too
    H = 3584
    assert FAM.hc_pre_kernel_bytes(MC, 1) == 7 * 2 * 5 * H * 2 == 501_760
    assert FAM.hc_post_kernel_bytes(MC, 1) == 7 * 2 * 9 * H * 2 == 903_168
    assert FAM.hc_stream_bytes_per_token(MC) == 7 * 2 * 14 * H * 2 \
        == 501_760 + 903_168 == 1_404_928
    assert FAM.hc_stream_bytes_per_token(MC) // 7 == 200_704   # a block
    assert FAM.hc_calls_per_row(MC) == 14
    # the map's product, the mean square and the weighted sum
    assert FAM.hc_pre_kernel_ops(MC, 1) == 14 * 2 * 4 * H * 26
    assert FAM.hc_post_kernel_ops(MC, 1) == 14 * 2 * 4 * 5 * H
    # bound by HBM, both, where nothing stays on the chip: a slab of 512
    # rows is 0.88 ms
    rows = 512
    assert (FAM.hc_pre_kernel_bytes(MC, rows) / 819e9
            > FAM.hc_pre_kernel_ops(MC, rows) / 197e12)
    assert (FAM.hc_post_kernel_bytes(MC, rows) / 819e9
            > FAM.hc_post_kernel_ops(MC, rows) / 197e12)
    assert rows * 1_404_928 / 819e9 == pytest.approx(0.878e-3, rel=0.01)
    # the shared shape functions are deepseek_v3's and olmoe's
    pair = 2 * 32 * (576 + 512)
    assert FAM.mla_decode_kernel_ops(MC, 68_000) == 7 * 68_000 * pair
    assert FAM.moe_kernel_ops(MC, 2048) == 2 * 2048 * 11_010_048


def test_the_configuration_is_the_published_one_but_for_its_depth():
    cat = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
           "hidden_size": 3584, "intermediate_size": 9216,
           "kv_lora_rank": 512, "max_position_embeddings": 262144,
           "moe_intermediate_size": 1024, "n_routed_experts": 64,
           "n_shared_experts": 1, "num_attention_heads": 32,
           "num_experts_per_tok": 4, "num_nextn_predict_layers": 1,
           "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
           "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
           "q_lora_rank": 768, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
           "rope_theta": 10000, "routed_scaling_factor": 2,
           "v_head_dim": 128, "vocab_size": 131072}
    assert {k: CONF[k] for k in cat} == cat
    assert CONF["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert CONF["reduced"] == ["num_hidden_layers"]
    assert (CONF["num_hidden_layers"], MC["num_layers"],
            MC["lead_dense_layers"]) == (5, 5, 2)
    # what the program is given says the same as the published keys
    assert (MC["hc_streams"], MC["hc_sinkhorn_iters"], MC["hc_eps"],
            MC["hc_res_clamp"], MC["q_lora_rank"], MC["num_experts"],
            MC["experts_per_token"], MC["intermediate_size"],
            MC["lead_intermediate_size"], MC["routed_scaling_factor"]) == (
        4, 20, 1e-6, 30.0, 768, 64, 4, 1024, 9216, 2.0)
    assert MC["yarn"] == [64.0, 4096.0, 32.0, 1.0, 1.0]
    assert MC["attn_scale"] == pytest.approx(2.00474, abs=1e-5)
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.registry import (
        get_model_config)
    assert ModelConfig(**MC) == get_model_config(CONF["serve_model"])
    assert ModelConfig(**CONF["rehearsal"]["model_config"]) == \
        get_model_config("xing-bench-test")


# ------------------------------------------- equations against the program

TOY = CONF["rehearsal"]["model_config"]


def _toy(leaf_dtype):
    """``(cfg, params)``: the rehearsal model on seeded weights whose
    matrices are stored as ``leaf_dtype``, norms moved off one."""
    import jax
    import jax.numpy as jnp
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    cfg = ModelConfig(**TOY)
    p = init_full_params(jax.random.PRNGKey(4), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 16))
    for tree in (p.layers, p.lead):
        for name in ("attn_norm_w", "mlp_norm_w", "kv_norm_w", "q_a_norm_w"):
            tree[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                       tree[name].shape)
    return cfg, jax.tree.map(
        lambda a: a if a.dtype == jnp.int32 else a.astype(leaf_dtype), p)


@pytest.mark.parametrize("leaves", ["float32", "bfloat16"])
def test_reference_equals_the_program_at_toy_size(leaves):
    """The family's equations (``[T, n, H]`` streams, ``[T, n, n]`` maps,
    decompressed keys and values, every expert for every row) through
    ``reference.emitted_logprobs`` against the program's ``stage_forward``
    (streams side by side, rows of coefficients, absorbed attention,
    sorted rows) on the same leaves."""
    import jax
    import jax.numpy as jnp
    import reference
    from distributed_inference_demo_tpu.models import KVCache, StageSpec
    from distributed_inference_demo_tpu.models.decoder import stage_forward
    cfg, p = _toy(leaves)
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    ids = [(13 * i + 5) % cfg.vocab_size for i in range(72)]
    logits, _ = stage_forward(
        wide, cfg, StageSpec(0, 1, 0, cfg.num_layers), jnp.asarray([ids]),
        KVCache.create(cfg, cfg.num_layers, 1, 80), jnp.arange(72)[None])
    lp = jax.nn.log_softmax(logits[0], -1)
    ref = reference.emitted_logprobs(p, TOY, ids, 44, SOUND)
    assert [float(lp[t - 1, ids[t]]) for t in range(44, 72)] == \
        pytest.approx(ref["logprobs"], abs=2e-4)
    assert [int(lp[t - 1].argmax()) for t in range(44, 72)] \
        == ref["best_ids"]


@pytest.mark.parametrize("poisoned", [None, 0, 1, 2, 3])
def test_every_block_runs_exactly_once(poisoned):
    """``reference.emitted_logprobs`` (the two leading blocks inside
    ``embed``, ``num_layers`` calls over the stack by index) against the
    whole loop written here, block after block, each once.  With one
    block's norm weights poisoned both must move alike.  (Its mixing map
    would not do for the LAST block: a doubly-stochastic map's columns sum
    to 1, so the final sum over the streams does not see it.)"""
    import jax
    import jax.numpy as jnp
    import reference
    cfg, p = _toy("float32")
    n_lead, n = cfg.lead_dense_layers, cfg.num_layers
    assert (n_lead, n) == (2, 2) and TOY["num_layers"] == 2
    if poisoned is not None:
        tree, i = ((p.lead, poisoned) if poisoned < n_lead
                   else (p.layers, poisoned - n_lead))
        tree["mlp_norm_w"] = tree["mlp_norm_w"].at[i].mul(1.7)
    ids = jnp.asarray([(7 * i + 2) % cfg.vocab_size for i in range(24)])
    lead_layer, layer = FAM.blocks(TOY, q_block=8)
    with jax.default_matmul_precision("highest"):
        x = p.embed["tokens"][ids]
        X = jnp.stack([x] * 4, 1)
        for i in range(n_lead):
            X = lead_layer({k: v[i] for k, v in p.lead.items()}, X)
        for i in range(n):
            X = layer({k: v[i] for k, v in p.layers.items()}, X)
        x = reference._rms_norm(X[:, 0] + X[:, 1] + X[:, 2] + X[:, 3],
                                p.final_norm["w"], cfg.norm_eps)
        lp = jax.nn.log_softmax(x @ p.lm_head["w"], -1)
    ref = reference.emitted_logprobs(p, TOY, [int(t) for t in ids], 10,
                                     SOUND)
    assert [float(lp[t - 1, ids[t]]) for t in range(10, 24)] == \
        pytest.approx(ref["logprobs"], abs=1e-5)
    if poisoned is not None:        # and the poison is seen at all
        clean = reference.emitted_logprobs(_toy("float32")[1], TOY,
                                           [int(t) for t in ids], 10, SOUND)
        assert max(abs(a - b) for a, b in zip(
            ref["logprobs"], clean["logprobs"])) > 1e-3


def test_the_reference_s_maps_are_doubly_stochastic():
    """The family's own reading of what the iterations leave (float32, the
    first block's attention map over a sequence's tokens): 20 steps reach
    1e-5, one step does not reach 1e-2."""
    cfg, p = _toy("float32")
    ids = [(7 * i + 2) % cfg.vocab_size for i in range(64)]
    assert FAM.sinkhorn_residual(p, TOY, ids) < 1e-5
    assert FAM.sinkhorn_residual(
        p, dict(TOY, hc_sinkhorn_iters=1), ids) > 1e-2


def test_the_reference_check_holds_the_served_maps_reading():
    """``replay``: the tokens scored as every left-to-right family's are,
    once the reply's ``generation.hc_sinkhorn_residual`` stands under the
    family's limit; a reply without it, one step's reading and bfloat16
    maps' (PERF.md section 6, PR 60) each refuse the run with a sentence."""
    import reference
    cfg, p = _toy("float32")
    ids = [(5 * i + 1) % cfg.vocab_size for i in range(24)]
    score = FAM.replay(TOY)
    rows, score_rows = reference.halves(p, TOY)
    plain = score_rows(rows(ids)[9:23], ids[10:])
    assert score(p, ids, 10, {"hc_sinkhorn_residual": 1.2e-6}) == plain
    assert reference.emitted_logprobs(
        p, TOY, ids, 10, {"hc_sinkhorn_residual": 0.0}) == plain
    for record in (None, {}, {"hc_sinkhorn_residual": None}):
        assert "carries no generation.hc_sinkhorn_residual" in score(
            p, ids, 10, record)["error"]
    for seen in (4.9e-3, 0.13, float("nan"), -1.0):
        said = score(p, ids, 10, {"hc_sinkhorn_residual": seen})["error"]
        assert "from doubly stochastic (limit 0.0001" in said
    assert FAM.HC_RESIDUAL_LIMIT == 1e-4


# ------------------------------------------------------------ the readers

def _ctx(pairs=(), pre_s=0.0, post_s=0.0, busy_s=1.0, hc=None, share=1.0):
    return {"config": CONF, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_open": {}, "marks": {},
            "stats_close": {"hc": hc} if hc else {},
            "trace": {"op_self_s": [
                ["_hc_pre_call.94", pre_s * 0.75],
                ["_hc_pre_call.95", pre_s * 0.25],
                ["_hc_post_call.94", post_s],
                ["fusion.1", busy_s - pre_s - post_s]],
                "op_self_total_s": busy_s} if busy_s else {},
            "_dispatch_join": {"pairs": list(pairs), "share": share}}


def test_readers_on_a_synthetic_run():
    from layer_metrics import (hc_kernel_busy_share_pct,
                               hc_post_kernel_ns_per_row,
                               hc_pre_kernel_roofline_pct,
                               hc_sinkhorn_residual_max)
    recs = [(0, 1, {"hc_rows": 512 + 4 * 16, "steps": 4}),
            (1, 2, {"hc_rows": 4 * 16, "steps": 4})]
    rows = 512 + 8 * 16
    pre = FAM.hc_pre_kernel_bytes(MC, rows) / 819e9
    post_s = 14 * rows * 55e-9            # 55 ns a row a call
    ctx = _ctx(recs, pre_s=2 * pre, post_s=post_s, busy_s=20 * pre)
    assert hc_pre_kernel_roofline_pct.read(ctx) == pytest.approx(50.0)
    assert hc_post_kernel_ns_per_row.read(ctx) == pytest.approx(55.0)
    assert hc_kernel_busy_share_pct.read(ctx) == pytest.approx(
        100 * (2 * pre + post_s) / (20 * pre))
    # half the trace's executions matched: half the calls' time is theirs
    half = _ctx(recs, pre_s=4 * pre, post_s=2 * post_s, busy_s=20 * pre,
                share=0.5)
    assert hc_pre_kernel_roofline_pct.read(half) == pytest.approx(50.0)
    assert hc_post_kernel_ns_per_row.read(half) == pytest.approx(55.0)
    # no such call in the trace (the plain path, another model): None
    none = _ctx(recs, busy_s=1.0)
    assert hc_pre_kernel_roofline_pct.read(none) is None
    assert hc_post_kernel_ns_per_row.read(none) is None
    assert hc_kernel_busy_share_pct.read(none) is None
    assert hc_kernel_busy_share_pct.read(dict(none, trace=None)) is None
    # records without the column (the parent's program): None, no raise
    old = _ctx([(0, 1, {"steps": 4})], pre_s=0.1, post_s=0.1)
    assert hc_pre_kernel_roofline_pct.read(old) is None
    assert hc_post_kernel_ns_per_row.read(old) is None
    # another family's configuration: None
    other = dict(_ctx(recs, pre_s=0.1, post_s=0.1),
                 config=json.loads((BENCH / "configs"
                                    / "kanana-2-30b-a3b-bf16.json")
                                   .read_text()))
    assert hc_pre_kernel_roofline_pct.read(other) is None
    assert hc_post_kernel_ns_per_row.read(other) is None
    # the join gave no pairs: None, as every accepted reader of the pairs
    unjoined = _ctx((), pre_s=2 * pre, post_s=post_s, busy_s=20 * pre)
    assert hc_pre_kernel_roofline_pct.read(unjoined) is None
    assert hc_post_kernel_ns_per_row.read(unjoined) is None
    # the program's own reading of its maps, as /stats holds it
    assert hc_sinkhorn_residual_max.read(
        _ctx(hc={"rows": 9, "sinkhorn_residual_max": 1.2e-6})) == 1.2e-6
    assert hc_sinkhorn_residual_max.read(_ctx()) is None
    assert hc_sinkhorn_residual_max.read(_ctx(hc={"rows": 9})) is None


def test_the_manifest_lists_the_cell():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b-bf16", "longdoc-sat", 1)
    listed = {m["name"] for m in M["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "mla_decode_kernel_roofline_pct",
        "mla_prefill_kernel_roofline_pct", "mla_pool_bytes_per_token",
        "hc_pre_kernel_roofline_pct", "hc_post_kernel_ns_per_row",
        "hc_kernel_busy_share_pct", "hc_sinkhorn_residual_max"}
    for m in M["per_layer"]:
        if m["name"].startswith("hc_"):
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
            assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()
    # a share of a roofline only where HBM is the call's bound
    assert not any(m["name"].startswith("hc_post") and "roofline" in
                   m["name"] for m in M["per_layer"])
    # the served streams' path is part of what a run is held to
    assert CONF["attention_paths"]["mixed_step/hc"] == {
        "chunk=1": "pallas_hc", "chunk=256": "pallas_hc"}


def test_the_cell_s_traffic_and_load():
    mix = json.loads((BENCH / "traffic" / "longdoc-sat.json").read_text())
    load = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    assert load == {"clients": 8} and mix["generator"] == "closed_loop"
    flags = CONF["serve_flags"]
    kanana = json.loads((BENCH / "configs" / "kanana-2-30b-a3b-bf16.json")
                        .read_text())["serve_flags"]
    assert flags == kanana        # the two cells read against each other
    max_seq = int(flags[flags.index("--max-seq") + 1])
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= max_seq == 12288
    assert mix["prompt_tokens"]["min"] > CONF["rope_scaling"][
        "original_max_position_embeddings"]     # past YaRN's original
    pages = -(-(10240 + 640) // 128)
    assert 8 * pages <= CONF["pool"]["blocks"]


def test_rehearsal_walks_the_new_cell():
    """``run.py --rehearse-cpu`` through the gateway with the toy model:
    exit 3, every part of ``correct`` (the family's ``replay`` holds the
    reply's reading of the served maps), and the residual path's counter
    metric on the line a chip run would print."""
    import os
    import subprocess
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seconds", "4", "--trace", "1", "--rehearse-cpu", "--seed",
         "2147483999", "--out", str(BENCH / "out" / "test_rehearsal_xing")],
        cwd=BENCH.parent, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("[rehearsal] the result line"))
    assert '"correct": true' in line and '"failed": 0' in line
    assert "hc_sinkhorn_residual_max" in line
    assert "mla_pool_bytes_per_token" in line
    checks = next(ln for ln in proc.stdout.splitlines()
                  if ln.startswith("[checks]"))
    assert "false" not in checks
