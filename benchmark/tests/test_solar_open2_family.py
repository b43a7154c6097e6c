"""``families/solar_open2.py``: the file against the catalog's numbers,
the shape arithmetic against the issue's and against the program's
parameter tree, the roofline counts by hand, the family's contract, and
the five new readers on made-up records."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
NAME = "solar-open2-250b-bf16-ep8"
CONF = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
MC = CONF["model_config"]
FAM = families.load("solar_open2")
CELL = f"{NAME}.reason-wide"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CATALOG = {     # the catalog row's numbers, copied: the file holds each
    "partial_rotary_factor": 1, "hidden_size": 4096,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "gqa_interval": 3, "n_shared_experts": 1, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}
PUBLISHED = {"num_hidden_layers": 48, "n_routed_experts": 320,
             "vocab_size": 196608}


# ------------------------------------------------------- shape arithmetic

def test_the_file_holds_the_source_s_numbers_and_names_its_cut():
    for key, value in CATALOG.items():
        assert CONF[key] == value, key
    assert CONF["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert CONF["gqa_layers"] == list(range(0, 48, 4))
    assert CONF["use_gqa_gate"] and CONF["kda_allow_neg_eigval"]
    assert not CONF["use_rope"] and not CONF["kda_use_full_proj"]
    assert CONF["reduced"] == list(PUBLISHED)
    assert CONF["published"] == PUBLISHED
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"],
            CONF["vocab_size"]) == (1, 40, 24576)
    assert MC["num_layers"] == 1 and MC["experts_held"] == [40, 0]
    assert MC["num_experts"] == 320 and MC["experts_per_token"] == 8
    assert MC["vocab_size"] == 196608 // 8
    assert [k["attn"] for k in MC["period"]] == ["full", "kda", "kda", "kda"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]


def test_a_block_and_the_cut_by_the_issue_s_arithmetic():
    full, kda = MC["period"][0], MC["period"][1]
    expert = 3 * 4096 * 1280
    assert expert == 15_728_640
    # q, k, v, o + two low-rank gates + beta + the taps: the issue's 137.7 M
    by_hand = (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
               + 3 * 8192 * 4)
    assert by_hand == 137_723_904
    small = 64 + 2 * 8192 + 128         # A_log, dt_bias and b_g, the norm
    assert FAM.mixer_elements(MC, kda) == by_hand + small
    # q 33.6 + k, v 8.4 + gate 33.6 + o 33.6: the issue's 109.1 M
    assert FAM.mixer_elements(MC, full) == 109_051_904
    router = 4096 * 320 + 320
    assert FAM.block_elements(MC, kda) == (by_hand + small + router
                                           + 41 * expert)
    period = FAM.layer_matrix_elements(MC)
    assert period == pytest.approx(3_107e6, rel=1e-4)     # 3,107 M
    head = 2 * 24576 * 4096
    assert (period + head) * 2 / 2 ** 30 == pytest.approx(6.16, abs=0.005)
    assert B.weight_bytes_per_pass(MC) == period * 2 + 24576 * 4096 * 2
    # the published size: 48 blocks of 320 experts + the rest = 250 B
    whole = (48 * 320 * expert + 36 * (by_hand + expert + router)
             + 12 * (109_051_904 + expert + router) + 2 * 196608 * 4096)
    assert whole / 1e9 == pytest.approx(250.3, abs=0.5)


def test_the_arithmetic_counts_the_program_s_parameter_tree():
    import jax

    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    cfg = ModelConfig(**MC)
    tree = jax.eval_shape(
        lambda: init_full_params(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))
    norms = 8 * 4096                    # two a block: vectors, not matrices
    assert count(tree.layers) == FAM.layer_matrix_elements(MC) + norms
    assert count(tree.embed) == count(tree.lm_head) == 24576 * 4096
    assert cfg.state_bytes_per_slot == FAM.kda_state_bytes_per_slot(MC)


def test_a_token_a_slot_and_the_pool_by_hand():
    assert B.kv_bytes_per_token(MC) == 2 * 8 * 128 * 2 == 4096
    assert FAM.kda_state_bytes(MC) == 64 * 128 * 128 * 4 == 4 << 20
    assert FAM.kda_state_bytes_per_slot(MC) == 13_025_280
    assert FAM.kda_state_bytes_per_slot(MC) == 3 * (
        (4 << 20) + 3 * 24576 * 2)
    assert FAM.kda_blocks(MC) == 3
    pool = CONF["pool"]
    assert pool["bytes_per_token"] == 4096 and pool["block_tokens"] == 128
    assert pool["state_bytes_per_slot"] == 13_025_280
    flags = CONF["serve_flags"]
    at = lambda f: int(flags[flags.index(f) + 1])
    assert at("--kv-cache-blocks") == pool["blocks"] == 64 * 16 + 128
    assert at("--batch-slots") + 1 == pool["state_slots"]
    assert at("--max-seq") == 16 * at("--kv-block-tokens")
    mix = json.loads((BENCH / "traffic" / "reason-wide.json").read_text())
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= at("--max-seq"))
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text()) == {
        "clients": 64}


def test_the_roofline_counts_by_hand():
    token = 6 * 128 * 128 * 64          # operations a token a block
    row = (5 * 64 * 128 + 64) * 4       # q, k, v, alpha, o and beta
    assert FAM.kda_decode_kernel_ops(MC, 256) == 3 * 256 * token
    assert FAM.kda_decode_kernel_bytes(MC, 256) == 3 * 256 * (
        (8 << 20) + row)
    assert FAM.kda_prefill_kernel_ops(MC, 768) == 3 * 768 * token
    assert FAM.kda_prefill_kernel_bytes(MC, 768, 3) == 3 * (
        3 * (8 << 20) + 768 * row)
    # a decode step of 64 rows moves the issue's 1.6 GB of state
    assert FAM.kda_decode_kernel_bytes(MC, 64) / 1e9 == pytest.approx(
        1.6, abs=0.05)
    assert FAM.moe_kernel_ops(MC, 100) == 100 * 6 * 4096 * 1280


def test_the_family_keeps_the_contract():
    families.require("solar_open2")
    embed, layer, final_norm = FAM.equations(MC)
    assert callable(embed) and callable(layer) and callable(final_norm)
    assert callable(FAM.replay(MC))     # left to right; it holds the STATE
    src = (BENCH / "families" / "solar_open2.py").read_text()
    assert "distributed_inference_demo_tpu" not in src
    assert FAM.layer_scale_elements(MC) == (
        4 * 41 * (2 * 1280 + 4096) + (2 * 8192 + 2 * 1024 + 4096)
        + 3 * (3 * 8192 + 4096))


# ------------------------------------------------- the replay and the state

TOY = CONF["rehearsal"]["model_config"]
IDS = [(7 * i + 3) % TOY["vocab_size"] for i in range(40)]
N_PROMPT = 24


def record_of(state, dtype="float32", heads=(0, 1, 2, 3), keys=(0, 8)):
    """A reply's ``kda_state`` as the engine writes it (``runtime/batching
    ._state_sample``), from one row's states ``[planes, heads, key,
    value]``."""
    import base64
    import numpy as np
    got = np.asarray(state, "<f4")[:, list(heads)][:, :, list(keys)]
    return {"pool_dtype": dtype, "heads": list(heads), "keys": list(keys),
            "shape": list(got.shape),
            "float32_b64": base64.b64encode(got.tobytes()).decode("ascii")}


@pytest.fixture(scope="module")
def program():
    """The program's own causal forward at toy size: its parameters, its
    log-probabilities of ``IDS`` and the state ``IDS[:n]`` leave."""
    import jax
    import jax.numpy as jnp
    from distributed_inference_demo_tpu.models.base import (KVCache,
                                                            ModelConfig,
                                                            StageSpec)
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params, stage_forward)

    cfg = ModelConfig(**TOY)
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    spec = StageSpec(0, 1, 0, cfg.num_layers)

    def forward(ids):
        cache = KVCache.create(cfg, cfg.num_layers, 1, 64)
        logits, cache = stage_forward(
            params, cfg, spec, jnp.asarray([ids], jnp.int32), cache,
            jnp.arange(len(ids), dtype=jnp.int32)[None])
        return (jax.nn.log_softmax(logits[0].astype(jnp.float32), -1),
                cache.keys[-1][:, 0])

    return params, forward


def test_the_replay_agrees_with_the_program_at_toy_size(program):
    """What ``tests/test_reference.py`` holds for a family without a
    replay, and the state: the reference's is the program's to 2e-4 of
    its norm, after all the ids but the last."""
    import reference
    params, forward = program
    lp, _ = forward(IDS)
    _, state = forward(IDS[:-1])
    want = [float(lp[t - 1, IDS[t]]) for t in range(N_PROMPT, len(IDS))]
    got = reference.emitted_logprobs(params, TOY, IDS, N_PROMPT,
                                     {"kda_state": record_of(state)})
    assert got["logprobs"] == pytest.approx(want, abs=2e-4)
    assert got["best_ids"] == [int(lp[t - 1].argmax())
                               for t in range(N_PROMPT, len(IDS))]
    sample, heads, keys, dtype = FAM.state_sample(record_of(state))
    assert (heads, keys, dtype) == ([0, 1, 2, 3], [0, 8], "float32")
    assert sample.shape == (3, 4, 2, 16)


def test_the_replay_refuses_a_state_that_is_not_the_one_stated(program):
    """The faults the record is there to catch, each by its sentence: a
    state rounded to bfloat16 (the numbers alone, and a pool that says
    so), a state one token short, one that is not there."""
    import numpy as np
    import reference
    params, forward = program
    _, state = forward(IDS[:-1])
    _, short = forward(IDS[:-2])
    ask = lambda generation: reference.emitted_logprobs(
        params, TOY, IDS, N_PROMPT, generation)
    rounded = FAM.rounded_to_bf16(np.asarray(state))
    for record in (record_of(rounded), record_of(rounded, "bfloat16"),
                   record_of(state, "bfloat16")):
        assert "not the float32 state" in ask({"kda_state": record})["error"]
    assert "after the same ids" in ask(
        {"kda_state": record_of(short)})["error"]
    assert "no generation.kda_state" in ask(None)["error"]
    assert "no generation.kda_state" in ask({})["error"]
    assert "the reference's sample (3, 2, 2, 16)" in ask(
        {"kda_state": record_of(state, heads=(0, 1, 2))
         | {"heads": [0, 1]}})["error"]


def test_the_state_s_two_readings_by_hand():
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    a = rng.normal(size=(3, 4, 2, 16)).astype(np.float32)
    rounded = FAM.rounded_to_bf16(a)
    assert np.array_equal(rounded, np.asarray(
        jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)))
    sound = FAM.state_readings(a, a * np.float32(1.01))
    assert sound["rel_err"] == pytest.approx([0.01 / 1.01] * 3, rel=1e-4)
    # float32 numbers lie about 1.6e-3 of their norm from their rounding
    assert all(1.2e-3 < r < 2.2e-3 for r in sound["f32_residue"])
    assert FAM.state_problem(sound, "float32") is None
    lower = FAM.state_readings(rounded, a)
    assert lower["f32_residue"] == [0.0, 0.0, 0.0]
    assert all(1.2e-3 < r < 2.2e-3 for r in lower["rel_err"])  # inside
    assert "not the float32 state" in FAM.state_problem(lower, "float32")
    assert FAM.STATE_F32_RESIDUE_MIN < 1.2e-3 / 2
    one_plane = {"rel_err": [0.001, 0.2, 0.001], "f32_residue": [2e-3] * 3}
    assert "after the same ids" in FAM.state_problem(one_plane, "float32")


# ------------------------------------------------------------ the readers

def _ctx(records, step_s, chunk_s, state=None, open_state=None):
    fields = ["seq", "t_launch", "t_done", "steps", "segments",
              "kda_row_steps", "kda_chunk_tokens"]
    rows = [[i + 1, float(i), float(i) + 0.5] + [r[f] for f in fields[3:]]
            for i, r in enumerate(records)]
    snap = lambda st, steps, kv: {
        "dispatch_trace": {"fields": fields, "recent": rows,
                           "kv_token_steps": kv},
        "device_loop": {"device_loop_steps": steps},
        "kvcache": {"kinds": {"state": st}} if st else {}}
    return {"config": CONF, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_close": snap(state, 1000, 40_000_000),
            "stats_open": snap(open_state, 0, 0),
            "trace": {"op_self_total_s": 1.0,
                      "op_self_s": [["_kda_step.7", step_s / 2],
                                    ["_kda_step.9", step_s / 2],
                                    ["_kda_chunk.3", chunk_s],
                                    ["moe_gmm.4", 0.3]]}}


RECORD = {"steps": 4, "segments": 2, "kda_row_steps": 250,
          "kda_chunk_tokens": 500}


def test_kernel_readers_on_made_up_records(monkeypatch):
    from layer_metrics import (kda_decode_kernel_roofline_pct as dec,
                               kda_kernel_busy_share_pct as busy,
                               kda_prefill_kernel_roofline_pct as pre,
                               mla_decode_kernel_roofline_pct as mla)
    pairs = [(None, None, RECORD)] * 3
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": pairs,
                                                  "share": 1.0})
    ctx = _ctx([RECORD] * 3, 0.04, 0.02)
    want = 3 * FAM.kda_decode_kernel_bytes(MC, 250) / 819e9
    assert dec.read(ctx) == pytest.approx(100 * want / 0.04)
    want = 3 * max(FAM.kda_prefill_kernel_bytes(MC, 500, 2) / 819e9,
                   FAM.kda_prefill_kernel_ops(MC, 500) / 197e12)
    assert pre.read(ctx) == pytest.approx(100 * want / 0.02)
    assert busy.read(ctx) == pytest.approx(6.0)
    # a program without the columns (the parent): nothing to read, no raise
    bare = [(None, None, {"steps": 4, "segments": 2})] * 3
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": bare,
                                                  "share": 1.0})
    assert dec.read(ctx) is None and pre.read(ctx) is None
    # a trace without the calls (the parent, another family)
    ctx["trace"]["op_self_s"] = [["moe_gmm.4", 0.3]]
    assert dec.read(ctx) is None and busy.read(ctx) is None
    assert busy.read(dict(ctx, trace={})) is None


def test_counter_readers_on_made_up_stats():
    from layer_metrics import (kda_state_bytes_per_slot as slot,
                               kda_state_stream_share_pct as share)
    state = {"slots": 65, "bytes_per_slot": 13_025_280, "held": 64,
             "held_peak": 65, "zeroed": 200, "row_steps": 64_000,
             "chunk_tokens": 150_000}
    ctx = _ctx([RECORD], 0.04, 0.02, state=state,
               open_state=dict(state, row_steps=0))
    assert slot.read(ctx) == 13_025_280 == FAM.kda_state_bytes_per_slot(MC)
    moved = FAM.kda_decode_kernel_bytes(MC, 64_000)
    weights = 1000 * B.weight_bytes_per_pass(MC)
    pages = 40_000_000 * 4096
    assert share.read(ctx) == pytest.approx(
        100 * moved / (moved + weights + pages))
    assert 15 < share.read(ctx) < 30    # the state is a fifth of a step
    # the parent's program says nothing of a state
    bare = _ctx([RECORD], 0.04, 0.02)
    assert slot.read(bare) is None and share.read(bare) is None


def test_the_manifest_lists_the_cell_where_the_issue_says():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reason-wide", 1)
    mine = {m["name"]: m for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())}
    new = {"kda_decode_kernel_roofline_pct", "kda_prefill_kernel_roofline_pct",
           "kda_kernel_busy_share_pct", "kda_state_bytes_per_slot",
           "kda_state_stream_share_pct"}
    joined = {"moe_kernel_busy_share_pct", "moe_kernel_roofline_pct",
              "moe_expert_load_max_over_mean", "moe_experts_touched_pct",
              "moe_rows_held_share_pct", "step_decode_ms_p50",
              "step_prefill_ms_p50"}
    assert set(mine) == new | joined
    for name in new:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "tpot_p50_ms"
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    for name in joined:
        assert mine[name]["workloads"][-1] == CELL
