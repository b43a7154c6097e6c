"""``families/granite_moe_hybrid.py``: the file against the catalog's
numbers, the shape arithmetic against the issue's and against the program's
parameter tree, the roofline counts by hand (fixed before any reading), the
family's contract, the replay's two limits on injected faults, and the five
new readers on made-up records."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
NAME = "granite-4.0-h-small-bf16-ep2"
CONF = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
MC = CONF["model_config"]
FAM = families.load("granite_moe_hybrid")
CELL = f"{NAME}.longdoc-wide"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CATALOG = {     # the catalog row's numbers, copied: the file holds each
    "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "max_position_embeddings": 131072,
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_key_value_heads": 8, "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 1536}
PUBLISHED = {"num_hidden_layers": 40, "num_local_experts": 72,
             "vocab_size": 100352}


# ------------------------------------------------------- shape arithmetic

def test_the_file_holds_the_source_s_numbers_and_names_its_cut():
    for key, value in CATALOG.items():
        assert CONF[key] == value, key
    assert CONF["model_type"] == "granitemoehybrid"
    assert CONF["position_embedding_type"] == "nope"
    assert CONF["mamba_conv_bias"] and not CONF["mamba_proj_bias"]
    assert CONF["tie_word_embeddings"] and CONF["rope_scaling"] is None
    types = CONF["layer_types"]
    assert len(types) == 40 and types.count("attention") == 4
    assert [i for i, t in enumerate(types) if t == "attention"] == [
        5, 15, 25, 35]
    assert CONF["reduced"] == list(PUBLISHED)
    assert CONF["published"] == PUBLISHED
    assert (CONF["num_hidden_layers"], CONF["num_local_experts"],
            CONF["vocab_size"]) == (1, 36, 50176)
    assert MC["num_layers"] == 1 and MC["experts_held"] == [36, 0]
    assert MC["num_experts"] == 72 and MC["experts_per_token"] == 10
    assert MC["vocab_size"] == 100352 // 2 and MC["tie_embeddings"]
    assert [k["attn"] for k in MC["period"]] == [
        {"mamba": "ssd", "attention": "full"}[t] for t in types[:10]]
    ssd = MC["period"][0]
    assert (ssd["state_heads"], ssd["state_head_dim"], ssd["state_size"],
            ssd["groups"], ssd["conv"], ssd["chunk"]) == (
        128, 64, 128, 1, 4, 256)
    assert ssd["state_heads"] * ssd["state_head_dim"] == 2 * 4096  # expand
    # the four multipliers; the softmax scale is hd ** -0.5 x attn_scale
    assert MC["embedding_multiplier"] == 12 and MC["logits_scaling"] == 16
    assert MC["residual_multiplier"] == 0.22
    assert 128 ** -0.5 * MC["attn_scale"] == pytest.approx(0.0078125,
                                                           rel=1e-12)
    assert MC["num_shared_experts"] * MC["intermediate_size"] == 1536
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]


def test_a_block_and_the_cut_by_the_issue_s_arithmetic():
    ssd, full = MC["period"][0], MC["period"][5]
    in_proj = 4096 * (8192 + 8192 + 2 * 128 + 128)
    assert in_proj == 4096 * 16768 == 68_681_728
    by_hand = in_proj + 8192 * 4096 + 8448 * 5 + 3 * 128 + 8192
    assert by_hand == 102_286_976                   # the issue's 102.29 M
    assert FAM.mixer_elements(MC, ssd) == by_hand
    assert FAM.mixer_elements(MC, full) == 41_943_040    # 41.94 M
    expert = 3 * 4096 * 768
    assert expert == 9_437_184 and 36 * expert == 339_738_624
    router, shared = 4096 * 72, 4096 * 3072 + 1536 * 4096
    assert shared == 2 * expert == 18_874_368
    assert FAM.block_elements(MC, ssd) == by_hand + router + 38 * expert
    assert FAM.block_elements(MC, ssd) == 461_194_880    # 461.2 M
    assert FAM.block_elements(MC, full) == 400_850_944   # 400.9 M
    period = FAM.layer_matrix_elements(MC)
    assert period == 9 * 461_194_880 + 400_850_944 == 4_551_604_864
    embedding = 50176 * 4096
    assert (period + embedding) * 2 / 2 ** 30 == pytest.approx(8.86,
                                                               abs=0.005)
    assert (period + embedding) * 2 / 16e9 > 0.55   # of the chip, weights
    assert B.weight_bytes_per_pass(MC) == (period + embedding) * 2
    # the published size: 40 blocks of 72 experts + the rest = 32 B
    whole = (36 * (by_hand + router + 74 * expert)
             + 4 * (41_943_040 + router + 74 * expert) + 100352 * 4096)
    assert whole / 1e9 == pytest.approx(32.2, abs=0.3)


def test_the_arithmetic_counts_the_program_s_parameter_tree():
    import jax

    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    cfg = ModelConfig(**MC)
    tree = jax.eval_shape(
        lambda: init_full_params(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))
    norms = 20 * 4096                   # two a block: vectors, not matrices
    assert count(tree.layers) == FAM.layer_matrix_elements(MC) + norms
    assert count(tree.embed) == 50176 * 4096 and tree.lm_head == {}
    assert cfg.state_bytes_per_slot == FAM.ssd_state_bytes_per_slot(MC)
    assert cfg.state_shapes == ((128, 64, 128), (3 * 8448,))


def test_a_token_a_slot_and_the_pool_by_hand():
    assert B.kv_bytes_per_token(MC) == 2 * 8 * 128 * 2 == 4096
    assert FAM.ssd_state_bytes(MC) == 128 * 64 * 128 * 4 == 4 << 20
    assert FAM.ssd_state_bytes_per_slot(MC) == 38_204_928
    assert FAM.ssd_state_bytes_per_slot(MC) == 9 * (
        (4 << 20) + 3 * 8448 * 2)
    assert FAM.ssd_blocks(MC) == 9
    pool = CONF["pool"]
    assert pool["bytes_per_token"] == 4096 and pool["block_tokens"] == 128
    assert pool["state_bytes_per_slot"] == 38_204_928
    flags = CONF["serve_flags"]
    at = lambda f: int(flags[flags.index(f) + 1])
    assert at("--kv-cache-blocks") == pool["blocks"] == 32 * 96 + 128
    assert at("--batch-slots") + 1 == pool["state_slots"]
    assert at("--max-seq") == 96 * at("--kv-block-tokens")
    assert at("--prefill-chunk") == MC["period"][0]["chunk"]
    mix = json.loads((BENCH / "traffic" / "longdoc-wide.json").read_text())
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= at("--max-seq"))
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "uniform", "min": 6144, "max": 10240},
        {"dist": "uniform", "min": 384, "max": 640})
    # the issue's, every one: a request sent in the window and not done
    # 30 s after it fails the run
    assert (mix["stagger_s"], mix["ramp_s"], mix["drain_s"]) == (0.05, 20,
                                                                  30)
    # a slab of whole segments beside the rows' decode block: the budget
    # is a multiple of the chunk + slots x --decode-block, so no variant
    # is compiled that a full house never runs
    room = at("--mixed-token-budget") - at("--batch-slots") * at(
        "--decode-block")
    assert room > 0 and room % at("--prefill-chunk") == 0
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text()) == {
        "clients": 32}


def test_the_roofline_counts_by_hand():
    """The step's counts are the issue's; the chunk form's are what the
    recurrence needs (``C B^T`` once a group, y at the model's dtype): the
    issue's read 118 % against the first trace."""
    step_ops = 5 * 128 * 64 * 128                   # a row-step a block
    row = (8192 + 2 * 128) * 2 + (128 + 8192) * 4   # x, B, C | dt, y
    assert FAM.ssd_decode_kernel_ops(MC, 256) == 9 * 256 * step_ops
    assert FAM.ssd_decode_kernel_bytes(MC, 256) == 9 * 256 * (
        2 * 128 * 64 * 128 * 4 + row)
    token = 2 * 256 * 128 + 128 * (2 * 256 * 64 + 4 * 128 * 64)
    assert token == 8_454_144                       # 8.45 MFLOP
    assert FAM.ssd_prefill_kernel_ops(MC, 768) == 9 * 768 * token
    # the issue's own count beside it: the product a head (16.8 MFLOP a
    # token a block) and the tokens' rows through HBM with the state
    ops, moved = FAM.ssd_prefill_kernel_as_issued(MC, 768, 3)
    assert ops == 9 * 768 * 128 * (2 * 256 * 128 + 2 * 256 * 64
                                   + 4 * 128 * 64)
    assert ops / 9 / 768 == pytest.approx(16.8e6, rel=0.01)
    assert moved == 9 * (3 * (8 << 20) + 768 * row)
    # through HBM: the state alone (the tokens' rows ride fast memory)
    assert FAM.ssd_prefill_kernel_bytes(MC, 768, 3) == 9 * 3 * (8 << 20)
    # a call of one segment of one chunk: 10.2 us of state, 11.0 of products
    assert FAM.ssd_prefill_kernel_bytes(MC, 256, 1) / 9 / 819e9 == (
        pytest.approx(10.2e-6, rel=0.01))
    assert FAM.ssd_prefill_kernel_ops(MC, 256) / 9 / 197e12 == (
        pytest.approx(11.0e-6, rel=0.01))
    # a decode step of 32 rows moves the issue's 2.4 GB of state ...
    assert FAM.ssd_decode_kernel_bytes(MC, 32) / 1e9 == pytest.approx(
        2.4, abs=0.05)
    # ... and is bound by bytes; a segment of one chunk stands at the ridge
    # (258 FLOP a byte of state against the chip's 240)
    assert (FAM.ssd_decode_kernel_bytes(MC, 32) / 819e9
            > 100 * FAM.ssd_decode_kernel_ops(MC, 32) / 197e12)
    ratio = (FAM.ssd_prefill_kernel_ops(MC, 256)
             / FAM.ssd_prefill_kernel_bytes(MC, 256, 1))
    assert 240 < ratio < 270
    assert FAM.moe_kernel_ops(MC, 100) == 100 * 6 * 4096 * 768


def test_the_family_keeps_the_contract():
    families.require("granite_moe_hybrid")
    embed, layer, final_norm = FAM.equations(MC)
    assert callable(embed) and callable(layer) and callable(final_norm)
    assert callable(FAM.replay(MC))     # left to right; it holds the STATE
    src = (BENCH / "families" / "granite_moe_hybrid.py").read_text()
    assert "distributed_inference_demo_tpu" not in src
    assert FAM.layer_scale_elements(MC) == (
        10 * 38 * (2 * 768 + 4096) + 9 * (8192 + 8448 + 128 + 4096)
        + (4096 + 2 * 1024 + 4096))


# ------------------------------------------------- the replay and the state

TOY = CONF["rehearsal"]["model_config"]
IDS = [(7 * i + 3) % TOY["vocab_size"] for i in range(40)]
N_PROMPT = 24


def record_of(state, dtype="float32", heads=(0, 2, 4, 6), keys=(0, 8)):
    """A reply's ``ssd_state`` as the engine writes it (``runtime/batching
    ._state_sample``), from one row's states ``[planes, heads, P, N]``."""
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
    import dataclasses
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

    def forward(ids, params=params, cfg=cfg):
        cache = KVCache.create(cfg, cfg.num_layers, 1, 64)
        logits, cache = stage_forward(
            params, cfg, spec, jnp.asarray([ids], jnp.int32), cache,
            jnp.arange(len(ids), dtype=jnp.int32)[None])
        return (jax.nn.log_softmax(logits[0].astype(jnp.float32), -1),
                cache.keys[-1][:, 0])

    def faulty(leaf):
        """The same program with one leaf of the ssd kind zeroed."""
        return dataclasses.replace(params, layers=dict(
            params.layers, **{leaf: 0.0 * params.layers[leaf]}))

    return params, forward, faulty


def test_the_replay_agrees_with_the_program_at_toy_size(program):
    """What ``tests/test_reference.py`` holds for a family without a
    replay, and the state: the reference's is the program's to 2e-4 of
    its norm, after all the ids but the last."""
    import reference
    params, forward, _ = program
    lp, _ = forward(IDS)
    _, state = forward(IDS[:-1])
    want = [float(lp[t - 1, IDS[t]]) for t in range(N_PROMPT, len(IDS))]
    got = reference.emitted_logprobs(
        params, TOY, IDS, N_PROMPT,
        {"ssd_state": record_of(state), "logprobs": want})
    assert got["logprobs"] == pytest.approx(want, abs=2e-4)
    assert got["best_ids"] == [int(lp[t - 1].argmax())
                               for t in range(N_PROMPT, len(IDS))]
    sample, heads, keys, dtype = FAM.state_sample(record_of(state))
    assert (heads, keys, dtype) == ([0, 2, 4, 6], [0, 8], "float32")
    assert sample.shape == (9, 4, 2, 16)
    readings = FAM.state_readings(sample, sample)
    assert max(readings["rel_err"]) == 0.0


def test_the_replay_refuses_a_state_that_is_not_the_one_stated(program):
    """The faults the record is there to catch, each by its sentence: a
    state rounded to bfloat16, the state of a program that dropped the
    skip ``D``, one that is not there, one of another shape (the label of
    the pool and a state one token short: ``state_problem``, below)."""
    import numpy as np
    import reference
    params, forward, faulty = program
    _, state = forward(IDS[:-1])
    ask = lambda generation: reference.emitted_logprobs(
        params, TOY, IDS, N_PROMPT, generation)
    rounded = FAM.rounded_to_bf16(np.asarray(state))
    assert "not the float32 state" in ask(
        {"ssd_state": record_of(rounded)})["error"]
    _, wrong = forward(IDS[:-1], params=faulty("D.ssd"))
    assert "after the same ids" in ask(
        {"ssd_state": record_of(wrong)})["error"]
    assert "no generation.ssd_state" in ask({"kda_state": 1})["error"]
    assert "the reference's sample (9, 2, 2, 16)" in ask(
        {"ssd_state": record_of(state, heads=(0, 2, 4))
         | {"heads": [0, 2]}})["error"]


def test_the_replay_holds_the_log_probabilities_to_its_own_limit(program):
    """What reaches the end of the period: a sound state with
    log-probabilities that stand off in the mean is refused by the
    family's limit, far inside the harness's 0.1 (what the limit sees
    of a fault behind the last state plane takes published widths: the
    chip readings, PERF.md section 2)."""
    import reference
    params, forward, _ = program
    lp, _ = forward(IDS)
    _, state = forward(IDS[:-1])
    sound = [float(lp[t - 1, IDS[t]]) for t in range(N_PROMPT, len(IDS))]
    ask = lambda served: reference.emitted_logprobs(   # noqa: E731
        params, TOY, IDS, N_PROMPT,
        {"ssd_state": record_of(state), "logprobs": served})
    assert "error" not in ask(sound)
    inside = [v + 0.9 * FAM.LOGPROB_MEAN_TOL for v in sound]
    assert "error" not in ask(inside)
    off = [v + 2 * FAM.LOGPROB_MEAN_TOL for v in sound]
    assert 2 * FAM.LOGPROB_MEAN_TOL < 0.1 / 5
    assert "in the mean over" in ask(off)["error"]
    assert "no generation.logprobs" in ask(None)["error"]
    assert "no generation.logprobs" in ask(sound[:-1])["error"]


def test_the_state_s_two_limits_by_hand():
    import numpy as np
    rng = np.random.default_rng(0)
    a = rng.normal(size=(9, 4, 8, 128)).astype(np.float32)
    sound = FAM.state_readings(a, a * np.float32(1.01))
    assert sound["rel_err"] == pytest.approx([0.01 / 1.01] * 9, rel=1e-4)
    assert all(1.2e-3 < r < 2.2e-3 for r in sound["f32_residue"])
    assert FAM.state_problem(sound, "float32") is None
    lower = FAM.state_readings(FAM.rounded_to_bf16(a), a)
    assert lower["f32_residue"] == [0.0] * 9
    assert "not the float32 state" in FAM.state_problem(lower, "float32")
    assert FAM.STATE_F32_RESIDUE_MIN < 1.2e-3 / 2
    one_plane = {"rel_err": [0.001] * 8 + [2 * FAM.STATE_REL_TOL],
                 "f32_residue": [2e-3] * 9}
    assert "after the same ids" in FAM.state_problem(one_plane, "float32")


# ------------------------------------------------------------ the readers

def _ctx(records, step_s, chunk_s, state=None, open_state=None, conf=CONF):
    fields = ["seq", "t_launch", "t_done", "steps", "segments",
              "ssd_row_steps", "ssd_chunk_tokens"]
    rows = [[i + 1, float(i), float(i) + 0.5] + [r[f] for f in fields[3:]]
            for i, r in enumerate(records)]
    snap = lambda st, steps, kv: {
        "dispatch_trace": {"fields": fields, "recent": rows,
                           "kv_token_steps": kv},
        "device_loop": {"device_loop_steps": steps},
        "kvcache": {"kinds": {"state": st}} if st else {}}
    return {"config": conf, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_close": snap(state, 1000, 250_000_000),
            "stats_open": snap(open_state, 0, 0),
            "trace": {"op_self_total_s": 1.0,
                      "op_self_s": [["_ssd_step.126", step_s / 2],
                                    ["_ssd_step.127", step_s / 2],
                                    ["_ssd_chunk.54", chunk_s],
                                    ["moe_gmm.4", 0.3]]}}


RECORD = {"steps": 4, "segments": 3, "ssd_row_steps": 128,
          "ssd_chunk_tokens": 768}


def test_kernel_readers_on_made_up_records(monkeypatch):
    from layer_metrics import (mla_decode_kernel_roofline_pct as mla,
                               ssd_decode_kernel_roofline_pct as dec,
                               ssd_kernel_busy_share_pct as busy,
                               ssd_prefill_kernel_roofline_pct as pre)
    pairs = [(None, None, RECORD)] * 3
    joined = lambda pairs: lambda ctx: {"pairs": pairs, "share": 1.0}  # noqa: E731
    monkeypatch.setattr(mla, "join", joined(pairs))
    monkeypatch.setattr(dec, "join", joined(pairs))
    ctx = _ctx([RECORD] * 3, 0.06, 0.02)
    want = 3 * FAM.ssd_decode_kernel_bytes(MC, 128) / 819e9
    assert dec.read(ctx) == pytest.approx(100 * want / 0.06)
    want = 3 * max(FAM.ssd_prefill_kernel_bytes(MC, 768, 3) / 819e9,
                   FAM.ssd_prefill_kernel_ops(MC, 768) / 197e12)
    assert pre.read(ctx) == pytest.approx(100 * want / 0.02)
    assert 0 < dec.read(ctx) < 100 and 0 < pre.read(ctx) < 100
    assert busy.read(ctx) == pytest.approx(8.0)
    # a program without the columns (the parent): nothing to read, no raise
    bare = [(None, None, {"steps": 4, "segments": 2})] * 3
    monkeypatch.setattr(mla, "join", joined(bare))
    monkeypatch.setattr(dec, "join", joined(bare))
    assert dec.read(ctx) is None and pre.read(ctx) is None
    # a trace without the calls (the parent, another family)
    ctx["trace"]["op_self_s"] = [["moe_gmm.4", 0.3], ["_kda_step.1", 0.1]]
    assert dec.read(ctx) is None and busy.read(ctx) is None
    assert busy.read(dict(ctx, trace={})) is None


def test_kernel_readers_where_the_join_fails(monkeypatch, capsys):
    """A trace that opens inside an execution (the device never idle): the
    join gives no pairs; the two shares are read by the records that ended
    inside the trace's span, on the join's own offset."""
    from layer_metrics import (mla_decode_kernel_roofline_pct as mla,
                               ssd_decode_kernel_roofline_pct as dec,
                               ssd_prefill_kernel_roofline_pct as pre)
    recs = [dict(RECORD, seq=i, t_launch=100 + 0.1 * i - 0.15,
                 t_done=100 + 0.1 * i) for i in range(8)]
    # the trace's zero at 100.13: cut pieces of 1 and 6, all of 2..5
    execs = [[0.0, 0.07e9]] + [[(0.07 + 0.1 * i) * 1e9, 0.1e9]
                               for i in range(4)] + [[0.47e9, 0.02e9]]
    failed = {"pairs": [], "share": 0.98, "offset": 100.13,
              "records": recs, "executions": execs}
    for mod in (mla, dec):
        monkeypatch.setattr(mod, "join", lambda ctx: failed)
    ctx = _ctx([RECORD], 0.06, 0.02)
    want = 5 * FAM.ssd_decode_kernel_bytes(MC, 128) / 819e9     # 1..5
    assert dec.read(ctx) == pytest.approx(100 * want / 0.06)
    assert "5 records that ended inside" in capsys.readouterr().out
    want = 5 * max(FAM.ssd_prefill_kernel_bytes(MC, 768, 3) / 819e9,
                   FAM.ssd_prefill_kernel_ops(MC, 768) / 197e12)
    assert pre.read(ctx) == pytest.approx(100 * want / 0.02)
    said = capsys.readouterr().out
    ops, moved = FAM.ssd_prefill_kernel_as_issued(MC, 768, 3)
    issued = 100 * 5 * max(moved / 819e9, ops / 197e12) / 0.02
    assert f"count (the tokens' rows" in said and f"{issued:.1f} %" in said
    assert issued > pre.read(ctx)
    # nothing to join at all (no stamp, no records): nothing to read
    failed.update(offset=None)
    assert dec.read(ctx) is None and pre.read(ctx) is None
    # the parent's program: records without the columns
    failed.update(offset=100.13, records=[
        {k: r[k] for k in ("seq", "t_launch", "t_done", "steps", "segments")}
        for r in recs])
    assert dec.read(ctx) is None and pre.read(ctx) is None


def test_counter_readers_on_made_up_stats():
    from layer_metrics import (ssd_state_bytes_per_slot as slot,
                               ssd_state_stream_share_pct as share)
    state = {"slots": 33, "bytes_per_slot": 38_204_928, "held": 32,
             "held_peak": 33, "zeroed": 90, "row_steps": 30_000,
             "chunk_tokens": 700_000}
    ctx = _ctx([RECORD], 0.06, 0.02, state=state,
               open_state=dict(state, row_steps=0))
    assert slot.read(ctx) == 38_204_928 == FAM.ssd_state_bytes_per_slot(MC)
    moved = FAM.ssd_decode_kernel_bytes(MC, 30_000)
    weights = 1000 * B.weight_bytes_per_pass(MC)
    pages = 250_000_000 * 4096
    assert share.read(ctx) == pytest.approx(
        100 * moved / (moved + weights + pages))
    assert 10 < share.read(ctx) < 25    # the state is a sixth of a step
    # the parent's program says nothing of a state
    bare = _ctx([RECORD], 0.06, 0.02)
    assert slot.read(bare) is None and share.read(bare) is None
    # the other state kind's cell is not this metric's
    solar = json.loads((BENCH / "configs" /
                        "solar-open2-250b-bf16-ep8.json").read_text())
    other = _ctx([RECORD], 0.06, 0.02, state=state, open_state=state,
                 conf=solar)
    assert slot.read(other) is None and share.read(other) is None


def test_the_manifest_lists_the_cell_where_it_may():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longdoc-wide", 1)
    # membership, not place: a later PR appends after this one
    assert NAME in [c["name"] for c in MANIFEST["configs"]]
    mine = {m["name"]: m for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())}
    new = ["ssd_decode_kernel_roofline_pct",
           "ssd_prefill_kernel_roofline_pct", "ssd_kernel_busy_share_pct",
           "ssd_state_bytes_per_slot", "ssd_state_stream_share_pct"]
    # (the accepted suite holds solar's cell LAST on the ``moe_*`` lists,
    # and a PR may only append: this cell is on none of them, PERF.md
    # section 7)
    assert sorted(mine) == sorted(new)
    for name in new:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "tpot_p50_ms"
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
