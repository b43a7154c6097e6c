"""``families/nemotron_h.py``: the file against the catalog's numbers, the
shape arithmetic against the issue's and against the program's parameter
tree, the kernels' counts by hand (fixed before any reading), the family's
contract, the replay against the program at toy size and on injected
faults, and the new readers on made-up records."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
NAME = "nemotron-3-nano-30b-a3b-bf16-ep2"
CONF = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
MC = CONF["model_config"]
FAM = families.load("nemotron_h")
CELL = f"{NAME}.reason-wide"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CATALOG = {     # the catalog row's numbers, copied: the file holds each
    "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_num_heads": 64, "max_position_embeddings": 262144,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1}
PUBLISHED = {"num_hidden_layers": 52, "n_routed_experts": 128,
             "vocab_size": 131072}
M, E, A = MC["period"][0], MC["period"][1], MC["period"][5]


# ------------------------------------------------------- shape arithmetic

def test_the_file_holds_the_source_s_numbers_and_names_its_cut():
    for key, value in CATALOG.items():
        assert CONF[key] == value, key
    assert CONF["model_type"] == "nemotron_h"
    assert CONF["mlp_hidden_act"] == "relu2" and CONF["norm_topk_prob"]
    assert CONF["use_conv_bias"] and not CONF["mamba_proj_bias"]
    assert not CONF["tie_word_embeddings"] and not CONF["residual_in_fp32"]
    pattern = CONF["hybrid_override_pattern"]
    assert len(pattern) == 52 and (pattern.count("M"), pattern.count("E"),
                                   pattern.count("*")) == (23, 23, 6)
    assert pattern[:9] == "MEMEM*EME"
    assert CONF["reduced"] == list(PUBLISHED) and len(CONF["reduced"]) == 3
    assert CONF["published"] == PUBLISHED
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"],
            CONF["vocab_size"]) == (1, 64, 65536)      # one period of nine
    assert MC["num_layers"] == 1 and MC["experts_held"] == [64, 0]
    assert MC["num_experts"] == 128 and MC["experts_per_token"] == 6
    assert MC["vocab_size"] == 131072 // 2 and not MC["tie_embeddings"]
    assert MC["mlp_act"] == "relu2" and MC["router_scoring"] == "sigmoid"
    assert MC["router_bias"] and MC["routed_scaling_factor"] == 2.5
    # one published layer is one entry of the period, one to one
    assert [{"ssd": "M", "none": "E", "full": "*"}[k["attn"]]
            for k in MC["period"]] == list(pattern[:9])
    assert (M["state_heads"], M["state_head_dim"], M["state_size"],
            M["groups"], M["conv"], M["chunk"]) == (64, 64, 128, 8, 4, 128)
    assert M["state_heads"] * M["state_head_dim"] == 4096  # not 2 x 2688
    assert M["mlp"] is False and A["mlp"] is False and "mlp" not in E
    assert A["rotary_share"] == 0.0 and A["num_heads"] == 32
    assert MC["num_shared_experts"] * MC["intermediate_size"] == 3712
    for key in ("reduced_how", "assumed", "deployment", "pool",
                "attention_paths", "rehearsal"):
        assert CONF[key], key
    assert "no rotary" in " ".join(CONF["assumed"])
    assert "two chips" in CONF["deployment"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]


def test_a_block_and_the_cut_by_the_issue_s_arithmetic():
    in_proj = 2688 * (4096 + 6144 + 64)
    assert in_proj == 2688 * 10304
    by_hand = in_proj + 4096 * 2688 + 6144 * 5 + 3 * 64 + 4096
    assert FAM.block_elements(MC, M) == by_hand == 38_742_208    # 38.74 M
    assert FAM.block_elements(MC, A) == 23_396_352               # 23.40 M
    expert = 2 * 2688 * 1856
    assert expert == 9_977_856
    held = 64 * expert + 2 * expert + 2688 * 128 + 128
    assert FAM.block_elements(MC, E) == held == 658_882_688      # 658.89 M
    period = FAM.layer_matrix_elements(MC)
    assert period == 4 * 38_742_208 + 4 * 658_882_688 + 23_396_352
    assert period / 1e6 == pytest.approx(2813.9, abs=0.05)
    both = 2 * 65536 * 2688                     # embedding and head
    norms = 10 * 2688                           # vectors, not matrices
    assert (period + both) / 1e6 == pytest.approx(3166.2, abs=0.1)
    assert (period + both + norms) * 2 / 2 ** 30 == pytest.approx(5.90,
                                                                  abs=0.005)
    # bytes.py reads the head once a pass, the embedding by row
    assert B.weight_bytes_per_pass(MC) == (period + 65536 * 2688) * 2
    # the published size: 23 M + 23 E (128 experts) + 6 *, embedding, head
    whole = (23 * by_hand + 23 * (held + 64 * expert) + 6 * 23_396_352
             + 2 * 131072 * 2688)
    assert whole / 1e9 == pytest.approx(31.58, abs=0.02)


def test_the_arithmetic_counts_the_program_s_parameter_tree():
    import jax

    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    cfg = ModelConfig(**MC)
    tree = jax.eval_shape(
        lambda: init_full_params(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))
    norms = 9 * 2688                    # ONE a block of one sublayer
    assert count(tree.layers) == FAM.layer_matrix_elements(MC) + norms
    assert count(tree.embed) == count(tree.lm_head) == 65536 * 2688
    assert tree.layers["w_up_t.mlp"].shape == (1, 4, 64, 1856, 2688)
    assert tree.layers["w_down.mlp"].shape == (1, 4, 64, 1856, 2688)
    assert not any(k.startswith(("w_gate", "ws_gate")) for k in tree.layers)
    assert cfg.state_bytes_per_slot == FAM.ssd_state_bytes_per_slot(MC)
    assert cfg.state_shapes == ((64, 64, 128), (3 * 6144,))
    assert cfg.cache_kinds == ((0, 1),) and cfg.state_planes == 4
    assert cfg.mlp_blocks == 4 == FAM.expert_blocks(MC)


def test_a_token_a_slot_and_the_pool_by_hand():
    assert B.kv_bytes_per_token(MC) == 2 * 2 * 128 * 2 == 1024  # of NINE blocks
    assert FAM.ssd_state_bytes(MC) == 64 * 64 * 128 * 4 == 2 << 20
    assert FAM.ssd_state_bytes_per_slot(MC) == 8_536_064 == 4 * (
        (2 << 20) + 3 * 6144 * 2)
    assert FAM.ssd_blocks(MC) == 4
    pool = CONF["pool"]
    assert pool["bytes_per_token"] == 1024 and pool["block_tokens"] == 128
    assert pool["state_bytes_per_slot"] == 8_536_064
    flags = CONF["serve_flags"]
    at = lambda f: int(flags[flags.index(f) + 1])
    assert at("--kv-cache-blocks") == pool["blocks"] == 64 * 16 + 128
    assert at("--batch-slots") + 1 == pool["state_slots"]
    assert at("--max-seq") == 16 * at("--kv-block-tokens")
    assert at("--prefill-chunk") == 2 * M["chunk"]  # two scan chunks
    mix = json.loads((BENCH / "traffic" / "reason-wide.json").read_text())
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= at("--max-seq"))
    room = at("--mixed-token-budget") - at("--batch-slots") * at(
        "--decode-block")
    assert room > 0 and room % at("--prefill-chunk") == 0
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text()) == {
        "clients": 64}
    assert CONF["attention_paths"] == {
        "mixed_step/full": {"chunk=1": "pallas_decode",
                            "chunk=256": "pallas_prefill"},
        "mixed_step/ssd": {"chunk=1": "pallas_ssd", "chunk=256": "pallas_ssd"}}


def test_the_kernels_counts_by_hand():
    """Fixed before any reading (ISSUE 66)."""
    rows = 64 * 6 * 4 // 2          # a 64-row step: half the rows are held
    assert FAM.moe_kernel_ops(MC, rows) == 2 * 2 * rows * 2688 * 1856
    touched = 4 * 61
    assert FAM.moe_kernel_bytes(MC, rows, touched) == (
        touched * 2 * 2688 * 1856 * 2 + rows * 2 * (2688 + 1856) * 2)
    # a step's experts: 5.11 GB if every held expert of 4 blocks is touched
    assert 4 * 64 * 2 * 2688 * 1856 * 2 / 1e9 == pytest.approx(5.11, abs=0.01)
    # ... and bound by bytes, 100 to 1
    assert (FAM.moe_kernel_bytes(MC, rows, touched) / 819e9
            > 50 * FAM.moe_kernel_ops(MC, rows) / 197e12)
    row = (4096 + 2 * 8 * 128) * 2 + (64 + 4096) * 4
    assert FAM.ssd_decode_kernel_ops(MC, 256) == 4 * 256 * 5 * 64 * 64 * 128
    assert FAM.ssd_decode_kernel_bytes(MC, 256) == 4 * 256 * (
        2 * (2 << 20) + row)
    # 64 rows a step move 1.09 GB of state in and out
    assert FAM.ssd_decode_kernel_bytes(MC, 64) / 1e9 == pytest.approx(
        1.08, abs=0.02)
    token = 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64)
    assert FAM.ssd_prefill_kernel_ops(MC, 512) == 4 * 512 * token
    assert FAM.ssd_prefill_kernel_bytes(MC, 512, 2) == 4 * 2 * 2 * (2 << 20)


def test_the_family_keeps_the_contract():
    families.require("nemotron_h")
    embed, layer, final_norm = FAM.equations(MC)
    assert callable(embed) and callable(layer) and callable(final_norm)
    assert callable(FAM.replay(MC))     # left to right; it holds the STATE
    src = (BENCH / "families" / "nemotron_h.py").read_text()
    assert "distributed_inference_demo_tpu" not in src
    assert FAM.layer_scale_elements(MC) == (
        4 * (4096 + 6144 + 64 + 2688) + 4 * 66 * (1856 + 2688)
        + (4096 + 2 * 256 + 2688))
    assert FAM.kind_name(E) == "mlp" and FAM.kind_name(M) == "ssd"


# ------------------------------------------------- the replay and the state

TOY = CONF["rehearsal"]["model_config"]
IDS = [(7 * i + 3) % TOY["vocab_size"] for i in range(40)]
N_PROMPT = 24


def record_of(state, dtype="float32", heads=range(8), keys=range(16)):
    """A reply's ``ssd_state`` as the engine writes it, from one row's
    states ``[planes, heads, P, N]``: here EVERY head and row of the toy
    state (2,048 numbers a plane; the engine's four heads' two rows are
    128, whose residue a few large entries decide)."""
    import base64
    import numpy as np
    got = np.asarray(state, "<f4")[:, list(heads)][:, :, list(keys)]
    return {"pool_dtype": dtype, "heads": list(heads), "keys": list(keys),
            "shape": list(got.shape),
            "float32_b64": base64.b64encode(got.tobytes()).decode("ascii")}


@pytest.fixture(scope="module")
def program():
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

    def faulty(leaf, place=None):
        """``leaf`` zeroed: everywhere, or at one place of its kind's
        stack ("last" is the period's last E block)."""
        a = params.layers[leaf]
        place = -1 if place == "last" else place
        a = 0.0 * a if place is None else a.at[:, place].set(0.0)
        return dataclasses.replace(params, layers=dict(params.layers,
                                                       **{leaf: a}))

    return params, forward, faulty


def test_the_replay_agrees_with_the_program_at_toy_size(program):
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
    assert sample.shape == (4, 8, 16, 16)       # the four M planes


def test_the_replay_refuses_what_is_not_the_configuration_s(program):
    """A state rounded to bfloat16, the state of a program that dropped the
    skip ``D``, a record that is not there; and, behind the last state
    plane, the LAST block's routed sum dropped: the log-probabilities' own
    limit is what reaches it (toy float32: the reading against the sound
    one; the limit itself is set by the chip readings, PERF.md section
    2)."""
    import numpy as np
    import reference
    params, forward, faulty = program
    lp, _ = forward(IDS)
    _, state = forward(IDS[:-1])
    sound = [float(lp[t - 1, IDS[t]]) for t in range(N_PROMPT, len(IDS))]
    ask = lambda generation: reference.emitted_logprobs(   # noqa: E731
        params, TOY, IDS, N_PROMPT, generation)
    rounded = FAM.rounded_to_bf16(np.asarray(state))
    assert "not the float32 state" in ask(
        {"ssd_state": record_of(rounded)})["error"]
    _, wrong = forward(IDS[:-1], params=faulty("D.ssd"))
    assert "after the same ids" in ask(
        {"ssd_state": record_of(wrong)})["error"]
    assert "no generation.ssd_state" in ask({"kda_state": 1})["error"]
    ok = {"ssd_state": record_of(state)}
    assert "no generation.logprobs" in ask(ok)["error"]
    assert "error" not in ask(dict(ok, logprobs=sound))
    off = [v + 2 * FAM.LOGPROB_MEAN_TOL for v in sound]
    assert "in the mean over" in ask(dict(ok, logprobs=off))["error"]
    # the last E block's held routed sum left out: every state plane is the
    # sound one, the log-probabilities are not
    lp_bad, state_bad = forward(IDS, params=faulty("w_down.mlp", "last"))
    _, state_bad = forward(IDS[:-1], params=faulty("w_down.mlp", "last"))
    np.testing.assert_array_equal(np.asarray(state_bad), np.asarray(state))
    bad = [float(lp_bad[t - 1, IDS[t]]) for t in range(N_PROMPT, len(IDS))]
    err = sum(abs(a - b) for a, b in zip(bad, sound)) / len(sound)
    assert 20 * 2e-4 < err < FAM.LOGPROB_MEAN_TOL   # under the mean's limit
    # ... and the PAIRED reading sees it: the served log-probabilities are
    # the reference's WITHOUT that block's routed sum, not the one with it
    # (0 to float32's rounding here, so the sentence may or may not come:
    # the limit is 0; ``test_the_paired_reading_by_hand`` holds the sentence)
    said = ask({"ssd_state": record_of(state_bad), "logprobs": bad})
    assert said["routed_share"][:3] == pytest.approx([1.0] * 3, abs=1e-4)
    assert abs(said["routed_share"][3]) < 1e-3


@pytest.mark.parametrize("block", [1, 2])
def test_the_replay_misses_no_middle_block_s_routed_sum(program, block,
                                                        capfd):
    """An E block with an M block behind it: the served state plane behind
    it is the reference's without that block's routed sum (share 0), under
    ``STATE_REL_TOL`` (0.05-0.06 here) and under the mean's limit (0.025 /
    0.022 here), so the paired reading is what refuses it; every block
    before it still reads 1."""
    import reference
    params, forward, faulty = program
    lost = faulty("w_down.mlp", block)
    lp, _ = forward(IDS, params=lost)
    _, state = forward(IDS[:-1], params=lost)
    served = [float(lp[t - 1, IDS[t]]) for t in range(N_PROMPT, len(IDS))]
    said = reference.emitted_logprobs(
        params, TOY, IDS, N_PROMPT,
        {"ssd_state": record_of(state), "logprobs": served})
    assert (f"E block {block}'s routed sum is not in the served state"
            in said["error"])
    line = next(ln for ln in capfd.readouterr().err.splitlines()
                if ln.startswith("[replay] routed_share"))
    shares = json.loads(line.split("routed_share ", 1)[1])["share"]
    assert shares[:block] == pytest.approx([1.0] * block, abs=1e-4)
    assert abs(shares[block]) < 1e-4


def test_the_paired_reading_by_hand():
    import numpy as np
    assert FAM.plane_behind(MC) == [1, 2, 3, None]      # M E M E M * E M E
    assert FAM.plane_behind(dict(MC, num_layers=2)) == [
        1, 2, 3, 4, 5, 6, 7, None]
    a, b = np.array([1.0, 2.0, 4.0]), np.array([1.0, 1.0, 2.0])
    assert FAM.routed_share(a, a, b) == 1.0
    assert FAM.routed_share(b, a, b) == 0.0
    assert FAM.routed_share((a + b) / 2, a, b) == 0.5
    # what is not along the routed sum moves no share
    assert FAM.routed_share(a + [5.0, 0.0, 0.0], a, b) == 1.0
    # a state plane is read a head at a time and the median is the
    # plane's: one head that is most of the plane's numbers and lost half
    # its share (its last token's expert swapped) does not move it
    A = np.stack([100 * a, a, a, a]).reshape(4, 3, 1)
    B = np.stack([100 * b, b, b, b]).reshape(4, 3, 1)
    served = A.copy()
    served[0] = (A[0] + B[0]) / 2
    assert FAM.routed_share(served, A, B) == 1.0
    assert FAM.routed_share(B, A, B) == 0.0
    pooled = ((served - B) * (A - B)).sum() / ((A - B) ** 2).sum()
    assert pooled < 0.51
    assert 0 <= FAM.ROUTED_SHARE_LOGPROB_MIN < FAM.ROUTED_SHARE_STATE_MIN < 1
    behind = [1, 2, 3, None]
    assert FAM.share_problem([1.0, 0.81, 1.48, 0.23], behind) is None
    assert "E block 1's" in FAM.share_problem([1.0, 0.4, 1.0, 1.0], behind)
    assert "served state" in FAM.share_problem([0.2, 1.0, 1.0, 1.0], behind)
    assert "served log-prob" in FAM.share_problem([1.0, 1.0, 1.0, -0.1],
                                                  behind)
    assert FAM.share_problem([1.0, 1.0, 1.0, 1.9], behind) is None


# ------------------------------------------------------------ the readers

def _ctx(records, gmm_s=0.3, state=None, open_state=None, moe=None,
         open_moe=None):
    fields = ["seq", "t_launch", "t_done", "steps", "segments",
              "ssd_row_steps", "ssd_chunk_tokens", "moe_rows",
              "moe_touched"]
    rows = [[i + 1, float(i), float(i) + 0.5] + [r.get(f) for f in fields[3:]]
            for i, r in enumerate(records)]
    snap = lambda st, mo, steps, kv: {
        "dispatch_trace": {"fields": fields, "recent": rows,
                           "kv_token_steps": kv},
        "device_loop": {"device_loop_steps": steps},
        "kvcache": {"kinds": {"state": st}} if st else {},
        **({"moe": mo} if mo else {})}
    return {"config": CONF, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_close": snap(state, moe, 1000, 50_000_000),
            "stats_open": snap(open_state, open_moe, 0, 0),
            "trace": {"op_self_total_s": 1.0,
                      "op_self_s": [["_ssd_step.48", 0.03],
                                    ["_ssd_step.49", 0.03],
                                    ["_ssd_chunk.12", 0.02],
                                    ["moe_gmm.32", gmm_s / 2],
                                    ["moe_gmm.33", gmm_s / 2]]}}


RECORD = {"steps": 4, "segments": 2, "ssd_row_steps": 256,
          "ssd_chunk_tokens": 512, "moe_rows": 4 * (768 + 3 * 192),
          "moe_touched": 4 * 4 * 62}


def test_kernel_readers_on_made_up_records(monkeypatch):
    from layer_metrics import (mla_decode_kernel_roofline_pct as mla,
                               moe2_kernel_busy_share_pct as gmm_busy,
                               moe2_kernel_roofline_pct as gmm,
                               ssd_decode_kernel_roofline_pct as base,
                               ssdg_decode_kernel_roofline_pct as dec,
                               ssdg_kernel_busy_share_pct as busy,
                               ssdg_prefill_kernel_roofline_pct as pre)
    pairs = [(None, None, RECORD)] * 3
    joined = lambda pairs: lambda ctx: {"pairs": pairs, "share": 1.0}  # noqa: E731
    for mod in (mla, base):
        monkeypatch.setattr(mod, "join", joined(pairs))
    ctx = _ctx([RECORD] * 3)
    want = 3 * FAM.moe_kernel_bytes(MC, RECORD["moe_rows"],
                                    RECORD["moe_touched"]) / 819e9
    assert gmm.read(ctx) == pytest.approx(100 * want / 0.3)
    assert 0 < gmm.read(ctx) < 100
    assert gmm_busy.read(ctx) == pytest.approx(30.0)
    want = 3 * FAM.ssd_decode_kernel_bytes(MC, 256) / 819e9
    assert dec.read(ctx) == pytest.approx(100 * want / 0.06)
    want = 3 * max(FAM.ssd_prefill_kernel_bytes(MC, 512, 2) / 819e9,
                   FAM.ssd_prefill_kernel_ops(MC, 512) / 197e12)
    assert pre.read(ctx) == pytest.approx(100 * want / 0.02)
    assert 0 < dec.read(ctx) < 100 and 0 < pre.read(ctx) < 100
    assert busy.read(ctx) == pytest.approx(8.0)
    # a program without the columns (the parent): nothing to read, no raise
    bare = [(None, None, {"steps": 4, "segments": 2})] * 3
    for mod in (mla, base):
        monkeypatch.setattr(mod, "join", joined(bare))
    assert gmm.read(ctx) is None and dec.read(ctx) is None
    assert pre.read(ctx) is None
    # a trace without the calls
    ctx["trace"]["op_self_s"] = [["_kda_step.1", 0.1]]
    assert gmm.read(ctx) is None and gmm_busy.read(ctx) is None
    assert dec.read(ctx) is None and busy.read(ctx) is None
    assert busy.read(dict(ctx, trace={})) is None


def test_kernel_readers_where_the_join_fails(monkeypatch, capsys):
    """No pairs: read by the records that ended inside the trace's span,
    by the accepted readers' ``span_share`` itself, unchanged."""
    from layer_metrics import (mla_decode_kernel_roofline_pct as mla,
                               moe2_kernel_roofline_pct as gmm,
                               ssd_decode_kernel_roofline_pct as base,
                               ssdg_decode_kernel_roofline_pct as dec,
                               ssdg_prefill_kernel_roofline_pct as pre)
    assert dec.read is base.read and gmm.span_share is base.span_share
    recs = [dict(RECORD, seq=i, t_launch=100 + 0.1 * i - 0.15,
                 t_done=100 + 0.1 * i) for i in range(8)]
    execs = [[0.0, 0.07e9]] + [[(0.07 + 0.1 * i) * 1e9, 0.1e9]
                               for i in range(4)] + [[0.47e9, 0.02e9]]
    failed = {"pairs": [], "share": 0.98, "offset": 100.13,
              "records": recs, "executions": execs}
    for mod in (mla, base):
        monkeypatch.setattr(mod, "join", lambda ctx: failed)
    ctx = _ctx([RECORD])
    want = 5 * FAM.ssd_decode_kernel_bytes(MC, 256) / 819e9     # 1..5
    assert dec.read(ctx) == pytest.approx(100 * want / 0.06)
    assert "5 records that ended inside" in capsys.readouterr().out
    assert pre.read(ctx) is not None and gmm.read(ctx) is not None
    failed.update(offset=None)      # nothing to join at all
    assert dec.read(ctx) is None and gmm.read(ctx) is None


def test_counter_readers_on_made_up_stats():
    from layer_metrics import (moe2_expert_load_max_over_mean as load,
                               moe2_experts_touched_pct as touched,
                               moe2_rows_held_share_pct as held,
                               ssdg_state_bytes_per_slot as slot,
                               ssdg_state_stream_share_pct as share)
    state = {"slots": 65, "bytes_per_slot": 8_536_064, "held": 64,
             "held_peak": 65, "zeroed": 300, "row_steps": 64_000,
             "chunk_tokens": 200_000}
    moe = {"experts": 64, "experts_routed": 128, "rows": 3_000_000,
           "valid_rows": 6_100_000, "rows_absent": 3_100_000,
           "touched": 240_000, "layer_calls": 4_000,
           "expert_rows": [30_000] * 32 + [60_000] * 32}
    zero = {k: 0 for k in moe} | {"experts": 64, "experts_routed": 128,
                                  "expert_rows": [0] * 64}
    ctx = _ctx([RECORD], state=state, open_state=dict(state, row_steps=0),
               moe=moe, open_moe=zero)
    assert slot.read(ctx) == 8_536_064 == FAM.ssd_state_bytes_per_slot(MC)
    assert held.read(ctx) == pytest.approx(100 * 3.0 / 6.1)
    assert touched.read(ctx) == pytest.approx(100 * 240_000 / (4_000 * 64))
    assert load.read(ctx) == pytest.approx(60 / 45)
    moved = FAM.ssd_decode_kernel_bytes(MC, 64_000)
    weights = 1000 * B.weight_bytes_per_pass(MC)
    pages = 50_000_000 * 1024
    assert share.read(ctx) == pytest.approx(
        100 * moved / (moved + weights + pages))
    assert 10 < share.read(ctx) < 20    # 1.09 of ~7 GB a step
    bare = _ctx([RECORD])               # the parent's program says nothing
    assert slot.read(bare) is None and share.read(bare) is None
    assert held.read(bare) is None and touched.read(bare) is None
    assert load.read(bare) is None


def test_the_manifest_lists_the_cell_and_its_entries():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reason-wide", 1)
    assert NAME in [c["name"] for c in MANIFEST["configs"]]
    mine = {m["name"]: m for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())}
    new = ["moe2_kernel_roofline_pct", "moe2_kernel_busy_share_pct",
           "moe2_experts_touched_pct", "moe2_rows_held_share_pct",
           "ssdg_decode_kernel_roofline_pct",
           "ssdg_prefill_kernel_roofline_pct", "ssdg_kernel_busy_share_pct",
           "ssdg_state_bytes_per_slot", "ssdg_state_stream_share_pct",
           # the review's round: how even the seeded router spreads a step's
           # rows, and the accepted count of contraction tiles
           "moe2_expert_load_max_over_mean", "moe_gmm_k_tiles_max"]
    # membership, not place or equality: a later cell may be appended to
    # these lists, and this cell to others
    assert set(new) <= set(mine)
    for name in new:
        assert CELL in mine[name]["workloads"]
        assert mine[name]["moves"] == "tpot_p50_ms"
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
