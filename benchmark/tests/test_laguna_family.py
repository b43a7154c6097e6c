"""``families/laguna.py``: the shape arithmetic against the issue's table,
the equations against the program at toy size, the reference's window mask
and YaRN table by hand, and the four new readers on made-up records."""
import importlib
import json
import math
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
CONF = json.loads((BENCH / "configs" / "laguna-s-2.1-bf16-ep4.json")
                  .read_text())
MC = CONF["model_config"]
FAM = families.load("laguna")
WINDOW, FULL = MC["period"][0], MC["period"][3]
CELL = "laguna-s-2.1-bf16-ep4.mixedlen-sat"


# ------------------------------------------------------- shape arithmetic

def test_block_elements_by_hand():
    # q and o 3072 x (heads x 128) each, k and v 3072 x 1024 each, the gate
    assert FAM.attention_elements(MC, FULL) == (
        2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48) == 44_187_648
    assert FAM.attention_elements(MC, WINDOW) == (
        2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72) == 63_135_744
    expert = 3 * 3072 * 1024
    assert expert == 9_437_184 and 3072 * 256 == 786_432
    # attention + router (256 wide) + 64 held + 1 shared
    assert FAM.expert_block_elements(MC, WINDOW) == (
        63_135_744 + 786_432 + 65 * expert) == 677_339_136
    assert FAM.expert_block_elements(MC, FULL) == 658_391_040
    assert FAM.lead_block_elements(MC) == 44_187_648 + 3 * 3072 * 12288 \
        == 157_433_856
    # bytes.py multiplies by num_layers (1 repeat): the cut's five blocks
    cut = 3 * 677_339_136 + 658_391_040 + 157_433_856
    assert MC["num_layers"] * B.layer_matrix_elements(MC) == cut
    # with embedding and head: 6.93 GB = 6.45 GiB (the issue's table)
    params = cut + 2 * 100352 * 3072
    assert params * 2 / 1e9 == pytest.approx(6.93, abs=0.005)
    assert params * 2 / 2 ** 30 == pytest.approx(6.45, abs=0.005)
    assert B.weight_bytes_per_pass(MC, "none") == pytest.approx(
        cut * 2 + 100352 * 3072 * 2)


@pytest.mark.parametrize("held,gb", [(256, 21.4), (128, 11.76), (64, 6.93)])
def test_the_issue_s_table_of_cuts(held, gb):
    mc = dict(MC, experts_held=[held, 0])
    params = B.layer_matrix_elements(mc) + 2 * 100352 * 3072
    assert params * 2 / 1e9 == pytest.approx(gb, rel=0.003)


def test_pool_bytes_by_kind_by_hand():
    # full kind: the leading block and the period's one, keys and values
    assert B.kv_bytes_per_token(MC) == 2 * 2 * 8 * 128 * 2 == 8192
    assert FAM.window_kv_bytes_per_token(MC) == 3 * 2 * 8 * 128 * 2 == 12288
    pool = CONF["pool"]
    assert pool["bytes_per_token"] == 8192
    assert pool["window_bytes_per_token"] == 12288
    # the engine's own sizing of the window pool: (slots + 1) quotas of
    # ceil((512 + 2 x 640) / 128) + 1 pages
    assert pool["window_blocks"] == 17 * (math.ceil((512 + 1280) / 128) + 1)


def test_window_kernel_functions_by_hand():
    # 16 rows deep in their prompts: 16 x 512 keys, three window blocks
    assert FAM.window_decode_kernel_bytes(MC, 8192) == 8192 * 12288
    pair = 4 * 72 * 128
    assert FAM.window_decode_kernel_ops(MC, 8192) == 3 * 8192 * pair
    # HBM-bound: 9 flop a byte x 2 under the ridge of 240
    assert (FAM.window_decode_kernel_bytes(MC, 8192) / 819e9
            > FAM.window_decode_kernel_ops(MC, 8192) / 197e12)
    # a chunk of 256 tokens deep in a prompt: 256 x 512 pairs
    pairs = 256 * 512
    assert FAM.window_prefill_kernel_ops(MC, pairs) == 3 * pairs * pair
    assert FAM.window_prefill_kernel_bytes(MC, pairs, 256) == 512 * 12288
    assert (FAM.window_prefill_kernel_ops(MC, pairs) / 197e12
            > FAM.window_prefill_kernel_bytes(MC, pairs, 256) / 819e9)
    assert FAM.moe_kernel_ops(MC, 100) == 2 * 100 * 9_437_184


def test_configuration_file_states_what_it_must():
    assert CONF["reduced"] == ["num_hidden_layers", "num_experts"]
    assert CONF["num_experts"] == 64 and MC["num_experts"] == 256
    assert MC["experts_held"] == [64, 0]
    for key, value in (("hidden_size", 3072), ("head_dim", 128),
                       ("intermediate_size", 12288), ("sliding_window", 512),
                       ("moe_intermediate_size", 1024),
                       ("num_experts_per_tok", 10), ("vocab_size", 100352),
                       ("num_key_value_heads", 8)):
        assert CONF[key] == value, key
    assert len(CONF["layer_types"]) == 48
    # the five blocks that run are the published list's first five
    kinds = [MC["lead_kind"]] + MC["period"]
    assert [{"full": "full_attention", "window": "sliding_attention"}
            [k["attn"]] for k in kinds] == CONF["layer_types"][:5]
    assert [k["num_heads"] for k in kinds] == \
        CONF["num_attention_heads_per_layer"][:5]
    yarn = CONF["rope_parameters"]["full_attention"]
    assert FULL["yarn"] == {k: yarn[k] for k in FULL["yarn"]}
    assert len(CONF["assumed"]) >= 8 and "four chips" in CONF["deployment"]


# ----------------------------------------------- the mask and the YaRN table

def test_window_mask_by_hand():
    """Query 600 of a window block sees keys 89 .. 600 and no other."""
    seen = [j for j in range(700) if FAM.allowed(600, j, 512)]
    assert seen == list(range(89, 601))
    assert [j for j in range(5) if FAM.allowed(3, j, 512)] == [0, 1, 2, 3]


def test_yarn_table_by_hand():
    inv, rd, factor = FAM.kind_inv_freq(MC, FULL)
    assert rd == 64 and len(inv) == 32 and factor == 1.4852030263919618
    dim = lambda rot: 64 * math.log(8192 / (rot * 2 * math.pi)) / (
        2 * math.log(5e5))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (9, 18)
    extrap = lambda i: 5e5 ** (-2 * i / 64)
    assert inv[:10] == pytest.approx([extrap(i) for i in range(10)])
    assert inv[18:] == pytest.approx([extrap(i) / 128 for i in range(18, 32)])
    assert inv[12] == pytest.approx(extrap(12) * (2 / 3 + 1 / 3 / 128))
    plain, rd_w, one = FAM.kind_inv_freq(MC, WINDOW)
    assert (rd_w, one) == (128, 1.0) and plain[1] == pytest.approx(
        1e4 ** (-2 / 128))


# ------------------------------------------- equations against the program

@pytest.mark.parametrize("leaves", ["float32", "bfloat16"])
def test_reference_equals_the_program_at_toy_size(leaves):
    """The family's period (every held expert for every row, a mask written
    out) through ``reference.emitted_logprobs`` against the program's
    ``stage_forward`` (sorted rows, a dense cache under a window) on the
    same leaves: stored as bf16 the reference reads them as float32 and the
    program is given them widened, so the agreement stays float32's."""
    import jax
    import jax.numpy as jnp
    import reference
    from distributed_inference_demo_tpu.models import KVCache, StageSpec
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params, stage_forward)
    toy = CONF["rehearsal"]["model_config"]
    cfg = ModelConfig(**toy)
    stored = jax.tree.map(lambda a: a.astype(leaves),
                          init_full_params(jax.random.PRNGKey(4), cfg))
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), stored)
    ids = [int(v) for v in jax.random.randint(jax.random.PRNGKey(1), (40,),
                                              0, cfg.vocab_size)]
    logits, _ = stage_forward(
        wide, cfg, StageSpec(0, 1, 0, cfg.num_layers), jnp.asarray([ids]),
        KVCache.create(cfg, cfg.num_layers, 1, 48), jnp.arange(40)[None])
    lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
    out = reference.emitted_logprobs(stored, toy, ids, 12)
    mine = [float(lp[t - 1, ids[t]]) for t in range(12, 40)]
    assert max(abs(a - b) for a, b in zip(mine, out["logprobs"])) < 5e-5


# ------------------------------------------------- readers on made-up runs

def _ctx(records, kernel_s, kvcache=None, moe=None):
    """A run with ``records`` matched one to one and ``kernel_s`` seconds
    of each window kernel in the trace."""
    fields = ["seq", "t_launch", "t_done", "steps", "kv_window_tokens",
              "prefill_window_pairs"]
    rows = [[i + 1, float(i), float(i) + 0.5, r["steps"],
             r["kv_window_tokens"], r["prefill_window_pairs"]]
            for i, r in enumerate(records)]
    stats = {"dispatch_trace": {"fields": fields, "records": rows},
             "kvcache": kvcache or {}, "moe": moe or {}}
    return {"config": CONF, "health": {"device_kind": "TPU v5 lite"},
            "stats_close": stats, "stats_open": {"moe": {k: 0 for k in moe or {}}},
            "trace": {"op_self_s": [["_paged_call_window.36", kernel_s],
                                    ["_paged_prefill_call_window.6",
                                     kernel_s],
                                    ["_paged_call.23", 1.0]]}}


def test_window_readers_on_made_up_records(monkeypatch):
    from layer_metrics import (mla_decode_kernel_roofline_pct as mla,
                               window_decode_kernel_roofline_pct as dec,
                               window_prefill_kernel_roofline_pct as pre)
    records = [{"steps": 4, "kv_window_tokens": 8192,
                "prefill_window_pairs": 256 * 512}] * 3
    pairs = [(None, None, r) for r in records]
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": pairs,
                                                  "share": 1.0})
    ctx = _ctx(records, 0.01)
    want = 3 * 4 * 8192 * 12288 / 819e9
    assert dec.read(ctx) == pytest.approx(100 * want / 0.01)
    want = 3 * FAM.window_prefill_kernel_ops(MC, 256 * 512) / 197e12
    assert pre.read(ctx) == pytest.approx(100 * want / 0.01)
    # a program without the columns (the parent): nothing to read, no raise
    bare = [(None, None, {"steps": 4})] * 3
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": bare,
                                                  "share": 1.0})
    assert dec.read(ctx) is None and pre.read(ctx) is None
    ctx["trace"] = {"op_self_s": [["_paged_call.23", 1.0]]}
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": pairs,
                                                  "share": 1.0})
    assert dec.read(ctx) is None and pre.read(ctx) is None


def test_counter_readers_on_made_up_stats():
    from layer_metrics import kv_window_held_share_pct as held
    from layer_metrics import moe_rows_held_share_pct as rows
    kinds = {"kinds": {"window": {"pages_held_peak": 90,
                                  "pages_unwindowed_peak": 800,
                                  "pages_returned": 5000}}}
    moe = {"rows": 2500, "valid_rows": 10_000, "rows_absent": 7500}
    ctx = _ctx([], 0.0, kvcache=kinds, moe=moe)
    assert held.read(ctx) == pytest.approx(11.25)
    assert rows.read(ctx) == pytest.approx(25.0)
    # a program that holds every expert, or has one pool: nothing to read
    parent = _ctx([], 0.0, kvcache={"blocks_used": 1},
                  moe={"rows": 10, "valid_rows": 10})
    assert held.read(parent) is None and rows.read(parent) is None
    assert held.read(_ctx([], 0.0)) is None and rows.read(_ctx([], 0.0)) is None


def test_the_manifest_lists_the_cell_where_the_issue_says():
    m = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {x["name"] for x in m["per_layer"]
              if CELL in x.get("workloads", [])}
    assert listed == {
        "moe_kernel_busy_share_pct", "moe_kernel_roofline_pct",
        "moe_expert_load_max_over_mean", "moe_experts_touched_pct",
        "window_decode_kernel_roofline_pct",
        "window_prefill_kernel_roofline_pct", "kv_window_held_share_pct",
        "moe_rows_held_share_pct"}
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "mixedlen-sat"
