"""``families/evabyte.py``: the shape arithmetic against the issue's
numbers, the roofline counts by hand, the equations' mask by hand and
against the program at toy size, and the six new readers on made-up
records."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
CONF = json.loads((BENCH / "configs" / "evabyte-6.5b-bf16.json").read_text())
MC = CONF["model_config"]
FAM = families.load("evabyte")
CELL = "evabyte-6.5b-bf16.longdoc-sat"
CATALOG = {     # the catalog row's numbers, copied: the file holds each
    "chunk_size": 16, "hidden_size": 4096, "init_std": 0.01275,
    "intermediate_size": 11008, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "num_attention_heads": 32,
    "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_theta": 100000, "vocab_size": 320, "window_size": 2048}


# ------------------------------------------------------- shape arithmetic

def test_the_file_holds_the_source_s_numbers_and_names_its_cut():
    for key, value in CATALOG.items():
        assert CONF[key] == value, key
    assert CONF["reduced"] == ["num_hidden_layers"]
    assert CONF["num_hidden_layers"] == MC["num_layers"] == 16
    assert (MC["eva_window"], MC["eva_chunk"]) == (2048, 16)
    assert CONF["attention_class"] == "eva" and CONF["fp32_logits"]
    assert CONF["norm_add_unit_offset"] and CONF["fp32_skip_add"]


def test_a_layer_and_the_cut_by_hand():
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert layer == 202_375_168                  # the issue's 202.4 M
    assert FAM.layer_matrix_elements(MC) == layer + 2 * 32 * 128
    assert FAM.layer_scale_elements(MC) == 4 * 4096 + 2 * 11008 + 4096
    assert 16 * layer * 2 / 2 ** 30 == pytest.approx(6.03, abs=0.005)
    assert 32 * layer * 2 / 2 ** 30 == pytest.approx(12.06, abs=0.005)
    # a pass reads the layers and the 320 columns of the head it reads
    assert B.weight_bytes_per_pass(MC) == pytest.approx(
        16 * FAM.layer_matrix_elements(MC) * 2 + 320 * 4096 * 2)


def test_a_row_a_page_and_the_pool_by_hand():
    assert FAM.row_bytes(MC) == 16 * 2 * 32 * 128 * 2 == 262_144
    assert B.kv_bytes_per_token(MC) == 262_144      # a ROW of the pool
    pool = CONF["pool"]
    assert pool["bytes_per_token"] == 262_144 and pool["block_tokens"] == 128
    assert 128 * 262_144 == 32 << 20                # a page is 32 MiB
    flags = CONF["serve_flags"]
    assert int(flags[flags.index("--kv-cache-blocks") + 1]) == pool["blocks"]
    assert pool["blocks"] * 32 / 1024 >= 5.5        # GiB of pages


@pytest.mark.parametrize("tokens, rows", [
    (0, 0), (1, 1), (2048, 2048), (2049, 129), (4096, 2176), (4097, 257),
    (6144, 2304), (10880, 5 * 128 + 640)])
def test_rows_held_by_hand(tokens, rows):
    assert FAM.rows_held(MC, tokens) == rows


@pytest.mark.parametrize("tokens, pages", [
    (6528, 16 + 4), (10880, 16 + 6), (1000, 8 + 1), (2048, 16 + 1),
    (2049, 16 + 2)])
def test_pages_a_request_leases_by_the_issue_s_formula(tokens, pages):
    """``min(16, ceil(n / 128)) + ceil(n / 2048)``, as the program's
    engine leases them (its own arithmetic, held here by hand)."""
    assert min(16, -(-tokens // 128)) + -(-tokens // 2048) == pages


def test_the_two_roofline_counts_by_hand():
    pair = 4 * 32 * 128
    # ten rows of ~1.5k attended rows: HBM-bound (16 flop a byte < 240)
    assert FAM.eva_decode_kernel_bytes(MC, 15_000) == 15_000 * 262_144
    assert FAM.eva_decode_kernel_ops(MC, 15_000) == 16 * 15_000 * pair
    assert (FAM.eva_decode_kernel_bytes(MC, 15_000) / 819e9
            > FAM.eva_decode_kernel_ops(MC, 15_000) / 197e12)
    # a chunk of 256 tokens over 1,536 rows: compute-bound
    pairs = 256 * 1536
    assert FAM.eva_prefill_kernel_ops(MC, pairs) == 16 * pairs * pair
    assert FAM.eva_prefill_kernel_bytes(MC, pairs, 256) == 1536 * 262_144
    assert (FAM.eva_prefill_kernel_ops(MC, pairs) / 197e12
            > FAM.eva_prefill_kernel_bytes(MC, pairs, 256) / 819e9)
    # pooling one chunk: 16 rows read and one written, a layer
    assert FAM.eva_summarise_kernel_bytes(MC, 1) == 17 * 262_144
    assert FAM.eva_summarise_kernel_ops(MC, 1) == 16 * 32 * 16 * 128 * 8


# ------------------------------------------------------------ the equations

def test_which_summaries_a_query_sees_by_hand():
    import numpy as np
    seen = np.asarray(FAM.summaries_seen(24, 15, 34, 16, 2))
    assert seen.shape == (19, 24)
    assert not seen[0].any()                         # token 15: window 0
    assert seen[1, :8].all() and not seen[1, 8:].any()   # token 16
    assert seen[17, :16].all() and not seen[17, 16:].any()   # token 32
    assert (seen[16] == seen[1]).all()               # token 31, as token 16


@pytest.mark.parametrize("leaves", ["float32", "bfloat16"])
def test_the_equations_agree_with_the_program_at_toy_size(leaves):
    """40 ids at the toy window of 16: two windows close.  bf16 leaves are
    read as data by both sides (the program in float32 arithmetic), so
    the agreement is the float32 one."""
    import jax
    import jax.numpy as jnp

    import reference
    from distributed_inference_demo_tpu.models.base import (KVCache,
                                                            ModelConfig,
                                                            StageSpec)
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
    assert max(abs(a - b) for a, b in zip(mine, out["logprobs"])) < 2e-4
    # the other heads' columns are held and never read
    assert stored.lm_head["w"].shape[1] == 3 * cfg.vocab_size


def test_a_summary_withheld_moves_the_reference_past_the_first_window():
    import jax
    import numpy as np

    import reference
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    toy = CONF["rehearsal"]["model_config"]
    params = init_full_params(jax.random.PRNGKey(4), ModelConfig(**toy))
    ids = [(5 * i + 1) % 64 for i in range(40)]
    sound = reference.emitted_logprobs(params, toy, ids, 4)["logprobs"]
    kept = FAM.summaries_seen
    try:
        FAM.summaries_seen = lambda n, lo, hi, w, c: np.zeros((hi - lo, n),
                                                              bool)
        blind = reference.emitted_logprobs(params, toy, ids, 4)["logprobs"]
    finally:
        FAM.summaries_seen = kept
    diff = np.abs(np.asarray(sound) - np.asarray(blind))
    assert diff[:12].max() == 0.0 and diff[12:].max() > 1e-3


# ------------------------------------------------- readers on made-up runs

def _ctx(records, kernel_s, kvcache=None):
    fields = ["seq", "t_launch", "t_done", "steps", "active_rows", "finals",
              "kv_attended_rows", "kv_summary_rows", "prefill_attended_rows"]
    rows = [[i + 1, float(i), float(i) + 0.5] + [r[f] for f in fields[3:]]
            for i, r in enumerate(records)]
    stats = {"dispatch_trace": {"fields": fields, "recent": rows},
             "kvcache": kvcache or {}}
    return {"config": CONF, "health": {"device_kind": "TPU v5 lite"},
            "stats_close": stats, "stats_open": {},
            "trace": {"op_self_total_s": 1.0,
                      "op_self_s": [["_paged_call_eva.9", kernel_s],
                                    ["_paged_prefill_call_eva.4", kernel_s],
                                    ["_eva_summarise.9", kernel_s],
                                    ["_paged_call.23", 1.0]]}}


RECORD = {"steps": 4, "active_rows": 10, "finals": 0,
          "kv_attended_rows": 15_000, "kv_summary_rows": 5_000,
          "prefill_attended_rows": 256 * 1536}


def test_kernel_readers_on_made_up_records(monkeypatch):
    from layer_metrics import (eva_decode_kernel_roofline_pct as dec,
                               eva_prefill_kernel_roofline_pct as pre,
                               eva_summarise_busy_share_pct as busy,
                               eva_summarise_kernel_roofline_pct as pool,
                               mla_decode_kernel_roofline_pct as mla)
    records = [RECORD] * 3
    pairs = [(None, None, r) for r in records]
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": pairs,
                                                  "share": 1.0})
    ctx = _ctx(records, 0.02)
    want = 3 * 4 * 15_000 * 262_144 / 819e9
    assert dec.read(ctx) == pytest.approx(100 * want / 0.02)
    want = 3 * FAM.eva_prefill_kernel_ops(MC, 256 * 1536) / 197e12
    assert pre.read(ctx) == pytest.approx(100 * want / 0.02)
    assert busy.read(ctx) == pytest.approx(2.0)
    want = 3 * FAM.eva_summarise_kernel_bytes(MC, 4 * 10 / 16) / 819e9
    assert pool.read(ctx) == pytest.approx(100 * want / 0.02)
    # a program without the columns (the parent): nothing to read, no raise
    bare = [(None, None, {"steps": 4, "active_rows": 1, "finals": 0})] * 3
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": bare,
                                                  "share": 1.0})
    assert dec.read(ctx) is None and pre.read(ctx) is None
    # ... or without the kernels in its trace
    ctx["trace"] = {"op_self_total_s": 1.0,
                    "op_self_s": [["_paged_call.23", 1.0]]}
    monkeypatch.setattr(mla, "join", lambda ctx: {"pairs": pairs,
                                                  "share": 1.0})
    assert [r.read(ctx) for r in (dec, pre, busy, pool)] == [None] * 4


def test_counter_readers_on_made_up_stats():
    from layer_metrics import eva_rows_held_share_pct as held
    from layer_metrics import eva_summary_row_share_pct as share
    eva = {"eva": {"rows_held_peak": 25_000, "tokens_held_peak": 90_000}}
    ctx = _ctx([RECORD, dict(RECORD, steps=0, kv_summary_rows=1)], 0.0,
               kvcache=eva)
    assert held.read(ctx) == pytest.approx(100 * 25 / 90)
    assert share.read(ctx) == pytest.approx(100 / 3)
    # the parent: no section, no columns, nothing to read
    parent = _ctx([], 0.0, kvcache={"blocks_used": 1})
    parent["stats_close"]["dispatch_trace"] = {
        "fields": ["seq", "steps"], "recent": [[1, 4]]}
    assert held.read(parent) is None and share.read(parent) is None


def test_the_manifest_lists_the_cell_where_the_issue_says():
    m = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {x["name"] for x in m["per_layer"]
              if CELL in x.get("workloads", [])}
    assert listed == {
        "eva_decode_kernel_roofline_pct", "eva_prefill_kernel_roofline_pct",
        "eva_summarise_busy_share_pct", "eva_summarise_kernel_roofline_pct",
        "eva_rows_held_share_pct", "eva_summary_row_share_pct"}
    assert all(x["moves"] == "tpot_p50_ms" for x in m["per_layer"]
               if x["name"].startswith("eva_"))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-sat"
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text()) == {
        "clients": 12}
    conf = next(c for c in m["configs"] if c["name"] == "evabyte-6.5b-bf16")
    assert conf["reduced"] == ["num_hidden_layers"]
