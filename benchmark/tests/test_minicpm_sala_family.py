"""``families/minicpm_sala.py``: the file against the catalog's numbers, the
shape arithmetic against the issue's and against the program's parameter
tree, the kernels' counts by hand (fixed before any reading), the family's
contract, the replay against the program's served path at toy size and on a
rounded state, and the new readers on made-up records."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH.parent / "tools"))
B = importlib.import_module("bytes")          # benchmark/bytes.py
NAME = "minicpm-sala-9b-bf16"
CONF = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
MC = CONF["model_config"]
FAM = families.load("minicpm_sala")
CELL = f"{NAME}.longctx-32k"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CATALOG = {     # the catalog row's numbers, copied: the file holds each
    "head_dim": 128, "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "max_position_embeddings": 524288, "num_attention_heads": 32,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256}
SPARSE, LIGHT = MC["period"][0], MC["period"][1]
NEW = ["sparse_decode_kernel_roofline_pct",
       "sparse_prefill_kernel_roofline_pct", "sparse_kernel_busy_share_pct",
       "sparse_select_busy_share_pct", "sparse_blocks_kept_share_pct",
       "la_decode_kernel_roofline_pct",
       "la_prefill_kernel_roofline_pct", "la_kernel_busy_share_pct",
       "la_state_bytes_per_slot"]


# ------------------------------------------------------- shape arithmetic

def test_the_file_holds_the_source_s_numbers_and_names_its_cut():
    for key, value in CATALOG.items():
        assert CONF[key] == value, key
    assert CONF["model_type"] == "minicpm_sala" and CONF["qk_norm"]
    assert not CONF["attn_use_rope"] and CONF["lightning_use_rope"]
    assert CONF["use_output_gate"] and CONF["use_output_norm"]
    assert CONF["attn_use_output_gate"] and not CONF["tie_word_embeddings"]
    mixers = CONF["mixer_types"]
    assert len(mixers) == 32 and mixers.count("minicpm4") == 8
    assert mixers[9:17] == (["minicpm4"] + ["lightning-attn"] * 6
                            + ["minicpm4"])
    assert CONF["reduced"] == ["num_hidden_layers"]
    assert CONF["published"] == {"num_hidden_layers": 32}
    # (repeats of the period, as every period model's file counts them)
    assert CONF["num_hidden_layers"] == 1 == MC["num_layers"]
    assert len(MC["period"]) == 8
    assert [k["attn"] for k in MC["period"]] == (
        ["sparse"] + ["lightning"] * 6 + ["sparse"])
    assert MC["residual_multiplier"] == pytest.approx(1.4 / 32 ** 0.5)
    assert MC["logits_scaling"] == 4096 / 256 and not MC["tie_embeddings"]
    assert len(CONF["assumed"]) >= 9 and all(
        word in " ".join(CONF["assumed"]) for word in (
            "kernel_size 32", "dense_len 8192", "BESIDE", "ties",
            "exp(-2 ** (-8 (h + 1) / 32))"))
    assert FAM.sparse_sizes(MC) == (32, 16, 64, 64, 1, 2048, 8192)


def test_a_layer_and_the_cut_by_the_issue_s_arithmetic():
    mlp = 3 * 4096 * 16384
    assert round((FAM.mixer_elements(MC, LIGHT) + mlp) / 1e6, 1) == 285.2
    assert round((FAM.mixer_elements(MC, SPARSE) + mlp) / 1e6, 1) == 253.8
    layers = FAM.layer_matrix_elements(MC)
    assert round(layers / 1e6, 1) == 2218.8
    total = layers + 2 * 73448 * 4096
    assert round(total / 1e6, 1) == 2820.5 and round(2 * total / 2 ** 30,
                                                     2) == 5.25
    # the published depth: 24 linear + 8 sparse layers, embedding and head
    full = (24 * (FAM.mixer_elements(MC, LIGHT) + mlp)
            + 8 * (FAM.mixer_elements(MC, SPARSE) + mlp) + 2 * 73448 * 4096)
    assert round(full / 1e9, 2) == 9.48


def test_the_arithmetic_counts_the_program_s_parameter_tree():
    import jax
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import init_full_params
    toy = CONF["rehearsal"]["model_config"]
    params = init_full_params(jax.random.PRNGKey(0), ModelConfig(**toy))
    norms = 2 * 64 * len(toy["period"])
    held = sum(a.size for a in jax.tree.leaves(params.layers))
    assert held == FAM.layer_matrix_elements(toy) + norms


def test_a_token_a_slot_and_the_pool_by_hand():
    assert FAM.kv_bytes_per_token(MC) == 2 * 2 * 2 * 128 * 2 + 2 * 32 == 2112
    assert FAM.index_bytes_per_token(MC) == 64
    assert FAM.la_state_bytes(MC) == 32 * 128 * 128 * 4
    assert FAM.la_state_bytes_per_slot(MC) == 12582912
    pool = CONF["pool"]
    assert pool["bytes_per_token"] == 2112
    assert pool["state_bytes_per_slot"] == 12582912
    flags = CONF["serve_flags"]
    slots = int(flags[flags.index("--batch-slots") + 1])
    assert pool["state_slots"] == slots + 1
    assert pool["blocks"] == int(flags[flags.index("--kv-cache-blocks") + 1])
    # twelve requests of the longest prompt and answer hold their pages
    assert 12 * -(-(40960 + 640) // 128) <= pool["blocks"]
    assert int(flags[flags.index("--max-seq") + 1]) >= 40960 + 640


def test_the_kernels_counts_by_hand():
    # one query past dense_len: 97 blocks of 64 keys and values a kv head,
    # and the index rows its position has closed
    kept, rows = 97, (30000 + 1 - 32) // 16 + 1
    assert FAM.sparse_kernel_bytes(MC, kept, rows) == 2 * 2 * (
        97 * 2 * 64 * 128 * 2 + rows * 128 * 2)
    assert FAM.sparse_kernel_ops(MC, kept, rows) == 2 * 32 * (
        97 * 4 * 64 * 128 + rows * 2 * 128)
    assert FAM.la_decode_kernel_ops(MC, 12) == 6 * 12 * 5 * 32 * 128 * 128
    assert FAM.la_decode_kernel_bytes(MC, 12) == 6 * 12 * (
        2 * 32 * 128 * 128 * 4 + 32 * 128 * (3 * 2 + 4))
    assert FAM.la_prefill_kernel_ops(MC, 256) == 6 * 256 * 32 * (
        2 * 256 * 128 + 2 * 256 * 128 + 4 * 128 * 128)
    assert FAM.la_prefill_kernel_bytes(MC, 256, 1) == 6 * 2 * 32 * 128 * 128 * 4


def test_the_family_keeps_the_contract():
    families.require("minicpm_sala")
    embed, layer, final_norm = FAM.equations(MC)
    assert callable(embed) and callable(layer) and callable(final_norm)
    assert callable(FAM.replay(MC))     # left to right; it holds the STATE
    src = (BENCH / "families" / "minicpm_sala.py").read_text()
    assert "distributed_inference_demo_tpu" not in src
    assert FAM.layer_scale_elements(MC) == (
        2 * ((64 + 4) * 128 + 4096 + 2 * 16384 + 4096)
        + 6 * ((64 + 64) * 128 + 4096 + 2 * 16384 + 4096))


# ------------------------------------------------- the replay and the state

TOY = CONF["rehearsal"]["model_config"]
N_PROMPT = 150


def record_of(state, dtype="float32", heads=range(4), keys=range(16)):
    """A reply's ``lightning_state`` as the engine writes it, from one
    row's states ``[planes, heads, value, key]``: every head and row of the
    toy state."""
    import base64
    import numpy as np
    got = np.asarray(state, "<f4")[:, list(heads)][:, :, list(keys)]
    return {"pool_dtype": dtype, "heads": list(heads), "keys": list(keys),
            "shape": list(got.shape),
            "float32_b64": base64.b64encode(got.tobytes()).decode("ascii")}


@pytest.fixture(scope="module")
def program():
    """The toy model through the SERVED path (pages, the index plane, the
    state pool: a dense cache has no index plane): prefill in chunks, six
    greedy tokens, and the whole state the request ended in."""
    import jax
    import numpy as np
    import model_parity
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import init_full_params

    cfg = ModelConfig(**TOY)
    params = init_full_params(jax.random.PRNGKey(3), cfg)
    prompt = model_parity.seeded_ids(5, N_PROMPT, cfg.vocab_size)

    def serve(steps):
        args = type("A", (), dict(page=32, chunk=32, steps=steps,
                                  kv_dtype="bf16"))
        toks, lps, _, _ = model_parity.served(cfg, params, prompt[None],
                                              args)
        return toks[0], lps[0]

    return cfg, params, prompt, serve


def _whole_state(cfg, params, ids):
    """The reference's states after ``ids``, whole."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference
    mc = dataclasses.asdict(cfg)
    embed, _, _ = FAM.equations(mc)
    layer = reference._make_layer_fn(FAM.blocks(mc)[1])
    with jax.default_matmul_precision("highest"):
        x, planes = layer(embed(params, jnp.asarray(ids, jnp.int32)),
                          params.layers, jnp.int32(0))
    return np.stack([np.asarray(S) for S in planes])


def test_the_replay_agrees_with_the_program_at_toy_size(program):
    import numpy as np
    import reference
    cfg, params, prompt, serve = program
    toks, lps = serve(6)
    ids = [int(t) for t in prompt] + [int(t) for t in toks]
    want = [float(lps[i, t]) for i, t in enumerate(toks)]
    state = _whole_state(cfg, params, ids[:-1])
    got = reference.emitted_logprobs(
        params, TOY, ids, N_PROMPT, {"lightning_state": record_of(state)})
    assert got["logprobs"] == pytest.approx(want, abs=2e-4)
    assert got["best_ids"] == [int(lps[i].argmax()) for i in range(6)]
    sample, heads, keys, dtype = FAM.state_sample(record_of(state))
    assert sample.shape == (2, 4, 16, 16)       # the two linear planes


def test_the_replay_refuses_what_is_not_the_configuration_s(program):
    import numpy as np
    import jax.numpy as jnp
    import reference
    cfg, params, prompt, serve = program
    toks, _ = serve(6)
    ids = [int(t) for t in prompt] + [int(t) for t in toks]
    state = _whole_state(cfg, params, ids[:-1])
    score = lambda gen: reference.emitted_logprobs(  # noqa: E731
        params, TOY, ids, N_PROMPT, gen)
    rounded = np.asarray(jnp.asarray(state).astype(jnp.bfloat16).astype(
        jnp.float32))
    assert "rounding to bfloat16" in score(
        {"lightning_state": record_of(rounded)})["error"]
    assert "not the float32 state" in score(
        {"lightning_state": record_of(state, dtype="bfloat16")})["error"]
    # the state a token late: the reference's after one more id
    late = _whole_state(cfg, params, ids)
    assert "not the reference's" in score(
        {"lightning_state": record_of(late)})["error"]
    assert "no generation.lightning_state" in score(None)["error"]
    assert "reference's sample" in score(
        {"lightning_state": record_of(state[:1])})["error"]


# ------------------------------------------------------------ the readers

RECORD = {"steps": 4, "segments": 5, "lightning_row_steps": 48,
          "lightning_chunk_tokens": 1280,
          "sparse_blocks_live": 1280 * 470 + 48 * 500,
          "sparse_blocks_kept": 1328 * 97,
          "sparse_index_rows": 1328 * 1900,
          "sparse_decode_blocks_kept": 48 * 97,
          "sparse_decode_index_rows": 48 * 1900}


def _ctx(records, sparse=None, open_sparse=None, state=None):
    fields = ["seq", "t_launch", "t_done"] + list(RECORD)
    rows = [[i + 1, float(i), float(i) + 0.5] + [r.get(f) for f in fields[3:]]
            for i, r in enumerate(records)]
    snap = lambda sp, st: {  # noqa: E731
        "dispatch_trace": {"fields": fields, "recent": rows},
        "kvcache": {"kinds": {"state": st}} if st else {},
        **({"sparse": sp} if sp else {})}
    return {"config": CONF, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_close": snap(sparse, state),
            "stats_open": snap(open_sparse, state),
            "trace": {"op_self_total_s": 1.0,
                      "op_self_s": [["_paged_call_sparse.14", 0.02],
                                    ["_paged_call_sparse.15", 0.02],
                                    ["_paged_prefill_call_sparse.4", 0.12],
                                    ["_sparse_scores.9", 0.01],
                                    ["_la_step.42", 0.03],
                                    ["_la_chunk.24", 0.06]]}}


def test_kernel_readers_on_made_up_records(monkeypatch):
    from layer_metrics import (la_decode_kernel_roofline_pct as la_dec,
                               la_kernel_busy_share_pct as la_busy,
                               la_prefill_kernel_roofline_pct as la_pre,
                               mla_decode_kernel_roofline_pct as mla,
                               sparse_decode_kernel_roofline_pct as dec,
                               sparse_kernel_busy_share_pct as busy,
                               sparse_prefill_kernel_roofline_pct as pre,
                               sparse_select_busy_share_pct as select,
                               ssd_decode_kernel_roofline_pct as base)
    pairs = [(None, None, RECORD)] * 3
    joined = lambda pairs: lambda ctx: {"pairs": pairs, "share": 1.0}  # noqa: E731
    for mod in (mla, base):
        monkeypatch.setattr(mod, "join", joined(pairs))
    ctx = _ctx([RECORD] * 3)
    want = 3 * FAM.sparse_kernel_bytes(MC, 48 * 97, 48 * 1900) / 819e9
    assert dec.read(ctx) == pytest.approx(100 * want / 0.04)
    # a slab's fold is held to its products alone (its queries share reads)
    ops = 3 * FAM.sparse_kernel_ops(MC, 1280 * 97, 1280 * 1900) / 197e12
    assert pre.read(ctx) == pytest.approx(100 * ops / 0.12)
    assert busy.read(ctx) == pytest.approx(16.0)
    assert select.read(ctx) == pytest.approx(1.0)
    want = 3 * FAM.la_decode_kernel_bytes(MC, 48) / 819e9
    assert la_dec.read(ctx) == pytest.approx(100 * want / 0.03)
    want = 3 * max(FAM.la_prefill_kernel_bytes(MC, 1280, 5) / 819e9,
                   FAM.la_prefill_kernel_ops(MC, 1280) / 197e12)
    assert la_pre.read(ctx) == pytest.approx(100 * want / 0.06)
    assert la_busy.read(ctx) == pytest.approx(9.0)
    for mod in (dec, pre, la_dec, la_pre):
        assert 0 < mod.read(ctx) < 100
    # a program without the columns (the parent): nothing to read, no raise
    bare = [(None, None, {"steps": 4, "segments": 2})] * 3
    for mod in (mla, base):
        monkeypatch.setattr(mod, "join", joined(bare))
    for mod in (dec, pre, la_dec, la_pre):
        assert mod.read(ctx) is None
    # a trace without the calls
    ctx["trace"]["op_self_s"] = [["_ssd_step.1", 0.1]]
    for mod in (dec, pre, busy, select, la_dec, la_pre, la_busy):
        assert mod.read(ctx) is None
    assert busy.read(dict(ctx, trace={})) is None


def test_counter_readers_on_made_up_stats():
    from layer_metrics import (la_state_bytes_per_slot as slot,
                               sparse_blocks_kept_share_pct as share)
    sparse = {"queries_dense": 10, "queries_sparse": 90_000,
              "blocks_live": 45_000_000, "blocks_kept": 8_730_000,
              "index_rows": 170_000_000, "device_blocks_kept": 8_730_000.0,
              "device_kept_a_sparse_query": 97.0}
    state = {"slots": 17, "bytes_per_slot": 12_582_924, "held": 12}
    ctx = _ctx([RECORD], sparse=sparse,
               open_sparse={k: 0 for k in sparse}, state=state)
    assert share.read(ctx) == pytest.approx(19.4)
    # the scheduler's arithmetic alone (no device counter) is not read
    host = {k: v for k, v in sparse.items() if not k.startswith("device")}
    assert share.read(_ctx([RECORD], sparse=host,
                           open_sparse={k: 0 for k in host})) is None
    assert slot.read(ctx) == 12_582_924 == (
        FAM.la_state_bytes_per_slot(MC) + 6 * 2)
    bare = _ctx([RECORD])               # the parent's program says nothing
    assert share.read(bare) is None and slot.read(bare) is None


def test_the_manifest_lists_the_cell_and_its_entries():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longctx-32k", 1)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONF["source"]
    mine = {m["name"]: m for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "tpot_p50_ms"
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    mix = json.loads((BENCH / "traffic" / "longctx-32k.json").read_text())
    assert mix["generator"] == "closed_loop"
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 24576,
                                    "max": 40960}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 384, "max": 640}
    assert (mix["stagger_s"], mix["ramp_s"], mix["drain_s"]) == (0.05, 20, 30)
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text()) == {
        "clients": 12}
