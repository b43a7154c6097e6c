"""``families/deepseek_v3.py``: the equations against the program at toy
size, every block exactly once, the shape arithmetic pinned by hand, and
the three readers of the latent layer's metrics on a synthetic run."""
import importlib
import json
import sys
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
CONF = json.loads((BENCH / "configs" / "kanana-2-30b-a3b-bf16.json")
                  .read_text())
MC = CONF["model_config"]
FAM = families.load("deepseek_v3")


# ------------------------------------------------------- shape arithmetic

def test_layer_elements_by_hand():
    # q 2048x6144, kv_a 2048x576, kv_b 512x8192, o 4096x2048
    assert FAM.attention_elements(MC) == (12_582_912 + 1_179_648
                                          + 4_194_304 + 8_388_608) \
        == 26_345_472
    expert = 3 * 2048 * 768
    assert expert == 4_718_592
    # attention + 2 shared + router 2048x128 + 128 routed
    assert FAM.expert_layer_matrix_elements(MC) == (
        26_345_472 + 9_437_184 + 262_144 + 603_979_776) == 640_024_576
    assert FAM.lead_layer_matrix_elements(MC) == 26_345_472 \
        + 3 * 2048 * 6144 == 64_094_208
    # bytes.py multiplies by num_layers (7): the whole model's 8 blocks
    assert MC["num_layers"] * B.layer_matrix_elements(MC) == pytest.approx(
        7 * 640_024_576 + 64_094_208)


def test_weight_pass_and_pool_bytes_by_hand():
    blocks = (7 * 640_024_576 + 64_094_208) * 2
    head = 128256 * 2048 * 2
    assert B.weight_bytes_per_pass(MC, "none") == pytest.approx(blocks + head)
    # with the embedding: 5,069.6 M parameters = 10.14 GB = 9.44 GiB
    params = 7 * 640_024_576 + 64_094_208 + 2 * 128256 * 2048
    assert params == 5_069_602_816
    assert params * 2 / 2 ** 30 == pytest.approx(9.44, abs=0.005)
    # one latent row a token a block: 576 values held in 640 lanes
    assert FAM.page_width(MC) == 640
    assert B.kv_bytes_per_token(MC) == 8 * 640 * 2 == 10_240
    assert 8 * (512 + 64) * 2 == 9_216          # the values themselves
    assert B.kv_bytes_per_token(MC) == CONF["pool"]["bytes_per_token"]
    # against 2 x 32 heads x (192 | 128) decompressed: 1 / 16 of it
    assert 32 * (192 + 128) * 2 * 8 == 163_840


def test_kernel_roofline_functions_by_hand():
    # routed projections: as olmoe's, bf16
    assert FAM.moe_kernel_ops(MC, 3072) == 2 * 3072 * 4_718_592
    assert FAM.moe_kernel_bytes(MC, 3072, 128, weight_bytes=2) \
        == 128 * 4_718_592 * 2 + 3072 * 3 * (2048 + 768) * 2
    # a decode step over 8 rows that hold 68,000 tokens between them
    pair = 2 * 32 * (576 + 512)
    assert FAM.mla_decode_kernel_ops(MC, 68_000) == 8 * 68_000 * pair
    assert FAM.mla_decode_kernel_bytes(MC, 68_000) == 8 * 68_000 * 1280
    # HBM-bound by the useful arithmetic: 54 flop a byte under the ridge
    assert pair / 1280 == pytest.approx(54.4)
    assert (FAM.mla_decode_kernel_bytes(MC, 68_000) / 819e9
            > FAM.mla_decode_kernel_ops(MC, 68_000) / 197e12)
    # a chunk of 256 tokens at start 4096: 256 x 4096 + 256 x 257 / 2
    pairs = 256 * 4096 + 256 * 257 // 2
    assert FAM.mla_prefill_kernel_ops(MC, pairs) == 8 * pairs * pair
    assert FAM.mla_prefill_kernel_bytes(MC, pairs, 256) == int(
        8 * pairs / 256 * 1280)
    assert (FAM.mla_prefill_kernel_ops(MC, pairs) / 197e12
            > FAM.mla_prefill_kernel_bytes(MC, pairs, 256) / 819e9)


# ------------------------------------------- equations against the program

TOY = CONF["rehearsal"]["model_config"]


def _toy(leaf_dtype):
    """``(cfg, params)``: the rehearsal model on seeded weights whose
    leaves are stored as ``leaf_dtype``, norms moved off one."""
    import jax
    import jax.numpy as jnp
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    cfg = ModelConfig(**TOY)
    p = init_full_params(jax.random.PRNGKey(4), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    for tree in (p.layers, p.lead):
        for name in ("attn_norm_w", "mlp_norm_w", "kv_norm_w"):
            tree[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                       tree[name].shape)
    return cfg, jax.tree.map(
        lambda a: a if a.dtype == jnp.int32 else a.astype(leaf_dtype), p)


@pytest.mark.parametrize("leaves", ["float32", "bfloat16"])
def test_reference_equals_the_program_at_toy_size(leaves):
    """The family's equations (decompressed keys and values, every expert
    for every row) through ``reference.emitted_logprobs`` against the
    program's ``stage_forward`` (absorbed form, sorted rows) on the same
    leaves: stored as bf16, the reference reads them as float32 and the
    program is given them widened, so the agreement stays float32's."""
    import jax
    import jax.numpy as jnp
    import reference
    from distributed_inference_demo_tpu.models import KVCache, StageSpec
    from distributed_inference_demo_tpu.models.decoder import stage_forward
    cfg, p = _toy(leaves)
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    ids = [(13 * i + 5) % cfg.vocab_size for i in range(40)]
    logits, _ = stage_forward(
        wide, cfg, StageSpec(0, 1, 0, cfg.num_layers), jnp.asarray([ids]),
        KVCache.create(cfg, cfg.num_layers, 1, 48), jnp.arange(40)[None])
    lp = jax.nn.log_softmax(logits[0], -1)
    ref = reference.emitted_logprobs(p, TOY, ids, 12)
    assert [float(lp[t - 1, ids[t]]) for t in range(12, 40)] == \
        pytest.approx(ref["logprobs"], abs=2e-4)
    assert [int(lp[t - 1].argmax()) for t in range(12, 40)] \
        == ref["best_ids"]


@pytest.mark.parametrize("poisoned", [None, 0, 1, 2, 3])
def test_every_block_runs_exactly_once(poisoned):
    """``reference.emitted_logprobs`` (the leading block inside ``embed``,
    ``num_layers`` calls over the stack by index) against the whole loop
    written here, block after block, each once.  With one block's norm
    weights poisoned both must move alike: a block that was skipped, run
    twice or reached through a clamped index would part them."""
    import jax
    import jax.numpy as jnp
    import reference
    cfg, p = _toy("float32")
    n_lead, n = cfg.lead_dense_layers, cfg.num_layers
    assert (n_lead, n) == (1, 3) and TOY["num_layers"] == 3
    if poisoned is not None:
        tree, i = ((p.lead, poisoned) if poisoned < n_lead
                   else (p.layers, poisoned - n_lead))
        tree["mlp_norm_w"] = tree["mlp_norm_w"].at[i].mul(1.7)
    ids = jnp.asarray([(7 * i + 2) % cfg.vocab_size for i in range(24)])
    lead_layer, layer = FAM.blocks(TOY, q_block=8)
    with jax.default_matmul_precision("highest"):
        x = p.embed["tokens"][ids]
        for i in range(n_lead):
            x = lead_layer({k: v[i] for k, v in p.lead.items()}, x)
        for i in range(n):
            x = layer({k: v[i] for k, v in p.layers.items()}, x)
        x = reference._rms_norm(x, p.final_norm["w"], cfg.norm_eps)
        lp = jax.nn.log_softmax(x @ p.lm_head["w"], -1)
    ref = reference.emitted_logprobs(p, TOY, [int(t) for t in ids], 10)
    assert [float(lp[t - 1, ids[t]]) for t in range(10, 24)] == \
        pytest.approx(ref["logprobs"], abs=1e-5)
    if poisoned is not None:        # and the poison is seen at all
        clean = reference.emitted_logprobs(_toy("float32")[1], TOY,
                                           [int(t) for t in ids], 10)
        assert max(abs(a - b) for a, b in zip(
            ref["logprobs"], clean["logprobs"])) > 1e-3


# ------------------------------------------------------------ the readers

def _ctx(pairs=(), decode_s=0.0, prefill_s=0.0, busy_s=1.0, kvcache=None):
    return {"config": CONF, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_open": {}, "marks": {},
            "stats_close": {"kvcache": kvcache} if kvcache else {},
            "trace": {"op_self_s": [
                ["_paged_call_latent.21", decode_s * 0.75],
                ["_paged_call_latent.22", decode_s * 0.25],
                ["_paged_prefill_call_latent.14", prefill_s],
                ["fusion.1", busy_s - decode_s - prefill_s]],
                "op_self_total_s": busy_s} if busy_s else {},
            "_dispatch_join": {"pairs": list(pairs), "share": 1.0}}


def test_roofline_readers_on_a_synthetic_run():
    from layer_metrics import (attn_kernel_busy_share_pct,
                               decode_kernel_hbm_pct,
                               mla_decode_kernel_roofline_pct,
                               mla_prefill_kernel_roofline_pct)
    pairs_n = 2 * (256 * 4096 + 256 * 257 // 2)
    rec = {"kv_tokens": 68_000, "steps": 4, "prefill_kv_tokens": pairs_n}
    decode = 4 * FAM.mla_decode_kernel_bytes(MC, 68_000) / 819e9
    prefill = FAM.mla_prefill_kernel_ops(MC, pairs_n) / 197e12
    ctx = _ctx([(0, 1, rec)], decode_s=2 * decode, prefill_s=4 * prefill,
               busy_s=10 * (decode + prefill))
    assert mla_decode_kernel_roofline_pct.read(ctx) == pytest.approx(50.0)
    assert mla_prefill_kernel_roofline_pct.read(ctx) == pytest.approx(25.0)
    # the accepted readers match the latent calls by their prefixes, and
    # the KV the decode kernel had to read is the family's bytes a token
    assert decode_kernel_hbm_pct.read(ctx) == pytest.approx(50.0)
    assert attn_kernel_busy_share_pct.read(ctx) == pytest.approx(
        100 * (2 * decode + 4 * prefill) / (10 * (decode + prefill)))
    # no latent call in the trace (another model, the gather path): None
    none = _ctx([(0, 1, rec)], busy_s=1.0)
    assert mla_decode_kernel_roofline_pct.read(none) is None
    assert mla_prefill_kernel_roofline_pct.read(none) is None
    # records without the column (the parent's program): None, no raise
    old = _ctx([(0, 1, {"kv_tokens": 5, "steps": 4})], decode_s=0.1,
               prefill_s=0.1)
    assert mla_prefill_kernel_roofline_pct.read(old) is None
    assert mla_decode_kernel_roofline_pct.read(old) is not None
    # another family's configuration: None
    other = dict(_ctx([(0, 1, rec)], decode_s=0.1, prefill_s=0.1),
                 config=json.loads((BENCH / "configs"
                                    / "olmoe-1b-7b-int8.json").read_text()))
    assert mla_decode_kernel_roofline_pct.read(other) is None
    assert mla_prefill_kernel_roofline_pct.read(other) is None


def test_pool_bytes_reader_reads_the_program_s_counter():
    from layer_metrics import mla_pool_bytes_per_token
    ctx = _ctx(kvcache={"bytes_per_token": 10240, "blocks_total": 2048})
    assert mla_pool_bytes_per_token.read(ctx) == 10240 \
        == B.kv_bytes_per_token(MC)
    # a pair pool (the latent stored twice) or decompressed pages would
    # read 20,480 or 163,840 here; the parent's /stats has no counter
    assert mla_pool_bytes_per_token.read(_ctx(kvcache={"blocks_total": 1})) \
        is None
    assert mla_pool_bytes_per_token.read(_ctx()) is None


def test_the_cell_s_traffic_and_load():
    mix = json.loads((BENCH / "traffic" / "longdoc-sat.json").read_text())
    load = json.loads((BENCH / "cells" /
                       "kanana-2-30b-a3b-bf16.longdoc-sat.json").read_text())
    assert load == {"clients": 8} and mix["generator"] == "closed_loop"
    assert (mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]) == (
        6144, 10240)
    assert (mix["output_tokens"]["min"], mix["output_tokens"]["max"]) == (
        384, 640)
    flags = CONF["serve_flags"]
    max_seq = int(flags[flags.index("--max-seq") + 1])
    assert 10240 + 640 <= max_seq == 12288
    # every client at its longest fits the pool with room to spare
    pages = -(-(10240 + 640) // 128)
    assert 8 * pages <= CONF["pool"]["blocks"]
