"""``families/olmoe.py``'s shape arithmetic and the grouped-matmul
kernel's operations and bytes, pinned by hand; and the four readers of the
expert layer's metrics on a synthetic run context."""
import importlib
import json
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
B = importlib.import_module("bytes")          # benchmark/bytes.py
MC = json.loads((BENCH / "configs" / "olmoe-1b-7b-int8.json").read_text())[
    "model_config"]
FAM = families.load("olmoe")


def test_layer_elements_by_hand():
    # q, k, v, o 2048x2048 each; router 2048x64; 64 experts x 3 x 2048x1024
    assert 4 * 2048 * 2048 == 16_777_216
    assert 2048 * 64 == 131_072
    assert 64 * 3 * 2048 * 1024 == 402_653_184
    assert B.layer_matrix_elements(MC) == 419_561_472
    # a float32 scale per int8 output channel: q, k, v, o and each
    # expert's gate, up (1024) and down (2048); the router has none
    assert B.layer_scale_elements(MC) == 4 * 2048 + 64 * (2 * 1024 + 2048) \
        == 270_336


def test_int8_weight_pass_by_hand():
    """The head counted (bf16, untied) and the embedding not; the router
    is counted at one byte an element with the rest, 2 MB in 6.9 GB."""
    layers = 16 * (419_561_472 + 270_336 * 4)
    head = 50304 * 2048 * 2
    assert B.weight_bytes_per_pass(MC, "int8") == layers + head
    assert B.weight_bytes_per_pass(MC, "int8") == pytest.approx(6.94e9,
                                                                rel=0.002)
    assert B.kv_bytes_per_token(MC) == 16 * 2 * 16 * 128 * 2 == 131_072


@pytest.mark.parametrize("rows,touched", [(256, 50), (4096, 64)])
def test_kernel_roofline_functions_by_hand(rows, touched):
    """One layer call: 256 rows over 50 touched experts (a decode step),
    4,096 rows over all 64 (the slab)."""
    expert = 3 * 2048 * 1024
    assert FAM.moe_kernel_ops(MC, rows) == 2 * rows * expert
    int8 = touched * (expert + (2 * 1024 + 2048) * 4) \
        + rows * 3 * (2048 + 1024) * 2
    assert FAM.moe_kernel_bytes(MC, rows, touched) == int8
    assert FAM.moe_kernel_bytes(MC, rows, touched, weight_bytes=2) \
        == touched * expert * 2 + rows * 3 * (2048 + 1024) * 2
    # which side of the v5e's ridge: both shapes are bound by HBM (an
    # int8 expert needs ~120 rows before its matmuls outlast its read)
    hbm_s, mxu_s = int8 / 819e9, 2 * rows * expert / 197e12
    assert hbm_s > mxu_s
    assert (rows, round(hbm_s * 1e6), round(mxu_s * 1e6)) in (
        (256, 391, 16), (4096, 585, 262))


def _ctx(moe_open, moe_close, pairs=(), kernel_s=0.0, busy_s=1.0):
    conf = json.loads((BENCH / "configs" / "olmoe-1b-7b-int8.json")
                      .read_text())
    return {"config": conf, "cell": {"chips": 1},
            "health": {"device_kind": "TPU v5 lite"},
            "stats_open": {"moe": moe_open} if moe_open else {},
            "stats_close": {"moe": moe_close} if moe_close else {},
            "marks": {},
            "trace": {"op_self_s": [["moe_gmm.45", kernel_s / 2],
                                    ["moe_gmm.47", kernel_s / 2],
                                    ["fusion.1", busy_s - kernel_s]],
                      "op_self_total_s": busy_s} if busy_s else {},
            "_dispatch_join": {"pairs": list(pairs), "share": 1.0}}


def _moe(rows, touched, calls, expert_rows):
    return {"experts": 4, "dispatches": 1, "rows": rows, "valid_rows": rows,
            "touched": touched, "load_max": 9, "layer_calls": calls,
            "expert_rows": expert_rows}


def test_counter_readers_on_a_synthetic_run():
    from layer_metrics import (moe_expert_load_max_over_mean,
                               moe_experts_touched_pct)
    ctx = _ctx(_moe(100, 30, 10, [10, 20, 30, 40]),
               _moe(300, 60, 20, [110, 40, 40, 110]))
    # 30 touched over 10 calls x 4 experts
    assert moe_experts_touched_pct.read(ctx) == pytest.approx(75.0)
    # window's rows: 100, 20, 10, 70 -> max 100 over mean 50
    assert moe_expert_load_max_over_mean.read(ctx) == pytest.approx(2.0)
    dense = _ctx(None, None)                 # a dense cell's /stats
    assert moe_experts_touched_pct.read(dense) is None
    assert moe_expert_load_max_over_mean.read(dense) is None


def test_kernel_readers_on_a_synthetic_run():
    from layer_metrics import (moe_kernel_busy_share_pct,
                               moe_kernel_roofline_pct)
    rec = {"moe_rows": 4096 * 16, "moe_touched": 64 * 16}
    bound = 16 * max(
        FAM.moe_kernel_bytes(MC, 4096, 64) / 819e9,
        FAM.moe_kernel_ops(MC, 4096) / 197e12)
    ctx = _ctx(None, None, pairs=[(0, 1, rec)], kernel_s=2 * bound,
               busy_s=8 * bound)
    assert moe_kernel_busy_share_pct.read(ctx) == pytest.approx(25.0)
    assert moe_kernel_roofline_pct.read(ctx) == pytest.approx(50.0)
    # no kernel in the trace (a dense cell, or the parent's program): None
    none = _ctx(None, None, pairs=[(0, 1, rec)], kernel_s=0.0)
    assert moe_kernel_busy_share_pct.read(none) is None
    assert moe_kernel_roofline_pct.read(none) is None
    # records without the counters (the parent's program): None
    old = _ctx(None, None, pairs=[(0, 1, {"steps": 4})], kernel_s=1.0,
               busy_s=2.0)
    assert moe_kernel_roofline_pct.read(old) is None
    assert moe_kernel_busy_share_pct.read(_ctx(None, None, busy_s=0)) is None
