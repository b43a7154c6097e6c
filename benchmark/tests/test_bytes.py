"""Byte functions against numbers worked by hand for both families."""
import importlib
import json
from pathlib import Path

import pytest

import peaks

B = importlib.import_module("bytes")          # benchmark/bytes.py
CONFIGS = Path(__file__).resolve().parent.parent / "configs"


def mc(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model_config"]


def test_qwen_layer_by_hand():
    q = mc("qwen2.5-7b-int8")
    # wq 3584x3584, wk and wv 3584x512, wo 3584x3584, three 3584x18944
    assert B.layer_matrix_elements(q) == (2 * 3584 * 3584 + 2 * 3584 * 512
                                          + 3 * 3584 * 18944) == 233_046_016
    assert B.layer_scale_elements(q) == 3584 + 512 + 512 + 3584 + 2 * 18944 + 3584


def test_qwen_int8_weight_pass_by_hand():
    q = mc("qwen2.5-7b-int8")
    layers = 28 * (233_046_016 + 49_664 * 4)
    head = 152064 * 3584 * 2
    assert B.weight_bytes_per_pass(q, "int8") == layers + head
    assert B.weight_bytes_per_pass(q, "int8") == pytest.approx(7.62e9, rel=0.01)


def test_qwen_bf16_on_four_chips_shards_layers_and_head():
    q = mc("qwen2.5-7b-bf16-tp4")
    assert B.weight_bytes_per_pass(q, "none", 4) == (
        28 * 233_046_016 * 2 + 152064 * 3584 * 2) / 4
    assert B.kv_bytes_per_token(q) == 57_344
    assert B.kv_bytes_per_token(q, chips=4) == 14_336


def test_bloom_by_hand():
    b = mc("bloom7b1-int8")
    # four 4096x4096 attention matrices, two 4096x16384
    assert B.layer_matrix_elements(b) == 4 * 4096 ** 2 + 2 * 4096 * 16384
    assert B.kv_bytes_per_token(b) == 30 * 2 * 32 * 128 * 2 == 491_520
    # the tied head is the replicated embedding table: never divided
    tied = 250880 * 4096 * 2
    assert B.weight_bytes_per_pass(b, "int8", 4) == (
        30 * (B.layer_matrix_elements(b) + B.layer_scale_elements(b) * 4) / 4
        + tied)
    assert B.kv_read_bytes_per_step(b, 5888) == 491_520 * 5888


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
