"""Bytes a step must move, from a configuration's shapes alone.

``model_config`` is the group of that name in a configuration file;
``quant`` is ``"int8"`` where the decoder layers are served as int8 with a
float32 scale per output channel (embedding and head stay bf16), else
``"none"`` (bf16 everywhere).  ``chips`` divides what tensor parallelism
shards: the layer matrices, the untied head and the KV pool; the
embedding is replicated (and read by row, so it is not counted).

What one decoder layer holds depends on its kind of block, so the two
per-layer counts are the family's own (``families/<family>.py``, found by
``model_config.family``); a family whose tokens hold something other than
full keys and values for every kv head (a latent, a window) may also bring
its own ``kv_bytes_per_token``."""

from __future__ import annotations

import families


def dims(mc: dict):
    """``(hidden, heads, kv heads, head size, intermediate, layers)``."""
    h, nh, nkv = mc["hidden_size"], mc["num_heads"], mc["num_kv_heads"]
    hd = mc.get("head_dim_override") or h // nh
    return h, nh, nkv, hd, mc["intermediate_size"], mc["num_layers"]


def attention_matrix_elements(mc: dict) -> int:
    """Elements of one layer's q, k, v and output projections."""
    h, nh, nkv, hd, _, _ = dims(mc)
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h


def attention_scale_elements(mc: dict) -> int:
    """Output channels of those four matrices."""
    h, nh, nkv, hd, _, _ = dims(mc)
    return nh * hd + 2 * nkv * hd + h


def layer_matrix_elements(mc: dict) -> int:
    """Elements of one decoder layer's matrices."""
    return families.load(mc["family"]).layer_matrix_elements(mc)


def layer_scale_elements(mc: dict) -> int:
    """Output channels of one layer's matrices (one float32 scale each)."""
    return families.load(mc["family"]).layer_scale_elements(mc)


def weight_bytes_per_pass(mc: dict, quant: str = "none",
                          chips: int = 1) -> float:
    """Bytes of weights one forward pass over the model reads on each
    chip: every layer matrix once and the head once (a tied head is the
    replicated embedding table, read whole on every chip)."""
    per_el = 1 if quant == "int8" else 2
    per_layer = layer_matrix_elements(mc) * per_el
    if quant == "int8":
        per_layer += layer_scale_elements(mc) * 4
    head = mc["vocab_size"] * mc["hidden_size"] * 2
    head_div = 1 if mc.get("tie_embeddings") else chips
    return mc["num_layers"] * per_layer / chips + head / head_div


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> float:
    """Bytes of keys and values one token holds on each chip."""
    own = getattr(families.load(mc["family"]), "kv_bytes_per_token", None)
    if own is not None:
        return own(mc, kv_bytes, chips)
    _, _, nkv, hd, _, layers = dims(mc)
    return layers * 2 * nkv * hd * kv_bytes / chips


def kv_read_bytes_per_step(mc: dict, context_tokens: int,
                           kv_bytes: int = 2, chips: int = 1) -> float:
    """Bytes of KV one decode step reads on each chip when the running
    requests hold ``context_tokens`` tokens between them."""
    return kv_bytes_per_token(mc, kv_bytes, chips) * context_tokens
