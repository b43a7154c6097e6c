"""Weight loading from HF safetensors checkpoints into StageParams pytrees.

TPU-native replacement for the reference's missing ``util.model_card``
ModelCard (load HF torch model -> split -> ONNX export -> int8 quantize ->
zip; SURVEY.md §2.2): here we map safetensors names directly onto the stacked
layer layout, optionally casting to bf16 or int8-per-channel, with no export
step — a stage's weights are an array slice of the full stack
(``base.slice_stage``).

Zero-egress environment: loading requires a *local* checkpoint directory.
Tests use random init instead.
"""

import functools
import json
import os
import re
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from .base import ModelConfig, StageParams
from .decoder import init_full_params


# safetensors name -> (our key, transpose?); attention/norm subset is shared
# by every rope-family mapper (llama dense MLP adds the mlp.* entries,
# mixtral swaps them for per-expert blocks).
_ATTN_NORM_MAP = {
    "input_layernorm.weight": ("attn_norm_w", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "post_attention_layernorm.weight": ("mlp_norm_w", False),
}

_LLAMA_LAYER_MAP = {
    **_ATTN_NORM_MAP,
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
}


def load_safetensors_dir(path: str, key_filter=None) -> Dict[str, np.ndarray]:
    """Read *.safetensors files in a checkpoint directory.  With a
    ``key_filter`` predicate only matching tensors are materialized
    (``safe_open`` lists keys lazily — a caller extracting one submodule
    from a large bundle never copies the rest into host RAM)."""
    from safetensors import safe_open
    tensors: Dict[str, np.ndarray] = {}
    files = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    for fname in files:
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for key in f.keys():
                if key_filter is None or key_filter(key):
                    tensors[key] = f.get_tensor(key)
    return tensors


def _get(raw: Dict[str, np.ndarray], name: str,
         prefixes=("model.", "transformer.", "")) -> np.ndarray:
    for prefix in prefixes:
        if prefix + name in raw:
            return np.asarray(raw[prefix + name])
    raise KeyError(name)


def llama_params_from_state_dict(raw: Dict[str, np.ndarray],
                                 cfg: ModelConfig) -> StageParams:
    """Map a llama-family HF state dict (``model.layers.{i}.*`` names) onto
    the stacked layout.  HF stores linears as [out, in]; ours are [in, out]
    einsum operands, hence the transposes.  Also serves qwen2 (identical
    names + ``self_attn.{q,k,v}_proj.bias`` under ``attn_qkv_bias``)."""
    dt = cfg.dtype
    layer_map = dict(_LLAMA_LAYER_MAP)
    if cfg.attn_qkv_bias:
        layer_map.update({
            "self_attn.q_proj.bias": ("bq", False),
            "self_attn.k_proj.bias": ("bk", False),
            "self_attn.v_proj.bias": ("bv", False)})
    layers: Dict[str, list] = {}
    for i in range(cfg.num_layers):
        for hf_name, (ours, transpose) in layer_map.items():
            w = _get(raw, f"layers.{i}.{hf_name}")
            if transpose:
                w = w.T
            layers.setdefault(ours, []).append(w)
    stacked = {k: jnp.asarray(np.stack(v), dt) for k, v in layers.items()}

    embed = {"tokens": jnp.asarray(_get(raw, "embed_tokens.weight"), dt)}
    final_norm = {"w": jnp.asarray(_get(raw, "norm.weight"), dt)}
    if cfg.tie_embeddings:
        lm_head = {}
    else:
        lm_head = {"w": jnp.asarray(_get(raw, "lm_head.weight", ("",)).T, dt)}
    return StageParams(layers=stacked, embed=embed, final_norm=final_norm,
                       lm_head=lm_head)


def bloom_params_from_state_dict(raw: Dict[str, np.ndarray],
                                 cfg: ModelConfig) -> StageParams:
    """Map a BloomForCausalLM state dict onto the stacked layout.

    The fused ``query_key_value`` weight is **per-head interleaved**:
    [nh, 3, hd, H] after reshape (q/k/v planes alternate within each head),
    not three contiguous blocks — the one genuinely tricky mapping in the
    family (reference ships pre-exported ONNX instead, SURVEY.md §2.2).
    """
    dt = cfg.dtype
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    layers: Dict[str, list] = {}

    def push(key, val):
        layers.setdefault(key, []).append(val)

    for i in range(cfg.num_layers):
        p = f"h.{i}."
        push("attn_norm_w", _get(raw, p + "input_layernorm.weight"))
        push("attn_norm_b", _get(raw, p + "input_layernorm.bias"))
        qkv_w = _get(raw, p + "self_attention.query_key_value.weight")
        qkv_b = _get(raw, p + "self_attention.query_key_value.bias")
        w = qkv_w.reshape(nh, 3, hd, H)
        b = qkv_b.reshape(nh, 3, hd)
        # [H, nh*hd] per projection (transpose of HF's [out, in])
        push("wq", w[:, 0].reshape(nh * hd, H).T)
        push("wk", w[:, 1].reshape(nh * hd, H).T)
        push("wv", w[:, 2].reshape(nh * hd, H).T)
        push("bq", b[:, 0].reshape(nh * hd))
        push("bk", b[:, 1].reshape(nh * hd))
        push("bv", b[:, 2].reshape(nh * hd))
        push("wo", _get(raw, p + "self_attention.dense.weight").T)
        push("bo", _get(raw, p + "self_attention.dense.bias"))
        push("mlp_norm_w", _get(raw, p + "post_attention_layernorm.weight"))
        push("mlp_norm_b", _get(raw, p + "post_attention_layernorm.bias"))
        push("w_up", _get(raw, p + "mlp.dense_h_to_4h.weight").T)
        push("b_up", _get(raw, p + "mlp.dense_h_to_4h.bias"))
        push("w_down", _get(raw, p + "mlp.dense_4h_to_h.weight").T)
        push("b_down", _get(raw, p + "mlp.dense_4h_to_h.bias"))
    stacked = {k: jnp.asarray(np.stack(v), dt) for k, v in layers.items()}

    embed = {
        "tokens": jnp.asarray(_get(raw, "word_embeddings.weight"), dt),
        "norm_w": jnp.asarray(
            _get(raw, "word_embeddings_layernorm.weight"), dt),
        "norm_b": jnp.asarray(
            _get(raw, "word_embeddings_layernorm.bias"), dt),
    }
    final_norm = {"w": jnp.asarray(_get(raw, "ln_f.weight"), dt),
                  "b": jnp.asarray(_get(raw, "ln_f.bias"), dt)}
    return StageParams(layers=stacked, embed=embed, final_norm=final_norm,
                       lm_head={})  # bloom ties the head to the embedding


# where a routed family keeps its router and its experts' three linears:
# (block prefix, gate / up / down names).  mixtral: w1 -> w_gate,
# w3 -> w_up, w2 -> w_down
_MOE_NAMES = {
    "mixtral": ("block_sparse_moe.", "w1", "w3", "w2"),
    "olmoe": ("mlp.", "gate_proj", "up_proj", "down_proj"),
}


def moe_params_from_state_dict(raw: Dict[str, np.ndarray],
                               cfg: ModelConfig) -> StageParams:
    """Map a MixtralForCausalLM or OlmoeForCausalLM state dict onto the
    stacked layout.

    Per-expert linears (``<block>experts.{e}.<name>.weight``) stack into
    [L, E, in, out] blocks, the router is ``<block>gate.weight``; olmoe
    adds ``self_attn.q_norm`` / ``k_norm`` (``cfg.qk_norm``).
    """
    dt = cfg.dtype
    E = cfg.num_experts
    block, *names = _MOE_NAMES[cfg.family]
    attn_map = dict(_ATTN_NORM_MAP)
    if cfg.qk_norm:
        attn_map.update({"self_attn.q_norm.weight": ("q_norm_w", False),
                         "self_attn.k_norm.weight": ("k_norm_w", False)})
    layers: Dict[str, list] = {}

    def push(key, val):
        layers.setdefault(key, []).append(val)

    for i in range(cfg.num_layers):
        p = f"layers.{i}."
        for hf_name, (ours, transpose) in attn_map.items():
            w = _get(raw, p + hf_name)
            push(ours, w.T if transpose else w)
        push("router", _get(raw, p + block + "gate.weight").T)
        for ours, name in zip(("w_gate", "w_up", "w_down"), names):
            push(ours, np.stack([
                _get(raw, p + f"{block}experts.{e}.{name}.weight").T
                for e in range(E)]))
    stacked = {k: jnp.asarray(np.stack(v), dt) for k, v in layers.items()}

    embed = {"tokens": jnp.asarray(_get(raw, "embed_tokens.weight"), dt)}
    final_norm = {"w": jnp.asarray(_get(raw, "norm.weight"), dt)}
    lm_head = ({} if cfg.tie_embeddings else
               {"w": jnp.asarray(_get(raw, "lm_head.weight", ("",)).T, dt)})
    return StageParams(layers=stacked, embed=embed, final_norm=final_norm,
                       lm_head=lm_head)


def deepseek_v3_params_from_state_dict(raw: Dict[str, np.ndarray],
                                       cfg: ModelConfig) -> StageParams:
    """Map a DeepseekV3ForCausalLM state dict (``q_lora_rank`` null:
    kanana-2-30b-a3b) onto the stacked layout: the first
    ``cfg.lead_dense_layers`` checkpoint layers are the leading dense
    blocks (``StageParams.lead``), the rest the repeated expert stack.

    ``kv_b_proj`` ``[nh (dn + dv), r]`` is kept as its two halves a head,
    laid out for the absorbed form: ``w_uk[i] = W_UK_i^T`` ``[dn, r]``
    (its rows as stored) and ``w_uv[i] = W_UV_i`` ``[r, dv]``.  The
    checkpoint's rope columns (``q_proj``'s last ``dr`` of a head,
    ``kv_a_proj_with_mqa``'s last ``dr``) are stored for INTERLEAVED
    pairs and the program ropes interleaved pairs, so no column moves
    (HF de-interleaves them and ropes in rotate-half form: the same
    scores; ``tests/test_kanana.py``)."""
    dt = cfg.dtype
    E, nh = cfg.num_experts, cfg.num_heads
    dn, dv, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    lin = lambda name: _get(raw, name).T          # [out, in] -> [in, out]

    def block(i: int) -> dict:
        p = f"layers.{i}."
        kv_b = _get(raw, p + "self_attn.kv_b_proj.weight").reshape(
            nh, dn + dv, r)
        out = {
            "attn_norm_w": _get(raw, p + "input_layernorm.weight"),
            "wq": lin(p + "self_attn.q_proj.weight"),
            "wkv_a": lin(p + "self_attn.kv_a_proj_with_mqa.weight"),
            "kv_norm_w": _get(raw, p + "self_attn.kv_a_layernorm.weight"),
            "w_uk": kv_b[:, :dn, :],
            "w_uv": kv_b[:, dn:, :].transpose(0, 2, 1),
            "wo": lin(p + "self_attn.o_proj.weight"),
            "mlp_norm_w": _get(raw, p + "post_attention_layernorm.weight"),
        }
        if i < cfg.lead_dense_layers:
            for ours, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                               ("w_down", "down_proj")):
                out[ours] = lin(p + f"mlp.{name}.weight")
            return out
        out["router"] = lin(p + "mlp.gate.weight")
        out["router_bias"] = _get(
            raw, p + "mlp.gate.e_score_correction_bias").astype(np.float32)
        for ours, name in (("gate", "gate_proj"), ("up", "up_proj"),
                           ("down", "down_proj")):
            out["w_" + ours] = np.stack([
                lin(p + f"mlp.experts.{e}.{name}.weight") for e in range(E)])
            out["ws_" + ours] = lin(p + f"mlp.shared_experts.{name}.weight")
        return out

    def stack(blocks: list) -> dict:
        return {k: jnp.asarray(np.stack([b[k] for b in blocks]),
                               jnp.float32 if k == "router_bias" else dt)
                for k in blocks[0]}

    n_lead = cfg.lead_dense_layers
    blocks = [block(i) for i in range(cfg.total_layers)]
    return StageParams(
        layers=stack(blocks[n_lead:]),
        embed={"tokens": jnp.asarray(_get(raw, "embed_tokens.weight"), dt)},
        final_norm={"w": jnp.asarray(_get(raw, "norm.weight"), dt)},
        lm_head={"w": jnp.asarray(_get(raw, "lm_head.weight", ("",)).T, dt)},
        lead=stack(blocks[:n_lead]) if n_lead else None)


def granite_hybrid_params_from_state_dict(raw: Dict[str, np.ndarray],
                                          cfg: ModelConfig) -> StageParams:
    """Map a GraniteMoeHybridForCausalLM state dict (granite-4.0-h-small)
    onto a period model's stacks, one a kind (``<leaf>.<kind name>``
    shaped ``[repeats, places of the kind in a period, ...]``): checkpoint
    layer ``r x len(period) + p`` is place ``p`` of repeat ``r``.

    ``mamba.in_proj`` keeps its column order ``z | x B C | dt``;
    ``mamba.conv1d.weight`` ``[channels, 1, taps]`` becomes ``[taps,
    channels]`` (torch's cross-correlation reads tap ``k`` at ``t - taps +
    1 + k``, as ``ops.kda.causal_conv`` does); ``input_linear`` of the
    experts and of the shared MLP holds gate THEN up on its output axis
    (``act(chunk 0) * chunk 1``).  A configuration cut to a chip's share
    takes experts ``[first, first + held)`` of the stacks, the router
    whole, and the first ``vocab_size`` rows of the tied embedding."""
    dt = cfg.dtype
    I = cfg.intermediate_size
    Is = cfg.num_shared_experts * I
    held, first = cfg.experts_held or (cfg.num_experts, 0)
    lin = lambda name: _get(raw, name).T          # [out, in] -> [in, out]
    f32 = ("A_log", "D", "dt_bias")

    def block(i: int, kind) -> dict:
        p = f"layers.{i}."
        out = {"attn_norm_w": _get(raw, p + "input_layernorm.weight"),
               "mlp_norm_w": _get(raw, p + "post_attention_layernorm.weight")}
        if kind.attn == "ssd":
            out.update({
                "w_in": lin(p + "mamba.in_proj.weight"),
                "conv_w": _get(raw, p + "mamba.conv1d.weight")[:, 0, :].T,
                "conv_b": _get(raw, p + "mamba.conv1d.bias"),
                "A_log": _get(raw, p + "mamba.A_log"),
                "D": _get(raw, p + "mamba.D"),
                "dt_bias": _get(raw, p + "mamba.dt_bias"),
                "ssd_norm_w": _get(raw, p + "mamba.norm.weight"),
                "wo": lin(p + "mamba.out_proj.weight")})
        else:
            out.update({"wq": lin(p + "self_attn.q_proj.weight"),
                        "wk": lin(p + "self_attn.k_proj.weight"),
                        "wv": lin(p + "self_attn.v_proj.weight"),
                        "wo": lin(p + "self_attn.o_proj.weight")})
        out["router"] = lin(p + "block_sparse_moe.router.layer.weight")
        w_in = _get(raw, p + "block_sparse_moe.input_linear.weight")[
            first:first + held]                         # [E, 2 I, H]
        out["w_gate"] = w_in[:, :I].transpose(0, 2, 1)
        out["w_up"] = w_in[:, I:].transpose(0, 2, 1)
        out["w_down"] = _get(
            raw, p + "block_sparse_moe.output_linear.weight")[
                first:first + held].transpose(0, 2, 1)   # [E, I, H]
        ws_in = _get(raw, p + "shared_mlp.input_linear.weight")  # [2 Is, H]
        out["ws_gate"], out["ws_up"] = ws_in[:Is].T, ws_in[Is:].T
        out["ws_down"] = lin(p + "shared_mlp.output_linear.weight")
        return out

    R, P = cfg.num_layers, len(cfg.period)
    layers = {}
    for name, kind, places in cfg.kinds:
        blocks = [[block(r * P + p, kind) for p in places] for r in range(R)]
        for leaf in blocks[0][0]:
            layers[f"{leaf}.{name}"] = jnp.asarray(
                np.stack([np.stack([b[leaf] for b in row])
                          for row in blocks]),
                jnp.float32 if leaf in f32 else dt)
    tokens = _get(raw, "embed_tokens.weight")[:cfg.vocab_size]
    return StageParams(
        layers=layers, embed={"tokens": jnp.asarray(tokens, dt)},
        final_norm={"w": jnp.asarray(_get(raw, "norm.weight"), dt)},
        lm_head=({} if cfg.tie_embeddings else
                 {"w": jnp.asarray(_get(raw, "lm_head.weight", ("",)).T, dt)}))


def gemma_params_from_state_dict(raw: Dict[str, np.ndarray],
                                 cfg: ModelConfig) -> StageParams:
    """Gemma: llama names end to end, but every RMSNorm applies
    ``(1 + w)`` — fold the +1 into the stored weights HERE so the
    decoder keeps one rms_norm rule for all families (a random-init
    gemma's ones-init norms equal HF w=0, the checkpoint identity)."""
    p = llama_params_from_state_dict(raw, cfg)
    layers = dict(p.layers)
    # fold in FLOAT32 and keep the folded vectors f32: HF computes
    # (1 + w.float()) exactly, and a bf16 re-round of the sum would lose
    # mantissa bits on every norm weight (norm vectors are tiny — the
    # f32 residency costs nothing; rms_norm consumes any dtype)
    for key in ("attn_norm_w", "mlp_norm_w"):
        layers[key] = layers[key].astype(jnp.float32) + 1.0
    final_norm = dict(p.final_norm)
    final_norm["w"] = final_norm["w"].astype(jnp.float32) + 1.0
    return StageParams(layers=layers, embed=p.embed,
                       final_norm=final_norm, lm_head=p.lm_head)


_SD_MAPPERS = {
    "llama": llama_params_from_state_dict,
    "qwen2": llama_params_from_state_dict,   # same names + qkv biases
    "gemma": gemma_params_from_state_dict,
    "bloom": bloom_params_from_state_dict,
    "mixtral": moe_params_from_state_dict,
    "olmoe": moe_params_from_state_dict,
    "deepseek_v3": deepseek_v3_params_from_state_dict,
    "granite_moe_hybrid": granite_hybrid_params_from_state_dict,
}


def params_from_state_dict(raw: Dict[str, np.ndarray],
                           cfg: ModelConfig) -> StageParams:
    """Family dispatch for HF-layout state dicts (numpy leaves)."""
    if cfg.family == "ouro":
        raise NotImplementedError(
            "no state-dict mapper for family 'ouro': the checkpoint's "
            "tensor names (the two output norms a block, the exit gate) "
            "could not be checked against a published checkpoint when the "
            "family was added, and a guessed name map loads wrong weights "
            "without a word; serve it on seeded weights, or add the map "
            "to models/loader.py from the checkpoint's own index")
    if cfg.family == "laguna":
        raise NotImplementedError(
            "no state-dict mapper for family 'laguna': the source gives a "
            "configuration and no modeling file, so the tensor names (the "
            "per-head gate, the stacks of the two kinds of block) could "
            "not be checked against a checkpoint, and a guessed name map "
            "loads wrong weights without a word; serve it on seeded "
            "weights, or add the map to models/loader.py from the "
            "checkpoint's own index")
    if cfg.family == "evabyte":
        raise NotImplementedError(
            "no state-dict mapper for family 'evabyte': no checkpoint's "
            "index was in the repository when the family was added, so "
            "the tensor names (adaptive_mu_k, adaptive_phi, the eight "
            "prediction heads' rows) could not be checked, and a guessed "
            "name map loads wrong weights without a word; serve it on "
            "seeded weights, or add the map to models/loader.py from the "
            "checkpoint's own index")
    if cfg.family not in _SD_MAPPERS:
        raise NotImplementedError(f"no state-dict mapper for {cfg.family!r}")
    return _SD_MAPPERS[cfg.family](raw, cfg)


def load_llama_params(path: str, cfg: ModelConfig) -> StageParams:
    """Assemble a llama-family HF checkpoint into stacked StageParams."""
    return llama_params_from_state_dict(load_safetensors_dir(path), cfg)


def stage_params_to_bytes(params: StageParams) -> bytes:
    """Serialize a StageParams tree for the control plane's artifact channel
    (the reference ships per-device ONNX zips, ``server.py:910-957``; we
    ship weight blobs in the versioned wire codec + a JSON manifest).
    Layout: ``<u32 manifest_len><manifest JSON><wire tensor message>``."""
    import struct

    from ..comm import wire

    from ..ops.quant import QuantizedArray

    if params.lead:
        raise TypeError(
            "leading dense blocks (StageParams.lead) run on one stage and "
            "are not shipped to pipeline stages")
    flat = {}
    for section in ("layers", "embed", "final_norm", "lm_head"):
        d = getattr(params, section)
        if d is None:
            continue
        for k, v in d.items():
            if isinstance(v, QuantizedArray):
                # ship weights pre-quantization; the receiving stage applies
                # its own config's quantization (ops/quant.maybe_quantize)
                raise TypeError(
                    f"{section}/{k} is quantized; serialize the float "
                    "params and quantize at the consumer")
            flat[f"{section}/{k}"] = np.asarray(v)
    names = sorted(flat)
    manifest = json.dumps({"names": names,
                           "present": {
                               s: getattr(params, s) is not None
                               for s in ("embed", "final_norm", "lm_head")}
                           }).encode("utf-8")
    blob = wire.serialize_tensors([flat[n] for n in names])
    return struct.pack("<I", len(manifest)) + manifest + blob


def stage_params_from_bytes(data: bytes) -> StageParams:
    """Inverse of :func:`stage_params_to_bytes`."""
    import struct

    from ..comm import wire

    (mlen,) = struct.unpack_from("<I", data, 0)
    manifest = json.loads(data[4:4 + mlen].decode("utf-8"))
    tensors = wire.deserialize_tensors(data[4 + mlen:]).tensors
    sections: Dict[str, dict] = {"layers": {}, "embed": {},
                                 "final_norm": {}, "lm_head": {}}
    for name, arr in zip(manifest["names"], tensors):
        sec, _, key = name.partition("/")
        sections[sec][key] = jnp.asarray(arr)
    present = manifest["present"]
    return StageParams(
        layers=sections["layers"],
        embed=sections["embed"] if present["embed"] else None,
        final_norm=sections["final_norm"] if present["final_norm"] else None,
        lm_head=sections["lm_head"] if present["lm_head"] else None)


def load_or_init(model_name: str, cfg: ModelConfig,
                 checkpoint_dir: Optional[str] = None,
                 seed: int = 0, quantize: bool = True,
                 mesh=None) -> StageParams:
    """Load from a local checkpoint if given/found, else random-init.

    The random path keeps every test and benchmark runnable with zero
    network egress; throughput is weight-value independent.  ``quantize=False`` returns the float tree
    even for ``-int8`` configs — used by the server app, whose artifact
    channel ships float weights and lets each stage quantize locally.

    ``mesh``: a tp mesh — the tree comes back in the engines' tp layout
    (``parallel.sharding``, replicated embed).  Seeded weights are BORN
    sharded: one jit with ``out_shardings`` writes every device's slice
    in place, because a model sized for N chips (qwen2.5-7b bf16, 15.2
    GB, on four 16 GB chips) does not fit on device 0 on its way there.
    The values are those of the unsharded init (the threefry stream
    does not depend on the partitioning; pinned by a test).
    """
    import jax
    if checkpoint_dir and os.path.isdir(checkpoint_dir):
        from ..checkpoint import _META
        if os.path.exists(os.path.join(checkpoint_dir, _META)):
            # our own orbax checkpoint format (checkpoint.save_params) —
            # already quantized as saved, so return directly.
            from ..checkpoint import load_params
            params, _ = load_params(checkpoint_dir, cfg,
                                    model_name=model_name)
        else:
            params = params_from_state_dict(
                load_safetensors_dir(checkpoint_dir), cfg)
            if quantize:
                from ..ops.quant import maybe_quantize
                params = maybe_quantize(params, cfg)
        if mesh is not None:
            from ..parallel.sharding import shard_params
            params = shard_params(params, cfg, mesh,
                                  vocab_parallel_embed=False)
        return params
    # random path: quantize during init (layer-chunked) so peak HBM
    # stays near the quantized footprint — an 8B -int8/-int4 config
    # must be initializable on exactly the chips its bf16 tree would
    # not fit.
    init = functools.partial(
        init_full_params, cfg=cfg,
        quantize=quantize and cfg.quantization in ("int8", "int4"))
    rng = jax.random.PRNGKey(seed)
    if mesh is None:
        return init(rng)
    from ..parallel.sharding import stage_param_shardings
    shardings = stage_param_shardings(jax.eval_shape(init, rng), cfg, mesh,
                                      vocab_parallel_embed=False)
    return jax.jit(init, out_shardings=shardings)(rng)


# ---------------------------------------------------------------------------
# vision tower (CLIP-ViT / LLaVA checkpoints)

def vision_params_from_clip_state_dict(raw: Dict[str, np.ndarray], vcfg,
                                       decoder_hidden: int,
                                       seed: int = 0) -> dict:
    """Map an HF CLIP vision tower (``vision_model.*`` names — standalone
    ``CLIPVisionModel`` exports and LLaVA bundles alike) onto the stacked
    ``models/vision.py`` layout.  Requires ``vcfg.clip_arch`` (the class
    token / pre-layernorm / projection-bias geometry those checkpoints
    ship).  The LLaVA ``multi_modal_projector`` weights are mapped when
    present; otherwise the projector stays seed-initialized (a plain CLIP
    export has no projector into the decoder's space).

    HF stores linears as [out, in]; ours are [in, out] matmul operands,
    hence the transposes.  The patch "conv" [H, C, p, p] flattens to our
    patchify order (row-in-patch, col-in-patch, channel) via
    ``transpose(2, 3, 1, 0)``.
    """
    import jax as _jax

    from .vision import init_vision_params

    if not vcfg.clip_arch:
        raise ValueError(
            "CLIP checkpoints need VisionConfig(clip_arch=True) — the "
            "plain tower has no class token / pre-layernorm to load into")

    def get(name):
        return _get(raw, name, prefixes=(
            "vision_model.",                         # CLIPVisionModel
            "vision_tower.vision_model.",            # LLaVA bundles
            "model.vision_tower.vision_model.", ""))

    dt = vcfg.dtype
    L = vcfg.num_layers
    layer_map = {
        "layer_norm1.weight": ("norm1_w", False),
        "layer_norm1.bias": ("norm1_b", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.v_proj.bias": ("bv", False),
        "self_attn.out_proj.weight": ("wo", True),
        "self_attn.out_proj.bias": ("bo", False),
        "layer_norm2.weight": ("norm2_w", False),
        "layer_norm2.bias": ("norm2_b", False),
        "mlp.fc1.weight": ("w_up", True),
        "mlp.fc1.bias": ("b_up", False),
        "mlp.fc2.weight": ("w_down", True),
        "mlp.fc2.bias": ("b_down", False),
    }
    layers: Dict[str, list] = {}
    for i in range(L):
        for hf_name, (ours, transpose) in layer_map.items():
            w = get(f"encoder.layers.{i}.{hf_name}")
            layers.setdefault(ours, []).append(w.T if transpose else w)
    stacked = {k: jnp.asarray(np.stack(v), dt) for k, v in layers.items()}

    patch = get("embeddings.patch_embedding.weight")     # [H, C, p, p]
    p_ = vcfg.patch_size
    patch = patch.transpose(2, 3, 1, 0).reshape(
        p_ * p_ * vcfg.channels, vcfg.hidden_size)

    # projector seed-init as the fallback; checkpoint weights overwrite
    out = init_vision_params(_jax.random.PRNGKey(seed), vcfg,
                             decoder_hidden)
    out.update({
        "patch_embed": jnp.asarray(patch, dt),
        "pos_embed": jnp.asarray(
            get("embeddings.position_embedding.weight"), dt),
        "cls_embed": jnp.asarray(
            get("embeddings.class_embedding").reshape(-1), dt),
        "pre_norm_w": jnp.asarray(get("pre_layrnorm.weight"), dt),
        "pre_norm_b": jnp.asarray(get("pre_layrnorm.bias"), dt),
        "post_norm_w": jnp.asarray(get("post_layernorm.weight"), dt),
        "post_norm_b": jnp.asarray(get("post_layernorm.bias"), dt),
        "layers": stacked,
    })
    for hf_name, ours, transpose in (
            ("multi_modal_projector.linear_1.weight", "proj_w1", True),
            ("multi_modal_projector.linear_1.bias", "proj_b1", False),
            ("multi_modal_projector.linear_2.weight", "proj_w2", True),
            ("multi_modal_projector.linear_2.bias", "proj_b2", False)):
        for prefix in ("", "model."):
            if prefix + hf_name in raw:
                w = np.asarray(raw[prefix + hf_name])
                out[ours] = jnp.asarray(w.T if transpose else w, dt)
                break
    if out["pos_embed"].shape[0] != vcfg.num_positions:
        raise ValueError(
            f"checkpoint position table has {out['pos_embed'].shape[0]} "
            f"rows; VisionConfig expects {vcfg.num_positions} "
            f"(image {vcfg.image_size} / patch {vcfg.patch_size} + cls)")
    # a projector sized for a different decoder must fail HERE with the
    # shapes spelled out, not as an XLA dot error on the first request
    want1 = (vcfg.hidden_size, decoder_hidden)
    want2 = (decoder_hidden, decoder_hidden)
    if (out["proj_w1"].shape != want1 or out["proj_w2"].shape != want2):
        raise ValueError(
            f"checkpoint projector maps {out['proj_w1'].shape} -> "
            f"{out['proj_w2'].shape}; this tower/decoder pairing needs "
            f"{want1} -> {want2} (decoder hidden {decoder_hidden})")
    return out


_VISION_KEY_PREFIXES = ("vision_model.", "vision_tower.",
                        "model.vision_tower.", "multi_modal_projector.",
                        "model.multi_modal_projector.")


def load_vision_params(path: str, vcfg, decoder_hidden: int,
                       seed: int = 0) -> dict:
    """CLIP/LLaVA vision weights from a safetensors checkpoint dir.

    Only vision-tower / projector keys are materialized — pointing this
    at a full LLaVA bundle must not copy the language model's weights
    into host RAM just to extract the tower."""
    tensors = load_safetensors_dir(
        path, key_filter=lambda k: k.startswith(_VISION_KEY_PREFIXES))
    return vision_params_from_clip_state_dict(tensors, vcfg,
                                              decoder_hidden, seed=seed)
