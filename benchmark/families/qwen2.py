"""Qwen2 blocks (Qwen2.5 technical report; HF ``modeling_qwen2``):
pre-RMSNorm, q/k/v projections with bias, rotary embedding in the
rotate-half form, grouped-query causal attention, SwiGLU feed-forward,
untied head."""

from __future__ import annotations

from bytes import (attention_matrix_elements, attention_scale_elements,
                   dims)


def layer_matrix_elements(mc: dict) -> int:
    """Attention's four matrices and the gate, up and down projections."""
    return (attention_matrix_elements(mc)
            + 3 * mc["hidden_size"] * mc["intermediate_size"])


def layer_scale_elements(mc: dict) -> int:
    return (attention_scale_elements(mc)
            + 2 * mc["intermediate_size"] + mc["hidden_size"])


def equations(mc: dict):
    import jax
    from reference import F32, _attention, _f32, _rms_norm, _rope

    _, nh, nkv, hd, _, _ = dims(mc)
    eps = mc.get("norm_eps", 1e-5)
    theta = mc.get("rope_theta", 10000.0)

    def embed(params, ids):
        return params.embed["tokens"][ids].astype(F32)

    def layer(p, x):
        t = x.shape[0]
        h = _rms_norm(x, p["attn_norm_w"], eps)
        q = h @ p["wq"] + p["bq"]
        k = h @ p["wk"] + p["bk"]
        v = h @ p["wv"] + p["bv"]
        q, k, v = (q.reshape(t, nh, hd), k.reshape(t, nkv, hd),
                   v.reshape(t, nkv, hd))
        q, k = _rope(q, theta), _rope(k, theta)
        a = _attention(q, k, v, None).reshape(t, nh * hd) @ p["wo"]
        x = x + a
        h = _rms_norm(x, p["mlp_norm_w"], eps)
        m = (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
        return x + m

    def final_norm(params, x):
        return _rms_norm(x, _f32(params.final_norm["w"]), eps)

    return embed, layer, final_norm
