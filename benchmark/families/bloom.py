"""BLOOM blocks (BLOOM paper, section 3; HF ``modeling_bloom``):
LayerNorm after the embedding, pre-LayerNorm blocks with biases
everywhere, ALiBi (score + slope_h * key_position), causal multi-head
attention, a 4H feed-forward with the tanh form of GELU, head tied to
the embedding."""

from __future__ import annotations

from bytes import (attention_matrix_elements, attention_scale_elements,
                   dims)


def layer_matrix_elements(mc: dict) -> int:
    """Attention's four matrices and the up and down projections."""
    return (attention_matrix_elements(mc)
            + 2 * mc["hidden_size"] * mc["intermediate_size"])


def layer_scale_elements(mc: dict) -> int:
    return (attention_scale_elements(mc)
            + mc["intermediate_size"] + mc["hidden_size"])


def equations(mc: dict):
    import jax.numpy as jnp
    from reference import (F32, _attention, _f32, _gelu_tanh, _layer_norm,
                           alibi_slopes)

    _, nh, nkv, hd, _, _ = dims(mc)
    eps = mc.get("norm_eps", 1e-5)
    slopes = jnp.asarray(alibi_slopes(nh), F32)

    def embed(params, ids):
        x = params.embed["tokens"][ids].astype(F32)
        return _layer_norm(x, _f32(params.embed["norm_w"]),
                           _f32(params.embed["norm_b"]), eps)

    def layer(p, x):
        t = x.shape[0]
        h = _layer_norm(x, p["attn_norm_w"], p["attn_norm_b"], eps)
        q = h @ p["wq"] + p["bq"]
        k = h @ p["wk"] + p["bk"]
        v = h @ p["wv"] + p["bv"]
        q, k, v = (q.reshape(t, nh, hd), k.reshape(t, nkv, hd),
                   v.reshape(t, nkv, hd))
        a = _attention(q, k, v, slopes).reshape(t, nh * hd) @ p["wo"]
        a = a + p["bo"]
        x = x + a
        h = _layer_norm(x, p["mlp_norm_w"], p["mlp_norm_b"], eps)
        m = _gelu_tanh(h @ p["w_up"] + p["b_up"]) @ p["w_down"] \
            + p["b_down"]
        return x + m

    def final_norm(params, x):
        return _layer_norm(x, _f32(params.final_norm["w"]),
                           _f32(params.final_norm["b"]), eps)

    return embed, layer, final_norm
