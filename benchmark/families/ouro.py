"""Ouro blocks, a LOOPED decoder.  ``embed`` here returns the rows that
enter the LAST pass, not the first layer: ``reference.py`` runs its layer
loop once and may not be edited, so passes ``0 .. T-2`` (each the whole
stack, closed by the final norm) are written out below in this file's
own loop over the stacked leaves, ``reference.py``'s loop is pass
``T-1``, and its ``final_norm`` closes that one.

The equations (Ouro / LoopLM, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741, and the source repository's modelling
file; ``config.json`` gives the sizes, ``rope_theta``, the eps,
``total_ut_steps`` and ``early_exit_threshold``).  ``T = ut_steps``
passes over the same ``L`` layers:

    x = E[ids]                                    no scale
    for t in 0..T-1:
      for l in 0..L-1:
        h = rmsnorm(x, g1[l])                     input_layernorm
        q, k, v = h Wq[l], h Wk[l], h Wv[l]       no bias
        q, k = rope(q, k)                         rotate-half, the token's
                                                  position, every pass
        a = causal softmax(q k^T / sqrt(d)) v     over pass t's own k, v
        x = x + rmsnorm(a Wo[l], g2[l])           on the sublayer's OUTPUT
        h = rmsnorm(x, g3[l])                     post_attention_layernorm
        x = x + rmsnorm((silu(h Wg[l]) * (h Wu[l])) Wd[l], g4[l])
      x = rmsnorm(x, g_final)                     after EVERY pass
    logits = x W_head                             untied, last pass

A pass attends to the keys and values that same pass made (the program
keeps ``T x L`` planes); written over the whole sequence at once that is
plain causal attention inside each layer call, so no cache appears here.
At ``early_exit_threshold`` 1 the exit gate decides nothing: all ``T``
passes run for every token and the gate is not evaluated.

Part 2, the shape arithmetic.  ``layer_matrix_elements`` and
``layer_scale_elements`` return ``T`` times one layer's count: the unit
``bytes.weight_bytes_per_pass`` and ``step_weight_stream_pct`` count in
is what one slab pass or one decode step of the PROGRAM reads, and such
a step streams every layer matrix ``T`` times (and the head once).  A
token holds keys and values in ``T x L`` planes.  ``decode_step_bytes``
is what the ``loop_*`` readers divide by."""

from __future__ import annotations

from bytes import (attention_matrix_elements, attention_scale_elements,
                   dims)


def _passes(mc: dict) -> int:
    return mc.get("ut_steps", 1)


def layer_matrix_elements(mc: dict) -> int:
    """Attention's four matrices and the gate, up and down projections,
    once for each pass of a token's step."""
    return _passes(mc) * (attention_matrix_elements(mc)
                          + 3 * mc["hidden_size"] * mc["intermediate_size"])


def layer_scale_elements(mc: dict) -> int:
    return _passes(mc) * (attention_scale_elements(mc)
                          + 2 * mc["intermediate_size"] + mc["hidden_size"])


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> float:
    """Keys and values in every plane: ``layers x passes`` of them."""
    _, _, nkv, hd, _, layers = dims(mc)
    return _passes(mc) * layers * 2 * nkv * hd * kv_bytes / chips


def head_bytes(mc: dict, chips: int = 1) -> float:
    """The untied bf16 head, read once a step."""
    return mc["vocab_size"] * mc["hidden_size"] * 2 / chips


def decode_step_bytes(mc: dict, kv_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2, chips: int = 1) -> float:
    """The least one decode step reads on a chip: the layer stack once a
    pass, the head once, and the keys and values of the ``kv_tokens``
    tokens the decoding rows hold, in every plane."""
    stack = (layer_matrix_elements(mc) * weight_bytes
             + (layer_scale_elements(mc) * 4 if weight_bytes == 1 else 0))
    return (mc["num_layers"] * stack / chips + head_bytes(mc, chips)
            + kv_tokens * kv_bytes_per_token(mc, kv_bytes, chips))


def equations(mc: dict):
    import jax
    import jax.numpy as jnp
    from reference import F32, _attention, _f32, _rms_norm, _rope

    _, nh, nkv, hd, _, n_layers = dims(mc)
    eps = mc.get("norm_eps", 1e-5)
    theta = mc.get("rope_theta", 10000.0)

    def layer(p, x):
        t = x.shape[0]
        h = _rms_norm(x, p["attn_norm_w"], eps)
        q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
        q, k, v = (q.reshape(t, nh, hd), k.reshape(t, nkv, hd),
                   v.reshape(t, nkv, hd))
        q, k = _rope(q, theta), _rope(k, theta)
        a = _attention(q, k, v, None).reshape(t, nh * hd) @ p["wo"]
        x = x + _rms_norm(a, p["attn_post_norm_w"], eps)
        h = _rms_norm(x, p["mlp_norm_w"], eps)
        m = (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
        return x + _rms_norm(m, p["mlp_post_norm_w"], eps)

    def final_norm(params, x):
        return _rms_norm(x, _f32(params.final_norm["w"]), eps)

    @jax.jit
    def layer_at(x, layers, i):
        p = {k: _f32(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            v)) for k, v in layers.items()}
        return layer(p, x)

    def embed(params, ids):
        """The embedding, then passes ``0 .. T-2`` (see the first lines):
        what enters the last pass."""
        x = params.embed["tokens"][ids].astype(F32)
        for _ in range(_passes(mc) - 1):
            for i in range(n_layers):
                x = layer_at(x, params.layers, jnp.int32(i))
            x = final_norm(params, x)
        return x

    return embed, layer, final_norm
