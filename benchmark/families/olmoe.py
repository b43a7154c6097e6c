"""OLMoE blocks (Muennighoff et al. 2024, "OLMoE: Open Mixture-of-Experts
Language Models", section 2; HF ``modeling_olmoe``): pre-RMSNorm, q/k/v
projections without bias, RMSNorm over the whole q and the whole k
projection (all heads' channels in one mean square, before the split
into heads and before rope), rotary embedding in the rotate-half form,
causal multi-head attention, and in place of the feed-forward a routed
mixture: softmax of the router's logits over ALL experts, the k largest
probabilities kept as they are (``norm_topk_prob`` false: they sum to
less than 1; true renormalises them), each chosen expert a SwiGLU of
width ``intermediate_size``.  No shared expert, no dense layer, no
auxiliary term at inference.  Untied head.

The expert sum is written as its definition: every expert computed for
every row, weights zero off the chosen k.  No sort, no grouping.

Also here, for the readers of the grouped-matmul kernel's metrics: the
operations and the least bytes of the expert layer's three projections,
from the program's routing counters."""

from __future__ import annotations

from bytes import (attention_matrix_elements, attention_scale_elements,
                   dims)


def _expert_elements(mc: dict) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def layer_matrix_elements(mc: dict) -> int:
    """Attention's four matrices, the router and every expert's three.
    A pass is taken to read all 64 experts; ``moe_experts_touched_pct``
    says how far that holds (a decode step over a few rows touches
    fewer)."""
    return (attention_matrix_elements(mc)
            + mc["hidden_size"] * mc["num_experts"]
            + mc["num_experts"] * _expert_elements(mc))


def layer_scale_elements(mc: dict) -> int:
    """Output channels served as int8: q, k, v, o and each expert's gate,
    up (``intermediate_size`` each) and down (``hidden_size``).  The
    router is never quantized and has none."""
    return (attention_scale_elements(mc) + mc["num_experts"]
            * (2 * mc["intermediate_size"] + mc["hidden_size"]))


def moe_kernel_ops(mc: dict, rows: int) -> int:
    """Multiply-adds x 2 of the three grouped matmuls over ``rows``
    token-expert rows: each row meets one expert's three matrices."""
    return 2 * rows * _expert_elements(mc)


def moe_kernel_bytes(mc: dict, rows: int, touched: int,
                     weight_bytes: int = 1, row_bytes: int = 2) -> int:
    """The least the three grouped matmuls move for ``rows`` rows over
    ``touched`` (layer call, expert) pairs with at least one row: each
    touched expert's three matrices once (and, as int8, a float32 scale a
    channel), and each row in and out of each projection (H in and I out
    twice, I in and H out once).  Never all experts: what was not touched
    is not read."""
    h, i = mc["hidden_size"], mc["intermediate_size"]
    scales = (2 * i + h) * 4 if weight_bytes == 1 else 0
    return (touched * (_expert_elements(mc) * weight_bytes + scales)
            + rows * 3 * (h + i) * row_bytes)


def equations(mc: dict):
    import jax
    import jax.numpy as jnp
    from reference import F32, _attention, _f32, _rms_norm, _rope

    _, nh, nkv, hd, _, _ = dims(mc)
    eps = mc.get("norm_eps", 1e-5)
    theta = mc.get("rope_theta", 10000.0)
    n_experts, top_k = mc["num_experts"], mc["experts_per_token"]
    renormalise = mc.get("norm_topk_prob", True)

    def embed(params, ids):
        return params.embed["tokens"][ids].astype(F32)

    def layer(p, x):
        t = x.shape[0]
        h = _rms_norm(x, p["attn_norm_w"], eps)
        q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
        q = _rms_norm(q, p["q_norm_w"], eps)        # over all nh x hd
        k = _rms_norm(k, p["k_norm_w"], eps)
        q, k, v = (q.reshape(t, nh, hd), k.reshape(t, nkv, hd),
                   v.reshape(t, nkv, hd))
        q, k = _rope(q, theta), _rope(k, theta)
        a = _attention(q, k, v, None).reshape(t, nh * hd) @ p["wo"]
        x = x + a
        h = _rms_norm(x, p["mlp_norm_w"], eps)
        probs = jax.nn.softmax(h @ p["router"], -1)             # [T, E]
        kth = jnp.sort(probs, -1)[:, n_experts - top_k][:, None]
        w = jnp.where(probs >= kth, probs, 0.0)
        if renormalise:
            w = w / w.sum(-1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(n_experts):
            act = jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])
            y = y + w[:, e:e + 1] * (act @ p["w_down"][e])
        return x + y

    def final_norm(params, x):
        return _rms_norm(x, _f32(params.final_norm["w"]), eps)

    return embed, layer, final_norm
