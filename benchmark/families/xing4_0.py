"""Xing4.0-29B-A4B's blocks (``model_type: xing4_0``): deepseek_v3's latent
attention and router (``families/deepseek_v3.py``; DeepSeek-V2,
arXiv:2405.04434, section 2.1; DeepSeek-V3, arXiv:2412.19437, section
2.1.2; HF ``modeling_deepseek_v3``) with a low-rank query and YaRN, on a
residual path of ``n`` streams a token mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, section 4, over
Hyper-Connections, arXiv:2409.19606, section 3).

Per token ``X`` ``[n, H]`` (``n = hc_streams`` = 4).  A block has two
sublayers ``F`` (attention, then feed-forward), each with its own float32
leaves ``phi`` ``[2n + n^2, n H]`` (``hc_<sub>_phi``: the paper's map
transposed), ``alpha`` ``[3]``, ``b`` ``[2n + n^2]`` and its own norm gain
``g``:

    u      = vec(X) ;  r = (mean(u^2) + norm_eps)^-1/2
    m      = phi (u r)                                       [n + n + n^2]
    h_pre  = sigmoid(alpha[0] m[0:n]  + b[0:n])
    h_post = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])
    A      = clip(alpha[2] mat(m[2n:]) + mat(b[2n:]), -hc_res_clamp, hc_res_clamp)
    M      = exp(A) ;  hc_sinkhorn_iters times:
             M = M / (colsum(M) + hc_eps) ;  M = M / (rowsum(M) + hc_eps)
    y      = F(rmsnorm(sum_i h_pre[i] X[i], g))
    X'[i]  = sum_j M[i, j] X[j] + h_post[i] y

    embed:   X[i] = emb(token) for every i ;  final:  rmsnorm(sum_i X[i], g_final)

``F`` = attention, with ``h`` the normed row, ``nh`` heads of ``dn`` no-rope
+ ``dr`` rope channels, values ``dv``, latent rank ``r``:

    q   = rmsnorm(h Wq_a, g_q) Wq                 -> [T, nh, dn + dr]   (q_lora_rank)
    ckv = h Wkv_a -> [c | k_pe] ;  c = rmsnorm(c, g_kv)
    k_nope_i = c W_UK_i ;  v_i = c W_UV_i
    q_pe, k_pe = rope(.)    INTERLEAVED pairs, YaRN frequencies (below), cos and
                            sin times ``attention_factor``
    a   = causal softmax([q_nope|q_pe] . [k_nope|k_pe] (dn + dr)^-1/2 attn_scale) v  Wo

YaRN (arXiv:2309.00071; HF ``_compute_yarn_parameters``): channel ``i``
of ``d / 2`` keeps ``theta^(-2i/d)`` where it turns ``beta_fast`` times or
more over the ``original`` positions, takes it over ``factor`` where it
turns ``beta_slow`` times or fewer, a linear ramp between.  ``attn_scale``
is deepseek's ``mscale^2`` with ``mscale = 0.1 ln(factor) + 1``.

``F`` = feed-forward: a SwiGLU of ``lead_intermediate_size`` in the leading
blocks; after them deepseek_v3's experts (sigmoid scores, the k largest of
score + bias, weights renormalised and scaled, one shared expert).

``reference.py`` runs ``num_layers`` calls of ``layer`` over
``params.layers`` (the REPEATED stack); the leading blocks run inside
``embed``, which returns ``[T, n, H]``; ``final_norm`` collapses.  Every
expert is computed for every row.  No line of the program.

Part 2, the shape arithmetic: deepseek_v3's with the query's two matrices
and the maps; and what the two residual-path kernels move.  Part 3,
``replay``: the reference check also holds the served maps' own reading.
"""

from __future__ import annotations

import math

from families.deepseek_v3 import (  # noqa: F401
    kv_bytes_per_token, mla_decode_kernel_bytes, mla_decode_kernel_ops,
    mla_prefill_kernel_bytes, mla_prefill_kernel_ops, page_width)
from families.olmoe import moe_kernel_bytes, moe_kernel_ops  # noqa: F401


def _streams(mc: dict) -> int:
    return mc["hc_streams"]


def _maps(mc: dict) -> int:
    n = _streams(mc)
    return 2 * n + n * n


def _blocks(mc: dict) -> int:
    return mc.get("lead_dense_layers", 0) + mc["num_layers"]


def attention_elements(mc: dict) -> int:
    """Wq_a, Wq, Wkv_a, Wkv_b and Wo."""
    h, nh = mc["hidden_size"], mc["num_heads"]
    dn, dr, dv, r = (mc["qk_nope_head_dim"], mc["qk_rope_head_dim"],
                     mc["v_head_dim"], mc["kv_lora_rank"])
    rq = mc["q_lora_rank"]
    return (h * rq + rq * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def hc_elements(mc: dict) -> int:
    """A block's two maps ``phi`` (float32: counted as elements)."""
    return 2 * _maps(mc) * _streams(mc) * mc["hidden_size"]


def _expert_elements(mc: dict) -> int:
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def expert_layer_matrix_elements(mc: dict) -> int:
    return (attention_elements(mc) + hc_elements(mc)
            + mc.get("num_shared_experts", 0) * _expert_elements(mc)
            + mc["hidden_size"] * mc["num_experts"]
            + mc["num_experts"] * _expert_elements(mc))


def lead_layer_matrix_elements(mc: dict) -> int:
    return (attention_elements(mc) + hc_elements(mc)
            + 3 * mc["hidden_size"] * mc.get("lead_intermediate_size", 0))


def layer_matrix_elements(mc: dict) -> float:
    """An expert block's elements plus the leading blocks' share
    (``bytes.py`` multiplies by ``num_layers``)."""
    return (expert_layer_matrix_elements(mc)
            + mc.get("lead_dense_layers", 0) * lead_layer_matrix_elements(mc)
            / mc["num_layers"])


def layer_scale_elements(mc: dict) -> float:
    """Output channels of the matrices an int8 variant would quantize (the
    query's second matrix, o, the experts' and the shared and dense
    SwiGLUs' three)."""
    h, nh = mc["hidden_size"], mc["num_heads"]
    i = mc["intermediate_size"]
    attn = nh * (mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"]) + h
    expert = (attn + (mc["num_experts"] + 1) * (2 * i + h)
              + 2 * (mc.get("num_shared_experts", 0) - 1) * i)
    lead = attn + 2 * mc.get("lead_intermediate_size", 0) + h
    return expert + mc.get("lead_dense_layers", 0) * lead / mc["num_layers"]


def hc_pre_kernel_bytes(mc: dict, rows: int, act_bytes: int = 2) -> int:
    """What the read calls move for ``rows`` token rows through every
    block: the ``n`` streams in and one row out, a sublayer (the maps'
    weights and a token's coefficients are not counted)."""
    n, h = _streams(mc), mc["hidden_size"]
    return 2 * _blocks(mc) * rows * (n + 1) * h * act_bytes


def hc_pre_kernel_ops(mc: dict, rows: int) -> int:
    """Multiply-adds x 2: the ``n H x (2n + n^2)`` product, the mean
    square and the weighted sum, a sublayer a block."""
    n, h = _streams(mc), mc["hidden_size"]
    return 2 * _blocks(mc) * rows * 2 * n * h * (_maps(mc) + 2)


def hc_post_kernel_bytes(mc: dict, rows: int, act_bytes: int = 2) -> int:
    """What the write calls move: the ``n`` streams and the sublayer's
    output in, the ``n`` streams out, ``2 n + 1`` rows a sublayer.  No
    roofline share is read from it: in the layer scan the compiler keeps
    a slab's streams in the chip's fast memory between the calls, where
    HBM's bandwidth is not the call's bound (a write call of 512 rows was
    measured at 27 us there, 40 us by this count at 819 GB/s, and at 53 us
    in the leading blocks, where the streams come from HBM: my chip runs,
    PR 60).  ``hc_post_kernel_ns_per_row`` reads the call's time as it
    is."""
    n, h = _streams(mc), mc["hidden_size"]
    return 2 * _blocks(mc) * rows * (2 * n + 1) * h * act_bytes


def hc_post_kernel_ops(mc: dict, rows: int) -> int:
    n, h = _streams(mc), mc["hidden_size"]
    return 2 * _blocks(mc) * rows * 2 * n * (n + 1) * h


def hc_stream_bytes_per_token(mc: dict, act_bytes: int = 2) -> int:
    """What the residual path moves a token through every block where
    nothing stays on the chip: both calls' bytes, ``3 n + 2`` rows of
    ``hidden_size`` a sublayer.  A constant of the configuration (the
    program counts the rows, ``/stats.hc.rows``, and not this)."""
    return (hc_pre_kernel_bytes(mc, 1, act_bytes)
            + hc_post_kernel_bytes(mc, 1, act_bytes))


def hc_calls_per_row(mc: dict) -> int:
    """Calls of either kind a token row passes: one a sublayer, two
    sublayers a block."""
    return 2 * _blocks(mc)


def yarn_inv_freq(d: int, theta: float, yarn):
    """YaRN's ``d / 2`` inverse frequencies as Python floats; plain rope's
    where ``yarn`` is empty."""
    extrap = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if not yarn:
        return extrap
    factor, original, beta_fast, beta_slow = yarn[:4]

    def turns_at(rotations):
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(extrap):
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def maps_of(mc: dict, p: dict, sub: str, X):
    """``(h_pre [T, n], h_post [T, n], M [T, n, n])`` of sublayer ``sub``
    ("attn" / "mlp") for streams ``X`` ``[T, n, H]``, float32."""
    import jax
    import jax.numpy as jnp

    n = _streams(mc)
    t = X.shape[0]
    u = X.reshape(t, -1)
    r = jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True)
                      + mc.get("norm_eps", 1e-5))
    m = (u * r) @ p[f"hc_{sub}_phi"].T
    alpha, b = p[f"hc_{sub}_alpha"], p[f"hc_{sub}_b"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    clamp = mc.get("hc_res_clamp", 30.0)
    A = jnp.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:], -clamp,
                 clamp).reshape(t, n, n)
    M = jnp.exp(A)
    eps = mc.get("hc_eps", 1e-6)
    for _ in range(mc.get("hc_sinkhorn_iters", 20)):
        M = M / (M.sum(1, keepdims=True) + eps)     # a column's sum
        M = M / (M.sum(2, keepdims=True) + eps)     # a row's
    return h_pre, h_post, M


def blocks(mc: dict, q_block: int = 512):
    """``(lead_layer, layer)``: one leading dense block and one expert
    block over ``[T, n, H]`` streams, ``p`` that block's float32 leaves."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _rms_norm

    nh = mc["num_heads"]
    dn, dr, dv, r = (mc["qk_nope_head_dim"], mc["qk_rope_head_dim"],
                     mc["v_head_dim"], mc["kv_lora_rank"])
    eps = mc.get("norm_eps", 1e-5)
    yarn = tuple(mc.get("yarn") or ())
    inv_freq = yarn_inv_freq(dr, mc.get("rope_theta", 10000.0), yarn)
    rope_gain = yarn[4] if yarn else 1.0
    softmax_scale = (dn + dr) ** -0.5 * mc.get("attn_scale", 1.0)
    n_experts, top_k = mc["num_experts"], mc["experts_per_token"]
    renormalise = mc.get("norm_topk_prob", True)
    scale = mc.get("routed_scaling_factor", 1.0)

    def rope(x):
        """Interleaved rotary embedding.  x: [T, heads, d], positions
        0..T-1; pair i is channels (2i, 2i + 1)."""
        t, heads, d = x.shape
        ang = (jnp.arange(t, dtype=F32)[:, None]
               * jnp.asarray(inv_freq, F32)[None, :])
        cos = (jnp.cos(ang) * rope_gain)[:, None, :]
        sin = (jnp.sin(ang) * rope_gain)[:, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         -1).reshape(t, heads, d)

    def attention(q, k, v):
        """Causal softmax attention, queries in blocks of ``q_block``."""
        t = q.shape[0]
        out = []
        for lo in range(0, t, q_block):
            hi = min(t, lo + q_block)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * softmax_scale
            causal = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])
            s = jnp.where(causal[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                  v[:hi]))
        return jnp.concatenate(out, 0)

    def attend(p, h):
        t = h.shape[0]
        q = _rms_norm(h @ p["wq_a"], p["q_a_norm_w"], eps) @ p["wq"]
        q = q.reshape(t, nh, dn + dr)
        ckv = h @ p["wkv_a"]
        c = _rms_norm(ckv[:, :r], p["kv_norm_w"], eps)
        k_nope = jnp.einsum("tr,hdr->thd", c, p["w_uk"])
        v = jnp.einsum("tr,hrv->thv", c, p["w_uv"])
        k_pe = rope(ckv[:, None, r:])                       # one head
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (t, nh, dr))], -1)
        return attention(q, k, v).reshape(t, nh * dv) @ p["wo"]

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ gate) * (h @ up)) @ down

    def dense_mlp(p, h):
        return swiglu(h, p["w_gate"], p["w_up"], p["w_down"])

    def experts(p, h):
        s = jax.nn.sigmoid(h @ p["router"])
        choice = s + p["router_bias"] if "router_bias" in p else s
        kth = jnp.sort(choice, -1)[:, n_experts - top_k][:, None]
        w = jnp.where(choice >= kth, s, 0.0)
        if renormalise:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * scale
        y = jnp.zeros_like(h)
        for e in range(n_experts):
            y = y + w[:, e:e + 1] * swiglu(h, p["w_gate"][e], p["w_up"][e],
                                           p["w_down"][e])
        if "ws_gate" in p:
            y = y + swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
        return y

    def sublayer(p, X, sub, norm_w, F):
        h_pre, h_post, M = maps_of(mc, p, sub, X)
        h = _rms_norm(jnp.einsum("tn,tnh->th", h_pre, X), p[norm_w], eps)
        return (jnp.einsum("tij,tjh->tih", M, X)
                + h_post[:, :, None] * F(p, h)[:, None, :])

    def lead_layer(p, X):
        X = sublayer(p, X, "attn", "attn_norm_w", attend)
        return sublayer(p, X, "mlp", "mlp_norm_w", dense_mlp)

    def layer(p, X):
        X = sublayer(p, X, "attn", "attn_norm_w", attend)
        return sublayer(p, X, "mlp", "mlp_norm_w", experts)

    return lead_layer, layer


def equations(mc: dict, q_block: int = 512):
    import jax
    import jax.numpy as jnp
    from reference import F32, _f32, _rms_norm

    lead_layer, layer = blocks(mc, q_block)
    eps = mc.get("norm_eps", 1e-5)
    n_lead = mc.get("lead_dense_layers", 0)
    n = _streams(mc)

    def final_norm(params, X):
        """The streams' sum, normed: ``[.., n, H]`` -> ``[.., H]``."""
        return _rms_norm(X.sum(-2), _f32(params.final_norm["w"]), eps)

    @jax.jit
    def lead_at(X, lead, i):
        p = {k: _f32(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            v)) for k, v in lead.items()}
        return lead_layer(p, X)

    def embed(params, ids):
        """The embedding replicated into ``n`` streams, then the leading
        dense blocks: the ``[T, n, H]`` that enters the repeated stack."""
        x = params.embed["tokens"][ids].astype(F32)
        X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
        for i in range(n_lead):
            X = lead_at(X, params.lead, jnp.int32(i))
        return X

    return embed, layer, final_norm


def sinkhorn_residual(params, mc: dict, ids) -> float:
    """Largest ``|row or column sum - 1|`` of the first block's attention
    map over the tokens ``ids`` (float32, these equations): what the
    served maps' own reading is held beside (:func:`replay`)."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _f32

    n = _streams(mc)
    first = params.lead if mc.get("lead_dense_layers", 0) else params.layers
    p = {k: _f32(jax.tree.map(lambda a: a[0], v)) for k, v in first.items()
         if k.startswith("hc_attn_")}
    x = params.embed["tokens"][jnp.asarray(ids, jnp.int32)].astype(F32)
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    with jax.default_matmul_precision("highest"):
        M = maps_of(mc, p, "attn", X)[2]
    return float(jnp.maximum(jnp.abs(M.sum(1) - 1).max(),
                             jnp.abs(M.sum(2) - 1).max()))


# ``generation.hc_sinkhorn_residual`` of a reply with log-probabilities:
# the largest |row or column sum - 1| of the maps the SERVED kernel made
# (the program's start-up reading, ``/stats.hc.sinkhorn_residual_max``).
# Log-probabilities of 16 tokens cannot tell 20 Sinkhorn steps from one,
# nor float32 maps from bfloat16 ones (PERF.md section 6, PR 60); this
# number does.  The limit lies between what the change read over its seeds
# (1.1e-6 .. 1.2e-6) and what the maps read in the nearest precision below
# the configuration's float32 (bfloat16: 4.9e-3; one step: 0.13 .. 0.17):
# my chip runs, PR 60.
HC_RESIDUAL_LIMIT = 1e-4


def residual_problem(served, reference: float) -> str | None:
    """Why the served maps' reading refuses the run, or ``None``."""
    if served is None:
        return ("the reply carries no generation.hc_sinkhorn_residual: how "
                "far the served maps stand from doubly stochastic is part "
                "of what this family's check holds")
    if not 0.0 <= served <= HC_RESIDUAL_LIMIT:
        return (f"the served stream maps stand {served:.3g} from doubly "
                f"stochastic (limit {HC_RESIDUAL_LIMIT:g}; the float32 "
                f"equations read {reference:.3g} over these tokens): fewer "
                f"Sinkhorn steps than hc_sinkhorn_iters, or maps computed "
                f"below float32")
    return None


def replay(mc: dict):
    """``score(params, ids, n_prompt, generation)``: the tokens scored
    left to right in one forward as every one-token-a-pass family is
    (``reference.emitted_logprobs``), after the reply's reading of the
    served maps is held to ``HC_RESIDUAL_LIMIT``."""

    def score(params, ids, n_prompt, generation):
        import json
        import sys
        import reference

        served = (generation or {}).get("hc_sinkhorn_residual")
        ours = sinkhorn_residual(params, mc, ids)
        print(f"[replay] hc_sinkhorn_residual "
              f"{json.dumps({'served': served, 'reference': ours})}",
              file=sys.stderr, flush=True)
        problem = residual_problem(served, ours)
        if problem:
            return {"error": problem}
        rows, score_rows = reference.halves(params, mc)
        return score_rows(rows(ids)[n_prompt - 1: len(ids) - 1],
                          ids[n_prompt:])

    return score
