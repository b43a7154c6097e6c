"""DeepSeek-V3 blocks as kanana-2-30b-a3b-instruct-2601 configures them
(``model_type: deepseek_v3``; DeepSeek-V2, arXiv:2405.04434, section 2.1
for the latent attention, DeepSeek-V3, arXiv:2412.19437, section 2.1.2
for the router; HF ``modeling_deepseek_v3``).  ``H`` hidden, ``nh``
heads, ``dn`` / ``dr`` / ``dv`` the no-rope, rope and value sizes of a
head, ``r`` the latent rank; no bias anywhere:

    h   = rmsnorm(x, g_attn)
    q   = h Wq                 -> [T, nh, dn + dr] = [q_nope | q_pe]   (q_lora_rank null)
    ckv = h Wkv_a              -> [T, r + dr]      = [c | k_pe]
    c   = rmsnorm(c, g_kv)                                              (kv_a_layernorm)
    k_nope_i = c W_UK_i ; v_i = c W_UV_i           Wkv_b = [W_UK_i | W_UV_i] a head
    q_pe, k_pe = rope(q_pe), rope(k_pe)            INTERLEAVED pairs (2i, 2i+1); k_pe is one
                                                   head, shared by all nh
    a   = causal softmax([q_nope|q_pe] . [k_nope|k_pe] * (dn + dr) ** -0.5) v  -> [T, nh dv] Wo
    x   = x + a ;  h = rmsnorm(x, g_mlp)
    leading blocks (first_k_dense_replace):  x = x + (silu(h Wg) * (h Wu)) Wd
    the others:  s = sigmoid(h Wr)                 [T, E]
                 pick = the k largest of (s + b)   b = e_score_correction_bias
                 (n_group 1, topk_group 1: the group step keeps every expert; a no-op)
                 w = s[pick] ; w = w / (sum w + 1e-20) ; w = scale * w
                 x = x + sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)
    logits = rmsnorm(x, g_final) W_head            untied

The program stores ``Wkv_b`` as its halves a head, ``w_uk[i] = W_UK_i^T``
``[dn, r]`` and ``w_uv[i] = W_UV_i`` ``[r, dv]``; here keys and values are
DECOMPRESSED a head from them (``k_nope_i = c w_uk[i]^T``), the form the
paper gives, with no cache.  Every expert is computed for every row,
weights zero off the chosen k.  The rope is this file's own (the shared
helper pairs ``(i, i + d/2)``).

``reference.py`` runs ``model_config.num_layers`` calls of ``layer`` over
``params.layers``, and that counts the REPEATED stack; the leading dense
blocks (``lead_dense_layers``, leaves in ``params.lead``) run inside
``embed`` here, once each, so ``embed`` returns the rows that leave them
(``families/ouro.py`` is the precedent for an ``embed`` that holds
layers).

Part 2, the shape arithmetic.  ``layer_matrix_elements`` is one expert
block's matrices (a pass taken to read all experts;
``moe_experts_touched_pct`` says how far that holds) PLUS the leading
blocks' share spread over ``num_layers``, because ``bytes.py`` multiplies
it by ``num_layers``.  A token holds ONE row a block in the page pool,
``[c | k_pe]`` padded to whole 128-lane tiles (576 -> 640 values): what
the chip's memory holds for a 576-wide row anyway, and what the kernels'
DMA needs stated."""

from __future__ import annotations

# the routed projections are olmoe's: three grouped matmuls a row, a
# touched expert's three matrices read once (the readers pass the bytes
# of a stored weight)
from families.olmoe import moe_kernel_bytes, moe_kernel_ops  # noqa: F401

LANES = 128


def _sizes(mc: dict):
    return (mc["hidden_size"], mc["num_heads"], mc["qk_nope_head_dim"],
            mc["qk_rope_head_dim"], mc["v_head_dim"], mc["kv_lora_rank"])


def _blocks(mc: dict) -> int:
    return mc.get("lead_dense_layers", 0) + mc["num_layers"]


def page_width(mc: dict) -> int:
    """Values in one token's row of a page: ``r + dr`` in whole lanes."""
    return -(-(mc["kv_lora_rank"] + mc["qk_rope_head_dim"]) // LANES) * LANES


def attention_elements(mc: dict) -> int:
    """Wq, Wkv_a, Wkv_b and Wo."""
    h, nh, dn, dr, dv, r = _sizes(mc)
    return h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv) + nh * dv * h


def _expert_elements(mc: dict) -> int:
    """One routed expert's gate, up and down matrices."""
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def expert_layer_matrix_elements(mc: dict) -> int:
    """One expert block: attention, the shared experts (one SwiGLU of
    their summed width), the router and every routed expert."""
    return (attention_elements(mc)
            + mc.get("num_shared_experts", 0) * _expert_elements(mc)
            + mc["hidden_size"] * mc["num_experts"]
            + mc["num_experts"] * _expert_elements(mc))


def lead_layer_matrix_elements(mc: dict) -> int:
    """One leading dense block: attention and a SwiGLU of the dense width."""
    return (attention_elements(mc)
            + 3 * mc["hidden_size"] * mc.get("lead_intermediate_size", 0))


def layer_matrix_elements(mc: dict) -> float:
    """An expert block's elements plus the leading blocks' share
    (``bytes.py`` multiplies by ``num_layers``; the product is the whole
    model's)."""
    return (expert_layer_matrix_elements(mc)
            + mc.get("lead_dense_layers", 0) * lead_layer_matrix_elements(mc)
            / mc["num_layers"])


def layer_scale_elements(mc: dict) -> float:
    """Output channels of the matrices an int8 variant would quantize (q,
    o, the experts' and the shared and dense SwiGLUs' three; the latent
    projections, router and norms stay as they are)."""
    h, nh, dn, dr, dv, _ = _sizes(mc)
    i = mc["intermediate_size"]
    attn = nh * (dn + dr) + h
    expert = (attn + (mc["num_experts"] + 1) * (2 * i + h)
              + 2 * (mc.get("num_shared_experts", 0) - 1) * i)
    lead = attn + 2 * mc.get("lead_intermediate_size", 0) + h
    return expert + mc.get("lead_dense_layers", 0) * lead / mc["num_layers"]


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> float:
    """One lane-padded latent row in every block's plane.  Nothing of it
    is sharded: every chip of a deployment would hold the whole row."""
    del chips
    return _blocks(mc) * page_width(mc) * kv_bytes


def _attend_ops_per_pair(mc: dict) -> int:
    """Multiply-adds x 2 for one (query token, cached token) pair in one
    block, absorbed form: every head's score over ``r + dr`` values and
    its output over ``r``.  The pad lanes and masked pairs are not work."""
    _, nh, _, dr, _, r = _sizes(mc)
    return 2 * nh * (r + dr + r)


def mla_decode_kernel_ops(mc: dict, kv_tokens: int) -> int:
    """One decode step over rows that hold ``kv_tokens`` tokens between
    them: one query token a row against each, in every block."""
    return _blocks(mc) * kv_tokens * _attend_ops_per_pair(mc)


def mla_decode_kernel_bytes(mc: dict, kv_tokens: int,
                            kv_bytes: int = 2) -> int:
    """The least one decode step reads: each held token's row once a
    block (tokens, where the kernel reads whole pages: never over)."""
    return _blocks(mc) * kv_tokens * page_width(mc) * kv_bytes


def mla_prefill_kernel_ops(mc: dict, pairs: int) -> int:
    """A slab whose chunk tokens attend over ``pairs`` (query, cached
    token) pairs (each token: its context and itself), in every block."""
    return _blocks(mc) * pairs * _attend_ops_per_pair(mc)


def mla_prefill_kernel_bytes(mc: dict, pairs: int, chunk: int,
                             kv_bytes: int = 2) -> int:
    """The least a slab reads: a chunk's context once a block.  ``pairs /
    chunk`` is under every chunk's context (a full chunk at start ``s``
    has ``chunk x s + chunk (chunk + 1) / 2`` pairs)."""
    return int(_blocks(mc) * pairs / max(1, chunk) * page_width(mc)
               * kv_bytes)


def blocks(mc: dict, q_block: int = 512):
    """``(lead_layer, layer)``: one leading dense block and one expert
    block over ``[T, H]`` rows, ``p`` that block's float32 leaves."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _rms_norm

    _, nh, dn, dr, dv, r = _sizes(mc)
    eps = mc.get("norm_eps", 1e-5)
    theta = mc.get("rope_theta", 10000.0)
    n_experts, top_k = mc["num_experts"], mc["experts_per_token"]
    renormalise = mc.get("norm_topk_prob", True)
    scale = mc.get("routed_scaling_factor", 1.0)
    sigmoid = mc.get("router_scoring", "softmax") == "sigmoid"

    def rope(x):
        """Interleaved rotary embedding.  x: [T, heads, d], positions
        0..T-1; pair i is channels (2i, 2i + 1)."""
        t, heads, d = x.shape
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
        ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         -1).reshape(t, heads, d)

    def attention(q, k, v):
        """Causal softmax attention, queries in blocks of ``q_block`` so
        that a long sequence's scores fit.  q, k: [T, nh, dn + dr]; v:
        [T, nh, dv]."""
        t = q.shape[0]
        out = []
        for lo in range(0, t, q_block):
            hi = min(t, lo + q_block)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * (dn + dr) ** -0.5
            causal = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])
            s = jnp.where(causal[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                  v[:hi]))
        return jnp.concatenate(out, 0)

    def attend(p, x):
        t = x.shape[0]
        h = _rms_norm(x, p["attn_norm_w"], eps)
        q = (h @ p["wq"]).reshape(t, nh, dn + dr)
        ckv = h @ p["wkv_a"]
        c = _rms_norm(ckv[:, :r], p["kv_norm_w"], eps)
        k_nope = jnp.einsum("tr,hdr->thd", c, p["w_uk"])
        v = jnp.einsum("tr,hrv->thv", c, p["w_uv"])
        k_pe = rope(ckv[:, None, r:])                       # one head
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (t, nh, dr))], -1)
        return x + attention(q, k, v).reshape(t, nh * dv) @ p["wo"]

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ gate) * (h @ up)) @ down

    def lead_layer(p, x):
        x = attend(p, x)
        h = _rms_norm(x, p["mlp_norm_w"], eps)
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])

    def layer(p, x):
        x = attend(p, x)
        h = _rms_norm(x, p["mlp_norm_w"], eps)
        logits = h @ p["router"]
        s = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, -1)
        choice = s + p["router_bias"] if "router_bias" in p else s
        # the group step of noaux_tc (n_group 1, topk_group 1) keeps every
        # expert: a no-op, not written
        kth = jnp.sort(choice, -1)[:, n_experts - top_k][:, None]
        w = jnp.where(choice >= kth, s, 0.0)
        if renormalise:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * scale
        y = jnp.zeros_like(x)
        for e in range(n_experts):
            y = y + w[:, e:e + 1] * swiglu(h, p["w_gate"][e], p["w_up"][e],
                                           p["w_down"][e])
        if "ws_gate" in p:
            y = y + swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
        return x + y

    return lead_layer, layer


def equations(mc: dict, q_block: int = 512):
    import jax
    import jax.numpy as jnp
    from reference import F32, _f32, _rms_norm

    lead_layer, layer = blocks(mc, q_block)
    eps = mc.get("norm_eps", 1e-5)
    n_lead = mc.get("lead_dense_layers", 0)

    def final_norm(params, x):
        return _rms_norm(x, _f32(params.final_norm["w"]), eps)

    @jax.jit
    def lead_at(x, lead, i):
        p = {k: _f32(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            v)) for k, v in lead.items()}
        return lead_layer(p, x)

    def embed(params, ids):
        """The embedding, then the leading dense blocks (see the first
        lines): what enters the repeated stack."""
        x = params.embed["tokens"][ids].astype(F32)
        for i in range(n_lead):
            x = lead_at(x, params.lead, jnp.int32(i))
        return x

    return embed, layer, final_norm
