"""Laguna blocks as Laguna-S-2.1 configures them (``model_type: laguna``;
the source is a configuration file and no modeling file, so what the keys
do not settle is ASSUMED, listed below and in the configuration file's
``assumed``).  ``H`` hidden, ``hd`` head size, ``nkv`` kv heads everywhere;
a block's KIND ``k`` (``layer_types``) sets its query heads ``nh(k)``
(``num_attention_heads_per_layer``), its mask and its rope
(``rope_parameters``); no bias anywhere:

    h  = rmsnorm(x, g_attn)
    q  = h Wq_k -> [T, nh(k), hd] ; key = h Wk -> [T, nkv, hd] ; v = h Wv
    full:   q, key = yarn_rope(first half of a head's channels) | the rest
            inv_freq = interp * ramp + extrap * (1 - ramp), interp = extrap / factor,
            ramp = clip((i - low) / (high - low), 0, 1), low / high = the correction
            range of (beta_fast, beta_slow) (floor / ceil, kept inside the dim);
            cos and sin TIMES attention_factor       (HF _compute_yarn_parameters)
    window: q, key = rope(q), rope(key)              theta 1e4, the whole head
    s_ij = q_i . key_j / sqrt(hd) ; allowed iff j <= i, and on a window block i - j < W
    a_h  = softmax_j(s) v          query head h reads kv head h // (nh(k) / nkv)
    gate = sigmoid(h Wg_k) -> [T, nh(k)] ; a_h <- gate_h * a_h      ("gating": "per-head")
    x  = x + concat_h(a_h) Wo_k ;  h = rmsnorm(x, g_mlp)
    block 0:     x = x + (silu(h Wgate) * (h Wup)) Wdown           (mlp_only_layers)
    the others:  p = softmax(h Wr) over ALL experts ; pick the k largest ;
                 w = p[pick] / sum p[pick] ; w = scale * w
                 x = x + sum_{e in pick, e HELD} w_e SwiGLU_e(h) + SwiGLU_shared(h)
    logits = rmsnorm(x, g_final) W_head                            untied

ASSUMED (each also in the file's ``assumed``): the gate's form (sigmoid of
a linear map of the block's normed input, one scalar a head, on the head's
output before ``Wo``: headwise gated attention, arXiv:2505.06708; the
config says only ``per-head``); softmax scoring (the config has the
Qwen-MoE keys and no ``scoring_func``; softcapping 0 = none); the shared
expert added ungated; no q/k norm; the window's edge ``i - j < W`` (HF
``sliding_window_overlay``); rotate-half pairing.

THE SHARE.  The configuration holds ``experts_held = [held, first]`` of
the routed experts on this chip (a deployment's four chips share a block's
256).  The router scores all of them at its published width; the sum runs
over the chosen experts that are HELD, and what the absent ones would add
is left out, here as in the program (``models/decoder._moe_routed``).

HOW ``reference.py`` WALKS THE LEAVES.  The program keeps one stack a kind
of block, every leaf named ``<leaf>.<kind>`` and shaped ``[repeats, blocks
of the kind in a period, ...]`` (``models/decoder.init_period_params``).
``reference.py`` indexes every leaf of ``params.layers`` by the same ``i``
and calls ``layer(p, x)`` ``model_config.num_layers`` times with no index:
here ``num_layers`` counts REPEATS of the period (1 in the cut) and
``layer`` is one whole period, its blocks in the order ``model_config.
period`` gives.  The leading dense block (``params.lead``) runs inside
``embed`` (``families/deepseek_v3.py`` is the precedent).  The indexed unit
is then a whole period's leaves; ``layer`` slices a place and an expert
out of each BEFORE it multiplies, so that the compiler widens a slice and
never the stack.

Part 2, the shape arithmetic.  ``layer_matrix_elements`` is one PERIOD's
matrices as cut (the experts HELD) plus the leading block's share spread
over ``num_layers``, because ``bytes.py`` multiplies it by ``num_layers``.
``kv_bytes_per_token`` is the FULL kind's planes alone, what grows with a
token: a window block holds its last ``W`` tokens whatever the context, so
``decode_kernel_hbm_pct`` (which divides by every ``_paged_call*``'s time,
the window calls' too) reads under here and never over."""

from __future__ import annotations

import math

from families.olmoe import moe_kernel_bytes, moe_kernel_ops  # noqa: F401


def _kinds(mc: dict):
    """``(lead kind or None, [the period's kinds])`` as the file gives them."""
    return mc.get("lead_kind"), list(mc["period"])


def _hd(mc: dict) -> int:
    return mc.get("head_dim_override") or mc["hidden_size"] // mc["num_heads"]


def _held(mc: dict) -> int:
    held = mc.get("experts_held") or ()
    return held[0] if held else mc["num_experts"]


def attention_elements(mc: dict, kind: dict) -> int:
    """Wq, Wk, Wv, Wo and the gate of one block of ``kind``."""
    h, hd, nkv, nh = (mc["hidden_size"], _hd(mc), mc["num_kv_heads"],
                      kind["num_heads"])
    gate = h * nh if kind.get("gate", "none") == "per-head" else 0
    return 2 * h * nh * hd + 2 * h * nkv * hd + gate


def _expert_elements(mc: dict) -> int:
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def expert_block_elements(mc: dict, kind: dict) -> int:
    """One expert block of ``kind`` as cut: attention, the router at its
    published width, the experts HELD and the shared one."""
    return (attention_elements(mc, kind)
            + mc["hidden_size"] * mc["num_experts"]
            + (_held(mc) + mc.get("num_shared_experts", 0))
            * _expert_elements(mc))


def lead_block_elements(mc: dict) -> int:
    lead, _ = _kinds(mc)
    return (attention_elements(mc, lead)
            + 3 * mc["hidden_size"] * mc.get("lead_intermediate_size", 0))


def layer_matrix_elements(mc: dict) -> float:
    """One period's elements plus the leading blocks' share (``bytes.py``
    multiplies by ``num_layers``, the repeats: the product is the cut's)."""
    _, period = _kinds(mc)
    return (sum(expert_block_elements(mc, k) for k in period)
            + mc.get("lead_dense_layers", 0) * lead_block_elements(mc)
            / mc["num_layers"])


def layer_scale_elements(mc: dict) -> float:
    """Output channels of the matrices an int8 variant would quantize (q,
    k, v, o, the experts' and the shared and dense SwiGLUs' three; the
    router, the gate and the norms stay as they are)."""
    h, hd, nkv, i = (mc["hidden_size"], _hd(mc), mc["num_kv_heads"],
                     mc["intermediate_size"])
    lead, period = _kinds(mc)
    attn = lambda k: k["num_heads"] * hd + 2 * nkv * hd + h
    experts = (_held(mc) + mc.get("num_shared_experts", 0)) * (2 * i + h)
    lead_ch = (attn(lead) + 2 * mc.get("lead_intermediate_size", 0) + h
               if lead else 0)
    return (sum(attn(k) + experts for k in period)
            + mc.get("lead_dense_layers", 0) * lead_ch / mc["num_layers"])


def _blocks_of(mc: dict, window: bool) -> int:
    """Blocks whose attention is (not) a window, the leading ones too."""
    lead, period = _kinds(mc)
    is_w = lambda k: k.get("attn") == "window"
    n = mc["num_layers"] * sum(1 for k in period if is_w(k) == window)
    if lead is not None and is_w(lead) == window:
        n += mc.get("lead_dense_layers", 0)
    return n


def _window_kind(mc: dict) -> dict:
    return next(k for k in mc["period"] if k.get("attn") == "window")


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> float:
    """The full kind's planes alone: keys and values of every kv head in
    each full-attention block (what grows with a token).  Nothing of it is
    sharded in the deployment (attention is replicated)."""
    del chips
    return (_blocks_of(mc, window=False) * 2 * mc["num_kv_heads"] * _hd(mc)
            * kv_bytes)


def window_kv_bytes_per_token(mc: dict, kv_bytes: int = 2) -> int:
    """What a token holds in the window kind's pool while it is inside a
    window."""
    return (_blocks_of(mc, window=True) * 2 * mc["num_kv_heads"] * _hd(mc)
            * kv_bytes)


def _pair_ops(mc: dict, kind: dict) -> int:
    """Multiply-adds x 2 for one (query token, key) pair in one block of
    ``kind``: every query head's score and its output over ``hd``."""
    return 4 * kind["num_heads"] * _hd(mc)


def window_decode_kernel_ops(mc: dict, kv_window_tokens: int) -> int:
    """One decode step of the window blocks over rows whose windows hold
    ``kv_window_tokens`` keys between them (min(held, W) a row)."""
    return (_blocks_of(mc, True) * kv_window_tokens
            * _pair_ops(mc, _window_kind(mc)))


def window_decode_kernel_bytes(mc: dict, kv_window_tokens: int,
                               kv_bytes: int = 2) -> int:
    """The least that step reads: each key and value inside a window once
    a window block (tokens, where the kernel reads whole pages: never
    over)."""
    return kv_window_tokens * window_kv_bytes_per_token(mc, kv_bytes)


def window_prefill_kernel_ops(mc: dict, pairs: int) -> int:
    """A slab whose prompt tokens attend over ``pairs`` (query, key) pairs
    inside their windows, in every window block."""
    return _blocks_of(mc, True) * pairs * _pair_ops(mc, _window_kind(mc))


def window_prefill_kernel_bytes(mc: dict, pairs: int, chunk: int,
                                kv_bytes: int = 2) -> int:
    """The least a slab reads: ``pairs / chunk`` keys and values once a
    window block (under every chunk's view: a full chunk deep in a prompt
    sees ``chunk + W - 1`` keys and has ``chunk x W`` pairs)."""
    return int(pairs / max(1, chunk) * window_kv_bytes_per_token(mc, kv_bytes))


# ---------------------------------------------------------------- equations

def yarn_inv_freq(rotary_dim: int, theta: float, factor: float,
                  original: float, beta_fast: float, beta_slow: float) -> list:
    """YaRN's inverse frequencies as a list of Python floats (this file's
    own: HF ``_compute_yarn_parameters``)."""
    def correction_dim(rotations):
        return (rotary_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(rotary_dim // 2):
        extrap = 1.0 / theta ** (2 * i / rotary_dim)
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        out.append(extrap / factor * ramp + extrap * (1.0 - ramp))
    return out


def kind_inv_freq(mc: dict, kind: dict):
    """``(inverse frequencies, rotary dim, cos / sin scale)`` of ``kind``."""
    rd = int(_hd(mc) * kind.get("rotary_share", 1.0))
    theta = kind.get("rope_theta", 10000.0)
    yarn = kind.get("yarn")
    if yarn:
        if isinstance(yarn, dict):
            yarn = (yarn["factor"], yarn["original_max_position_embeddings"],
                    yarn["beta_fast"], yarn["beta_slow"],
                    yarn["attention_factor"])
        return yarn_inv_freq(rd, theta, *yarn[:4]), rd, yarn[4]
    return [1.0 / theta ** (2 * i / rd) for i in range(rd // 2)], rd, 1.0


def allowed(i: int, j: int, window: int) -> bool:
    """May query ``i`` see key ``j``?  The mask, stated once."""
    return j <= i and (not window or i - j < window)


def blocks(mc: dict, q_block: int = 512):
    """``(lead_layer, period_layer)``: the leading dense block over its
    float32 leaves, and one whole period over the kinds' stacks."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _rms_norm

    hd, nkv = _hd(mc), mc["num_kv_heads"]
    eps = mc.get("norm_eps", 1e-5)
    n_experts, top_k = mc["num_experts"], mc["experts_per_token"]
    renormalise = mc.get("norm_topk_prob", True)
    scale = mc.get("routed_scaling_factor", 1.0)
    held = mc.get("experts_held") or (n_experts, 0)
    lead_kind, period = _kinds(mc)

    def rope(x, kind):
        """Rotate-half over the first ``rd`` channels of a head, positions
        0..T-1; the rest pass.  x: [T, heads, hd]."""
        inv, rd, factor = kind_inv_freq(mc, kind)
        ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)
        cos = (jnp.cos(ang) * factor)[:, None, :]
        sin = (jnp.sin(ang) * factor)[:, None, :]
        x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rd:]], -1)

    def attention(q, k, v, window):
        """Masked softmax attention, queries in blocks of ``q_block`` (and
        a window block's keys cut to what the block can see) so that a long
        sequence's scores fit.  q: [T, nh, hd]; k, v: [T, nkv, hd]."""
        t, nh, _ = q.shape
        g = nh // nkv
        out = []
        for lo in range(0, t, q_block):
            hi = min(t, lo + q_block)
            k0 = max(0, lo - window + 1) if window else 0
            kk = jnp.repeat(k[k0:hi], g, axis=1)
            vv = jnp.repeat(v[k0:hi], g, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], kk) / math.sqrt(hd)
            i = jnp.arange(lo, hi)[:, None]
            j = jnp.arange(k0, hi)[None, :]
            ok = j <= i
            if window:
                ok = ok & (i - j < window)
            s = jnp.where(ok[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv))
        return jnp.concatenate(out, 0)

    def attend(leaf, x, kind):
        """``leaf(name)``: this block's float32 leaf of that name."""
        t, nh = x.shape[0], kind["num_heads"]
        h = _rms_norm(x, leaf("attn_norm_w"), eps)
        q = (h @ leaf("wq")).reshape(t, nh, hd)
        k = (h @ leaf("wk")).reshape(t, nkv, hd)
        v = (h @ leaf("wv")).reshape(t, nkv, hd)
        a = attention(rope(q, kind), rope(k, kind), v, kind.get("window", 0))
        if kind.get("gate", "none") == "per-head":
            a = a * jax.nn.sigmoid(h @ leaf("wg"))[:, :, None]
        return x + a.reshape(t, nh * hd) @ leaf("wo")

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ gate) * (h @ up)) @ down

    def lead_layer(p, x):
        x = attend(lambda n: p[n], x, lead_kind)
        h = _rms_norm(x, p["mlp_norm_w"], eps)
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])

    def expert_block(leaf, x, kind):
        x = attend(leaf, x, kind)
        h = _rms_norm(x, leaf("mlp_norm_w"), eps)
        probs = jax.nn.softmax(h @ leaf("router"), -1)      # all experts
        kth = jnp.sort(probs, -1)[:, n_experts - top_k][:, None]
        w = jnp.where(probs >= kth, probs, 0.0)
        if renormalise:
            w = w / w.sum(-1, keepdims=True)
        w = w * scale
        y = jnp.zeros_like(x)
        n_held, first = held
        for e in range(n_held):         # the held experts; the rest left out
            y = y + w[:, first + e:first + e + 1] * swiglu(
                h, leaf("w_gate", e), leaf("w_up", e), leaf("w_down", e))
        if mc.get("num_shared_experts", 0):
            y = y + swiglu(h, leaf("ws_gate"), leaf("ws_up"),
                           leaf("ws_down"))
        return x + y

    def names():
        """The period's places as ``(kind, stack name, index in it)``, in
        order: a kind's name is its ``attn`` (with its first place where two
        kinds share one), as ``ModelConfig.kinds`` names the stacks."""
        seen = []
        for p, k in enumerate(period):
            for entry in seen:
                if entry[0] == k:
                    entry[2].append(p)
                    break
            else:
                seen.append([k, k["attn"], [p]])
        attns = [e[1] for e in seen]
        out = {}
        for k, attn, at in seen:
            name = attn if attns.count(attn) == 1 else f"{attn}{at[0]}"
            for j, p in enumerate(at):
                out[p] = (k, name, j)
        return [out[p] for p in range(len(period))]

    def period_layer(p, x):
        for kind, name, j in names():
            def leaf(n, e=None, name=name, j=j):
                a = p[f"{n}.{name}"][j]     # the place, then the expert,
                return a if e is None else a[e]     # before any product
            x = expert_block(leaf, x, kind)
        return x

    return lead_layer, period_layer


def equations(mc: dict, q_block: int = 512):
    import jax
    import jax.numpy as jnp
    from reference import F32, _f32, _rms_norm

    lead_layer, period_layer = blocks(mc, q_block)
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
        """The embedding, then the leading dense block (see the first
        lines): what enters the repeated periods."""
        x = params.embed["tokens"][ids].astype(F32)
        for i in range(n_lead):
            x = lead_at(x, params.lead, jnp.int32(i))
        return x

    return embed, period_layer, final_norm
