"""EvaByte blocks (``model_type: evabyte``, EvaByte/EvaByte ``config.json``
and its modelling code; EVA: Zheng et al., "Efficient Attention via
Control Variates", ICLR 2023, arXiv:2302.04542, as that code specialises
it).  A byte-level llama-shaped decoder, MHA, no bias anywhere.  One
layer over ``T`` positions, head ``h``, ``d`` head size, ``W`` =
``eva_window`` (``window_size``), ``C`` = ``eva_chunk`` (``chunk_size``),
``s = d ** -0.5``:

    a        = x rsqrt(mean(x^2) + eps) (1 + w_attn)          norm_add_unit_offset; x float32 (fp32_skip_add)
    q, k, v  = a Wq, a Wk, a Wv -> [T, heads, d];  q, k = rope(q), rope(k)
                                                              rotate-half, rope_theta, true positions, BEFORE pooling
    chunk c  = tokens C c .. C c + C - 1:
      k~_c = sum_j softmax_j(k_j . mu_h) k_j      v~_c = sum_j softmax_j(k_j . phi_h) v_j
                                                              mu_h, phi_h in R^d learned, a head a layer
    query i, window w_i = i // W:
      E_i = { j <= i : j // W = w_i }                         exact keys: its own window, causal
      S_i = { c : c // (W / C) < w_i }                        summaries: every chunk of every CLOSED window
      o_i = ( sum_{E_i} e^{s q_i.k_j} v_j + sum_{S_i} e^{s q_i.k~_c} v~_c )
            / ( sum_{E_i} e^{s q_i.k_j} + sum_{S_i} e^{s q_i.k~_c} )
    x        = x + o Wo                                       float32 add
    m        = norm_1p(x, w_mlp);   x = x + ( silu(m Wg) * (m Wu) ) Wd
    logits   = float32( norm_1p(x_T, w_final) ) W_head[:, 0:V]     fp32_logits

A chunk's summary is visible to no query of its own window, so a partial
chunk is never pooled and no chunk mask is needed: a window that closes
holds ``W / C`` whole chunks.  The head holds ``num_pred_heads x V``
columns (head ``n`` predicts byte ``t + n``); ``reference.py`` reads
columns ``[0, vocab_size)`` of it, the next-byte head, and the others are
held and not read (multi-byte decoding is not served).

ASSUMED, where ``config.json`` is silent (each also in the configuration
file's ``assumed``): ``head_dim`` = hidden / heads (the key is null); the
pooling logits are ``k_j . mu_h`` and ``k_j . phi_h`` with no further
factor, BOTH from the roped keys; ``mu``, ``phi`` seeded as the published
initialiser, ``clip(N(0, 1), +-1) d ** -0.5``; the stored norm weight is
the gain's OFFSET.  Nothing here departs from the issue's reading of the
published description.

Every array is float32 here, whatever the program keeps in bf16.  The
scores of a long sequence are taken in query blocks (``Q_BLOCK``) so that
they fit; the arithmetic is the same.

Part 2, the shape arithmetic.  ``layer_matrix_elements`` counts the seven
matrices and the two pooling vectors a head.  A token holds no fixed
number of bytes (a window of exact rows, then ``C`` times fewer summary
rows), so there is no ``kv_bytes_per_token`` here: ``bytes.py``'s default
is what one ROW of the pool holds, and the ``eva_*`` counts below take
rows, which the program's dispatch records give
(``kv_attended_rows``, ``prefill_attended_rows``)."""

from __future__ import annotations

from bytes import (attention_matrix_elements, attention_scale_elements,
                   dims)

Q_BLOCK = 512


def layer_matrix_elements(mc: dict) -> int:
    """Attention's four matrices, the gate, up and down projections, and
    the two pooling vectors a kv head."""
    _, _, nkv, hd, _, _ = dims(mc)
    return (attention_matrix_elements(mc)
            + 3 * mc["hidden_size"] * mc["intermediate_size"]
            + 2 * nkv * hd)


def layer_scale_elements(mc: dict) -> int:
    return (attention_scale_elements(mc)
            + 2 * mc["intermediate_size"] + mc["hidden_size"])


def row_bytes(mc: dict, kv_bytes: int = 2) -> int:
    """One row of the pool, exact or summary: a key and a value a kv
    head a layer."""
    _, _, nkv, hd, _, layers = dims(mc)
    return layers * 2 * nkv * hd * kv_bytes


def rows_held(mc: dict, tokens: int) -> int:
    """Rows of the pool that hold ``tokens`` tokens: ``W / C`` summaries a
    closed window and the open window's exact rows."""
    window, chunk = mc["eva_window"], mc["eva_chunk"]
    if tokens <= 0:
        return 0
    closed = (tokens - 1) // window
    return closed * (window // chunk) + tokens - closed * window


def _pair_ops(mc: dict) -> int:
    """Multiply-adds x 2 for one (query, row) pair in one layer: every
    head's score and its output over ``hd``."""
    _, nh, _, hd, _, _ = dims(mc)
    return 4 * nh * hd


def eva_decode_kernel_ops(mc: dict, attended_rows: int) -> int:
    """One decode step over rows that attend ``attended_rows`` rows of the
    pool between them (summaries and exact keys alike), in every layer."""
    return mc["num_layers"] * attended_rows * _pair_ops(mc)


def eva_decode_kernel_bytes(mc: dict, attended_rows: int,
                            kv_bytes: int = 2) -> int:
    """The least that step reads: each attended row once a layer (rows,
    where the kernel reads whole pages: never over)."""
    return attended_rows * row_bytes(mc, kv_bytes)


def eva_prefill_kernel_ops(mc: dict, pairs: int) -> int:
    """A slab whose prompt tokens attend over ``pairs`` (query, row)
    pairs, in every layer."""
    return mc["num_layers"] * pairs * _pair_ops(mc)


def eva_prefill_kernel_bytes(mc: dict, pairs: int, chunk: int,
                             kv_bytes: int = 2) -> int:
    """The least a slab reads: a chunk's attended rows once a layer
    (``pairs / chunk`` is under every chunk's view)."""
    return int(pairs / max(1, chunk) * row_bytes(mc, kv_bytes))


def eva_summarise_kernel_bytes(mc: dict, chunks: int,
                               kv_bytes: int = 2) -> int:
    """Pooling ``chunks`` completed chunks from the cached rows: ``C``
    keys and values read, one summary pair written, a layer."""
    return chunks * (mc["eva_chunk"] + 1) * row_bytes(mc, kv_bytes)


def eva_summarise_kernel_ops(mc: dict, chunks: int) -> int:
    """Two logits and two weighted sums over ``C`` rows of ``hd`` a kv
    head a layer, multiply-adds x 2."""
    _, _, nkv, hd, _, layers = dims(mc)
    return chunks * layers * nkv * mc["eva_chunk"] * hd * 8


def summaries_seen(n_chunks: int, lo: int, hi: int, window: int,
                   chunk: int):
    """``[hi - lo, n_chunks]`` bool: which chunk summaries the queries at
    positions ``lo .. hi - 1`` see, every chunk of every window before
    their own.  A function of its own so that a parity tool can withhold
    them (``tools/model_parity.py --summaries-withheld``)."""
    import jax.numpy as jnp
    return (jnp.arange(n_chunks)[None, :] // (window // chunk)
            < jnp.arange(lo, hi)[:, None] // window)


def equations(mc: dict):
    import sys

    import jax
    import jax.numpy as jnp
    from reference import F32, _f32, _rope

    _, nh, nkv, hd, _, _ = dims(mc)
    eps = mc.get("norm_eps", 1e-5)
    theta = mc.get("rope_theta", 10000.0)
    window, chunk = mc["eva_window"], mc["eva_chunk"]
    me = sys.modules[__name__]

    def norm_1p(x, w):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                * (1.0 + w))

    def embed(params, ids):
        return params.embed["tokens"][ids].astype(F32)

    def eva(q, k, v, mu, phi):
        """q, k, v: [T, heads, d] (MHA: nkv = nh); mu, phi: [heads, d]."""
        t = q.shape[0]
        g = nh // nkv
        n_c = t // chunk
        kc = k[:n_c * chunk].reshape(n_c, chunk, nkv, hd)
        vc = v[:n_c * chunk].reshape(n_c, chunk, nkv, hd)
        wk = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, mu), axis=1)
        wv = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, phi), axis=1)
        ks = jnp.einsum("cjh,cjhd->chd", wk, kc)            # [n_c, nkv, d]
        vs = jnp.einsum("cjh,cjhd->chd", wv, vc)
        rep = lambda a: jnp.repeat(a, g, axis=1)
        k, v, ks, vs = rep(k), rep(v), rep(ks), rep(vs)
        out = []
        for lo in range(0, t, Q_BLOCK):
            hi = min(t, lo + Q_BLOCK)
            i = jnp.arange(lo, hi)[:, None]
            j = jnp.arange(hi)[None, :]
            exact = (j <= i) & (j // window == i // window)
            seen = me.summaries_seen(n_c, lo, hi, window, chunk)
            s_e = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * hd ** -0.5
            s_s = jnp.einsum("qhd,chd->hqc", q[lo:hi], ks) * hd ** -0.5
            s = jnp.concatenate([jnp.where(exact[None], s_e, -jnp.inf),
                                 jnp.where(seen[None], s_s, -jnp.inf)], -1)
            p = jax.nn.softmax(s, -1)
            out.append(jnp.einsum("hqk,khd->qhd", p[..., :hi], v[:hi])
                       + jnp.einsum("hqc,chd->qhd", p[..., hi:], vs))
        return jnp.concatenate(out, 0)

    def layer(p, x):
        t = x.shape[0]
        a = norm_1p(x, p["attn_norm_w"])
        q, k, v = a @ p["wq"], a @ p["wk"], a @ p["wv"]
        q, k, v = (q.reshape(t, nh, hd), k.reshape(t, nkv, hd),
                   v.reshape(t, nkv, hd))
        q, k = _rope(q, theta), _rope(k, theta)
        o = eva(q, k, v, p["adaptive_mu_k"], p["adaptive_phi"])
        x = x + o.reshape(t, nh * hd) @ p["wo"]
        m = norm_1p(x, p["mlp_norm_w"])
        return x + (jax.nn.silu(m @ p["w_gate"]) * (m @ p["w_up"])) @ p["w_down"]

    def final_norm(params, x):
        return norm_1p(x, _f32(params.final_norm["w"]))

    return embed, layer, final_norm
