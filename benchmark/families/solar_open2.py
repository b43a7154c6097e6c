"""Solar-Open2 blocks as Solar-Open2-250B configures them (``model_type:
solar_open2``; the source is a configuration file and no modeling file, so
what the keys do not settle is ASSUMED, listed below and in the
configuration file's ``assumed``).  ``T`` positions, ``H`` hidden, ``hd``
the head's size, eps ``rms_norm_eps``; a block's KIND (``gqa_layers`` /
``gqa_interval``: a period is [full, kda, kda, kda]) sets its mixer; every
block is an expert block (``first_k_dense_replace`` 0); no bias but ``b_g``
and ``dt_bias``:

    a = rms_norm(x, w_in);   x = x + mixer(a);   m = rms_norm(x, w_post);   x = x + moe(m)

    full:  q = a Wq -> [T, nh, hd];  k, v = a Wk, a Wv -> [T, nkv, hd]     NO rope (use_rope false), no q/k norm
           o_i = softmax_{j <= i}(q_i . k_j / sqrt(hd)) v_j                query head h reads kv head h // (nh / nkv)
           mixer = (sigmoid(a Wg) * o) Wo                                  Wg [H, nh hd], elementwise (use_gqa_gate)

    kda:   q, k, v = silu(conv(a Wq)), silu(conv(a Wk)), silu(conv(a Wv)) -> [T, nh, hd]
               conv: depthwise, causal, ``taps`` a channel, no bias:
               y_t = sum_{tau = 0 .. taps-1} c[tau] u_{t - taps + 1 + tau},  u_{< 0} = 0
           q = q / |q| * hd ** -0.5 ;  k = k / |k|                         L2 over the head (|x| = sqrt(sum x^2 + 1e-6))
           alpha_t = exp(-exp(A_log_h) * softplus((a Wf_dn Wf_up)_t + dt_bias))    in (0, 1), a decay a key CHANNEL
           beta_t  = 2 sigmoid(a Wb)                                       in (0, 2) (kda_allow_neg_eigval)
           a head, S_0 = 0 in R^[hd key, hd value]:
               Sd  = diag(alpha_t) S_{t-1}
               S_t = Sd + beta_t k_t (v_t - Sd^T k_t)^T
               o_t = S_t^T q_t
           y_t = rms_norm_hd(o_t, w_o) * sigmoid((a Wg_dn Wg_up + b_g)_t)   a head's own norm, one weight [hd]; low-rank gate
           mixer = y Wo

    moe:   s = sigmoid(m Wr) over ALL experts;  chosen = top-k of (s + b);  w = s[chosen] / sum s[chosen]  (x scale)
           moe = sum_{e chosen, e HELD} w_e SwiGLU_e(m) + SwiGLU_shared(m)

    logits = rms_norm(x, w_f) W_head                                       untied; this chip's rows of the vocabulary

ASSUMED (each also in the file's ``assumed``): the full kind's gate is
elementwise from the block's normed input (arXiv:2505.06708);
``num_kv_heads`` null = as many key heads as query heads in a kda block;
the low-rank gates' inner width is the head's size and ``b_g`` exists; q is
scaled by ``hd ** -0.5`` after its L2 norm; the state in float32; the
router's convention (sigmoid + stored bias, one group, chosen by ``s + b``,
weighted by ``s``); the shared expert one routed expert wide.

Where this file's reading of the published description (Kimi Linear,
arXiv:2510.26692) differs from the issue's lines: nowhere; the L2 norm's
``1e-6`` under the root is the reference implementation's and is stated
here because the issue's ``|q|`` does not say it.

THE SHARE.  ``experts_held = [held, first]`` of the routed experts are on
this chip (a deployment's eight chips share a block's 320).  The router
scores all of them; the sum runs over the chosen experts that are HELD,
and what the absent ones would add is left out, here as in the program.

HOW ``reference.py`` WALKS THE LEAVES: as for ``families/laguna.py``.
``model_config.num_layers`` counts REPEATS of the period (1 in the cut)
and ``layer`` is one whole period over the kinds' stacks (``<leaf>.<kind>``
shaped ``[places of the kind in a period, ...]``); it slices a place and
an expert out of each BEFORE it multiplies, so that the compiler widens a
block's slice and never the period.

Part 2, the shape arithmetic.  ``layer_matrix_elements`` is one PERIOD's
matrices as cut (the experts HELD).  ``kv_bytes_per_token`` is the full
kind's planes alone, what grows with a token; what a request holds
whatever its length is ``kda_state_bytes_per_slot``.  The ``kda_*_kernel``
functions count THE RECURRENCE'S OWN WORK, whatever form implements it: a
row a block a step reads and writes the state once (``2 x heads x hd x hd
x 4`` bytes) and moves the row's q, k, v, alpha, beta and o; ``6 x hd x hd
x heads`` operations (decay, the read ``Sd^T k``, the rank-one correction:
a multiply and an add each, and the output ``S^T q``); a segment of a
prompt the same operations a token, the state once a segment and the
tokens' rows.  So a share of the roofline reads under, never over.

Part 3, the ``replay`` (at the end of the file): the tokens are scored left
to right as any family's; it is there to hold the SERVED state, a sample
of which the reply with log-probabilities carries, to the reference's and
to float32 (``STATE_REL_TOL``, ``STATE_F32_RESIDUE_MIN``)."""

from __future__ import annotations

import json

from families.olmoe import moe_kernel_bytes, moe_kernel_ops  # noqa: F401


def _hd(mc: dict) -> int:
    return mc.get("head_dim_override") or mc["hidden_size"] // mc["num_heads"]


def _held(mc: dict) -> int:
    held = mc.get("experts_held") or ()
    return held[0] if held else mc["num_experts"]


def _is_kda(kind: dict) -> bool:
    return kind.get("attn") == "kda"


def _kda_kind(mc: dict) -> dict:
    return next(k for k in mc["period"] if _is_kda(k))


def kda_blocks(mc: dict) -> int:
    return mc["num_layers"] * sum(1 for k in mc["period"] if _is_kda(k))


def mixer_elements(mc: dict, kind: dict) -> int:
    """One block's mixer: a full block's Wq, Wk, Wv, Wg and Wo; a kda
    block's Wq, Wk, Wv, Wo, the two low-rank gates, beta, the taps, and
    the small vectors (A_log, dt_bias, b_g, the head's norm)."""
    h, hd, nh = mc["hidden_size"], _hd(mc), kind["num_heads"]
    d = nh * hd
    if _is_kda(kind):
        return (4 * h * d + 2 * (h * hd + hd * d) + h * nh
                + kind["conv"] * 3 * d + nh + 2 * d + hd)
    gate = h * d if kind.get("gate") == "elementwise" else 0
    return 2 * h * d + 2 * h * mc["num_kv_heads"] * hd + gate


def _expert_elements(mc: dict) -> int:
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def block_elements(mc: dict, kind: dict) -> int:
    """One block as cut: its mixer, the router at its published width
    with its bias, the experts HELD and the shared one."""
    return (mixer_elements(mc, kind)
            + (mc["hidden_size"] + 1) * mc["num_experts"]
            + (_held(mc) + mc.get("num_shared_experts", 0))
            * _expert_elements(mc))


def layer_matrix_elements(mc: dict) -> int:
    """One period's elements (``bytes.py`` multiplies by ``num_layers``,
    the repeats)."""
    return sum(block_elements(mc, k) for k in mc["period"])


def layer_scale_elements(mc: dict) -> int:
    """Output channels of the matrices an int8 variant would quantize
    (the mixers' projections and the SwiGLUs' three; router, gates'
    vectors, taps and norms stay as they are)."""
    h, hd, i = mc["hidden_size"], _hd(mc), mc["intermediate_size"]
    experts = (_held(mc) + mc.get("num_shared_experts", 0)) * (2 * i + h)
    total = 0
    for k in mc["period"]:
        d = k["num_heads"] * hd
        total += experts + (3 * d + h if _is_kda(k) else
                            2 * d + 2 * mc["num_kv_heads"] * hd + h)
    return total


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> int:
    """The full kind's planes alone: keys and values of every kv head in
    each full block (what grows with a token)."""
    del chips
    full = mc["num_layers"] * sum(1 for k in mc["period"] if not _is_kda(k))
    return full * 2 * mc["num_kv_heads"] * _hd(mc) * kv_bytes


def kda_state_bytes(mc: dict) -> int:
    """One head block's state in one plane: ``heads x hd x hd`` float32."""
    return _kda_kind(mc)["num_heads"] * _hd(mc) ** 2 * 4


def kda_state_bytes_per_slot(mc: dict, act_bytes: int = 2) -> int:
    """What a request holds whatever its length: a float32 state and the
    convolution's last ``taps - 1`` inputs of the q, k and v channels, a
    kda block."""
    kind = _kda_kind(mc)
    tail = (kind["conv"] - 1) * 3 * kind["num_heads"] * _hd(mc) * act_bytes
    return kda_blocks(mc) * (kda_state_bytes(mc) + tail)


def _row_bytes(mc: dict) -> int:
    """A token's q, k, v, alpha and o (``heads x hd`` each) and beta (a
    head), float32, in one kda block."""
    nh = _kda_kind(mc)["num_heads"]
    return (5 * nh * _hd(mc) + nh) * 4


def _token_ops(mc: dict) -> int:
    return 6 * _hd(mc) ** 2 * _kda_kind(mc)["num_heads"]


def kda_decode_kernel_ops(mc: dict, row_steps: int) -> int:
    """``row_steps`` (rows x steps that advanced a state) in every kda
    block."""
    return kda_blocks(mc) * row_steps * _token_ops(mc)


def kda_decode_kernel_bytes(mc: dict, row_steps: int) -> int:
    """The least those steps move: the state read and written once a row
    a block a step, and the row's vectors."""
    return kda_blocks(mc) * row_steps * (2 * kda_state_bytes(mc)
                                         + _row_bytes(mc))


def kda_prefill_kernel_ops(mc: dict, tokens: int) -> int:
    return kda_blocks(mc) * tokens * _token_ops(mc)


def kda_prefill_kernel_bytes(mc: dict, tokens: int, segments: int) -> int:
    """The least a slab moves: the state read and written once a segment
    a block, and the tokens' vectors."""
    return kda_blocks(mc) * (segments * 2 * kda_state_bytes(mc)
                             + tokens * _row_bytes(mc))


# ---------------------------------------------------------------- equations

def blocks(mc: dict, q_block: int = 512):
    """``(period_layer(p, x), period_states(p, x))``: one whole period over
    the kinds' stacks; the second also returns each kda block's state."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _rms_norm

    hd, nkv = _hd(mc), mc["num_kv_heads"]
    eps = mc.get("norm_eps", 1e-5)
    n_experts, top_k = mc["num_experts"], mc["experts_per_token"]
    renormalise = mc.get("norm_topk_prob", True)
    scale = mc.get("routed_scaling_factor", 1.0)
    held = mc.get("experts_held") or (n_experts, 0)
    period = list(mc["period"])

    def attention(q, k, v):
        """Causal softmax attention, queries in blocks of ``q_block``.
        q: [T, nh, hd]; k, v: [T, nkv, hd]."""
        t, nh, _ = q.shape
        g = nh // nkv
        out = []
        for lo in range(0, t, q_block):
            hi = min(t, lo + q_block)
            kk = jnp.repeat(k[:hi], g, axis=1)
            vv = jnp.repeat(v[:hi], g, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], kk) * hd ** -0.5
            ok = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
            s = jnp.where(ok[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv))
        return jnp.concatenate(out, 0)

    def full_mixer(leaf, a, kind):
        t, nh = a.shape[0], kind["num_heads"]
        q = (a @ leaf("wq")).reshape(t, nh, hd)         # no rope
        k = (a @ leaf("wk")).reshape(t, nkv, hd)
        v = (a @ leaf("wv")).reshape(t, nkv, hd)
        o = attention(q, k, v).reshape(t, nh * hd)
        if kind.get("gate") == "elementwise":
            o = jax.nn.sigmoid(a @ leaf("wg")) * o
        return o @ leaf("wo"), None         # no state: its cache is rows

    def conv(u, taps_w):
        """y_t = sum_tau c[tau] u_{t - taps + 1 + tau}, zeros before 0."""
        taps, t = taps_w.shape[0], u.shape[0]
        padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
        return sum(taps_w[tau] * padded[tau:tau + t] for tau in range(taps))

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def kda_mixer(leaf, a, kind):
        t, nh = a.shape[0], kind["num_heads"]
        d = nh * hd
        c = leaf("conv_w")                          # [taps, 3 d]: q | k | v
        heads = lambda x: x.reshape(t, nh, hd)
        q = heads(jax.nn.silu(conv(a @ leaf("wq"), c[:, :d])))
        k = heads(jax.nn.silu(conv(a @ leaf("wk"), c[:, d:2 * d])))
        v = heads(jax.nn.silu(conv(a @ leaf("wv"), c[:, 2 * d:])))
        q, k = unit(q) * hd ** -0.5, unit(k)
        f = (a @ leaf("wf_dn")) @ leaf("wf_up") + leaf("dt_bias")
        alpha = jnp.exp(-jnp.exp(leaf("A_log"))[None, :, None]
                        * heads(jax.nn.softplus(f)))
        beta = 2.0 * jax.nn.sigmoid(a @ leaf("wb"))             # [T, nh]

        def token(S, x):
            q_t, k_t, v_t, alpha_t, beta_t = x
            Sd = alpha_t[:, :, None] * S                        # [nh, key, value]
            read = jnp.einsum("hkv,hk->hv", Sd, k_t)
            S = Sd + beta_t[:, None, None] * (
                k_t[:, :, None] * (v_t - read)[:, None, :])
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        S, o = jax.lax.scan(token, jnp.zeros((nh, hd, hd), F32),
                            (q, k, v, alpha, beta))
        gate = jax.nn.sigmoid((a @ leaf("wg_dn")) @ leaf("wg_up")
                              + leaf("bg"))
        y = _rms_norm(o, leaf("o_norm_w"), eps) * heads(gate)
        return y.reshape(t, d) @ leaf("wo"), S

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ gate) * (h @ up)) @ down

    def moe(leaf, m):
        s = jax.nn.sigmoid(m @ leaf("router"))                  # all experts
        choice = s + leaf("router_bias")
        kth = jnp.sort(choice, -1)[:, n_experts - top_k][:, None]
        w = jnp.where(choice >= kth, s, 0.0)
        if renormalise:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * scale
        y = jnp.zeros_like(m)
        n_held, first = held
        for e in range(n_held):         # the held experts; the rest left out
            y = y + w[:, first + e:first + e + 1] * swiglu(
                m, leaf("w_gate", e), leaf("w_up", e), leaf("w_down", e))
        if mc.get("num_shared_experts", 0):
            y = y + swiglu(m, leaf("ws_gate"), leaf("ws_up"), leaf("ws_down"))
        return y

    def block(leaf, x, kind):
        a = _rms_norm(x, leaf("attn_norm_w"), eps)
        mixer = kda_mixer if _is_kda(kind) else full_mixer
        y, S = mixer(leaf, a, kind)
        x = x + y
        return x + moe(leaf, _rms_norm(x, leaf("mlp_norm_w"), eps)), S

    def names():
        """The period's places as ``(kind, stack name, index in it)``, in
        order: a kind's name is its ``attn`` (with its first place where two
        kinds share one), as the program names its stacks."""
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

    def period_states(p, x):
        """``(x, [S a kda block, in order])`` after the period: each
        ``[heads, key, value]``, the state the last position left."""
        states = []
        for kind, name, j in names():
            def leaf(n, e=None, name=name, j=j):
                a = p[f"{n}.{name}"][j]     # the place, then the expert,
                return a if e is None else a[e]     # before any product
            x, S = block(leaf, x, kind)
            if S is not None:
                states.append(S)
        return x, states

    return (lambda p, x: period_states(p, x)[0]), period_states


def equations(mc: dict, q_block: int = 512):
    from reference import F32, _f32, _rms_norm

    period_layer, _ = blocks(mc, q_block)
    eps = mc.get("norm_eps", 1e-5)

    def embed(params, ids):
        return params.embed["tokens"][ids].astype(F32)

    def final_norm(params, x):
        return _rms_norm(x, _f32(params.final_norm["w"]), eps)

    return embed, period_layer, final_norm


# ------------------------------------------------------------------- replay
#
# The model generates one token a pass, left to right, and its tokens are
# scored as every such family's are.  The replay is here for the STATE: the
# configuration states a float32 state a request, the log-probabilities of a
# model with bf16 weights and activations cannot tell it from a bfloat16 one
# (tools/model_parity.py, READINGS_STATE), and it is the decode step's second
# largest stream.  So the reply of a request with log-probabilities carries
# a sample of the state the request ended in (``generation.kda_state``: of
# every plane, some heads' some keys with all their values, the pool's own
# numbers), and the replay holds it to two limits, answering ``error`` (the
# contract: the run fails with that sentence) where either is passed:
#
# * STATE_REL_TOL: the sample against the reference's state after the same
#   ids (all but the last emitted, which nothing absorbed), the difference's
#   norm over the reference's, the largest plane.  Between the largest sound
#   reading and the smallest of a state one token short; PERF.md section 2
#   has both.
# * STATE_F32_RESIDUE_MIN: the distance of the sample from its own rounding
#   to bfloat16, over its norm, the smallest plane.  Float32 numbers read
#   about 1.6e-3 whatever they are (a rounding error uniform in half a unit
#   of 2^-8); a state held in bfloat16, or rounded to it by any op that
#   writes it, reads 0 exactly.  This is what refuses the lower precision.
STATE_REL_TOL = 0.10
STATE_F32_RESIDUE_MIN = 5e-4


def rounded_to_bf16(a):
    """float32 numbers rounded to the nearest bfloat16 (ties to even), as
    float32: NumPy on the bits, no dtype of another package."""
    import numpy as np
    bits = np.ascontiguousarray(a, "<f4").view("<u4")
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view("<f4")


def state_sample(record) -> tuple:
    """``(sample [planes, heads, keys, values], heads, keys, dtype name)``
    of a reply's ``kda_state`` record."""
    import base64
    import numpy as np
    sample = np.frombuffer(base64.b64decode(record["float32_b64"]),
                           "<f4").reshape(record["shape"])
    return sample, record["heads"], record["keys"], record["pool_dtype"]


def state_readings(sample, reference) -> dict:
    """The two numbers the limits hold, a plane: ``sample`` and
    ``reference`` are ``[planes, heads, keys, values]``."""
    import numpy as np
    norm = lambda a: np.sqrt((a.astype(np.float64) ** 2).sum((1, 2, 3)))
    return {"rel_err": (norm(sample - reference)
                        / np.maximum(norm(reference), 1e-30)).tolist(),
            "f32_residue": (norm(sample - rounded_to_bf16(sample))
                            / np.maximum(norm(sample), 1e-30)).tolist()}


def state_problem(readings: dict, dtype: str):
    """The sentence a served state is refused with, or None."""
    worst, least = max(readings["rel_err"]), min(readings["f32_residue"])
    if dtype != "float32" or least < STATE_F32_RESIDUE_MIN:
        return (f"the served state is not the float32 state the "
                f"configuration states: the pool is {dtype} and the sample "
                f"lies {least:.3g} of its norm from its own rounding to "
                f"bfloat16 (float32 numbers read about 1.6e-3, the limit "
                f"is {STATE_F32_RESIDUE_MIN}); a plane: "
                f"{readings['f32_residue']}")
    if not worst <= STATE_REL_TOL:
        return (f"the served state is not the reference's after the same "
                f"ids: relative difference {worst:.3g} (limit "
                f"{STATE_REL_TOL}); a plane: {readings['rel_err']}")
    return None


def replay(mc: dict):
    """``score(params, ids, n_prompt, generation)``: the tokens scored left
    to right, one forward over ``ids[:-1]`` (row ``t - 1`` scores token
    ``t``), which is also the forward that leaves the state the served
    request ended in; the reply's sample of that state held to the two
    limits above.  The layer loop is repeated here (four lines) because
    ``reference.halves``' ``rows`` returns the rows alone."""

    def score(params, ids, n_prompt, generation):
        import sys
        import jax
        import jax.numpy as jnp
        import numpy as np
        import reference

        record = (generation or {}).get("kda_state")
        if record is None:
            return {"error": "the reply carries no generation.kda_state: "
                             "the state the request ended in is part of "
                             "what this family's check holds"}
        embed, _, _ = equations(mc)
        layer = reference._make_layer_fn(blocks(mc)[1])
        _, score_rows = reference.halves(params, mc)
        states = []
        with jax.default_matmul_precision("highest"):
            x = embed(params, jnp.asarray(ids[:-1], jnp.int32))
            for i in range(mc["num_layers"]):
                x, planes = layer(x, params.layers, jnp.int32(i))
                states += planes
        sample, heads, keys, dtype = state_sample(record)
        want = np.stack([np.asarray(S)[heads][:, keys] for S in states])
        if want.shape != sample.shape:
            return {"error": f"generation.kda_state is {sample.shape}, the "
                             f"reference's sample {want.shape}"}
        readings = state_readings(sample, want)
        print(f"[replay] kda_state {json.dumps(readings)}", file=sys.stderr,
              flush=True)
        problem = state_problem(readings, dtype)
        if problem:
            return {"error": problem}
        return score_rows(x[n_prompt - 1:], ids[n_prompt:])

    return score
