"""NemotronH blocks as NVIDIA-Nemotron-3-Nano-30B-A3B configures them
(``model_type: nemotron_h``; 31.6B-A3.2B).  ``T`` positions, ``H`` hidden
2,688, eps ``layer_norm_epsilon``, RMSNorm with a weight, a bfloat16 stream
(``residual_in_fp32`` false), no bias but the convolution's, an untied
head.  A published layer is ONE sublayer, its KIND a character of
``hybrid_override_pattern`` (``MEMEM*EMEMEM*...``):

    x = E[ids]
    x = x + mixer_c(rms_norm(x, w_i))          block i of kind c: M, * or E
    logits = rms_norm(x, w_f) W_head

    M:  (Mamba-2, arXiv:2405.21060; d_inner 4,096 = 64 heads x P 64, N 128, 8 groups)
        z | xBC | dt = a W_in                                            4096 | 6144 | 64
        xBC = silu(conv4(xBC) + b_conv)                                  depthwise, causal, zeros before 0:
            y_t = sum_{tau = 0 .. 3} c[tau] u_{t - 3 + tau}
        x | B | C = xBC                                                  4096 | 8 x 128 | 8 x 128
        D_t = softplus(dt_t + dt_bias) a head                            time_step_limit (0, inf): no clamp
        a_t = exp(D_t A),  A = -exp(A_log) a head
        head h reads B, C of group h // 8;  S_0 = 0 in R^[64, 128]:
            S_t = a_t S_{t-1} + D_t x_t B_t^T
            y_t = S_t C_t + D x_t                                        D a head (the skip)
        y = rms_norm_by_group(y * silu(z), w_norm)                       the gate FIRST, then one mean square a
                                                                         group of 4096 / 8 = 512 channels
        mixer = y W_out

    *:  q = a Wq -> [T, 32, 128];  k, v = a Wk, a Wv -> [T, 2, 128]      NO rope (the published block applies none)
        o_i = softmax_{j <= i}(q_i . k_j * 128 ** -0.5) v_j              head h reads kv head h // 16
        mixer = o Wo

    E:  l = a Wr (128 logits, float32);  s = sigmoid(l)
        chosen = the 6 largest of s + e_score_correction_bias            (n_group 1, topk_group 1: every expert stands)
        w = 2.5 s[chosen] / (sum s[chosen] + 1e-20)
        e(a) = relu(a W_up) ** 2 W_down                                  TWO matrices an expert, width 1,856
        mixer = sum_{e chosen, e HELD} w_e e(a) + relu(a Ws_up) ** 2 Ws_down     shared width 3,712, every token

ASSUMED (each also in the file's ``assumed``): the state in float32 and the
convolution's tail in the model's dtype; no rotary embedding (``rope_theta``
stands in ``config.json`` unread); the gated norm's group of 512 with the
gate first; the seeded ``A``, ``dt`` and ``D`` of the mamba_ssm initialiser.

THE PROGRAM'S LEAVES.  One stack a kind, ``<leaf>.<kind name>`` with the
names ``ssd``, ``full`` and ``mlp`` (a block without a mixer), shaped
``[places of the kind in a period, ...]``; a block holds the leaves of its
one sublayer and one norm (``attn_norm_w`` for M and *, ``mlp_norm_w`` for
E).  The routed up projection is stored TRANSPOSED, ``w_up_t`` ``[experts
held, 1856, 2688]`` (the chip lays the lane-filling dimension minor; the
program's ``ops.grouped_matmul`` says why), and is read here as its
transpose; the shared expert's ``ws_up`` ``[2688, 3712]`` is not.

THE SHARE.  ``experts_held = [held, first]`` of the routed experts are on
this chip (a layer's two chips share its 128).  The router scores all of
them; the sum runs over the chosen experts that are HELD, and what the
absent ones would add is left out, here as in the program.  The shared
expert is whole on every chip.

HOW ``reference.py`` WALKS THE LEAVES: as for ``families/granite_moe_hybrid``.
``model_config.num_layers`` counts REPEATS of the period (1 in the cut) and
``layer`` is one whole period over the kinds' stacks; it slices a place and
an expert out of each BEFORE it multiplies.

Part 2, the shape arithmetic.  ``layer_matrix_elements`` is one PERIOD's
matrices as cut (the experts HELD).  ``kv_bytes_per_token`` is the ONE
attention block's planes over the period's nine blocks, what grows with a
token; what a request holds whatever its length is
``ssd_state_bytes_per_slot``.  The kernels' counts, fixed before any
reading (ISSUE 66): the grouped matmuls do ``2 x 2 x rows x 2688 x 1856``
operations over ``rows`` token-expert rows (two matrices a row) and move at
least two matrices a touched expert and each row in and out of both; the
``ssd_*`` counts are granite's recurrence counts with this kind's sizes (a
row-step a block moves the ``[64, 64, 128]`` float32 state once in and once
out; a prompt token a block does ``2 Q N`` a group and ``2 Q P + 4 N P`` a
head at ``Q`` = 128, and a segment moves the state once in and out).

Part 3, the ``replay``: the tokens are scored left to right as any
family's; it is there to hold the SERVED state (``generation.ssd_state``)
to the reference's and to float32, the served log-probabilities to a
limit of this family's own on their mean, and each E block's ROUTED SUM to
a paired reading: the reference run once more without that block's routed
experts, and the served numbers (the state plane behind the block, or the
log-probabilities behind the last) placed between the two."""

from __future__ import annotations

import json

from families.solar_open2 import (rounded_to_bf16,  # noqa: F401
                                  state_readings, state_sample)


def _hd(mc: dict) -> int:
    return mc.get("head_dim_override") or mc["hidden_size"] // mc["num_heads"]


def _held(mc: dict) -> int:
    held = mc.get("experts_held") or ()
    return held[0] if held else mc["num_experts"]


def _is_ssd(kind: dict) -> bool:
    return kind.get("attn") == "ssd"


def _is_experts(kind: dict) -> bool:
    return kind.get("attn") == "none"


def _ssd_kind(mc: dict) -> dict:
    return next(k for k in mc["period"] if _is_ssd(k))


def _ssd_dims(kind: dict) -> tuple:
    """``(heads, P, N, groups, d_inner, conv channels)``."""
    nh, p, n, g = (kind["state_heads"], kind["state_head_dim"],
                   kind["state_size"], kind.get("groups", 1))
    return nh, p, n, g, nh * p, nh * p + 2 * g * n


def _count(mc: dict, which) -> int:
    return mc["num_layers"] * sum(1 for k in mc["period"] if which(k))


def ssd_blocks(mc: dict) -> int:
    return _count(mc, _is_ssd)


def expert_blocks(mc: dict) -> int:
    return _count(mc, _is_experts)


def _expert_elements(mc: dict) -> int:
    """Two matrices an expert."""
    return 2 * mc["hidden_size"] * mc["intermediate_size"]


def block_elements(mc: dict, kind: dict) -> int:
    """One block as cut, which is one sublayer: a Mamba-2 mixer (in- and
    out-projection, taps and bias, ``A_log``, ``D``, ``dt_bias``, the gated
    norm's weight), an attention (Wq, Wk, Wv, Wo), or the router at its
    published width with its bias, the experts HELD and the shared one."""
    h = mc["hidden_size"]
    if _is_ssd(kind):
        nh, _, _, _, d, c = _ssd_dims(kind)
        return (h * (d + c + nh) + d * h + (kind["conv"] + 1) * c
                + 3 * nh + d)
    if _is_experts(kind):
        return (h * mc["num_experts"] + mc["num_experts"]
                + (_held(mc) + mc.get("num_shared_experts", 0))
                * _expert_elements(mc))
    return 2 * h * kind["num_heads"] * _hd(mc) + 2 * h * mc[
        "num_kv_heads"] * _hd(mc)


def layer_matrix_elements(mc: dict) -> int:
    """One period's elements (``bytes.py`` multiplies by ``num_layers``,
    the repeats).  A pass is taken to read every held expert."""
    return sum(block_elements(mc, k) for k in mc["period"])


def layer_scale_elements(mc: dict) -> int:
    """Output channels of the matrices an int8 variant would quantize (the
    mixers' projections and the experts' two; router, taps, vectors and
    norms stay as they are)."""
    h, i = mc["hidden_size"], mc["intermediate_size"]
    total = 0
    for k in mc["period"]:
        if _is_ssd(k):
            nh, _, _, _, d, c = _ssd_dims(k)
            total += d + c + nh + h
        elif _is_experts(k):
            total += (_held(mc) + mc.get("num_shared_experts", 0)) * (i + h)
        else:
            total += (k["num_heads"] + 2 * mc["num_kv_heads"]) * _hd(mc) + h
    return total


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> int:
    """The attention blocks' planes alone: keys and values of every kv
    head in each (what grows with a token); an M or an E block holds no
    row of any page."""
    del chips
    full = _count(mc, lambda k: k.get("attn") in ("full", "window"))
    return full * 2 * mc["num_kv_heads"] * _hd(mc) * kv_bytes


def ssd_state_bytes(mc: dict) -> int:
    """One block's state of one request: ``heads x P x N`` float32."""
    nh, p, n, _, _, _ = _ssd_dims(_ssd_kind(mc))
    return nh * p * n * 4


def ssd_state_bytes_per_slot(mc: dict, act_bytes: int = 2) -> int:
    """What a request holds whatever its length: a float32 state and the
    convolution's last ``taps - 1`` inputs of the ``x | B | C`` channels,
    an M block."""
    kind = _ssd_kind(mc)
    tail = (kind["conv"] - 1) * _ssd_dims(kind)[5] * act_bytes
    return ssd_blocks(mc) * (ssd_state_bytes(mc) + tail)


def _row_bytes(mc: dict, act_bytes: int = 2) -> int:
    """A decoding row's x, B and C (the model's dtype), dt and y (float32),
    in one M block."""
    nh, _, n, g, d, _ = _ssd_dims(_ssd_kind(mc))
    return (d + 2 * g * n) * act_bytes + (nh + d) * 4


def ssd_decode_kernel_ops(mc: dict, row_steps: int) -> int:
    """``row_steps`` (rows x steps that advanced a state) in every M
    block: 5 operations an element of the state."""
    nh, p, n, _, _, _ = _ssd_dims(_ssd_kind(mc))
    return ssd_blocks(mc) * row_steps * 5 * nh * p * n


def ssd_decode_kernel_bytes(mc: dict, row_steps: int) -> int:
    """The least those steps move: the state read and written once a row a
    block a step, and the row's vectors."""
    return ssd_blocks(mc) * row_steps * (2 * ssd_state_bytes(mc)
                                         + _row_bytes(mc))


def ssd_prefill_kernel_ops(mc: dict, tokens: int) -> int:
    """The chunk form's dense count a prompt token a block at the kind's
    chunk ``Q``: ``groups x 2 Q N + heads x (2 Q P + 4 N P)``."""
    kind = _ssd_kind(mc)
    nh, p, n, g, _, _ = _ssd_dims(kind)
    q = kind["chunk"]
    return ssd_blocks(mc) * tokens * (g * 2 * q * n
                                      + nh * (2 * q * p + 4 * n * p))


def ssd_prefill_kernel_bytes(mc: dict, tokens: int, segments: int) -> int:
    """The least a slab must move through HBM: the state read and written
    once a segment a block (the tokens' rows ride the chip's fast memory
    around the call: granite's family says how that was found)."""
    del tokens
    return ssd_blocks(mc) * segments * 2 * ssd_state_bytes(mc)


def moe_kernel_ops(mc: dict, rows: int) -> int:
    """Multiply-adds x 2 of the TWO grouped matmuls over ``rows``
    token-expert rows: ``2 x 2 x rows x H x I``."""
    return 2 * rows * _expert_elements(mc)


def moe_kernel_bytes(mc: dict, rows: int, touched: int,
                     weight_bytes: int = 2, row_bytes: int = 2) -> int:
    """The least the two grouped matmuls move for ``rows`` rows over
    ``touched`` (layer call, expert) pairs with at least one row: each
    touched expert's two matrices once at the PUBLISHED width, and each
    row in and out of both projections (H in and I out, I in and H out)."""
    h, i = mc["hidden_size"], mc["intermediate_size"]
    return (touched * _expert_elements(mc) * weight_bytes
            + rows * 2 * (h + i) * row_bytes)


# ---------------------------------------------------------------- equations

def kind_name(kind: dict) -> str:
    """What the program names a kind's stacks by: its ``attn``, "mlp" for a
    block without a mixer."""
    return "mlp" if _is_experts(kind) else kind["attn"]


def blocks(mc: dict, q_block: int = 512):
    """``(period_layer(p, x), period_states(p, x))``: one whole period over
    the kinds' stacks; the second also returns each M block's state."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _rms_norm

    hd, nkv = _hd(mc), mc["num_kv_heads"]
    eps = mc.get("norm_eps", 1e-5)
    n_experts, top_k = mc["num_experts"], mc["experts_per_token"]
    held = mc.get("experts_held") or (n_experts, 0)
    scaling = mc.get("routed_scaling_factor", 1.0)
    served = jnp.dtype(mc.get("dtype_name", "bfloat16"))
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
        return o @ leaf("wo"), None         # no state: its cache is rows

    def conv(u, taps_w, bias):
        """y_t = sum_tau c[tau] u_{t - taps + 1 + tau} + b, zeros before 0."""
        taps, t = taps_w.shape[0], u.shape[0]
        padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
        return bias + sum(taps_w[tau] * padded[tau:tau + t]
                          for tau in range(taps))

    def ssd_mixer(leaf, a, kind):
        t = a.shape[0]
        nh, p, n, g, d, c = _ssd_dims(kind)
        u = a @ leaf("w_in")
        z, xbc, dt = u[:, :d], u[:, d:d + c], u[:, d + c:]
        xbc = jax.nn.silu(conv(xbc, leaf("conv_w"), leaf("conv_b")))
        x = xbc[:, :d].reshape(t, nh, p)
        # head h reads group h // (heads a group)
        B = jnp.repeat(xbc[:, d:d + g * n].reshape(t, g, n), nh // g, 1)
        C = jnp.repeat(xbc[:, d + g * n:].reshape(t, g, n), nh // g, 1)
        delta = jax.nn.softplus(dt + leaf("dt_bias"))           # [T, nh]
        decay = jnp.exp(delta * -jnp.exp(leaf("A_log")))

        def token(S, row):
            x_t, B_t, C_t, delta_t, decay_t = row
            S = (decay_t[:, None, None] * S
                 + (delta_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            return S, jnp.einsum("hpn,hn->hp", S, C_t)

        S, y = jax.lax.scan(token, jnp.zeros((nh, p, n), F32),
                            (x, B, C, delta, decay))
        y = (y + leaf("D")[:, None] * x).reshape(t, d)
        # the gate first, then one mean square a group of d / g channels
        gated = (y * jax.nn.silu(z)).reshape(t, g, d // g)
        gated = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, -1, keepdims=True) + eps)
        return (gated.reshape(t, d) * leaf("ssd_norm_w")) @ leaf("wo"), S

    def relu2(h, up, down):
        return jnp.square(jax.nn.relu(h @ up)) @ down

    def experts(leaf, m, kind, keep=1.0):
        """``keep`` scales the ROUTED sum (1.0: the equations; 0.0: the
        block without its routed experts, what the replay's paired
        readings stand the served numbers against)."""
        del kind
        s = jax.nn.sigmoid(m @ leaf("router"))                  # all experts
        choice = s + leaf("router_bias")
        kth = jnp.sort(choice, -1)[:, n_experts - top_k][:, None]
        w = jnp.where(choice >= kth, s, 0.0)        # chosen by s + bias ...
        w = scaling * w / (w.sum(-1, keepdims=True) + 1e-20)    # weighed by s
        n_held, first = held

        def expert(e, y):       # a held expert; the rest are left out
            pick = lambda n: jax.lax.dynamic_index_in_dim(
                leaf(n).astype(served), e, 0, keepdims=False).astype(F32)
            w_e = jax.lax.dynamic_slice_in_dim(w, first + e, 1, axis=1)
            return y + w_e * relu2(m, pick("w_up_t").T, pick("w_down"))

        # (a loop over one expert's slices at a time, as granite's family
        # has it and for its reason: the check compiles in seconds and
        # widens one expert's matrices, not the stacks)
        y = jnp.zeros_like(m)
        if n_held:
            y = jax.lax.fori_loop(0, n_held, expert, y)
        return keep * y + relu2(m, leaf("ws_up"), leaf("ws_down")), None

    def block(leaf, x, kind, keep=1.0):
        """ONE sublayer: x + mixer(rms_norm(x))."""
        if _is_experts(kind):
            a = _rms_norm(x, leaf("mlp_norm_w"), eps)
            return x + experts(leaf, a, kind, keep)[0], None
        mixer = ssd_mixer if _is_ssd(kind) else full_mixer
        y, S = mixer(leaf, _rms_norm(x, leaf("attn_norm_w"), eps), kind)
        return x + y, S

    def names():
        """The period's places as ``(kind, stack name, index in it)``, in
        order (a kind's name takes its first place where two kinds share
        one, as the program names its stacks)."""
        seen = []
        for p, k in enumerate(period):
            for entry in seen:
                if entry[0] == k:
                    entry[2].append(p)
                    break
            else:
                seen.append([k, kind_name(k), [p]])
        all_names = [e[1] for e in seen]
        out = {}
        for k, name, at in seen:
            name = name if all_names.count(name) == 1 else f"{name}{at[0]}"
            for j, p in enumerate(at):
                out[p] = (k, name, j)
        return [out[p] for p in range(len(period))]

    def period_states(p, x, keep=None):
        """``(x, [S an M block, in order])`` after the period: each
        ``[heads, P, N]``, the state the last position left.  ``keep``,
        one number an E block of the period in order, scales that block's
        routed sum (``None``: the equations as published)."""
        states, e = [], 0
        for kind, name, j in names():
            # the place's leaves by an index that is ``j`` but waits for
            # the block's input: the compiler then widens ONE block's
            # slices at a time (granite's family has the readings)
            tail = "." + name
            at = j + jnp.where(x[0, 0] * 0.0 == 1.0, 1, 0)   # j, after x
            mine = {k[:-len(tail)]: jax.lax.dynamic_index_in_dim(
                v, at, 0, keepdims=False)
                for k, v in p.items() if k.endswith(tail)}
            if _is_experts(kind):
                x, _ = block(mine.__getitem__, x, kind,
                             1.0 if keep is None else keep[e])
                e += 1
                continue
            x, S = block(mine.__getitem__, x, kind)
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
# As granite's: tokens are scored left to right; the replay holds the SERVED
# state (a sample of every M plane, ``generation.ssd_state``) to
#
# * STATE_REL_TOL: against the reference's state after the same ids, the
#   difference's norm over the reference's, the largest plane;
# * STATE_F32_RESIDUE_MIN: the sample's distance from its own rounding to
#   bfloat16 over its norm, the smallest plane (float32 numbers read about
#   1.6e-3, a state rounded to bfloat16 anywhere reads 0);
#
# the served log-probabilities (``generation.logprobs``) to
#
# * LOGPROB_MEAN_TOL: the MEAN over the emitted tokens of |served -
#   reference| (PERF.md section 2 has the readings).  The head is untied and
#   of unit variance, so the harness's 0.1 on the largest of the sixteen has
#   teeth here; the mean holds what lies behind the last state plane: the
#   last block's WHOLE sublayer dropped reads 0.41, the convolution's tail
#   dropped 0.36, against a sound 0.006-0.022 (mean 0.013, 93 canaries;
#   my chip runs, PR 66).  It was 0.025 when the selection bias was seeded
#   at 0.1 (sound 0.010-0.015): the smaller bias leaves more of the
#   router's choices near a tie, the served router and the reference's
#   then differ in more experts, and the sound readings' tail (seven of 86
#   over 0.018, an excess over 0.015 of 0.003 in the mean) would have met
#   0.025 once in some two hundred runs;
#
# and each E block's ROUTED SUM to a PAIRED reading.  One block's held
# routed experts move the stream by about as much as bfloat16's rounding
# does, so no limit on a distance from the reference can tell a program
# with them from one without (the last block's dropped: 0.015-0.020 in the
# mean against a sound 0.010-0.015).  The reference is therefore run once
# more a block, WITHOUT that block's routed sum (``keep``), and the served
# numbers are placed between the two on the same ids and the same noise:
#
#     share = <served - without, with - without> / |with - without| ** 2
#
# 1 for a program that has the block's routed sum, 0 for one that lost it,
# whatever else separates served from reference, so long as that is not
# aligned with the routed sum itself.  The numbers are the first state plane
# BEHIND the block (the M block that reads what the E block wrote: four
# sampled heads of 1,024 numbers, a share a head and the median of the
# four: sharp), or, for a block no state plane follows (the LAST one), the
# emitted tokens' log-probabilities (sixteen numbers, blunt: PERF.md section
# 2 has both sides' readings);
#
# * ROUTED_SHARE_STATE_MIN: the least a share read from a state plane may
#   be: sound 0.93-1.09 / 0.78-1.13 / 0.72-1.20 for the blocks at places 1,
#   3, 6 (means 1.00 / 0.99 / 0.98, deviations 0.03 / 0.06 / 0.10 over 144 /
#   128 / 64 readings) against -0.05-0.06 / -0.09-0.06 / -0.25-0.28 with
#   the block's routed sum dropped (16 / 16 / 64 readings; my chip runs, PR
#   66): the limit stands five deviations from either side of the loosest
#   block;
# * ROUTED_SHARE_LOGPROB_MIN: the least a share read from the
#   log-probabilities may be.  Sixteen numbers whose distance from the
#   reference is as large as the routed sum's own effect (0.015 a token
#   each): sound 0.23-1.64, mean 0.99 and deviation 0.25 over 152 readings;
#   dropped -0.26-0.44, mean 0.08 and deviation 0.19 over 24.  The two
#   overlap, so one run cannot decide; a check is a dozen runs or more on
#   fresh seeds, and the limit is set for the check: at 0 (the served
#   numbers lean AWAY from the block's routed sum) a sound run is refused
#   once in some thousands (3.9 deviations), a faulty one three times in
#   eight (9 of the 24), so a fault survives fourteen runs once in 700.
STATE_REL_TOL = 0.10
STATE_F32_RESIDUE_MIN = 5e-4
LOGPROB_MEAN_TOL = 0.04
ROUTED_SHARE_STATE_MIN = 0.45
ROUTED_SHARE_LOGPROB_MIN = 0.0


def logprob_problem(served, reference):
    """The sentence the served log-probabilities are refused with, or
    None; the reading is printed either way."""
    import sys
    if served is None or len(served) != len(reference) or not served:
        return ("the reply carries no generation.logprobs, one a token "
                "emitted: the family's own limit on them is part of its "
                "check")
    mean = sum(abs(a - b) for a, b in zip(served, reference)) / len(served)
    print(f"[replay] logprobs mean |err| {mean:.5f} over {len(served)} "
          f"tokens (limit {LOGPROB_MEAN_TOL})", file=sys.stderr, flush=True)
    if not mean <= LOGPROB_MEAN_TOL:
        return (f"the served log-probabilities stand {mean:.4g} from the "
                f"reference's in the mean over {len(served)} tokens (limit "
                f"{LOGPROB_MEAN_TOL}, the family's own)")
    return None


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


def routed_share(served, with_sum, without) -> float:
    """Where ``served`` stands between the reference WITHOUT a block's
    routed sum (0) and WITH it (1), along the line between the two, summed
    in float64.  Arrays of more than one dimension (a state plane's sample,
    ``[heads, keys, values]``) are read a leading index at a time and the
    MEDIAN of the heads' shares is the plane's: a head that forgets fast is
    its last token or two, its numbers can be most of the plane's sum of
    squares (up to 0.99 of it), and one expert swapped at a near tie in
    that token then moves a share pooled over the heads by tenths (0.40
    read in a sound run of the cell, 0.57-0.74 in eight of 64 canaries; the
    median of the same canaries: 0.72 at least; my chip runs, PR 66)."""
    import numpy as np
    served, a, b = (np.asarray(v, np.float64) for v in (served, with_sum,
                                                        without))
    lead = a.shape[0] if a.ndim > 1 else 1
    d = (a - b).reshape(lead, -1)
    num = ((served - b).reshape(lead, -1) * d).sum(1)
    return float(np.median(num / np.maximum((d * d).sum(1), 1e-300)))


def plane_behind(mc: dict) -> list:
    """For each E block in running order (every repeat of the period), the
    index of the first state plane an M block behind it writes, or None
    where no M block follows."""
    kinds = list(mc["period"]) * mc["num_layers"]
    out, plane = [], 0
    for at, kind in enumerate(kinds):
        if _is_experts(kind):
            behind = any(_is_ssd(k) for k in kinds[at + 1:])
            out.append(plane if behind else None)
        plane += _is_ssd(kind)
    return out


def share_problem(shares: list, behind: list):
    """The sentence a routed share is refused with, or None."""
    for e, (share, plane) in enumerate(zip(shares, behind)):
        where, least = (("log-probabilities", ROUTED_SHARE_LOGPROB_MIN)
                        if plane is None else
                        (f"state (plane {plane}, the M block behind it)",
                         ROUTED_SHARE_STATE_MIN))
        if not share >= least:
            return (f"E block {e}'s routed sum is not in the served "
                    f"{where}: it stands {share:.3g} of the way from the "
                    f"reference without that sum to the reference with it "
                    f"(1 is a program that has it, 0 one that lost it; the "
                    f"limit is {least}); a block: {shares}")
    return None


def replay(mc: dict):
    """``score(params, ids, n_prompt, generation)``: one forward over
    ``ids[:-1]`` (row ``t - 1`` scores token ``t``), which also leaves the
    state the served request ended in; the reply's sample of that state
    held to the two limits above and its log-probabilities to the third;
    then one forward more an E block, without that block's routed sum, for
    the paired readings.  The layer loop is repeated here because
    ``reference.halves``' ``rows`` returns the rows alone and knows no
    ``keep``."""

    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference

    embed, _, _ = equations(mc)
    period_states = blocks(mc)[1]
    behind = plane_behind(mc)
    per = len(behind) // mc["num_layers"]       # E blocks a period

    @jax.jit        # (one program for every ``keep``, and for every request
    def layer(x, layers, i, keep):      # of one length this replay scores)
        p = {k: reference._f32(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False), v))
            for k, v in layers.items()}
        return period_states(p, x, keep)

    def score(params, ids, n_prompt, generation):
        record = (generation or {}).get("ssd_state")
        if record is None:
            return {"error": "the reply carries no generation.ssd_state: "
                             "the state the request ended in is part of "
                             "what this family's check holds"}
        _, score_rows = reference.halves(params, mc)
        sample, heads, keys, dtype = state_sample(record)

        def forward(dropped=None):
            """``(rows that predict the emitted tokens, the states'
            sample)`` with E block ``dropped``'s routed sum left out."""
            keep = np.ones((mc["num_layers"], per), np.float32)
            if dropped is not None:
                keep[divmod(dropped, per)] = 0.0
            states = []
            with jax.default_matmul_precision("highest"):
                x = embed(params, jnp.asarray(ids[:-1], jnp.int32))
                for i in range(mc["num_layers"]):
                    x, planes = layer(x, params.layers, jnp.int32(i),
                                      jnp.asarray(keep[i]))
                    states += planes
            return x[n_prompt - 1:], np.stack(
                [np.asarray(S)[heads][:, keys] for S in states])

        rows, want = forward()
        if want.shape != sample.shape:
            return {"error": f"generation.ssd_state is {sample.shape}, the "
                             f"reference's sample {want.shape}"}
        readings = state_readings(sample, want)
        print(f"[replay] ssd_state {json.dumps(readings)}", file=sys.stderr,
              flush=True)
        scored = score_rows(rows, ids[n_prompt:])
        served = generation.get("logprobs")
        problem = (state_problem(readings, dtype)
                   or logprob_problem(served, scored["logprobs"]))
        # the paired readings, one forward more a block (read whatever the
        # limits above said, so that a refused run's line has them too)
        shares, said = [], {}
        for e, plane in enumerate(behind):
            rows_b, want_b = forward(dropped=e)
            if plane is not None:
                shares.append(routed_share(sample[plane], want[plane],
                                           want_b[plane]))
                said.setdefault("heads", []).append([
                    routed_share(*(v[plane][h].ravel()
                                   for v in (sample, want, want_b)))
                    for h in range(len(heads))])
            elif served is not None and len(served) == len(
                    scored["logprobs"]):
                without = score_rows(rows_b, ids[n_prompt:])["logprobs"]
                shares.append(routed_share(served, scored["logprobs"],
                                           without))
                said.update(served=served, without=without,
                            **{"with": scored["logprobs"]})
        print("[replay] routed_share " + json.dumps(
            {"share": shares, "plane_behind": behind, **said}),
            file=sys.stderr, flush=True)
        problem = problem or share_problem(shares, behind)
        return dict({"error": problem} if problem else scored,
                    routed_share=shares, state_rel_err=readings["rel_err"])

    return score
