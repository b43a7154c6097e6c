"""GraniteMoeHybrid blocks as granite-4.0-h-small configures them
(``model_type: granitemoehybrid``; Granite 4.0-H Small 32B-A9B).  ``T``
positions, ``H`` hidden, eps ``rms_norm_eps``, RMSNorm with a weight, no
bias but the convolution's; a block's KIND (``layer_types``: a period is
mamba x 5, attention, mamba x 4) sets its mixer; every block has routed
experts AND a shared MLP; four multipliers:

    x = 12 E[ids]                                                          embedding_multiplier
    a = rms_norm(x, w_in);   x = x + 0.22 mixer(a)                         residual_multiplier
    m = rms_norm(x, w_post); x = x + 0.22 (moe(m) + shared(m))
    logits = (rms_norm(x, w_f) E^T) / 16                                   logits_scaling; the head is tied

    full:  q = a Wq -> [T, 32, 128];  k, v = a Wk, a Wv -> [T, 8, 128]      NO rope (position_embedding_type nope)
           o_i = softmax_{j <= i}(q_i . k_j * 0.0078125) v_j                attention_multiplier; head h reads kv head h // 4
           mixer = o Wo

    ssd:   (Mamba-2, arXiv:2405.21060; d_inner 8192 = 128 heads x P 64, N 128, 1 group)
           z | xBC | dt = a W_in                                            8192 | 8448 | 128
           xBC = silu(conv4(xBC) + b_conv)                                  depthwise, causal, zeros before 0:
               y_t = sum_{tau = 0 .. 3} c[tau] u_{t - 3 + tau}
           x | B | C = xBC                                                  8192 | 128 | 128
           D_t = softplus(dt_t + dt_bias) a head                            time_step_limit (0, inf): no clamp
           a_t = exp(D_t A),  A = -exp(A_log) a head
           a head, S_0 = 0 in R^[64, 128]:
               S_t = a_t S_{t-1} + D_t x_t B_t^T
               y_t = S_t C_t + D x_t                                        D a head (the skip)
           y = rms_norm_8192(y * silu(z), w_norm)                           the gate BEFORE the norm, one group
           mixer = y W_out

    moe:   l = m Wr (72 logits);  chosen = the 10 largest;  w = softmax over those 10 logits
           e(m) = (silu(m W_in[:, :768]) * m W_in[:, 768:]) W_out           (stored as gate, up, down)
           moe = sum_{e chosen, e HELD} w_e e(m)
    shared(m): the same gated MLP at width 1,536, every token

ASSUMED (each also in the file's ``assumed``): the state in float32 and
the convolution's tail in the model's dtype; ``intermediate_size`` is one
routed expert's width; the seeded ``A``, ``dt`` and ``D`` of the mamba_ssm
initialiser.

THE SHARE.  ``experts_held = [held, first]`` of the routed experts are on
this chip (a layer's two chips share its 72).  The router scores all of
them; the sum runs over the chosen experts that are HELD, and what the
absent ones would add is left out, here as in the program.  The shared MLP
is whole on every chip.

HOW ``reference.py`` WALKS THE LEAVES: as for ``families/solar_open2.py``.
``model_config.num_layers`` counts REPEATS of the period (1 in the cut)
and ``layer`` is one whole period over the kinds' stacks (``<leaf>.<kind>``
shaped ``[places of the kind in a period, ...]``); it slices a place and
an expert out of each BEFORE it multiplies, and a place by an index that
waits for the block's input, so that the compiler widens one block's
slices at a time and never the period.  ``reference.py`` keeps the head, and
the head is linear: ``final_norm`` returns ``rms_norm(x) / 16``.

Part 2, the shape arithmetic.  ``layer_matrix_elements`` is one PERIOD's
matrices as cut (the experts HELD).  ``kv_bytes_per_token`` is the full
kind's planes alone, what grows with a token; what a request holds
whatever its length is ``ssd_state_bytes_per_slot``.  The ``ssd_*_kernel``
functions count THE RECURRENCE'S DENSE WORK, fixed before any reading
(ISSUE 62): a row-step a block moves the state once in and once out (``2 x
128 x 64 x 128 x 4`` bytes) and the row's x, B, C (the model's dtype), dt
and y (float32), and does ``5 x 128 x 64 x 128`` operations (decay, the
outer product's multiply and add, the read's multiply and add); a prompt
token a block at a chunk of ``Q`` does ``2 Q N`` a GROUP (``C B^T`` is the
group's: B and C are shared by its heads) and ``2 Q P + 4 N P`` a head
(the masked product with x, the state's read and update), and a segment
moves the state once in and once out a block through HBM.  ISSUE 62 also
counted ``C B^T`` a head and the tokens' rows (y at 4 bytes): against the
first traces (19-22 us a call) that bound was 25.9 us, a share of 118 %,
and with ``C B^T`` a group and y at 2 bytes still 108 %: the rows ride the
chip's fast memory between the fusion that makes them and the call, so
HBM does not bind them, and a share over 100 says the count is too high.
What is left is what the recurrence needs: the state through HBM (10.2 us
a call) and its products on the matrix unit (11.0 us), whichever is more.

Part 3, the ``replay`` (at the end of the file): the tokens are scored left
to right as any family's; it is there to hold the SERVED state, a sample
of which the reply with log-probabilities carries (``generation.ssd_state``),
to the reference's and to float32 (``STATE_REL_TOL``,
``STATE_F32_RESIDUE_MIN``)."""

from __future__ import annotations

import json

from families.olmoe import moe_kernel_bytes, moe_kernel_ops  # noqa: F401
from families.solar_open2 import (rounded_to_bf16,  # noqa: F401
                                  state_readings, state_sample)


def _hd(mc: dict) -> int:
    return mc.get("head_dim_override") or mc["hidden_size"] // mc["num_heads"]


def _held(mc: dict) -> int:
    held = mc.get("experts_held") or ()
    return held[0] if held else mc["num_experts"]


def _is_ssd(kind: dict) -> bool:
    return kind.get("attn") == "ssd"


def _ssd_kind(mc: dict) -> dict:
    return next(k for k in mc["period"] if _is_ssd(k))


def _ssd_dims(kind: dict) -> tuple:
    """``(heads, P, N, groups, d_inner, conv channels)``."""
    nh, p, n, g = (kind["state_heads"], kind["state_head_dim"],
                   kind["state_size"], kind.get("groups", 1))
    return nh, p, n, g, nh * p, nh * p + 2 * g * n


def ssd_blocks(mc: dict) -> int:
    return mc["num_layers"] * sum(1 for k in mc["period"] if _is_ssd(k))


def mixer_elements(mc: dict, kind: dict) -> int:
    """One block's mixer: a full block's Wq, Wk, Wv and Wo; an ssd block's
    in- and out-projection, the taps and their bias, ``A_log``, ``D``,
    ``dt_bias`` and the gated norm's weight."""
    h = mc["hidden_size"]
    if _is_ssd(kind):
        nh, _, _, _, d, c = _ssd_dims(kind)
        return (h * (d + c + nh) + d * h + (kind["conv"] + 1) * c
                + 3 * nh + d)
    return 2 * h * kind["num_heads"] * _hd(mc) + 2 * h * mc[
        "num_kv_heads"] * _hd(mc)


def _expert_elements(mc: dict) -> int:
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def block_elements(mc: dict, kind: dict) -> int:
    """One block as cut: its mixer, the router at its published width, the
    experts HELD and the shared MLP (``num_shared_experts`` experts
    wide)."""
    return (mixer_elements(mc, kind)
            + mc["hidden_size"] * mc["num_experts"]
            + (_held(mc) + mc.get("num_shared_experts", 0))
            * _expert_elements(mc))


def layer_matrix_elements(mc: dict) -> int:
    """One period's elements (``bytes.py`` multiplies by ``num_layers``,
    the repeats)."""
    return sum(block_elements(mc, k) for k in mc["period"])


def layer_scale_elements(mc: dict) -> int:
    """Output channels of the matrices an int8 variant would quantize
    (the mixers' projections and the gated MLPs' three; router, taps,
    vectors and norms stay as they are)."""
    h, i = mc["hidden_size"], mc["intermediate_size"]
    experts = (_held(mc) + mc.get("num_shared_experts", 0)) * (2 * i + h)
    total = 0
    for k in mc["period"]:
        if _is_ssd(k):
            nh, _, _, _, d, c = _ssd_dims(k)
            total += experts + d + c + nh + h
        else:
            total += experts + (k["num_heads"] + 2 * mc["num_kv_heads"]
                                ) * _hd(mc) + h
    return total


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> int:
    """The full kind's planes alone: keys and values of every kv head in
    each full block (what grows with a token)."""
    del chips
    full = mc["num_layers"] * sum(1 for k in mc["period"] if not _is_ssd(k))
    return full * 2 * mc["num_kv_heads"] * _hd(mc) * kv_bytes


def ssd_state_bytes(mc: dict) -> int:
    """One block's state of one request: ``heads x P x N`` float32."""
    nh, p, n, _, _, _ = _ssd_dims(_ssd_kind(mc))
    return nh * p * n * 4


def ssd_state_bytes_per_slot(mc: dict, act_bytes: int = 2) -> int:
    """What a request holds whatever its length: a float32 state and the
    convolution's last ``taps - 1`` inputs of the ``x | B | C`` channels,
    an ssd block."""
    kind = _ssd_kind(mc)
    tail = (kind["conv"] - 1) * _ssd_dims(kind)[5] * act_bytes
    return ssd_blocks(mc) * (ssd_state_bytes(mc) + tail)


def _row_bytes(mc: dict, act_bytes: int = 2) -> int:
    """A decoding row's x, B and C (the model's dtype), dt and y (float32),
    in one ssd block."""
    nh, _, n, g, d, _ = _ssd_dims(_ssd_kind(mc))
    return (d + 2 * g * n) * act_bytes + (nh + d) * 4


def ssd_decode_kernel_ops(mc: dict, row_steps: int) -> int:
    """``row_steps`` (rows x steps that advanced a state) in every ssd
    block: 5 operations an element of the state."""
    nh, p, n, _, _, _ = _ssd_dims(_ssd_kind(mc))
    return ssd_blocks(mc) * row_steps * 5 * nh * p * n


def ssd_decode_kernel_bytes(mc: dict, row_steps: int) -> int:
    """The least those steps move: the state read and written once a row
    a block a step, and the row's vectors."""
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
    once a segment a block.  The tokens' rows are NOT counted: the
    compiler keeps them in the chip's fast memory between the fusion that
    makes them and the call (and the call's y on to the gated norm), where
    HBM's bandwidth does not bind them; counted, the share read 108 % (my
    chip run, PR 62)."""
    del tokens
    return ssd_blocks(mc) * segments * 2 * ssd_state_bytes(mc)


def ssd_prefill_kernel_as_issued(mc: dict, tokens: int,
                                 segments: int) -> tuple:
    """``(operations, bytes)`` of the chunk form as ISSUE 62 fixed them
    before any reading, kept beside the family's own count so that a traced
    run prints both: ``heads x (2 Q N + 2 Q P + 4 N P)`` a token a block
    (``C B^T`` counted a head, where the call forms it once a group), and
    through HBM the state in and out a segment a block AND the tokens' x, y,
    B, C and dt at their served widths.  By this count the call read 118 %
    (my chip run, PR 62): it is too high, twice (the family's count has the
    product a group; the tokens' rows sit in ``S(1)``, the chip's fast
    memory, in the optimized program's layouts: PERF.md section 6)."""
    kind = _ssd_kind(mc)
    nh, p, n, _, _, _ = _ssd_dims(kind)
    q = kind["chunk"]
    ops = ssd_blocks(mc) * tokens * nh * (2 * q * n + 2 * q * p + 4 * n * p)
    return ops, (ssd_prefill_kernel_bytes(mc, tokens, segments)
                 + ssd_blocks(mc) * tokens * _row_bytes(mc))


# ---------------------------------------------------------------- equations

def blocks(mc: dict, q_block: int = 512):
    """``(period_layer(p, x), period_states(p, x))``: one whole period over
    the kinds' stacks; the second also returns each ssd block's state."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _rms_norm

    hd, nkv = _hd(mc), mc["num_kv_heads"]
    eps = mc.get("norm_eps", 1e-5)
    n_experts, top_k = mc["num_experts"], mc["experts_per_token"]
    held = mc.get("experts_held") or (n_experts, 0)
    residual = mc.get("residual_multiplier", 1.0)
    served = jnp.dtype(mc.get("dtype_name", "bfloat16"))
    softmax_scale = hd ** -0.5 * mc.get("attn_scale", 1.0)
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
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], kk) * softmax_scale
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
        y = _rms_norm(y * jax.nn.silu(z), leaf("ssd_norm_w"), eps)
        return y @ leaf("wo"), S

    def gated(h, gate, up, down):
        return (jax.nn.silu(h @ gate) * (h @ up)) @ down

    def moe(leaf, m):
        logits = m @ leaf("router")                             # all experts
        kth = jnp.sort(logits, -1)[:, n_experts - top_k][:, None]
        w = jax.nn.softmax(jnp.where(logits >= kth, logits, -jnp.inf), -1)
        n_held, first = held

        def expert(e, y):       # a held expert; the rest are left out
            pick = lambda n: jax.lax.dynamic_index_in_dim(
                leaf(n).astype(served), e, 0, keepdims=False).astype(F32)
            w_e = jax.lax.dynamic_slice_in_dim(w, first + e, 1, axis=1)
            return y + w_e * gated(m, pick("w_gate"), pick("w_up"),
                                   pick("w_down"))

        # (a loop, not 36 copies of its body: the check compiles in
        # seconds.  ``reference.py`` hands the stacks over widened; taking
        # them back to the dtype they are served in is exact, the compiler
        # folds the round trip away, and what is widened is then one
        # expert's slice inside its own turn: 0.5 GiB of temporaries where
        # the widened stacks, hoisted out of the loop, were 5.1)
        y = jnp.zeros_like(m)
        if n_held:
            y = jax.lax.fori_loop(0, n_held, expert, y)
        return y + gated(m, leaf("ws_gate"), leaf("ws_up"), leaf("ws_down"))

    def block(leaf, x, kind):
        a = _rms_norm(x, leaf("attn_norm_w"), eps)
        mixer = ssd_mixer if _is_ssd(kind) else full_mixer
        y, S = mixer(leaf, a, kind)
        x = x + residual * y
        return x + residual * moe(leaf, _rms_norm(x, leaf("mlp_norm_w"),
                                                  eps)), S

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
        """``(x, [S an ssd block, in order])`` after the period: each
        ``[heads, P, N]``, the state the last position left."""
        states = []
        for kind, name, j in names():
            # the place's leaves, picked by an index that is ``j`` but is
            # not known before the rows that enter the block are: the
            # compiler then widens ONE block's slices at a time.  With a
            # static index it widened blocks ahead of their turn, 4.9 GiB
            # of temporaries where 4.1 were free beside the served pools
            # (my chip run, PR 62); so 0.5 GiB (libtpu's analysis, no chip)
            tail = "." + name
            at = j + jnp.where(x[0, 0] * 0.0 == 1.0, 1, 0)   # j, after x
            mine = {k[:-len(tail)]: jax.lax.dynamic_index_in_dim(
                v, at, 0, keepdims=False)
                for k, v in p.items() if k.endswith(tail)}

            leaf = mine.__getitem__
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
        return (mc.get("embedding_multiplier", 1.0)
                * params.embed["tokens"][ids].astype(F32))

    def final_norm(params, x):
        # (the head, which ``reference.py`` keeps, is linear: its division)
        return (_rms_norm(x, _f32(params.final_norm["w"]), eps)
                / mc.get("logits_scaling", 1.0))

    return embed, period_layer, final_norm


# ------------------------------------------------------------------- replay
#
# As ``families/solar_open2.py``'s: the model generates one token a pass,
# left to right, and its tokens are scored as every such family's are.  The
# replay is here for the STATE: the configuration states a float32 state a
# request (36 MiB of it, the decode step's second largest stream), the
# log-probabilities cannot tell it from a bfloat16 one, so the reply of a
# request with log-probabilities carries a sample of the state the request
# ended in (``generation.ssd_state``: of every plane, four heads' every
# eighth row of P with all N, the pool's own numbers), and the replay holds
# it to two limits, answering ``error`` where either is passed:
#
# * STATE_REL_TOL: the sample against the reference's state after the same
#   ids (all but the last emitted, which nothing absorbed), the difference's
#   norm over the reference's, the largest plane.  Between the largest sound
#   reading and the smallest of a faulty program; PERF.md section 2 has both.
# * STATE_F32_RESIDUE_MIN: the distance of the sample from its own rounding
#   to bfloat16, over its norm, the smallest plane.  Float32 numbers read
#   about 1.6e-3 whatever they are; a state held in bfloat16, or rounded to
#   it by any op that writes it, reads 0 exactly.
#
# The state sees a block through the nine planes, and the last plane is
# written before the last block's out-projection, experts, shared MLP, the
# final norm and the head: what reaches the END of the period is the
# log-probabilities, and the harness's own limit on them (0.1, every
# family's) is wider than anything this model's logits can do (the tied
# head's / 16 over seeded embeddings leaves them a spread of ~0.08).  So the
# record carries the served log-probabilities once more
# (``generation.logprobs``) and the replay holds them to a limit of this
# family's own:
#
# * LOGPROB_MEAN_TOL: the MEAN over the emitted tokens of |served -
#   reference| (the mean of sixteen is steadier than their largest: sound
#   runs read 0.0016-0.0034 as a mean and 0.0040-0.0083 as a largest).
#   Between the largest sound reading and the nearest control; PERF.md
#   section 2 has both, and what no log-probability can see.
STATE_REL_TOL = 0.10
STATE_F32_RESIDUE_MIN = 5e-4
LOGPROB_MEAN_TOL = 0.0065


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
                f"{LOGPROB_MEAN_TOL}: the family's own, the harness's 0.1 "
                f"is wider than this model's logits)")
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


def replay(mc: dict):
    """``score(params, ids, n_prompt, generation)``: the tokens scored left
    to right, one forward over ``ids[:-1]`` (row ``t - 1`` scores token
    ``t``), which is also the forward that leaves the state the served
    request ended in; the reply's sample of that state held to the two
    limits above, and its log-probabilities to the third.  The layer loop is repeated here (four lines) because
    ``reference.halves``' ``rows`` returns the rows alone."""

    def score(params, ids, n_prompt, generation):
        import sys
        import jax
        import jax.numpy as jnp
        import numpy as np
        import reference

        record = (generation or {}).get("ssd_state")
        if record is None:
            return {"error": "the reply carries no generation.ssd_state: "
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
            return {"error": f"generation.ssd_state is {sample.shape}, the "
                             f"reference's sample {want.shape}"}
        readings = state_readings(sample, want)
        print(f"[replay] ssd_state {json.dumps(readings)}", file=sys.stderr,
              flush=True)
        problem = state_problem(readings, dtype)
        if problem:
            return {"error": problem}
        scored = score_rows(x[n_prompt - 1:], ids[n_prompt:])
        problem = logprob_problem(generation.get("logprobs"),
                                  scored["logprobs"])
        return {"error": problem} if problem else scored

    return score
