"""MiniCPM-SALA blocks as openbmb/MiniCPM-SALA configures them
(``model_type: minicpm_sala``; 9B dense, 32 layers: 8 ``minicpm4`` sparse
attention, 24 ``lightning-attn`` linear attention; ``mixer_types`` says
which).  ``T`` positions, ``H`` hidden 4,096, RMSNorm with a weight, eps
1e-6, no bias anywhere, an untied head, muP's three multipliers:

    x = 12 E[ids]                                                   scale_emb
    a = rms_norm(x, w_in);   x = x + (1.4 / sqrt(32)) mixer(a)      scale_depth over the PUBLISHED 32 layers
    m = rms_norm(x, w_post); x = x + (1.4 / sqrt(32)) down(silu(m gate) * m up)       width 16,384
    logits = head(rms_norm(x, w_f) / 16)                            hidden_size / dim_model_base

    sparse (``minicpm4``; InfLLM-v2):
        q = a Wq -> [T, 32, 128];  k, v = a Wk, a Wv -> [T, 2, 128];  head h reads kv head h // 16
        q, k <- rms_norm a head (weight [128]);  NO rope (attn_use_rope false)
        query t < dense_len:  o_t = softmax_{j <= t}(q_t . k_j * 128 ** -0.5) v_j
        else, a kv group g (its 16 query heads a):
            c_j = mean(k[16 j .. 16 j + 31])              every j with 16 j + 31 <= t
            p_a = softmax_j(q_a . c_j * 128 ** -0.5);  s_j = sum_a p_a[j]
            score(b) = max(s_j : j = 4 b - 1 .. 4 b + 3, those that exist)        block b = tokens 64 b .. 64 b + 63
            kept = block 0, blocks t // 64 - 31 .. t // 64, and the 64 best-scoring of the others
                   (all of them where fewer exist; ties to the lower block)
            o_t = softmax over the tokens <= t of the kept blocks, a head, at 128 ** -0.5
        o <- o * sigmoid(a Wg);  mixer = o Wo

    lightning (``lightning-attn``):
        q, k, v = a Wq, a Wk, a Wv -> [T, 32, 128] each;  q, k <- rms_norm a head;  rope (theta 1e4, whole head,
        rotate-half) on q and k
        a head, S_0 = 0 in R^[128 key, 128 value], float32:
            S_t = lambda_h S_{t-1} + k_t^T v_t,   lambda_h = exp(-2 ** (-8 (h + 1) / 32))
            o_t = (q_t S_t) * 128 ** -0.5
        o <- rms_norm(o) a head, weight [4096];  o <- o * sigmoid(a Wg);  mixer = o Wo

ASSUMED (each in the configuration file's ``assumed`` with its reason): the
seven sizes of ``sparse_config``; the dense rule a QUERY; ``topk`` counts
the blocks chosen BESIDE the forced ones; an exact softmax over the pooled
keys; q / k norm on both kinds; ties to the lower block; the Lightning
slopes, the same in every layer; no activation on q, k, v; the output
norm's form.

HOW ``reference.py`` WALKS THE LEAVES: as ``families/granite_moe_hybrid.py``
says.  ``model_config.num_layers`` counts REPEATS of the period (1 in the
cut, whose period is eight published layers) and ``layer`` is one whole
period over the kinds' stacks.  The selection is written as the equations
read: scores over every closed kernel, the group's sum, the max over the
kernels that meet a block, the forced blocks, a stable sort for the top-k;
queries in blocks of ``q_block`` so that a 41k context fits.  The
recurrence is a scan a token.

Part 2, the shape arithmetic.  ``kv_bytes_per_token`` is the sparse kind's
planes and their index rows; what a request holds whatever its length is
``la_state_bytes_per_slot``.  The kernels' least work, fixed before any
reading (ISSUE 69): a sparse query folds the KEPT blocks' keys and values
(``sparse_*_kernel_bytes``: those blocks once a (query tile, kv head) is
the least any tiling can move, and a decode row is a tile of one; plus the
visible index rows) and does their products and the scores'
(``sparse_*_kernel_ops``), whatever an implementation folds under a mask
beside them; the linear kind's counts are the SSD family's at groups =
heads, P = N = 128, chunk 256.

Part 3, the ``replay``: the tokens are scored left to right; it holds the
SERVED Lightning state (``generation.lightning_state``) to the
reference's and to float32 (``STATE_REL_TOL``, ``STATE_F32_RESIDUE_MIN``)."""

from __future__ import annotations

import json

from families.solar_open2 import (rounded_to_bf16,  # noqa: F401
                                  state_readings, state_sample)

LA_CHUNK = 256      # the chunk the linear kind's prefill call scans in
MLP_ROWS = 4096     # rows of the reference's SwiGLU at once


def _hd(mc: dict) -> int:
    return mc.get("head_dim_override") or mc["hidden_size"] // mc["num_heads"]


def _kinds(mc: dict, attn: str) -> list:
    return [k for k in mc["period"] if k.get("attn") == attn]


def sparse_blocks(mc: dict) -> int:
    return mc["num_layers"] * len(_kinds(mc, "sparse"))


def la_blocks(mc: dict) -> int:
    return mc["num_layers"] * len(_kinds(mc, "lightning"))


def sparse_sizes(mc: dict) -> tuple:
    """``(kernel, stride, block, topk, init, local, dense_len)``."""
    k = _kinds(mc, "sparse")[0]
    return tuple(k[f"sparse_{n}"] for n in (
        "kernel", "stride", "block", "topk", "init", "local", "dense_len"))


def mixer_elements(mc: dict, kind: dict) -> int:
    """One block's mixer: q, k, v, the gate and o, and the two norms'
    weights (and the output norm's, the linear kind)."""
    h, hd, nh = mc["hidden_size"], _hd(mc), kind["num_heads"]
    if kind["attn"] == "lightning":
        return 5 * h * nh * hd + 2 * hd + nh * hd
    return 3 * h * nh * hd + 2 * h * mc["num_kv_heads"] * hd + 2 * hd


def layer_matrix_elements(mc: dict) -> int:
    """One period's elements (``bytes.py`` multiplies by ``num_layers``,
    the repeats): every block's mixer and its SwiGLU."""
    mlp = 3 * mc["hidden_size"] * mc["intermediate_size"]
    return sum(mixer_elements(mc, k) + mlp for k in mc["period"])


def layer_scale_elements(mc: dict) -> int:
    """Output channels of the matrices an int8 variant would quantize."""
    h, i, hd = mc["hidden_size"], mc["intermediate_size"], _hd(mc)
    total = 0
    for k in mc["period"]:
        nh = k["num_heads"]
        kv = nh if k["attn"] == "lightning" else mc["num_kv_heads"]
        total += (2 * nh + 2 * kv) * hd + h + 2 * i + h
    return total


def index_bytes_per_token(mc: dict, kv_bytes: int = 2) -> int:
    """The index rows a token adds: one pooled key a ``stride`` tokens a
    kv head a sparse block."""
    stride = sparse_sizes(mc)[1]
    return sparse_blocks(mc) * mc["num_kv_heads"] * _hd(mc) * kv_bytes // stride


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2, chips: int = 1) -> int:
    """The sparse kind's planes (keys and values of every kv head) and
    their index rows: what grows with a token."""
    del chips
    return (sparse_blocks(mc) * 2 * mc["num_kv_heads"] * _hd(mc) * kv_bytes
            + index_bytes_per_token(mc, kv_bytes))


def la_state_bytes(mc: dict) -> int:
    """One linear block's state of one request: ``heads x hd x hd``
    float32."""
    return _kinds(mc, "lightning")[0]["num_heads"] * _hd(mc) ** 2 * 4


def la_state_bytes_per_slot(mc: dict) -> int:
    """What a request holds whatever its length (no convolution: the pool's
    placeholder for a tail is one element a block)."""
    return la_blocks(mc) * la_state_bytes(mc)


def la_decode_kernel_ops(mc: dict, row_steps: int) -> int:
    """5 operations an element of the state a row-step a block (decay, the
    outer product's multiply and add, the read's multiply and add)."""
    return la_blocks(mc) * row_steps * 5 * la_state_bytes(mc) // 4


def la_decode_kernel_bytes(mc: dict, row_steps: int,
                           act_bytes: int = 2) -> int:
    """The state once in and once out a row a block a step, and the row's
    q, k, v (the model's dtype) and output (float32)."""
    row = _kinds(mc, "lightning")[0]["num_heads"] * _hd(mc)
    return la_blocks(mc) * row_steps * (2 * la_state_bytes(mc)
                                        + row * (3 * act_bytes + 4))


def la_prefill_kernel_ops(mc: dict, tokens: int) -> int:
    """The chunk form a prompt token a block at chunk ``Q`` = 256, every
    head with its own k q^T: ``heads x (2 Q N + 2 Q P + 4 N P)``."""
    nh, hd, q = _kinds(mc, "lightning")[0]["num_heads"], _hd(mc), LA_CHUNK
    return la_blocks(mc) * tokens * nh * (2 * q * hd + 2 * q * hd
                                          + 4 * hd * hd)


def la_prefill_kernel_bytes(mc: dict, tokens: int, segments: int) -> int:
    """The state once in and once out a segment a block (the tokens' rows
    are not counted: ``families/granite_moe_hybrid.py`` says why)."""
    del tokens
    return la_blocks(mc) * segments * 2 * la_state_bytes(mc)


def sparse_kernel_bytes(mc: dict, blocks_kept: int, index_rows: int,
                        kv_bytes: int = 2) -> int:
    """The least the folds of ``blocks_kept`` (query, block) pairs move,
    counted a kv head a sparse block (as the scheduler's record counts
    them): a kept block's keys and values, and the ``index_rows`` pooled
    keys the selections read; every kv head of every sparse block does as
    much."""
    block, hd = sparse_sizes(mc)[2], _hd(mc)
    return (sparse_blocks(mc) * mc["num_kv_heads"]
            * (blocks_kept * 2 * block * hd + index_rows * hd) * kv_bytes)


def sparse_kernel_ops(mc: dict, blocks_kept: int, index_rows: int) -> int:
    """The products any implementation of the equations does for those
    queries: a kept block's ``q k^T`` and ``p v`` for the group's heads
    (``4 x block x hd`` a head) and the scores over the visible index rows
    (``2 x hd`` a head a row)."""
    block, hd = sparse_sizes(mc)[2], _hd(mc)
    heads = _kinds(mc, "sparse")[0]["num_heads"]    # kv heads x a group's
    return sparse_blocks(mc) * heads * (blocks_kept * 4 * block * hd
                                        + index_rows * 2 * hd)


# ---------------------------------------------------------------- equations

def lightning_decay(heads: int):
    """``lambda_h`` ``[heads]``: the Lightning Attention slopes."""
    import jax.numpy as jnp
    return jnp.exp(-2.0 ** (-8.0 * jnp.arange(1, heads + 1) / heads))


def kept_blocks(q, k, t: int, sizes: tuple):
    """The selection as the equations read, for ONE query position ``t``
    past ``dense_len`` and one kv group: ``q`` ``[g, hd]`` the group's
    query heads, ``k`` ``[t + 1, hd]`` the group's keys.  Returns the kept
    block ids, ascending (numpy, float32; a test's and the tool's oracle
    for single queries)."""
    import numpy as np
    kernel, stride, block, topk, init, local, _ = sizes
    hd = q.shape[-1]
    n_k = (t + 1 - kernel) // stride + 1 if t + 1 >= kernel else 0
    last = t // block
    forced = sorted(set(range(min(init, last + 1)))
                    | set(range(max(0, last - local // block + 1), last + 1)))
    if n_k <= 0:
        return forced
    c = np.stack([k[stride * j:stride * j + kernel].mean(0)
                  for j in range(n_k)])
    s = (q @ c.T) * hd ** -0.5
    p = np.exp(s - s.max(-1, keepdims=True))
    sj = (p / p.sum(-1, keepdims=True)).sum(0)
    score = {}
    for b in range(last + 1):
        js = [j for j in range(block // stride * b - (kernel // stride - 1),
                               block // stride * (b + 1)) if 0 <= j < n_k]
        if b not in forced and js:
            score[b] = max(sj[j] for j in js)
    best = sorted(score, key=lambda b: (-score[b], b))[:topk]
    return sorted(set(forced) | set(best))


def blocks(mc: dict, q_block: int = 512):
    """``(period_layer(p, x), period_states(p, x))``: one whole period over
    the kinds' stacks; the second also returns each linear block's state,
    ``[heads, value, key]`` as the served pool lays it."""
    import jax
    import jax.numpy as jnp
    from reference import F32, _rms_norm, _rope

    hd, nkv = _hd(mc), mc["num_kv_heads"]
    eps = mc.get("norm_eps", 1e-6)
    residual = mc.get("residual_multiplier", 1.0)
    period = list(mc["period"])
    scale = hd ** -0.5

    def selection(q, c, pos, sizes, NB):
        """Kept blocks ``[Q, nkv, NB]`` of queries ``q`` ``[Q, nkv, g, hd]``
        at ``pos`` over pooled keys ``c`` ``[nkv, J, hd]``; ``NB`` the
        blocks that hold a token."""
        kernel, stride, block, topk, init, local, dense = sizes
        J = c.shape[1]
        ratio = block // stride
        s = jnp.einsum("qngd,njd->qngj", q, c) * scale
        closed = (stride * jnp.arange(J) + kernel - 1)[None] <= pos[:, None]
        s = jnp.where(closed[:, None, None], s, -jnp.inf)
        # (a query no kernel has closed for is under dense_len: its row of
        # NaN is never read)
        sj = jnp.sum(jax.nn.softmax(s, -1), axis=2)             # [Q, nkv, J]
        sj = jnp.where(closed[:, None], sj, -jnp.inf)
        sj = jnp.pad(sj, ((0, 0), (0, 0), (kernel // stride - 1,
                                           NB * ratio - J)),
                     constant_values=-jnp.inf)
        # block b: kernels ratio b - (kernel / stride - 1) .. ratio b + ratio - 1
        width = ratio + kernel // stride - 1
        meets = (ratio * jnp.arange(NB)[:, None] + jnp.arange(width)[None])
        score = jnp.max(sj[:, :, meets], axis=-1)               # [Q, nkv, NB]
        blk = jnp.arange(NB)[None]
        last = (pos // block)[:, None]
        exists = blk <= last
        forced = exists & ((blk < init) | (blk > last - local // block))
        score = jnp.where((exists & ~forced)[:, None], score, -jnp.inf)
        order = jnp.argsort(-score, axis=-1, stable=True)   # ties: lower id
        rank = jnp.argsort(order, axis=-1, stable=True)
        chosen = (rank < topk) & jnp.isfinite(score)
        keep = forced[:, None] | chosen
        return jnp.where((pos < dense)[:, None, None], exists[:, None], keep)

    def sparse_mixer(leaf, a, kind):
        t, nh = a.shape[0], kind["num_heads"]
        g = nh // nkv
        sizes = tuple(kind[f"sparse_{n}"] for n in (
            "kernel", "stride", "block", "topk", "init", "local",
            "dense_len"))
        kernel, stride, block = sizes[:3]
        q = (a @ leaf("wq")).reshape(t, nh, hd)
        k = (a @ leaf("wk")).reshape(t, nkv, hd)
        v = (a @ leaf("wv")).reshape(t, nkv, hd)
        if kind.get("qk_norm"):
            q = _rms_norm(q, leaf("q_norm_w"), eps)
            k = _rms_norm(k, leaf("k_norm_w"), eps)
        q = q.reshape(t, nkv, g, hd)
        n_k = max(0, (t - kernel) // stride + 1)
        if n_k:
            at = stride * jnp.arange(n_k)[:, None] + jnp.arange(kernel)[None]
            c = jnp.mean(k[at], axis=1).transpose(1, 0, 2)      # [nkv, J, hd]
        out = []
        for lo in range(0, t, q_block):
            hi = min(t, lo + q_block)
            pos = jnp.arange(lo, hi)
            see = pos[:, None] >= jnp.arange(hi)[None]          # [Q, hi]
            if n_k and hi > sizes[6]:
                keep = selection(q[lo:hi], c, pos, sizes, -(-t // block))
                keep = jnp.repeat(keep, block, axis=-1)
                see = see[:, None] & keep[:, :, :hi]            # [Q, nkv, hi]
            else:
                see = jnp.broadcast_to(see[:, None], (hi - lo, nkv, hi))
            s = jnp.einsum("qngd,knd->qngk", q[lo:hi], k[:hi]) * scale
            s = jnp.where(see[:, :, None], s, -jnp.inf)
            out.append(jnp.einsum("qngk,knd->qngd", jax.nn.softmax(s, -1),
                                  v[:hi]))
        o = jnp.concatenate(out, 0).reshape(t, nh * hd)
        o = o * jax.nn.sigmoid(a @ leaf("wg"))
        return o @ leaf("wo"), None

    def lightning_mixer(leaf, a, kind):
        t, nh = a.shape[0], kind["num_heads"]
        q = (a @ leaf("wq")).reshape(t, nh, hd)
        k = (a @ leaf("wk")).reshape(t, nh, hd)
        v = (a @ leaf("wv")).reshape(t, nh, hd)
        if kind.get("qk_norm"):
            q = _rms_norm(q, leaf("q_norm_w"), eps)
            k = _rms_norm(k, leaf("k_norm_w"), eps)
        theta = kind.get("rope_theta", 10000.0)
        q, k = _rope(q, theta), _rope(k, theta)
        lam = lightning_decay(nh).astype(F32)

        def token(S, row):
            q_t, k_t, v_t = row
            S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
            return S, jnp.einsum("hk,hkv->hv", q_t, S) * scale

        S, o = jax.lax.scan(token, jnp.zeros((nh, hd, hd), F32), (q, k, v))
        o = _rms_norm(o, jnp.ones((hd,), F32), eps).reshape(t, nh * hd)
        o = o * leaf("o_norm_w") * jax.nn.sigmoid(a @ leaf("wg"))
        return o @ leaf("wo"), jnp.swapaxes(S, 1, 2)    # [heads, value, key]

    def block(leaf, x, kind):
        a = _rms_norm(x, leaf("attn_norm_w"), eps)
        mixer = (lightning_mixer if kind["attn"] == "lightning"
                 else sparse_mixer)
        y, S = mixer(leaf, a, kind)
        x = x + residual * y
        m = _rms_norm(x, leaf("mlp_norm_w"), eps)
        # (rows in blocks: at 41k tokens the SwiGLU's width-16,384
        # intermediates are 2.7 GB each in float32)
        y = jnp.concatenate([
            (jax.nn.silu(m[lo:lo + MLP_ROWS] @ leaf("w_gate"))
             * (m[lo:lo + MLP_ROWS] @ leaf("w_up"))) @ leaf("w_down")
            for lo in range(0, m.shape[0], MLP_ROWS)])
        return x + residual * y, S

    def names():
        """The period's places as ``(kind, stack name, index in it)``, as
        the program names its stacks (``families/granite_moe_hybrid.py``)."""
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
        states = []
        for kind, name, j in names():
            # (an index that waits for the block's input: the compiler then
            # widens one block's slices at a time, as granite's does)
            tail = "." + name
            at = j + jnp.where(x[0, 0] * 0.0 == 1.0, 1, 0)
            mine = {k[:-len(tail)]: jax.lax.dynamic_index_in_dim(
                v, at, 0, keepdims=False)
                for k, v in p.items() if k.endswith(tail)}
            x, S = block(mine.__getitem__, x, kind)
            if S is not None:
                states.append(S)
        return x, states

    return (lambda p, x: period_states(p, x)[0]), period_states


def equations(mc: dict, q_block: int = 512):
    from reference import F32, _f32, _rms_norm

    period_layer, _ = blocks(mc, q_block)
    eps = mc.get("norm_eps", 1e-6)

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
# As ``families/granite_moe_hybrid.py``'s: the tokens are scored left to
# right; the replay is here for the STATE.  The configuration states a
# float32 state a request (12 MiB of it), the log-probabilities cannot tell
# it from a bfloat16 one, so the reply of a request with log-probabilities
# carries a sample of the state the request ended in
# (``generation.lightning_state``: of every plane, four heads' every eighth
# value row with all keys, the pool's own numbers) and the replay holds it
# to two limits:
#
# * STATE_REL_TOL: the sample against the reference's state after the same
#   ids, the difference's norm over the reference's, the largest plane.
#   Between the largest sound reading (0.033, the sixth plane of a canary
#   in ten benchmark runs; 0.020-0.023 at 600 to 30,000 tokens in the tool)
#   and a faulty program's (0.272, every segment's state started from zero,
#   ``tools/model_parity.py --state-not-carried`` at 1,000 tokens; my chip
#   runs, PR 69; PERF.md section 6).
# * STATE_F32_RESIDUE_MIN: the sample's distance from its own rounding to
#   bfloat16 over its norm, the smallest plane: float32 numbers read about
#   1.6e-3, a state held in or rounded to bfloat16 reads 0 exactly.
STATE_REL_TOL = 0.10
STATE_F32_RESIDUE_MIN = 5e-4


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
    """``score(params, ids, n_prompt, generation)``: one forward over
    ``ids[:-1]`` (row ``t - 1`` scores token ``t``), which also leaves the
    state the served request ended in; the reply's sample of that state is
    held to the two limits above."""

    def score(params, ids, n_prompt, generation):
        import sys
        import jax
        import jax.numpy as jnp
        import numpy as np
        import reference

        record = (generation or {}).get("lightning_state")
        if record is None:
            return {"error": "the reply carries no generation."
                             "lightning_state: the state the request ended "
                             "in is part of what this family's check holds"}
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
            return {"error": f"generation.lightning_state is {sample.shape}, "
                             f"the reference's sample {want.shape}"}
        readings = state_readings(sample, want)
        print(f"[replay] lightning_state {json.dumps(readings)}",
              file=sys.stderr, flush=True)
        problem = state_problem(readings, dtype)
        if problem:
            return {"error": problem}
        return score_rows(x[n_prompt - 1:], ids[n_prompt:])

    return score
