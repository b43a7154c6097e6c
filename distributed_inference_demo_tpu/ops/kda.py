"""Kimi Delta Attention: the gated delta rule over a recurrent state.

A head holds a state ``S`` ``[key, value]`` in float32 and every token
rewrites it (docs/DESIGN.md section 27):

    Sd  = diag(alpha_t) S_{t-1}                    alpha in (0, 1) a key CHANNEL
    S_t = Sd + beta_t k_t (v_t - Sd^T k_t)^T       beta in (0, 2)
    o_t = S_t^T q_t

Two device ops, named so that a trace tells them apart:

* ``_kda_step`` (:func:`kda_step`): one token a row.  The state is read,
  decayed, corrected and written in place, a row of the pool a request.
* ``_kda_chunk`` (:func:`kda_chunk`): a prefill segment in the chunkwise
  (WY) form.  Inside a chunk of ``chunk`` tokens the token-to-token
  products ``A[t, s] = sum_d k_t k_s exp(G_t - G_s)`` (``G`` the running sum
  of ``log alpha``) are taken with the difference formed BEFORE the
  exponential, explicitly inside sub-blocks of ``sub`` tokens and between
  sub-blocks through the later block's first boundary, so every exponent
  is <= 0 and no ``exp(+cumulative decay)`` is ever formed.  With
  ``N = diag(beta) A`` (strictly lower) and ``S`` the state the chunk
  starts from:

      U   = (I + N)^-1 diag(beta) (V - (K * exp(G)) S)      the corrections
      O   = (Q * exp(G)) S + B U                            B[t, s <= t] as A, with q_t
      S'  = diag(exp(G_c)) S + (K * exp(G_c - G))^T U

  XLA builds the chunk's matrices (they do not touch the state); the pass
  over the chunks that reads and writes the state is the kernel.
  ``(I + N)^-1`` is formed by products (:func:`unit_lower_inverse`:
  substitution inside diagonal blocks of ``sub`` rows, block merges
  above), not by XLA's triangular solve, a custom-call six times slower
  on the chip, and not by the series ``(I - N)(I + N^2)(I + N^4) ..``,
  whose powers overflow float32 where one key repeats.

A token that is not there (a padded position of a segment's last chunk, a
row that decodes nothing) has ``log alpha = 0`` and ``beta = 0``: it leaves
the state as it was, bit for bit (a dead row of :func:`kda_step` is not
touched at all).  On the chip both ops are Pallas calls at a head size of
128; elsewhere, and in float32 tests, plain XLA with the same arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

# the chunk of the state pass and the sub-block of the explicit
# differences at a head size of 128; a smaller segment is one chunk
CHUNK, SUB = 128, 32
# heads a grid step of each kernel
_STEP_HEADS, _CHUNK_HEADS = 8, 4


def on_kernel(state_shape, chunk: int = 1, backend: str = "auto",
              platform=None) -> tuple:
    """``(kernel?, why not)``: the Pallas calls serve a state of
    ``[.., heads, 128, 128]`` on a TPU, a segment in whole chunks of 128."""
    platform = platform or jax.default_backend()
    if backend == "xla":
        return False, "backend xla"
    if platform != "tpu" and backend != "pallas":
        return False, f"platform {platform}"
    h, dk, dv = state_shape[-3:]
    if (dk, dv) != (128, 128) or h % _STEP_HEADS:
        return False, f"state {h} x {dk} x {dv}"
    if chunk > 1 and chunk % CHUNK:
        return False, f"segment {chunk} not whole chunks of {CHUNK}"
    return True, ""


# --------------------------------------------------------------- the step

def _step_math(S, q, k, v, g, beta):
    """One token over states ``S`` ``[.., dk, dv]``: ``(o [.., dv], S')``.
    ``q, k, g``: ``[.., dk]``; ``v``: ``[.., dv]``; ``beta``: ``[..]``.
    Products and sums on the vector unit, in float32."""
    alpha = jnp.exp(g)
    Sd = S * alpha[..., :, None]
    u = jnp.sum(Sd * k[..., :, None], axis=-2)
    w = beta[..., None] * (v - u)
    S_new = Sd + k[..., :, None] * w[..., None, :]
    o = jnp.sum(S_new * q[..., :, None], axis=-2)
    return o, S_new


def _kda_step_kernel(rows_ref, plane_ref, x_ref, s_ref, o_ref, out_ref, *,
                     heads: int):
    """Grid (rows, head blocks).  ``x_ref`` ``[1, 5, heads, 128]``: q, k,
    v, log alpha and beta (a lane each) of the block's heads; ``s_ref`` /
    ``out_ref`` ``[1, 1, heads, 128, 128]``, the same block of the pool.
    A vector over the key channels is turned into a column by a masked
    lane sum (``eye``): no transpose, no matmul, float32 throughout."""
    del rows_ref, plane_ref
    d = s_ref.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))

    def col(row):                       # [1, d] -> [d, 1]
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    for h in range(heads):
        S = s_ref[0, 0, h]
        q = x_ref[0, 0, pl.ds(h, 1), :]
        k = x_ref[0, 1, pl.ds(h, 1), :]
        v = x_ref[0, 2, pl.ds(h, 1), :]
        g = x_ref[0, 3, pl.ds(h, 1), :]
        beta = x_ref[0, 4, pl.ds(h, 1), :]
        k_col = col(k)
        Sd = S * col(jnp.exp(g))
        u = jnp.sum(Sd * k_col, axis=0, keepdims=True)
        w = beta * (v - u)
        S_new = Sd + k_col * w
        out_ref[0, 0, h] = S_new
        o_ref[0, pl.ds(h, 1), :] = jnp.sum(S_new * col(q), axis=0,
                                           keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_step_call(rows, plane, x, state, *, interpret=False):
    """``x`` ``[b, 5, H, 128]``, ``state`` ``[P, R, H, 128, 128]`` aliased
    to the second output; row ``i`` works on ``state[plane, rows[i]]``."""
    b, _, H, d = x.shape
    hb = _STEP_HEADS
    s_spec = pl.BlockSpec((1, 1, hb, d, d),
                          lambda i, j, rows, plane: (plane[0], rows[i], j,
                                                     0, 0))
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, H // hb),
            in_specs=[pl.BlockSpec((1, 5, hb, d),
                                   lambda i, j, rows, plane: (i, 0, j, 0)),
                      s_spec],
            out_specs=[pl.BlockSpec((1, hb, d),
                                    lambda i, j, rows, plane: (i, j, 0)),
                       s_spec]),
        out_shape=[jax.ShapeDtypeStruct((b, H, d), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={3: 1},    # operands count the two scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="_kda_step",
    )(rows, plane, x, state)


def kda_step(state, plane, rows, q, k, v, g, beta, live, *,
             kernel: bool = False, interpret: bool = False):
    """One token a row.  ``state`` ``[P, R, H, dk, dv]`` float32, the
    whole pool; ``plane`` an int32 scalar; ``rows`` ``[b]`` the pool row of
    each batch row, or ``None`` where row ``i`` is pool row ``i`` (a dense
    cache); ``q, k, g`` ``[b, H, dk]``, ``v`` ``[b, H, dv]``, ``beta``
    ``[b, H]`` float32; ``live`` ``[b]`` bool or ``None``.  Returns ``(o
    [b, H, dv] float32, state')``.  A dead row's state is not touched; two
    live rows never name one pool row.  The last pool row is nobody's
    (dead rows point there where the kernel needs a place)."""
    R = state.shape[1]
    b = q.shape[0]
    if live is None:
        live = jnp.ones((b,), bool)
    if rows is None:
        rows = jnp.arange(b, dtype=jnp.int32)
        trash = None
    else:
        trash = R - 1
        rows = jnp.where(live, jnp.minimum(rows, trash), trash)
    if kernel:
        assert trash is not None, "the kernel addresses a pool by row"
        x = jnp.stack([q, k, v, g,
                       jnp.broadcast_to(beta[..., None], q.shape)],
                      axis=1).astype(F32)
        o, state = _kda_step_call(rows.astype(jnp.int32),
                                  jnp.reshape(plane, (1,)).astype(jnp.int32),
                                  x, state, interpret=interpret)
        return jnp.where(live[:, None, None], o, 0.0), state
    # XLA: the pool's plane is worked on where it lies, every row of it,
    # and what is small (q, k, v, the gates, the outputs) moves instead
    S = jax.lax.dynamic_index_in_dim(state, plane, 0, keepdims=False)
    if trash is None:
        at, alive = jnp.arange(R), live
    else:
        at = jnp.full((R,), b, jnp.int32).at[rows].set(
            jnp.arange(b, dtype=jnp.int32)).at[trash].set(b)
        alive = at < b
    pad = lambda a: jnp.concatenate(
        [a.astype(F32), jnp.zeros((1,) + a.shape[1:], F32)])[at]
    o, S_new = _step_math(S, pad(q), pad(k), pad(v), pad(g), pad(beta))
    S = jnp.where(alive[:, None, None, None], S_new, S)
    state = jax.lax.dynamic_update_index_in_dim(state, S, plane, 0)
    o = jnp.where(live[:, None, None], o[rows], 0.0)
    return o, state


# -------------------------------------------------------------- the chunk

def _blocks(N, b: int, at):
    """The ``b x b`` blocks of ``N`` ``[.., c, c]`` at the block
    coordinates ``at``, stacked ``[.., len(at), b, b]``."""
    return jnp.stack([N[..., i * b:(i + 1) * b, j * b:(j + 1) * b]
                      for i, j in at], axis=-3)


def _merge_inverted_blocks(X, N):
    """``(I + N)^-1`` from its inverted diagonal blocks ``X`` ``[.., c / b,
    b, b]``: level by level two of them ``A``, ``D`` and the block ``N21``
    of ``N`` under ``A`` become ``[[A, 0], [-D N21 A, D]]``, float32
    ``HIGHEST`` products."""
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    c, b = N.shape[-1], X.shape[-1]
    while b < c:
        A, D = X[..., 0::2, :, :], X[..., 1::2, :, :]
        N21 = _blocks(N, b, [(2 * i + 1, 2 * i) for i in range(c // b // 2)])
        X = jnp.concatenate(
            [jnp.concatenate([A, jnp.zeros_like(A)], axis=-1),
             jnp.concatenate([-dot(dot(D, N21), A), D], axis=-1)], axis=-2)
        b *= 2
    return X[..., 0, :, :]


def unit_lower_inverse(N, base: int = SUB):
    """``(I + N)^-1`` of strictly lower-triangular ``N`` ``[.., c, c]``
    (float32), formed by products and no solve.  The diagonal blocks of
    ``b`` rows (``c`` halved while it is even and above ``base``) are
    inverted by substitution, every block of every matrix at once and the
    blocks along the LANES (``[b, b, blocks]``: a step's two factors are
    then slices of the leading axes, nothing crosses a lane): column ``j``
    of ``N`` times the inverse's finished row ``j`` comes off the rows
    under it, ``b - 1`` steps of one broadcast product each, exact and in
    the order a row-by-row solve adds them.  Then the blocks are merged
    (:func:`_merge_inverted_blocks`).  No series in ``N``: its powers
    overflow where one key repeats (docs/DESIGN.md section 27)."""
    c = N.shape[-1]
    b = c
    while b > base and b % 2 == 0:
        b //= 2
    D = _blocks(N, b, [(i, i) for i in range(c // b)])
    Dl = jnp.moveaxis(D.reshape((-1, b, b)), 0, 2)       # [b, b, blocks]

    def step(j, X):
        return X - (jax.lax.dynamic_slice_in_dim(Dl, j, 1, axis=1)
                    * jax.lax.dynamic_slice_in_dim(X, j, 1, axis=0))

    X = jax.lax.fori_loop(0, b - 1, step, jnp.broadcast_to(
        jnp.eye(b, dtype=N.dtype)[:, :, None], Dl.shape))
    return _merge_inverted_blocks(jnp.moveaxis(X, 2, 0).reshape(D.shape), N)


def chunk_matrices(q, k, v, g, beta, chunk: int, sub: int) -> dict:
    """What one segment's chunks need beside the state, from ``q, k, g``
    ``[s, H, dk]``, ``v`` ``[s, H, dv]``, ``beta`` ``[s, H]`` (float32, ``s``
    a multiple of ``chunk``, ``chunk`` of ``sub``): a dict of ``[n, H, ...]``
    arrays over the ``n = s / chunk`` chunks.  Every exponent is <= 0."""
    s, H, dk = q.shape
    n, nb = s // chunk, chunk // sub
    cut = lambda a: jnp.moveaxis(
        a.reshape((n, chunk) + a.shape[1:]), 1, 2)       # [n, H, c, ..]
    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    G = jnp.cumsum(g, axis=2)                            # [n, H, c, dk]
    Gc = G[:, :, -1]
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    A = jnp.zeros((n, H, chunk, chunk), F32)
    B = jnp.zeros((n, H, chunk, chunk), F32)
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    for i in range(nb):
        blk = slice(i * sub, (i + 1) * sub)
        Gi, ki, qi = G[:, :, blk], k[:, :, blk], q[:, :, blk]
        # inside the sub-block: the difference, then the exponential
        diff = Gi[:, :, :, None, :] - Gi[:, :, None, :, :]
        E = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        kk = ki[:, :, None, :, :] * E
        A = A.at[:, :, blk, blk].set(
            jnp.sum(ki[:, :, :, None, :] * kk, axis=-1))
        B = B.at[:, :, blk, blk].set(
            jnp.sum(qi[:, :, :, None, :] * kk, axis=-1))
        if i:
            # against every earlier sub-block, through this block's edge
            edge = G[:, :, i * sub - 1][:, :, None, :]
            past = k[:, :, :i * sub] * jnp.exp(edge - G[:, :, :i * sub])
            here = jnp.exp(Gi - edge)
            A = A.at[:, :, blk, :i * sub].set(
                ein("nhtd,nhsd->nhts", ki * here, past))
            B = B.at[:, :, blk, :i * sub].set(
                ein("nhtd,nhsd->nhts", qi * here, past))
    N = beta[..., None] * jnp.tril(A, -1)
    Tm = unit_lower_inverse(N) * beta[..., None, :]
    decay = jnp.exp(G)
    return {"Kg": k * decay, "Qg": q * decay,
            "KendT": jnp.swapaxes(k * jnp.exp(Gc[:, :, None] - G), 2, 3),
            "V": v, "Tm": Tm, "Bm": B, "gc": jnp.exp(Gc)}


def _chunk_pass(S, m):
    """The pass over one chunk: ``(O [.., c, dv], S')`` from the state
    ``S`` ``[.., dk, dv]`` and the chunk's matrices."""
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    U = dot(m["Tm"], m["V"] - dot(m["Kg"], S))
    O = dot(m["Qg"], S) + dot(m["Bm"], U)
    return O, S * m["gc"][..., :, None] + dot(m["KendT"], U)


def _kda_chunk_kernel(row_ref, plane_ref, fresh_ref, kg_ref, qg_ref, kt_ref,
                      v_ref, tm_ref, bm_ref, gc_ref, s_ref, o_ref, out_ref, *,
                      heads: int):
    """Grid (head blocks, chunks), the chunks in order.  The block of the
    pool ``[1, 1, heads, dk, dv]`` stays in ``out_ref`` from the first
    chunk (where it is the pool's, or zero for a segment that starts a
    request) to the last.  Every operand a ``[128, 128]`` float32 tile a
    head: ``gc_ref`` is the chunk's whole decay spread over the lanes."""
    del row_ref, plane_ref

    @pl.when(pl.program_id(1) == 0)
    def _first():
        s0 = s_ref[...]
        out_ref[...] = jnp.where(fresh_ref[0] > 0, jnp.zeros_like(s0), s0)

    dot = functools.partial(jnp.dot, preferred_element_type=F32,
                            precision=HIGHEST)
    for h in range(heads):
        S = out_ref[0, 0, h]
        U = dot(tm_ref[0, h], v_ref[0, h] - dot(kg_ref[0, h], S))
        o_ref[0, h] = dot(qg_ref[0, h], S) + dot(bm_ref[0, h], U)
        out_ref[0, 0, h] = S * gc_ref[0, h] + dot(kt_ref[0, h], U)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_chunk_call(row, plane, fresh, m, state, *, interpret=False):
    """``m``: :func:`chunk_matrices`' arrays, ``gc`` spread to ``[n, H,
    dk, dv]``; ``state`` aliased to the second output."""
    n, H, c, d = m["V"].shape
    hb = _CHUNK_HEADS
    tile = pl.BlockSpec((1, hb, c, d), lambda j, i, *_: (i, j, 0, 0))
    s_spec = pl.BlockSpec(
        (1, 1, hb, d, d),
        lambda j, i, row, plane, fresh: (plane[0], row[0], j, 0, 0))
    names = ("Kg", "Qg", "KendT", "V", "Tm", "Bm", "gc")
    return pl.pallas_call(
        functools.partial(_kda_chunk_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(H // hb, n),
            in_specs=[tile] * len(names) + [s_spec],
            out_specs=[tile, s_spec]),
        out_shape=[jax.ShapeDtypeStruct((n, H, c, d), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={3 + len(names): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="_kda_chunk",
    )(row, plane, fresh, *[m[k] for k in names], state)


def kda_chunk(state, plane, row, fresh, q, k, v, g, beta, *,
              chunk: int = CHUNK, sub: int = SUB, kernel: bool = False,
              interpret: bool = False):
    """One segment of ``s`` tokens of one request, in order, starting from
    ``state[plane, row]`` (from zero where ``fresh``: the segment starts a
    request) and leaving its final state there.  ``q, k, g`` ``[s, H,
    dk]``, ``v`` ``[s, H, dv]``, ``beta`` ``[s, H]`` float32; ``row`` and
    ``plane`` int32 scalars (``row`` inside the pool).  Returns ``(o [s,
    H, dv] float32, state')``.  ``s`` is padded to whole chunks with
    tokens that are not there."""
    s, H, dk = q.shape
    if s < chunk:           # a short segment is one chunk
        chunk = s
    if chunk % sub:
        sub = chunk
    pad = -s % chunk
    if pad:
        z = lambda a: jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)
    f32 = lambda a: a.astype(F32)
    m = chunk_matrices(f32(q), f32(k), f32(v), f32(g), f32(beta), chunk,
                       sub)
    n = m["V"].shape[0]
    if kernel:
        m["gc"] = jnp.broadcast_to(m["gc"][..., :, None],
                                   m["KendT"].shape[:3] + (v.shape[-1],))
        one = lambda a, dt=jnp.int32: jnp.reshape(a, (1,)).astype(dt)
        O, state = _kda_chunk_call(one(row), one(plane), one(fresh), m,
                                   state, interpret=interpret)
    else:
        S = jnp.where(fresh, 0.0, state[plane, row])
        outs = []
        for i in range(n):
            O_i, S = _chunk_pass(S, {a: b[i] for a, b in m.items()})
            outs.append(O_i)
        O = jnp.stack(outs)
        state = state.at[plane, row].set(S)
    o = jnp.moveaxis(O, 1, 2).reshape((n * chunk, H, -1))
    return o[:s], state


def kda_recurrence(S, q, k, v, g, beta):
    """The recurrence token by token (``lax.scan``): what both forms are
    held to in tests, and nothing the serving path runs.  ``S`` ``[H, dk,
    dv]``; the rest as :func:`kda_chunk`.  Returns ``(o [s, H, dv], S')``."""
    def body(S, x):
        o, S = _step_math(S, *x)
        return S, o
    S, o = jax.lax.scan(body, S, (q, k, v, g, beta))
    return o, S


# ------------------------------------------------- the short convolution

def causal_conv(u, tail, w, ntok, bias=None):
    """Depthwise causal convolution, ``taps`` a channel, then silu:
    ``y_t = silu(sum_tau w[tau] x_{t - taps + 1 + tau} + bias)`` over ``x =
    tail ++ u``.  ``u`` ``[b, s, C]``; ``tail`` ``[b, taps - 1, C]`` the
    inputs before the call (zeros at a request's start); ``w`` ``[taps,
    C]``; ``bias`` ``[C]`` or None (``ops.ssd``'s blocks have one);
    ``ntok`` ``[b]`` the tokens each row holds (its first ones).  Returns
    ``(y [b, s, C] float32, tail')``: the last ``taps - 1`` inputs a row
    holds after its ``ntok`` tokens (the old tail where it holds none)."""
    taps = w.shape[0]
    s = u.shape[1]
    x = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    wf = w.astype(F32)
    y = sum(wf[t] * x[:, t:t + s].astype(F32) for t in range(taps))
    if bias is not None:
        y = y + bias.astype(F32)
    at = ntok[:, None] + jnp.arange(taps - 1)[None, :]
    new_tail = jnp.take_along_axis(x, at[:, :, None], axis=1)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)
