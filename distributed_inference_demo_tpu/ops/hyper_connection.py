"""Manifold-constrained hyper-connections: ``n`` residual streams a token.

A token rides its blocks as ``X`` ``[n, H]`` (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606; docs/DESIGN.md section 28).  Each
sublayer ``F`` of a block has its own ``phi`` ``[2n + n^2, n H]`` (the
paper's map, stored transposed: a coefficient's weights lie along the
lanes), ``alpha`` ``[3]`` and ``b`` ``[2n + n^2]``, all float32:

    u      = vec(X) ; r = (mean(u^2) + norm_eps)^-1/2
    m      = phi (u r)                                  [2n + n^2]
    h_pre  = sigmoid(alpha[0] m[0:n]  + b[0:n])
    h_post = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])
    A      = clip(alpha[2] mat(m[2n:]) + mat(b[2n:]), -clamp, clamp)
    M      = exp(A) ; iters times:  M /= colsum(M) + eps ; M /= rowsum(M) + eps
    y      = F(norm(sum_i h_pre[i] X[i]))
    X'[i]  = sum_j M[i, j] X[j] + h_post[i] y

Two device ops, named so that a trace tells them apart:

* ``_hc_pre_call`` (:func:`hc_pre`): reads the ``n`` streams once, leaves
  ``sum_i h_pre[i] X[i]`` ``[T, H]`` and the token's ``2n + n^2``
  coefficients.
* ``_hc_post_call`` (:func:`hc_post`): reads the ``n`` streams, the
  sublayer's output and the coefficients, writes the ``n`` streams.

The stream is carried as ``[.., n H]``, stream ``i`` the lanes ``[i H, (i +
1) H)``: an ``[.., n, H]`` array would pad ``n`` to a whole sublane tile in
the chip's memory.  The coefficients are never ``[T, n, n]`` (each token's
16 values padded to an (8, 128) tile, and 40 passes over it): they are ROWS
of tokens, coefficient ``k`` of every token one ``[T]`` row, so the Sinkhorn
steps are elementwise over 16 rows, unrolled (:func:`_coefficients`, the
one place that states the maps; the kernel and the plain path both run it).
In the kernels tokens lie on the lanes for those steps: ``m`` comes off the
MXU as ``[tokens, 128]``, is turned once (a 128 x 128 tile), and the
finished rows are turned back to columns that scale the streams.  With a
bf16 stream ``phi`` is split in the kernel into three bf16 pieces (``hi +
mid + lo``, float32 to the last bit) side by side in the MXU's 128 output
columns: one bf16 pass with float32 accumulation gives the float32
product.  Elsewhere, and for shapes the kernels do not take, plain
``jax.numpy`` with the same arithmetic (``on_kernel`` says which and why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
# tokens a grid step of either kernel (a smaller call is one step)
TILE_TOKENS = 128
_VMEM_LIMIT = 100 * 1024 * 1024
# the dtype the coefficient maps are computed in.  float32; a parity tool
# lowers it to show what that costs (``tools/model_parity.py``), on the
# plain path alone
COEF_DTYPE = jnp.float32


def on_kernel(tokens: int, width: int, n: int, backend: str = "auto",
              platform=None) -> tuple:
    """``(kernel?, why not)``: the Pallas calls serve ``n <= 4`` streams of
    whole 128-lane tiles on a TPU (bf16 or float32), tokens in sixteens,
    one grid step or whole steps of ``TILE_TOKENS``."""
    platform = platform or jax.default_backend()
    if backend == "xla":
        return False, "backend xla"
    if platform != "tpu" and backend != "pallas":
        return False, f"platform {platform}"
    if COEF_DTYPE != jnp.float32:
        return False, f"coefficient maps in {jnp.dtype(COEF_DTYPE).name}"
    if n < 2 or 2 * n + n * n > 32:
        return False, f"{n} streams"
    if width % (n * _LANES):
        return False, f"a stream of {width // max(1, n)} lanes"
    if tokens % _ROWS or (tokens > TILE_TOKENS and tokens % TILE_TOKENS):
        return False, f"{tokens} tokens"
    return True, ""


def whole_tiles(tokens: int) -> int:
    """The rows the kernels serve that hold ``tokens``: sixteens up to one
    grid step, whole steps of ``TILE_TOKENS`` past it (:func:`on_kernel`).
    A caller whose rows are no such count pads them to it (zeros: the ops
    work a row at a time)."""
    unit = _ROWS if tokens <= TILE_TOKENS else TILE_TOKENS
    return -(-tokens // unit) * unit


# ------------------------------------------------------------- the maps

def _coefficients(m, alpha, b, n: int, iters: int, eps: float,
                  clamp: float) -> list:
    """``m``: the ``2n + n^2`` rows of ``phi (u r)``, each an array of
    one value a token; ``alpha`` / ``b``: indexable scalars.  Returns the
    rows ``h_pre`` (n), ``h_post`` (n) and the doubly-stochastic map (n^2,
    row-major), in that order.  Elementwise over tokens, no reduction:
    the same lines run on ``[1, 128]`` rows in the kernels and on ``[T]``
    rows outside them."""
    pre = [jax.nn.sigmoid(alpha[0] * m[i] + b[i]) for i in range(n)]
    post = [2.0 * jax.nn.sigmoid(alpha[1] * m[n + i] + b[n + i])
            for i in range(n)]
    M = [[jnp.exp(jnp.clip(alpha[2] * m[2 * n + n * i + j]
                           + b[2 * n + n * i + j], -clamp, clamp))
          for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for j in range(n):          # a column's entries sum to 1
            inv = 1.0 / (sum(M[i][j] for i in range(n)) + eps)
            for i in range(n):
                M[i][j] = M[i][j] * inv
        for i in range(n):          # then a row's
            inv = 1.0 / (sum(M[i]) + eps)
            M[i] = [v * inv for v in M[i]]
    return pre + post + [v for row in M for v in row]


# ------------------------------------------------------- the plain path

def _pre_xla(x, phi, alpha, b, *, n, iters, eps, clamp, norm_eps):
    """``x`` ``[T, n H]`` -> ``(h [T, H], coef [2n + n^2, T] float32)``."""
    T, W = x.shape
    dt = COEF_DTYPE
    xf = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + norm_eps)
    m = jnp.einsum("ck,tk->ct", phi.astype(F32), xf, precision=HIGHEST) * r
    alpha, b = alpha.astype(dt), b.astype(dt)
    coef = jnp.stack(_coefficients(list(m.astype(dt)), alpha, b, n, iters,
                                   eps, clamp)).astype(F32)
    h = jnp.einsum("nt,tnh->th", coef[:n], xf.reshape(T, n, W // n))
    return h.astype(x.dtype), coef


def _post_xla(x, y, coef, *, n):
    T, W = x.shape
    xs = x.astype(F32).reshape(T, n, W // n)
    res = coef[2 * n:].reshape(n, n, T)
    out = (jnp.einsum("ijt,tjh->tih", res, xs)
           + coef[n:2 * n].T[:, :, None] * y.astype(F32)[:, None, :])
    return out.reshape(T, W).astype(x.dtype)


# ---------------------------------------------------------- the kernels

_ROWS = 16      # tokens a pass of the streaming loops holds in registers


def _turned(a):
    """``a`` ``[rows <= 128, 128]`` float32, its rows padded to a whole
    128 x 128 tile with zeros, transposed."""
    rows = a.shape[0]
    if rows < _LANES:
        a = jnp.concatenate(
            [a, jnp.zeros((_LANES - rows, _LANES), a.dtype)], axis=0)
    return a.T


def _row_groups(tokens: int, body) -> None:
    """``body(rows)`` for each group of ``_ROWS`` tokens of the step: a
    coefficient of the group is two registers, spread over the lanes once
    and held while the group's streams pass tile by tile."""
    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, tokens // _ROWS, step, 0)


def _columns(ct_ref, rows, lo: int, count: int) -> list:
    """Coefficients ``lo .. lo + count`` of the tokens ``rows`` out of
    ``ct_ref`` ``[128 tokens, 128]``, each spread over a tile's lanes."""
    block = ct_ref[rows, :]
    return [jnp.broadcast_to(block[:, k:k + 1], (_ROWS, _LANES))
            for k in range(lo, lo + count)]


def _hc_pre_kernel(ab_ref, x_ref, phi_ref, h_ref, coef_ref, ct_ref, *, n,
                   iters, eps, clamp, norm_eps, chunk):
    """One grid step: ``tokens`` rows of the stream.  ``x_ref`` ``[tokens,
    n H]``, ``phi_ref`` ``[maps, n H]`` float32, ``ab_ref`` (scalar
    memory) ``alpha`` then ``b``; ``h_ref`` ``[tokens, H]`` and ``coef_ref``
    ``[1, 32, 128]`` (a token a lane, the first ``tokens`` of them; the
    first ``maps`` rows).  ``ct_ref``: the coefficients turned back, a
    token a row."""
    tokens, W = x_ref.shape
    H, maps = W // n, phi_ref.shape[0]
    split = x_ref.dtype == jnp.bfloat16
    pad = jnp.zeros((32 - maps, chunk), F32)
    rest_rows = jnp.zeros((32, chunk), jnp.bfloat16) if split else \
        jnp.zeros((96, chunk), F32)      # the MXU's unused output columns
    acc = jnp.zeros((tokens, _LANES), F32)
    sq = jnp.zeros((tokens, _LANES), F32)
    for lo in range(0, W, chunk):
        x = x_ref[:, lo:lo + chunk]
        p = jnp.concatenate([phi_ref[:, lo:lo + chunk], pad], axis=0)
        if split:       # hi + mid + lo: float32 to the last bit
            hi = p.astype(jnp.bfloat16)
            rest = p - hi.astype(F32)
            mid = rest.astype(jnp.bfloat16)
            low = (rest - mid.astype(F32)).astype(jnp.bfloat16)
            w = jnp.concatenate([hi, mid, low, rest_rows], axis=0)
            acc += jax.lax.dot_general(
                x, w, (((1,), (1,)), ((), ())), preferred_element_type=F32)
        else:
            w = jnp.concatenate([p, rest_rows], axis=0)
            acc += jax.lax.dot_general(
                x.astype(F32), w, (((1,), (1,)), ((), ())),
                precision=HIGHEST, preferred_element_type=F32)
        xf = x.astype(F32)
        xf = xf * xf
        for t in range(0, chunk, _LANES):
            sq += xf[:, t:t + _LANES]
    acc_t = _turned(acc)                     # [128 columns, 128 tokens]
    if split:
        acc_t = acc_t[0:32] + acc_t[32:64] + acc_t[64:96]
    r = jax.lax.rsqrt(jnp.sum(_turned(sq), axis=0, keepdims=True) / W
                      + norm_eps)            # [1, 128 tokens]
    m = acc_t[0:32] * r
    ab = [ab_ref[k] for k in range(3 + maps)]
    rows = _coefficients([m[k:k + 1] for k in range(maps)], ab[:3], ab[3:],
                         n, iters, eps, clamp)
    coef_ref[0] = jnp.zeros((32, _LANES), F32)
    for k, row in enumerate(rows):
        coef_ref[0, k:k + 1, :] = row
    ct_ref[...] = _turned(coef_ref[0])

    def weighted_sum(rows):
        pre = _columns(ct_ref, rows, 0, n)
        for t in range(0, H, _LANES):
            h = pre[0] * x_ref[rows, t:t + _LANES].astype(F32)
            for i in range(1, n):
                h += pre[i] * x_ref[rows, i * H + t:i * H + t + _LANES
                                    ].astype(F32)
            h_ref[rows, t:t + _LANES] = h.astype(h_ref.dtype)

    _row_groups(tokens, weighted_sum)


def _hc_post_kernel(x_ref, y_ref, coef_ref, o_ref, ct_ref, *, n):
    """One grid step: ``x_ref`` / ``o_ref`` ``[tokens, n H]``, ``y_ref``
    ``[tokens, H]``, ``coef_ref`` ``[1, 32, 128]``."""
    tokens, W = x_ref.shape
    H = W // n
    ct_ref[...] = _turned(coef_ref[0])

    def mix(rows):
        post = _columns(ct_ref, rows, n, n)
        res = _columns(ct_ref, rows, 2 * n, n * n)
        for t in range(0, H, _LANES):
            y = y_ref[rows, t:t + _LANES].astype(F32)
            xs = [x_ref[rows, j * H + t:j * H + t + _LANES].astype(F32)
                  for j in range(n)]
            for i in range(n):
                o = post[i] * y
                for j in range(n):
                    o += res[n * i + j] * xs[j]
                o_ref[rows, i * H + t:i * H + t + _LANES] = o.astype(
                    o_ref.dtype)

    _row_groups(tokens, mix)


def _tile(tokens: int) -> int:
    return min(tokens, TILE_TOKENS)


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "clamp", "norm_eps", "interpret"))
def _hc_pre_call(x, phi, alpha, b, *, n, iters, eps, clamp, norm_eps,
                 interpret=False):
    """``_hc_pre_call`` in a trace: ``(h [T, H], coef [T / tile, 32,
    128])``, a step's coefficients the first ``maps`` rows of its slab."""
    T, W = x.shape
    tile, maps = _tile(T), phi.shape[0]
    chunk = 512 if W % 512 == 0 else _LANES
    return pl.pallas_call(
        functools.partial(_hc_pre_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp, norm_eps=norm_eps, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T // tile,),
            in_specs=[pl.BlockSpec((tile, W), lambda t, ab: (t, 0)),
                      pl.BlockSpec((maps, W), lambda t, ab: (0, 0))],
            out_specs=[pl.BlockSpec((tile, W // n), lambda t, ab: (t, 0)),
                       pl.BlockSpec((1, 32, _LANES),
                                    lambda t, ab: (t, 0, 0))],
            scratch_shapes=[pltpu.VMEM((_LANES, _LANES), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((T, W // n), x.dtype),
                   jax.ShapeDtypeStruct((T // tile, 32, _LANES), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="_hc_pre_call",
        interpret=interpret,
    )(jnp.concatenate([alpha.astype(F32), b.astype(F32)]), x,
      phi.astype(F32))


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _hc_post_call(x, y, coef, *, n, interpret=False):
    """``_hc_post_call`` in a trace: the ``n`` streams ``[T, n H]``."""
    T, W = x.shape
    tile = _tile(T)
    return pl.pallas_call(
        functools.partial(_hc_post_kernel, n=n),
        grid=(T // tile,),
        in_specs=[pl.BlockSpec((tile, W), lambda t: (t, 0)),
                  pl.BlockSpec((tile, W // n), lambda t: (t, 0)),
                  pl.BlockSpec((1, 32, _LANES), lambda t: (t, 0, 0))],
        out_specs=pl.BlockSpec((tile, W), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, W), x.dtype),
        scratch_shapes=[pltpu.VMEM((_LANES, _LANES), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="_hc_post_call",
        interpret=interpret,
    )(x, y, coef)


# ------------------------------------------------------------ the seam

def coef_rows(coef, tokens: int, n: int):
    """Either path's coefficients as ``[2n + n^2, T]``: row ``k`` is
    coefficient ``k`` of every token (``h_pre``, ``h_post``, then the map
    row-major)."""
    if coef.ndim == 2:
        return coef
    tile = _tile(tokens)
    return (coef[:, :2 * n + n * n, :tile].transpose(1, 0, 2)
            .reshape(2 * n + n * n, -1))


def hc_pre(x, phi, alpha, b, *, n: int, iters: int, eps: float, clamp: float,
           norm_eps: float, interpret: bool = False, note=None):
    """``x`` ``[.., n H]`` (the streams side by side) -> ``(h [.., H],
    coef)``: the row the sublayer's norm reads, and the token's
    coefficients for :func:`hc_post` (opaque: :func:`coef_rows` reads
    them).  ``note(path, why)``: told which path the call (and the
    :func:`hc_post` that takes its coefficients) compiled onto,
    ``pallas_hc`` or ``xla_hc`` and why not the kernels."""
    lead, W = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, W)
    kernel, why = on_kernel(xt.shape[0], W, n,
                            "pallas" if interpret else "auto")
    if note is not None:
        note("pallas_hc" if kernel else "xla_hc", why)
    kw = dict(n=n, iters=iters, eps=float(eps), clamp=float(clamp),
              norm_eps=float(norm_eps))
    if kernel:
        h, coef = _hc_pre_call(xt, phi, alpha, b, interpret=interpret, **kw)
    else:
        h, coef = _pre_xla(xt, phi, alpha, b, **kw)
    return h.reshape(lead + (W // n,)), coef


def hc_post(x, y, coef, *, n: int, interpret: bool = False):
    """The streams after the sublayer: ``x`` ``[.., n H]``, its output
    ``y`` ``[.., H]``, ``coef`` as :func:`hc_pre` left it (the path it
    took is the path taken here)."""
    lead, W = x.shape[:-1], x.shape[-1]
    xt, yt = x.reshape(-1, W), y.reshape(-1, W // n).astype(x.dtype)
    if coef.ndim == 3:
        out = _hc_post_call(xt, yt, coef, n=n, interpret=interpret)
    else:
        out = _post_xla(xt, yt, coef, n=n)
    return out.reshape(lead + (W,))


def expand(x, n: int):
    """``[.., H]`` -> ``[.., n H]``: every stream starts as the embedding."""
    return jnp.tile(x, (1,) * (x.ndim - 1) + (n,))


def collapse(x, n: int):
    """``[.., n H]`` -> ``[.., H]`` float32: the streams' sum."""
    H = x.shape[-1] // n
    return sum(x[..., i * H:(i + 1) * H].astype(F32) for i in range(n))


def sinkhorn_residual(coef, tokens: int, n: int):
    """Largest ``|row or column sum - 1|`` of the tokens' maps."""
    res = coef_rows(coef, tokens, n)[2 * n:].reshape(n, n, -1)
    return jnp.maximum(jnp.abs(res.sum(0) - 1.0).max(),
                       jnp.abs(res.sum(1) - 1.0).max())
