"""Mamba-2's state-space duality (SSD, arXiv:2405.21060): a scalar decay a
head over a recurrent state.

A head holds a state ``S`` ``[P, N]`` in float32 (``P`` the head's channels,
``N`` the state size) and every token rewrites it (docs/DESIGN.md section
29); ``B`` and ``C`` ``[N]`` are shared by the heads of a group (head ``h``
of ``H`` in ``G`` groups reads group ``h // (H / G)``):

    a_t = exp(dt_t A)                              dt > 0, A < 0 a head: a in (0, 1)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T             x_t [P]
    y_t = S_t C_t                                  (the ``D`` skip is the mixer's)

No delta rule and no solve (``ops.kda``): the decay is one scalar a head, so
inside a chunk the token-to-token weights are a 1-semiseparable mask over
``C B^T``.  Two device ops, named so that a trace tells them apart:

* ``_ssd_step`` (:func:`ssd_step`): one token a row.  A row's whole block
  of the pool ``[heads, P, N]`` is read, decayed, updated and written in
  place; the rows that decode go FIRST, and a row that decodes nothing
  moves no block of the pool and waits for none (it names what the last
  live row named, which the call already holds).  The block is worked a
  TILE of 128 ``(h, p)`` rows at a time (two heads where ``P`` = 64): the
  decay is a scalar, ``dt x`` and the output are the arrays as they lie,
  ``[heads P / 128, 128]``, a tile a row of lanes; ``dt x`` becomes the
  column the arithmetic wants by one transpose of its row laid on every
  sublane, and ``y = S C`` is summed over the sublanes of the tile's one
  transpose.  No lane reduce and no lane-slice broadcast a register: the
  call is bound by what the chip's DMA moves in beside what it moves out
  (docs/DESIGN.md section 29 has the table).
* ``_ssd_chunk`` (:func:`ssd_chunk`): a prefill segment, ``chunk`` tokens a
  pass.  With ``l`` the running sum of ``dt A`` inside the chunk (<= 0, and
  every difference below is formed BEFORE its exponential, so no exponent
  is positive) and ``S`` the state the chunk starts from:

      Y^T  = (dt x)^T (B C^T * exp(l_t - l_s) [s <= t]) + (S C^T) * exp(l_t)
      S'   = exp(l_end) S + ((dt x) * exp(l_end - l_s))^T B

  all of it on the matrix unit, operands in the tokens' own dtype (bfloat16
  when served, as the published kernels have it), sums, decays and the
  state in float32.  One call runs a segment's chunks in order over a head
  block of the pool that stays where it is from the first to the last.

A token that is not there (a padded position, a row that decodes nothing)
has ``dt = 0``: it leaves the state as it was, bit for bit (a dead row of
:func:`ssd_step` is not touched at all).  On the chip both ops are Pallas
calls at a state of ``[128 k heads, P, 128]`` of one group (granite) or
of ``[heads, P, 128]`` in groups of 8 or 16 heads (nemotron_h's 64 heads
in eight groups), ``P`` a power of two from 8 to 128 whose heads fill
whole tiles: the step reads a head's B and C from its group's row; the
chunk call takes a
block of heads OF ONE GROUP a grid step (16, or the group's where it has
fewer) with that group's ``B C^T``.  B and C may also be a HEAD's own
(groups = heads: a linear-attention kind, whose state is ``sum lambda^(t-s)
v_s k_s^T`` and whose read-out is ``S q``; :func:`lightning_log_decay`):
the chunk call's block of heads then brings its own B and C, one ``B C^T``
a head.  Elsewhere, and in float32 tests, plain
XLA with the same arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

# the published chunk of the scan; a shorter segment is one chunk
CHUNK = 256
# heads a grid step of the chunk kernel at most, all of one group (the step
# takes a row's every head)
_CHUNK_HEADS = 16
_LANES = 128
# tiles of the step kernel's loop unrolled together: 8 is within 1 % of
# the whole loop unrolled and lowers in a fifth of its time
_UNROLL = 8
_VMEM = 48 * 1024 * 1024


def on_kernel(state_shape, groups: int = 1, chunk: int = 1,
              backend: str = "auto", platform=None) -> tuple:
    """``(kernel?, why not)``: the Pallas calls serve, on a TPU, a state
    of ``[.., 128 k heads, P, 128]`` of one group, or of ``[.., heads, P,
    128]`` in groups of 8 or 16 heads (a head block of the chunk call is
    then one group), ``P`` of 8 .. 128 dividing the lanes and the heads in
    whole tiles of ``128 / P`` (the step works a tile of 128 ``(h, p)``
    rows at once), a segment in chunks of whole lanes."""
    platform = platform or jax.default_backend()
    if backend == "xla":
        return False, "backend xla"
    if platform != "tpu" and backend != "pallas":
        return False, f"platform {platform}"
    h, p, n = state_shape[-3:]
    # what has been through Mosaic and read on the chip: one group of 128 k
    # heads (granite), or groups of 8 or 16 heads, one head block each
    # (nemotron_h's 64 heads in 8)
    per = h // groups if h % groups == 0 else 0
    # ... or B and C a HEAD (groups = heads, a linear-attention kind's k
    # and q: the chunk call then takes a block of heads with its own B C^T
    # each), whole blocks of 8 heads
    if n != 128 or p % 8 or _LANES % p or h % (_LANES // p) or not (
            h % 128 == 0 if groups == 1 else
            h % 8 == 0 if per == 1 else per in (8, _CHUNK_HEADS)):
        return False, f"state {h} x {p} x {n}, {groups} groups"
    if chunk > 1 and chunk % 128:
        return False, f"chunk {chunk} not whole lanes of 128"
    return True, ""


def _chunk_heads(heads: int, groups: int) -> int:
    """Heads a grid step of the chunk kernel: ``_CHUNK_HEADS``, or the
    group's where it has fewer (a block never spans two groups); where B
    and C are a head's own (groups = heads) a block of heads brings its B
    and C along, so it is as many as divide the heads."""
    if groups == heads:
        return math.gcd(_CHUNK_HEADS, heads)
    return min(_CHUNK_HEADS, heads // groups)


def _dot(a, b):
    """A matrix product with float32 sums: at ``HIGHEST`` where the
    operands are float32, one pass where they are narrower."""
    return jnp.dot(a, b, preferred_element_type=F32,
                   precision=HIGHEST if a.dtype == F32 else None)


# --------------------------------------------------------------- the step

def _step_math(S, x, B, C, dt, A):
    """One token over states ``S`` ``[.., H, P, N]``: ``(y [.., H, P],
    S')``.  ``x`` ``[.., H, P]``, ``B, C`` ``[.., H, N]`` (a group's spread
    over its heads), ``dt`` ``[.., H]``, ``A`` ``[H]``; float32, on the
    vector unit."""
    a = jnp.exp(dt * A)
    S = (S * a[..., None, None]
         + (dt[..., None] * x)[..., :, None] * B[..., None, :])
    return jnp.sum(S * C[..., None, :], axis=-1), S


def _ssd_step_kernel(rows_ref, at_ref, plane_ref, n_ref, a_ref, dx_ref,
                     bc_ref, s_ref, y_ref, out_ref, *, groups: int,
                     unroll: int):
    """Grid (rows,), the ``n_ref[0]`` live rows FIRST: step ``i`` is batch
    row ``at_ref[i]``.  ``a_ref`` ``[b H]`` in scalar memory: the decay of
    batch row ``r``'s head ``h`` at ``r H + h``; ``dx_ref`` ``[1, H P /
    128, 128]``: the row's ``dt x`` as it lies, a TILE of 128 ``(h, p)`` a
    row of lanes; ``bc_ref`` ``[1, 2, G, N]``: B and C, a row a group;
    ``s_ref`` / ``out_ref`` ``[1, 1, H, P, N]``, the same block of the
    pool; ``y_ref`` as ``dx_ref``.  float32 throughout.  A step behind the
    live ones names what the last live step named, operand for operand:
    nothing comes or goes for it, its body is skipped and the block is
    left as that row left it.  Where no row is live every step names
    nobody's row, which the first passes through as it came.

    A tile's 128 rows of the state (two heads where ``P`` = 64) are
    worked together: ``dt x`` becomes a column spread over the lanes by ONE
    transpose of its row laid on every sublane, and the read-out sums
    ``S C`` over the SUBLANES of the tile's one transpose (vector adds
    and one fold), so that ``y`` leaves as a whole row of lanes."""
    del rows_ref, plane_ref
    i = pl.program_id(0)
    heads, P, N = s_ref.shape[2:]
    hp = _LANES // P                                # heads a tile

    def tile(t):
        dx = jnp.broadcast_to(dx_ref[0, pl.ds(t, 1), :],
                              (N, _LANES)).T        # [128 (h, p), N]
        SC = []
        for k in range(hp):
            h = t * hp + k
            g = h // (heads // groups)              # the head's group
            B = bc_ref[0, 0, pl.ds(g, 1), :]        # [1, N]
            C = bc_ref[0, 1, pl.ds(g, 1), :]
            S = (s_ref[0, 0, h] * a_ref[at_ref[i] * heads + h]
                 + dx[k * P:(k + 1) * P] * B)       # [P, N]
            out_ref[0, 0, h] = S
            SC.append(S * C)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            jnp.concatenate(SC, axis=0).T, axis=0, keepdims=True)

    @pl.when(i < n_ref[0])
    def _live():
        def some(q, _):
            for u in range(unroll):
                tile(q * unroll + u)
        jax.lax.fori_loop(0, heads // hp // unroll, some, None)

    @pl.when((n_ref[0] == 0) & (i == 0))
    def _through():
        out_ref[...] = s_ref[...]


def _step_operands(x, B, C, dt, A):
    """What XLA lays out for the step call from ``x`` ``[b, H, P]``, ``B,
    C`` ``[b, G, N]``, ``dt`` ``[b, H]`` and ``A`` ``[H]`` (float32): the
    decays ``[b H]``, ``dt x`` ``[b, H P / 128, 128]`` (no transpose: the
    array as it lies) and B over C ``[b, 2, G, N]``."""
    b, H, P = x.shape
    return (jnp.exp(dt * A).reshape(b * H),
            (dt[..., None] * x).reshape(b, H * P // _LANES, _LANES),
            jnp.stack([B, C], axis=1))


@functools.partial(jax.jit, static_argnames=("unroll", "interpret", "name"))
def _ssd_step_call(rows, at, plane, n, a, dx, bc, state, *, unroll=_UNROLL,
                   interpret=False, name="_ssd_step"):
    """``a`` ``[b H]``, ``dx`` ``[b, H P / 128, 128]``, ``bc`` ``[b, 2, G,
    N]``, ``state`` ``[Pl, R, H, P, N]`` aliased to the second output;
    step ``i`` works batch row ``at[i]`` on ``state[plane, rows[i]]`` where
    ``i < n`` (:func:`_blocks_of`).  Returns ``(y as dx, state')``; a row
    that is not live has no ``y`` (what memory held)."""
    b, tiles, _ = dx.shape
    G, N = bc.shape[-2:]
    H, P = state.shape[2:4]
    s_spec = pl.BlockSpec((1, 1, H, P, N),
                          lambda i, rows, at, plane, n: (plane[0], rows[i],
                                                         0, 0, 0))
    tile = pl.BlockSpec((1, tiles, _LANES),
                        lambda i, rows, at, *_: (at[i], 0, 0))
    return pl.pallas_call(
        functools.partial(_ssd_step_kernel, groups=G,
                          unroll=math.gcd(unroll, tiles)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile,
                      pl.BlockSpec((1, 2, G, N),
                                   lambda i, rows, at, *_: (at[i], 0, 0, 0)),
                      s_spec],
            out_specs=[tile, s_spec]),
        out_shape=[jax.ShapeDtypeStruct(dx.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},    # operands count the four scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name=name,
    )(rows, at, plane, n, a, dx, bc, state)


def _blocks_of(rows, live, trash: int):
    """``(rows', at, n)`` for the step kernel: its ``n`` live rows first,
    in the order they came (step ``i`` is batch row ``at[i]`` on pool row
    ``rows'[i]``), and every step behind them the last live one again
    (nobody's row where there is none), so a block comes in and goes out
    once, for a live row, and a dead row moves nothing and waits for
    nothing: a dead step BETWEEN two live ones would hold the next row's
    block back until the step itself, uncovered by any arithmetic (3.0 us
    a dead row at granite's shape; PERF.md section 6, PR 67)."""
    b = rows.shape[0]
    upto = jnp.cumsum(live, dtype=jnp.int32)        # live rows up to here
    n = upto[-1]
    step = jnp.minimum(jnp.arange(b, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    # the batch row of the k-th live one: those with fewer than k + 1 so far
    at = jnp.minimum(jnp.sum(upto[None, :] <= step[:, None], axis=1,
                             dtype=jnp.int32), b - 1)
    return (jnp.where(n > 0, rows[at], trash).astype(jnp.int32), at,
            n.reshape(1))


def _of_heads(a, heads: int):
    """``[.., G, N]`` -> ``[.., H, N]``: a group's B or C for each of its
    heads."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def ssd_step(state, plane, rows, x, B, C, dt, A, live, *,
             kernel: bool = False, interpret: bool = False,
             name: str = "_ssd_step"):
    """One token a row.  ``state`` ``[Pl, R, H, P, N]`` float32, the whole
    pool; ``plane`` an int32 scalar; ``rows`` ``[b]`` the pool row of each
    batch row, or ``None`` where row ``i`` is pool row ``i`` (a dense
    cache); ``x`` ``[b, H, P]``, ``B, C`` ``[b, G, N]``, ``dt`` ``[b, H]``
    (after its softplus), ``A`` ``[H]`` (negative); ``live`` ``[b]`` bool or
    ``None``.  Returns ``(y [b, H, P] float32, state')``, ``y`` without the
    ``D`` skip.  A dead row's state is not touched; two live rows never
    name one pool row.  The last pool row is nobody's (dead rows point
    there where the kernel needs a place).  ``name``: the Pallas call's in a
    trace (a linear-attention kind's own, ``_la_step``)."""
    R, H = state.shape[1:3]
    b = x.shape[0]
    x, B, C, dt, A = (t.astype(F32) for t in (x, B, C, dt, A))
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
        rows, at, n = _blocks_of(rows, live, trash)
        y, state = _ssd_step_call(
            rows, at, jnp.reshape(plane, (1,)).astype(jnp.int32), n,
            *_step_operands(x, B, C, dt, A), state, interpret=interpret,
            name=name)
        return jnp.where(live[:, None, None], y.reshape(x.shape), 0.0), state
    # XLA: the pool's plane is worked on where it lies, every row of it,
    # and what is small (x, B, C, dt, the outputs) moves instead
    S = jax.lax.dynamic_index_in_dim(state, plane, 0, keepdims=False)
    if trash is None:
        at, alive = jnp.arange(R), live
    else:
        at = jnp.full((R,), b, jnp.int32).at[rows].set(
            jnp.arange(b, dtype=jnp.int32)).at[trash].set(b)
        alive = at < b
    pad = lambda t: jnp.concatenate(
        [t, jnp.zeros((1,) + t.shape[1:], F32)])[at]
    y, S_new = _step_math(S, pad(x), pad(_of_heads(B, H)),
                          pad(_of_heads(C, H)), pad(dt), A)
    S = jnp.where(alive[:, None, None, None], S_new, S)
    state = jax.lax.dynamic_update_index_in_dim(state, S, plane, 0)
    return jnp.where(live[:, None, None], y[rows], 0.0), state


# -------------------------------------------------------------- the chunk

def _chunk_pass(S, dx, B, C, l):
    """One chunk over every head (the XLA form): ``(y [Q, H, P] float32,
    S')`` from ``S`` ``[H, P, N]`` float32, ``dx = dt x`` ``[Q, H, P]`` and
    ``B, C`` ``[Q, H, N]`` in the tokens' dtype, ``l`` ``[Q, H]`` the
    running sum of ``dt A``."""
    Q = dx.shape[0]
    dt_ = dx.dtype
    prec = HIGHEST if dt_ == F32 else None
    ein = functools.partial(jnp.einsum, preferred_element_type=F32,
                            precision=prec)
    lower = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]     # [t, s]
    diff = l[:, None, :] - l[None, :, :]                        # [t, s, H]
    M = (ein("thn,shn->tsh", C, B)
         * jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf)))
    y = (ein("tsh,shp->thp", M.astype(dt_), dx)
         + ein("thn,hpn->thp", C, S.astype(dt_)) * jnp.exp(l)[:, :, None])
    end = l[-1]                                                 # [H]
    w = jnp.exp(end[None, :] - l)                               # [Q, H]
    S = (S * jnp.exp(end)[:, None, None]
         + ein("shp,shn->hpn", (dx.astype(F32) * w[:, :, None]).astype(dt_),
               B))
    return y, S


def _ssd_chunk_kernel(row_ref, plane_ref, fresh_ref, dx_ref, l_ref, lt_ref,
                      le_ref, b_ref, ct_ref, s_ref, y_ref, out_ref, *,
                      heads: int, per_head: bool = False):
    """Grid (head blocks, chunks), the chunks in order.  The block of the
    pool ``[1, 1, heads, P, N]`` stays in ``out_ref`` from the first chunk
    (where it is the pool's, or zero for a segment that starts a request)
    to the last.  ``dx_ref`` ``[1, heads, P, Q]`` is ``(dt x)^T`` a head,
    ``l_ref`` ``[1, heads, Q]`` the running log decay as rows, ``lt_ref``
    ``[1, 1, Q, heads]`` the same as columns, ``le_ref`` ``[1, heads, N]``
    its last entry spread over the state's lanes (Mosaic spreads one number
    over one axis at a time), ``b_ref`` ``[1, 1, Q, N]`` and
    ``ct_ref`` ``[1, 1, N, Q]`` B and C^T of the head block's group
    (``per_head``: ``[1, heads, Q, N]`` / ``[1, heads, N, Q]``, a head's
    own); ``y_ref`` ``[1, heads, P, Q]`` the output transposed."""
    del row_ref, plane_ref

    @pl.when(pl.program_id(1) == 0)
    def _first():
        s0 = s_ref[...]
        out_ref[...] = jnp.where(fresh_ref[0] > 0, jnp.zeros_like(s0), s0)

    B, CT = b_ref[0, 0], ct_ref[0, 0]
    dt_ = B.dtype
    Q = B.shape[0]
    if not per_head:
        G = _dot(B, CT)                             # [s, t] = B_s . C_t
    upper = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))   # s <= t
    for h in range(heads):
        if per_head:
            B, CT = b_ref[0, h], ct_ref[0, h]
            G = _dot(B, CT)
        lrow = l_ref[0, h:h + 1, :]                 # [1, Q] over t (or s)
        lcol = lt_ref[0, 0, :, h:h + 1]             # [Q, 1] over s
        MT = (G * jnp.exp(jnp.where(upper, lrow - lcol, -jnp.inf))
              ).astype(dt_)
        dx = dx_ref[0, h]                           # [P, Q]
        S = out_ref[0, 0, h]                        # [P, N]
        y = _dot(dx, MT) + _dot(S.astype(dt_), CT) * jnp.exp(lrow)
        y_ref[0, h] = y.astype(y_ref.dtype)
        end = lrow[:, Q - 1:Q]                      # [1, 1]
        w = jnp.exp(end - lrow)
        out_ref[0, 0, h] = S * jnp.exp(le_ref[0, h:h + 1, :]) + _dot(
            (dx.astype(F32) * w).astype(dt_), B)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _ssd_chunk_call(row, plane, fresh, dxT, l, lT, lE, Bm, CT, state, *,
                    interpret=False, name="_ssd_chunk"):
    """``dxT`` ``[n, H, P, Q]``, ``l`` ``[n, H, Q]``, ``lT`` ``[n, H / hb,
    Q, hb]``, ``lE`` ``[n, H, N]``, ``Bm`` ``[n, G, Q, N]``, ``CT`` ``[n,
    G, N, Q]``; ``state`` aliased to the second output.  Head block ``j``
    lies in group ``j hb // (H / G)``."""
    n, H, P, Q = dxT.shape
    G, N = Bm.shape[1], Bm.shape[-1]
    hb = lT.shape[-1]
    per_head = G == H               # B and C a head: a block brings its own
    per = 1 if per_head else (H // G) // hb     # head blocks a group
    gb = hb if per_head else 1      # rows of B and C a head block
    s_spec = pl.BlockSpec(
        (1, 1, hb, P, N),
        lambda j, i, row, plane, fresh: (plane[0], row[0], j, 0, 0))
    tile = pl.BlockSpec((1, hb, P, Q), lambda j, i, *_: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, heads=hb,
                          **({"per_head": True} if per_head else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(H // hb, n),
            in_specs=[tile,
                      pl.BlockSpec((1, hb, Q), lambda j, i, *_: (i, j, 0)),
                      pl.BlockSpec((1, 1, Q, hb),
                                   lambda j, i, *_: (i, j, 0, 0)),
                      pl.BlockSpec((1, hb, N), lambda j, i, *_: (i, j, 0)),
                      pl.BlockSpec((1, gb, Q, N),
                                   lambda j, i, *_: (i, j // per, 0, 0)),
                      pl.BlockSpec((1, gb, N, Q),
                                   lambda j, i, *_: (i, j // per, 0, 0)),
                      s_spec],
            out_specs=[tile, s_spec]),
        out_shape=[jax.ShapeDtypeStruct(dxT.shape, dxT.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={9: 1},    # operands count the three scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name=name,
    )(row, plane, fresh, dxT, l, lT, lE, Bm, CT, state)


def ssd_chunk(state, plane, row, fresh, x, B, C, dt, A, *,
              chunk: int = CHUNK, kernel: bool = False,
              interpret: bool = False, name: str = "_ssd_chunk"):
    """One segment of ``s`` tokens of one request, in order, starting from
    ``state[plane, row]`` (from zero where ``fresh``: the segment starts a
    request) and leaving its final state there.  ``x`` ``[s, H, P]`` and
    ``B, C`` ``[s, G, N]`` in the tokens' dtype (the products' operands),
    ``dt`` ``[s, H]`` float32 after its softplus and 0 at a token that is
    not there, ``A`` ``[H]`` (negative); ``row`` and ``plane`` int32
    scalars (``row`` inside the pool).  Returns ``(y [s, H, P] in ``x``'s
    dtype, state')``, ``y`` without the ``D`` skip.  ``s`` is padded to
    whole chunks with tokens that are not there.  ``name``: the Pallas
    call's in a trace (a linear-attention kind's own, ``_la_chunk``)."""
    s, H, P = x.shape
    chunk = min(chunk, s)           # a short segment is one chunk
    pad = -s % chunk
    dt = dt.astype(F32)
    dx = (dt[..., None] * x.astype(F32)).astype(x.dtype)
    dA = dt * A.astype(F32)
    if pad:
        z = lambda a: jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
        dx, B, C, dA = z(dx), z(B), z(C), z(dA)
    n = (s + pad) // chunk
    cut = lambda a: a.reshape((n, chunk) + a.shape[1:])
    dx, B, C = cut(dx), cut(B), cut(C)
    l = jnp.cumsum(cut(dA), axis=1)                         # [n, Q, H]
    if kernel:
        hb = _chunk_heads(H, B.shape[-2])
        one = lambda a: jnp.reshape(a, (1,)).astype(jnp.int32)
        yT, state = _ssd_chunk_call(
            one(row), one(plane), one(fresh),
            jnp.transpose(dx, (0, 2, 3, 1)),                # [n, H, P, Q]
            jnp.swapaxes(l, 1, 2),
            jnp.swapaxes(l.reshape(n, chunk, H // hb, hb), 1, 2),
            jnp.broadcast_to(l[:, -1, :, None], (n, H, B.shape[-1])),
            jnp.swapaxes(B, 1, 2),                          # [n, G, Q, N]
            jnp.transpose(C, (0, 2, 3, 1)), state,          # [n, G, N, Q]
            interpret=interpret, name=name)
        y = jnp.transpose(yT, (0, 3, 1, 2))                 # [n, Q, H, P]
    else:
        S = jnp.where(fresh, 0.0, state[plane, row])
        outs = []
        for i in range(n):
            y_i, S = _chunk_pass(S, dx[i], _of_heads(B[i], H),
                                 _of_heads(C[i], H), l[i])
            outs.append(y_i.astype(x.dtype))
        y = jnp.stack(outs)
        state = state.at[plane, row].set(S)
    return y.reshape((n * chunk, H, P))[:s], state


def lightning_log_decay(heads: int):
    """``log lambda_h`` ``[heads]`` float32 of a Lightning linear-attention
    kind (``models.decoder._lightning_mixer``), the recurrence above with
    ``dt`` = 1 and this for ``A``: the Lightning Attention slopes,
    ``lambda_h = exp(-2 ** (-8 (h + 1) / heads))``, the same in every
    layer."""
    return -jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads)


def ssd_recurrence(S, x, B, C, dt, A):
    """The recurrence token by token (``lax.scan``), float32: what both
    forms are held to in tests, and nothing the serving path runs.  ``S``
    ``[H, P, N]``; the rest as :func:`ssd_chunk`.  Returns ``(y [s, H, P],
    S')``."""
    H = S.shape[0]
    x, B, C, dt, A = (t.astype(F32) for t in (x, B, C, dt, A))

    def body(S, t):
        y, S = _step_math(S, *t, A)
        return S, y
    S, y = jax.lax.scan(body, S, (x, _of_heads(B, H), _of_heads(C, H), dt))
    return y, S
