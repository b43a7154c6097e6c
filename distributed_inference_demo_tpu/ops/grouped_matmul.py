"""Grouped matmul over ragged groups of rows: the experts' projections.

``grouped_matmul(lhs, rhs, group_sizes)``: rows ``[m, k]`` sorted by
group (expert), one ``[k, n]`` matrix a group, ``out[r] = lhs[r] @
rhs[group of r]``.  Shapes are static, the group sizes are data: an expert
with no rows costs nothing, one with all of them gets all of them, and no
row is ever dropped.  Rows past ``sum(group_sizes)`` belong to no group and
their output is unspecified (the caller masks them; only a rank that holds
a slice of the experts has any).

Two forms of the same arithmetic, picked like the paged attention paths
(``route_grouped_matmul``, a pure function of what the trace can see):

* on the chip a Pallas call, ``_moe_gmm_call`` (its custom call shows in a
  device trace as ``moe_gmm.<n>``).  It started from
  ``jax.experimental.pallas.ops.tpu.megablox.gmm`` and keeps its grid and
  its group metadata (``make_group_metadata`` is imported, not copied):
  one grid step a (row tile, group) visit, consecutive visits of a row
  tile accumulate into the same output block under a row mask.  What
  megablox refuses is what this adds: an **int8 right-hand side**, whose
  ``[tk, tn]`` tile is cast to the rows' dtype in VMEM and whose float32
  per-channel scale (of the visit's expert) multiplies the float32
  accumulator once, at the store, so no bf16 copy of an expert stack is
  ever written to HBM; row counts that are no multiple of the tile (padded
  here); a contraction tile that spans ``k`` where it fits, so an
  expert's matrix is read once however many row tiles its group covers;
  and a right-hand side that is one layer OF a stack (``stacked.LayerOf``):
  the kernel takes the whole ``[L, E, k, n]`` stack and the layer's index
  as a scalar, because a layer sliced out of the stack for a custom call
  is a copy of it in HBM (128 MiB a projection a layer call at
  olmoe-1b-7b's width: 27 % of the device's busy time before this; my
  chip run, PR 28).
* elsewhere (CPU tests, shapes the kernel's tiling does not cover)
  ``jax.lax.ragged_dot`` over the same sorted rows, the int8 scale gathered
  to the rows.

int4 stacks have no kernel form: the nibbles are unpacked and a layer's
whole expert stack is dequantized to the rows' dtype (a full-width copy in
HBM, 4 x the packed bytes, a layer call) and takes the bf16 form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant import QuantizedArray, QuantizedArray4
from .stacked import LayerOf

PATH_KERNEL = "pallas_gmm"
PATH_XLA = "ragged_dot"

_LANES = 128
# bytes of one right-hand tile in VMEM (double-buffered by the pipeline,
# and an int8 tile is widened once more for the MXU)
_RHS_TILE_BYTES = 2 << 20


def row_tile(m: int) -> int:
    """Rows of one tile.  Every touched expert is visited with at least
    one whole tile, so few rows spread over many experts (a decode step:
    256 rows over 64 experts) take a small one.  On the v5e the call's
    time barely moves with it (16 to 128 rows at 256, 64 to 512 at 4,096:
    within 10 %, 64 the best at 4,096; my chip run, PR 28): the experts'
    matrices bound it."""
    if m >= 2048:
        return 64
    return 32 if m >= 32 else 16


def _divisor_tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is <= cap."""
    best = _LANES
    for t in range(_LANES, min(dim, cap) + 1, _LANES):
        if dim % t == 0:
            best = t
    return best


def tiling(m: int, k: int, n: int, rhs_itemsize: int) -> tuple:
    """``(tm, tk, tn)`` for a kernel call: ``tn`` up to 1,024 columns, and
    ``tk`` the whole contraction where a ``[tk, tn]`` tile fits
    ``_RHS_TILE_BYTES`` (an expert's matrix is then read once, not once a
    row tile)."""
    tn = _divisor_tile(n, 1024)
    tk = _divisor_tile(k, max(_LANES, _RHS_TILE_BYTES // (tn * rhs_itemsize)))
    return row_tile(m), tk, tn


def route_grouped_matmul(platform: str, k: int, n: int) -> str:
    """The kernel on a TPU where both matrix dimensions fill the lanes,
    ``ragged_dot`` otherwise."""
    if platform == "tpu" and k % _LANES == 0 and n % _LANES == 0:
        return PATH_KERNEL
    return PATH_XLA


def _gmm_kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, layer_ref,
                lhs_ref, rhs_ref, *refs, tm, tn, tiles_k, quantized):
    del layer_ref                       # the index maps read it
    if quantized:
        scale_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lhs = lhs_ref[...]
    # an int8 tile is widened here, in VMEM: HBM holds the integers only
    acc_ref[...] += jax.lax.dot_general(
        lhs, rhs_ref[...].astype(lhs.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        group = group_ids_ref[visit]
        rows = (m_tile_ids_ref[visit] * tm
                + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0))
        mine = (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])
        acc = acc_ref[...]
        if quantized:
            acc = acc * scale_ref[...]            # [1, tn] of this expert
        out_ref[...] = jnp.where(
            mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _moe_gmm_call(lhs, rhs, scale, group_sizes, layer, *, tiles,
                  interpret=False):
    """The Pallas call.  ``lhs`` [m, k] with ``m % tm == 0``; ``rhs``
    [L, E, k, n] (int8 with ``scale`` [L, E or 1, 1, n] float32, or the
    rows' dtype with ``scale`` None); ``layer`` [1] int32 picks the
    layer."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    tm, tk, tn = tiles
    m, k = lhs.shape
    n = rhs.shape[3]
    tiles_k, tiles_n = k // tk, n // tn
    (offsets, group_ids, m_tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=rhs.shape[1], visit_empty_groups=False)
    quantized = scale is not None

    in_specs = [
        pl.BlockSpec((tm, tk), lambda n_i, v, k_i, off, gid, mid, lay:
                     (mid[v], k_i)),
        pl.BlockSpec((None, None, tk, tn),
                     lambda n_i, v, k_i, off, gid, mid, lay:
                     (lay[0], gid[v], k_i, n_i)),
    ]
    operands = [lhs, rhs]
    if quantized:
        per_expert = scale.shape[1] > 1
        in_specs.append(pl.BlockSpec(
            (None, None, 1, tn), lambda n_i, v, k_i, off, gid, mid, lay:
            (lay[0], gid[v] if per_expert else 0, 0, n_i)))
        operands.append(scale)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          quantized=quantized),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, off, gid, mid, lay:
                (mid[v], n_i)),
            grid=(tiles_n, visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, group_ids, m_tile_ids, layer, *operands)


def _ragged(lhs, rhs, scale, group_sizes):
    """The XLA form: ``ragged_dot`` with float32 accumulation; an int8
    stack's scale (of each row's expert) multiplies the output, as in the
    kernel."""
    out = jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes,
                             preferred_element_type=jnp.float32)
    if scale is not None:
        if scale.shape[0] > 1:
            expert = jnp.repeat(jnp.arange(rhs.shape[0]), group_sizes,
                                total_repeat_length=lhs.shape[0])
            out = out * scale[expert, 0]
        else:
            out = out * scale[0]
    return out.astype(lhs.dtype)


def grouped_matmul(lhs: jax.Array, rhs, group_sizes: jax.Array, *,
                   backend: str = "auto", interpret: bool = False
                   ) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[g(r)]`` for rows sorted by group.

    ``lhs`` [m, k]; ``rhs`` [E, k, n] as an array of the rows' dtype, a
    :class:`QuantizedArray` (int8, scales ``[E or 1, 1, n]``) or a
    :class:`QuantizedArray4` (dequantized whole, see the module), or a
    :class:`LayerOf` a stack of any of those;
    ``group_sizes`` [E] int32 with ``sum <= m``.  Returns [m, n] in the
    rows' dtype, accumulated in float32.  ``backend``: "auto" (the rule
    above), "xla", or "pallas" (tests: the kernel in interpret mode)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    path = (PATH_XLA if backend == "xla" else PATH_KERNEL
            if backend == "pallas"
            else route_grouped_matmul(jax.default_backend(), k, n))
    layer = jnp.zeros((1,), jnp.int32)
    if isinstance(rhs, LayerOf):
        if path == PATH_XLA or isinstance(rhs.stack, QuantizedArray4):
            rhs = rhs.sliced()
        else:
            rhs, layer = rhs.stack, rhs.layer.astype(jnp.int32).reshape(1)
    if isinstance(rhs, QuantizedArray4):
        rhs = rhs.dequantize(lhs.dtype)
    scale = None
    if isinstance(rhs, QuantizedArray):
        rhs, scale = rhs.q, rhs.scale
    if path == PATH_XLA:
        return _ragged(lhs, rhs, scale, group_sizes)
    if rhs.ndim == 3:                   # a stack of one layer
        rhs = rhs[None]
        scale = None if scale is None else scale[None]
    tiles = tiling(m, k, n, rhs.dtype.itemsize)
    pad = -m % tiles[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _moe_gmm_call(lhs, rhs, scale, group_sizes.astype(jnp.int32),
                        layer, tiles=tiles, interpret=interpret)
    return out[:m] if pad else out
