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
  ever written to HBM; row counts that are no multiple of the tile (the
  last tile is partial: what it reads past ``m`` belongs to no group, a
  row's output depends on no other row, and its store stops at ``m``; no
  padded copy of the rows is made); the tiles and the VMEM the call
  declares (next paragraph); and a right-hand side that is one layer OF a
  stack (``stacked.LayerOf``):
  the kernel takes the whole ``[L, E, k, n]`` stack and the layer's index
  as a scalar, because a layer sliced out of the stack for a custom call
  is a copy of it in HBM (128 MiB a projection a layer call at
  olmoe-1b-7b's width: 27 % of the device's busy time before this; my
  chip run, PR 28).
  **The tiles** (``tiling``, a function of the call's shapes alone).  The
  grid is ``(column tiles, visits, contraction tiles)`` and the right-hand
  block of a step is ``(layer, expert of the visit, k_i, n_i)``: with one
  contraction tile, consecutive visits of one expert name the same block
  and the pipeline fetches it once; with more, every visit streams the
  expert's ``[k, tn]`` slice again.  So ``tk`` spans ``k`` wherever the
  VMEM the call then needs (``vmem_bytes``: two buffers of the right-hand
  tile, an int8 tile's widened copy, two row tiles, two output blocks, the
  float32 accumulator and the store's temporaries) and declares
  (``vmem_limit``: a quarter more, ``CompilerParams.vmem_limit_bytes``)
  stays inside a quarter of the chip's 128 MiB: 3 to 7 MiB a tile at the
  benchmark's six expert configurations, all of which read once; mixtral's
  ``[14336, 4096]`` still splits, four ways.  Until PR 63 the tile was
  held to 2 MiB, olmoe's int8 ``[2048, 1024]`` to the byte, and the five
  bf16 configurations cut ``k`` in 2 to 4: a slab's gate / up call took
  1.2-2.2 x what it takes now (granite's, 182 rows a group: 1,292 -> 570
  us), a decode call, one visit an expert either way, 1.01-1.09 x for its
  grid steps (my chip run, PR 63, ``tools/gmm_table.py``; PERF.md section
  6).  The row tile (``row_tile``) follows the rows a group is expected
  to hold, ``m`` over the experts routed over.  ``/stats.moe.gmm`` lists
  every traced call's shape, tiles and limit (``noting_calls``).
* elsewhere (CPU tests, shapes the kernel's tiling does not cover)
  ``jax.lax.ragged_dot`` over the same sorted rows, the int8 scale gathered
  to the rows.

int4 stacks have no kernel form: the nibbles are unpacked and a layer's
whole expert stack is dequantized to the rows' dtype (a full-width copy in
HBM, 4 x the packed bytes, a layer call) and takes the bf16 form.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant import QuantizedArray, QuantizedArray4
from .stacked import LayerOf

PATH_KERNEL = "pallas_gmm"
PATH_XLA = "ragged_dot"

_LANES = 128
# a v5e core's VMEM; a call declares what its tiles need of it
# (``vmem_bytes``) and the rule below keeps that under a quarter, so a
# call fits beside whatever XLA keeps there around it.  Mosaic gives a
# call that declares nothing 16 MiB, and no call declares less.
_VMEM_BYTES = 128 << 20
_VMEM_BUDGET = _VMEM_BYTES // 4
_VMEM_DEFAULT = 16 << 20
# rows a 128 x 128 matrix unit takes in one pass: a group that fills
# them takes a tile of two passes
_UNIT_ROWS = 128

# while a program is traced: the table its calls' shapes go to
_noting = threading.local()


def row_tile(m: int, groups: int) -> int:
    """Rows of one tile, from the rows a group can be expected to hold
    (``m`` rows routed over ``groups`` experts).  Every touched expert is
    visited with at least one whole tile, so few rows spread over many
    experts (a decode step: 256 rows over 64 experts) take a small one.
    With an expert's matrix read once a group the call is bound by its
    bytes, the matrices' and the rows', and its time moves little with
    the tile: over 32 / 64 / 128 / 256 rows a slab call of 14 to 68 rows a
    group reads within 6 % from 64 on (128 the least by 1-4 %, 32 the
    most by up to 20 % where the contraction is short), a decode call
    within 3 % up to 128 (the table of ``tools/gmm_table.py``, PERF.md
    section 6, PR 63; PR 28's "within 10 %" was the same finding at
    olmoe's shapes).  Groups that fill the matrix unit's rows (granite's
    slab: 182 a group) take 256, the least there by 3-7 %: fewer visits,
    each of whole passes."""
    if m >= _UNIT_ROWS * groups:
        return 2 * _UNIT_ROWS
    if m >= 2048:
        return 64
    return 32 if m >= 32 else 16


def _divisor_tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is <= cap; a
    ``dim`` that is not whole lanes is one tile (a block may span a whole
    dimension whatever its size: nemotron_h's expert width 1,856 = 14.5 x
    128, the ``n`` of its up projection)."""
    if dim % _LANES:
        return dim
    best = _LANES
    for t in range(_LANES, min(dim, cap) + 1, _LANES):
        if dim % t == 0:
            best = t
    return best


def vmem_bytes(tiles: tuple, rhs_itemsize: int, lhs_itemsize: int) -> int:
    """What a call's tiles hold in VMEM: the pipeline's two buffers of the
    right-hand tile and, where it is narrower than the rows, its widened
    copy; two row tiles; two output blocks; and in float32 the
    accumulator, the product added to it, and the store's masked block
    and mask."""
    tm, tk, tn = tiles
    rhs = tk * tn * (2 * rhs_itemsize
                     + (lhs_itemsize if rhs_itemsize < lhs_itemsize else 0))
    return (rhs + 2 * tm * tk * lhs_itemsize + 2 * tm * tn * lhs_itemsize
            + 4 * tm * tn * 4)


def vmem_limit(tiles: tuple, rhs_itemsize: int, lhs_itemsize: int) -> int:
    """The limit a call declares: its tiles and a quarter more for what
    Mosaic keeps beside them, and never less than an undeclared call
    gets."""
    need = vmem_bytes(tiles, rhs_itemsize, lhs_itemsize)
    return max(_VMEM_DEFAULT, need + need // 4)


def tiling(m: int, k: int, n: int, rhs_itemsize: int, groups: int,
           lhs_itemsize: int = 2) -> tuple:
    """``(tm, tk, tn)`` for a kernel call: ``tn`` up to 1,024 columns, and
    ``tk`` the whole contraction wherever the call's limit
    (``vmem_limit``) then stays inside ``_VMEM_BUDGET``: an expert's
    ``[k, tn]`` slice is then read once a group, not once a row tile.
    A ``k`` or ``n`` that is not whole lanes is one tile that spans it
    (``route_grouped_matmul`` admits it only where that fits).
    Where it does not (mixtral's ``[14336, 4096]``) ``tk`` is the largest
    divisor of ``k`` that does; no other order of the grid reads a matrix
    once without a float32 ``[m, tn]`` of partial sums."""
    tm = row_tile(m, groups)
    tn = _divisor_tile(n, 1024)
    if k % _LANES:      # not whole lanes: the whole contraction, one tile
        return tm, k, tn
    tk = _LANES
    for t in range(_LANES, k + 1, _LANES):
        if k % t == 0 and vmem_limit((tm, t, tn), rhs_itemsize,
                                     lhs_itemsize) <= _VMEM_BUDGET:
            tk = t
    return tm, tk, tn


def call_shape(m: int, k: int, n: int, rhs_itemsize: int, groups: int,
               lhs_itemsize: int = 2) -> dict:
    """One line of ``/stats.moe.gmm``: a call's shape, the tiles the rule
    gives it, and what they cost."""
    tiles = tiling(m, k, n, rhs_itemsize, groups, lhs_itemsize)
    return {"m": m, "k": k, "n": n, "tiles": list(tiles),
            "tiles_k": k // tiles[1],
            "rhs_tile_bytes": tiles[1] * tiles[2] * rhs_itemsize,
            "vmem_limit_bytes": vmem_limit(tiles, rhs_itemsize,
                                           lhs_itemsize)}


@contextlib.contextmanager
def noting_calls(table: dict):
    """While a program is traced inside (on this thread), every call whose
    shape the kernel covers leaves its ``call_shape`` in ``table``,
    whatever path it takes: an engine serves the values under
    ``/stats.moe.gmm``."""
    before = getattr(_noting, "table", None)
    _noting.table = table
    try:
        yield
    finally:
        _noting.table = before


# the most rows a tile holds (``row_tile``): what a width off the lanes is
# admitted at, since the route is asked before the rows are known
_ROWS_MOST = 2 * _UNIT_ROWS


def route_grouped_matmul(platform: str, k: int, n: int,
                         rhs_itemsize: int = 2) -> str:
    """The kernel on a TPU where both matrix dimensions fill the lanes, or
    where one that does not (wider than the lanes, and whole sublanes of 16
    all the same) fits the call's VMEM as ONE tile that spans it, at the
    largest row tile:
    nemotron_h's ``[2688, 1856]`` and ``[1856, 2688]`` in bfloat16.
    ``ragged_dot`` otherwise: on a ``LayerOf`` stack that slices the layer
    out first, a copy of it in HBM a call."""
    if platform != "tpu":
        return PATH_XLA
    if k % _LANES == 0 and n % _LANES == 0:
        return PATH_KERNEL
    if k % 16 or n % 16 or min(k, n) < _LANES:
        return PATH_XLA
    # the tile ``tiling`` would give it there: whole where off the lanes,
    # the smallest the rule can fall back to where not
    tiles = (_ROWS_MOST, k if k % _LANES else _LANES, _divisor_tile(n, 1024))
    if vmem_limit(tiles, rhs_itemsize, 2) <= _VMEM_BUDGET:
        return PATH_KERNEL
    return PATH_XLA


def _gmm_kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, layer_ref,
                lhs_ref, rhs_ref, *refs, tm, tn, tiles_k, quantized,
                transposed=False):
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
    # (a transposed right-hand tile is [tn, tk]: both contract their last)
    acc_ref[...] += jax.lax.dot_general(
        lhs, rhs_ref[...].astype(lhs.dtype),
        (((1,), (1 if transposed else 0,)), ((), ())),
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


@functools.partial(jax.jit,
                   static_argnames=("tiles", "interpret", "transposed"))
def _moe_gmm_call(lhs, rhs, scale, group_sizes, layer, *, tiles,
                  interpret=False, transposed=False):
    """The Pallas call.  ``lhs`` [m, k] with ``m % tm == 0``; ``rhs``
    [L, E, k, n] (int8 with ``scale`` [L, E or 1, 1, n] float32, or the
    rows' dtype with ``scale`` None), or ``[L, E, n, k]`` where
    ``transposed``; ``layer`` [1] int32 picks the layer."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    tm, tk, tn = tiles
    m, k = lhs.shape
    n = rhs.shape[2 if transposed else 3]
    tiles_k, tiles_n = k // tk, n // tn
    (offsets, group_ids, m_tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes, m=-(-m // tm) * tm, tm=tm,
        start_group=jnp.int32(0),
        num_nonzero_groups=rhs.shape[1], visit_empty_groups=False)
    quantized = scale is not None

    in_specs = [
        pl.BlockSpec((tm, tk), lambda n_i, v, k_i, off, gid, mid, lay:
                     (mid[v], k_i)),
        pl.BlockSpec((None, None, tn, tk),
                     lambda n_i, v, k_i, off, gid, mid, lay:
                     (lay[0], gid[v], n_i, k_i))
        if transposed else
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
                          quantized=quantized, transposed=transposed),
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
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(tiles, rhs.dtype.itemsize,
                                        lhs.dtype.itemsize)),
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
                   routed: Optional[int] = None, backend: str = "auto",
                   interpret: bool = False,
                   transposed: bool = False) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[g(r)]`` for rows sorted by group.

    ``lhs`` [m, k]; ``rhs`` [E, k, n] as an array of the rows' dtype, a
    :class:`QuantizedArray` (int8, scales ``[E or 1, 1, n]``) or a
    :class:`QuantizedArray4` (dequantized whole, see the module), or a
    :class:`LayerOf` a stack of any of those;
    ``group_sizes`` [E] int32 with ``sum <= m``.  Returns [m, n] in the
    rows' dtype, accumulated in float32.  ``routed``: the groups the ``m``
    rows were routed over, where ``rhs`` holds a share of them and the
    other groups' rows lie past the last group (default: ``E``); the row
    tile follows ``m / routed``.  ``backend``: "auto" (the rule above),
    "xla", or "pallas" (tests: the kernel in interpret mode).
    ``transposed``: ``rhs`` holds each group's matrix as ``[n, k]``, and
    the product is ``lhs[r] @ rhs[g(r)].T``: how a stack whose ``n`` is
    not whole lanes is STORED (nemotron_h's up projection, ``[1856,
    2688]`` a group).  The chip lays an array's lane-filling dimension
    minor, so ``[.., 2688, 1856]`` sits in HBM transposed and a custom
    call that wants it row-major gets a copy of the whole stack first,
    2.6 GB a call at four ``E`` blocks (libtpu's analysis, PR 66); stored
    ``[.., 1856, 2688]`` it is read where it lies, a ``[tn, tk]`` tile
    contracted on its last dimension."""
    m, k = lhs.shape
    n = rhs.shape[1 if transposed else 2]
    if transposed and isinstance(getattr(rhs, "stack", rhs),
                                 (QuantizedArray, QuantizedArray4)):
        raise ValueError("a transposed right-hand side is a plain array "
                         "of the rows' dtype")
    routed = routed or rhs.shape[0]
    item = (1 if isinstance(getattr(rhs, "stack", rhs), QuantizedArray)
            else lhs.dtype.itemsize)
    path = (PATH_XLA if backend == "xla" else PATH_KERNEL
            if backend == "pallas"
            else route_grouped_matmul(jax.default_backend(), k, n, item))
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
    shape = (m, k, n, rhs.dtype.itemsize, routed, lhs.dtype.itemsize)
    table = getattr(_noting, "table", None)
    if table is not None and route_grouped_matmul(
            "tpu", k, n, item) == PATH_KERNEL:
        table[shape] = call_shape(*shape)
    if path == PATH_XLA:
        return _ragged(lhs, jnp.swapaxes(rhs, 1, 2) if transposed else rhs,
                       scale, group_sizes)
    if rhs.ndim == 3:                   # a stack of one layer
        rhs = rhs[None]
        scale = None if scale is None else scale[None]
    return _moe_gmm_call(lhs, rhs, scale, group_sizes.astype(jnp.int32),
                         layer, tiles=tiling(*shape), interpret=interpret,
                         transposed=transposed)
