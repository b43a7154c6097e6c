"""Weight-only int8 quantization.

The reference ships separate int8 ONNX exports per model
(``data/Data.kt:19-33`` ``-int8`` variants; ModelCard ``quantization_option``,
``server.py:831``).  TPU-native version: weights live in HBM as int8 with a
float32 per-output-channel scale (half the HBM bytes and bandwidth of bf16 —
decode is bandwidth-bound, so this is a throughput feature, not just a memory
one), and are dequantized on the fly inside the matmul — XLA fuses the
``convert + multiply`` into the MXU feed, so there is no materialized bf16
copy.

``QuantizedArray`` is a pytree whose leaves both carry the stacked-layer
leading axis, so pipeline-stage slicing (``base.slice_stage``) works on
quantized params unchanged.
"""

import os
from dataclasses import dataclass
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.tree_util.register_dataclass,
         data_fields=["q", "scale"], meta_fields=[])
@dataclass
class QuantizedArray:
    """int8 values + float32 scale broadcastable over the last axis."""

    q: jax.Array      # int8, original shape
    scale: jax.Array  # float32, shape = (*1s, last_dim)

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def quantize_array(w: jax.Array) -> QuantizedArray:
    """Symmetric per-output-channel (last axis) int8 quantization.

    The absmax is taken over the INPUT axis (-2) alone, so every leading
    axis keeps scales of its own: the layer stack (required for lax.scan
    over layers and for stage slicing) and, in an expert stack
    ``[L, E, in, out]``, each expert (scale ``[L, E, 1, out]``: the
    grouped matmul applies the scale of a row's expert to its output).
    """
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QuantizedArray(q=q, scale=scale)


@partial(jax.tree_util.register_dataclass,
         data_fields=["q", "scale"], meta_fields=["group"])
@dataclass
class QuantizedArray4:
    """Packed int4 values + group-wise float32 scales.

    Half the HBM bytes of int8 again: decode streams every weight byte
    once per step, so at the bandwidth-bound batch sizes int4 is the
    throughput configuration above int8.  Two int4 values pack into one
    uint8 NIBBLE-wise along the INPUT axis (axis -2) — explicit packing,
    not jnp.int4, so the storage halving holds on every backend.  The
    15-level grid needs finer scale granularity than int8's per-output-
    channel: scales are per ``group`` input positions per output channel
    (GPTQ-style group-wise), costing 4/group extra bytes per weight.

    Layout: ``q``: uint8 ``(..., in/2, out)`` (low nibble = even input
    index, high = odd); ``scale``: f32 ``(..., in/group, 1, out)``.
    Leading axes (layer stack, experts) ride along untouched, so
    ``base.slice_stage`` works unchanged — like :class:`QuantizedArray`.
    """

    q: jax.Array
    scale: jax.Array
    group: int

    @property
    def shape(self):
        return (*self.q.shape[:-2], self.q.shape[-2] * 2,
                self.q.shape[-1])

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        lo = (self.q & 0xF).astype(jnp.int8) - 8
        hi = (self.q >> 4).astype(jnp.int8) - 8
        v = jnp.stack([lo, hi], axis=-2)          # (..., in/2, 2, out)
        *lead, half, _, out = v.shape
        full = half * 2
        v = v.reshape(*lead, full, out).astype(jnp.float32)
        v = v.reshape(*lead, full // self.group, self.group, out)
        v = v * self.scale                        # (..., in/g, 1, out)
        return v.reshape(*lead, full, out).astype(dtype)


DEFAULT_INT4_GROUP = 64


def int4_group_for(inner: int) -> int:
    """The group size actually used for an input dim — ONE owner shared
    with the layer-chunked init (which rebuilds the QuantizedArray4
    wrapper outside the jitted quantize and must agree on the group)."""
    return min(DEFAULT_INT4_GROUP, inner)


def quantize_array4(w: jax.Array, group: int = None) -> QuantizedArray4:
    """Symmetric group-wise int4 quantization along the input axis
    (axis -2).  ``group`` defaults per :func:`int4_group_for`; the
    input size must be even (every decoder weight here is)."""
    wf = w.astype(jnp.float32)
    *lead, inner, out = wf.shape
    if inner % 2:
        raise ValueError(f"int4 packing needs an even input dim, got "
                         f"{inner}")
    group = int4_group_for(inner) if group is None else min(group, inner)
    if inner % group:
        raise ValueError(f"group={group} does not divide input dim "
                         f"{inner}")
    gw = wf.reshape(*lead, inner // group, group, out)
    absmax = jnp.max(jnp.abs(gw), axis=-2, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(gw / scale), -8, 7).astype(jnp.int8)
    q = q.reshape(*lead, inner, out)
    pairs = q.reshape(*lead, inner // 2, 2, out) + 8   # nibbles unsigned
    packed = (pairs[..., 0, :] | (pairs[..., 1, :] << 4)).astype(jnp.uint8)
    return QuantizedArray4(q=packed, scale=scale, group=group)


AnyQuantized = (QuantizedArray, QuantizedArray4)

# Weight keys worth quantizing: the large matmul operands.  Norm scales,
# biases and router gates stay in the model dtype (tiny, precision-critical).
_QUANTIZABLE = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_layer_params(layers: dict, mode: str = "int8") -> dict:
    quant = quantize_array4 if mode == "int4" else quantize_array
    return {k: (quant(v)
                if k in _QUANTIZABLE and not isinstance(v, AnyQuantized)
                else v)
            for k, v in layers.items()}


def maybe_quantize(params, cfg):
    """Apply the config's quantization mode to a full StageParams tree
    (no-op for "none").  The one shared site for the int8/int4 rewrap
    used by loader / checkpoint / tests."""
    if cfg.quantization not in ("int8", "int4"):
        return params
    import dataclasses
    return dataclasses.replace(
        params,
        layers=quantize_layer_params(params.layers, cfg.quantization),
        lead=(None if params.lead is None else
              quantize_layer_params(params.lead, cfg.quantization)))


# ---------------------------------------------------------------------------
# Quantized KV pages (docs/DESIGN.md §17)
#
# The page-pool twin of the weight rails above: K/V pages stored int8 or
# packed int4 with per-(token, kv-head) float32 scales riding alongside
# the block table.  Granularity is per-token over the head_dim axis —
# NOT the weights' per-output-channel — because a page is written once
# per token at insert time and never revisited: the token's own absmax
# is the only statistic available at write time, and it keeps the scale
# sidecar a trailing-singleton leaf so one sharding spec / one scatter
# index serves data and scales alike.

KV_DTYPES = ("bf16", "int8", "int4")


def resolve_kv_dtype(kv_dtype: Optional[str] = None) -> str:
    """``kv_dtype`` arg over ``DWT_KV_DTYPE`` env over "bf16" — the one
    owner of KV-width resolution, called at every pool-creation site so
    the env knob reaches engines that never grew an explicit kwarg."""
    dt = kv_dtype or os.environ.get("DWT_KV_DTYPE", "") or "bf16"
    if dt not in KV_DTYPES:
        raise ValueError(
            f"unknown kv dtype {dt!r}; expected one of {KV_DTYPES}")
    return dt


@partial(jax.tree_util.register_dataclass,
         data_fields=["data", "scale", "zero"], meta_fields=["bits"])
@dataclass
class QuantizedKVPages:
    """Narrow KV pages + per-(…, token) scale sidecar over the last axis.

    ``data``: int8 ``(..., hd)`` (bits=8, symmetric) or uint8
    ``(..., hd/2)`` (bits=4, asymmetric, low nibble = even lane);
    ``scale``: f32 ``(..., 1)``; ``zero``: f32 ``(..., 1)`` minimum for
    int4, ``None`` for int8 (a ``None`` child vanishes from the pytree,
    so tree-mapped scatters/gathers and sharding-prefix specs see only
    real leaves).  Every leaf keeps the full leading-axis stack
    (``[L, N, H, bt, ·]`` pools, per-layer ``[N, H, bt, ·]`` slices,
    exported ``[n, L, H, bt, ·]`` runs), so the same tree-mapped page
    program serves them all.
    """

    data: jax.Array
    scale: jax.Array
    zero: Optional[jax.Array]
    bits: int

    @property
    def shape(self):
        """LOGICAL shape (full head_dim, nibbles unpacked)."""
        d = self.data.shape[-1] * (2 if self.bits == 4 else 1)
        return (*self.data.shape[:-1], d)

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def nbytes(self):
        return (self.data.nbytes + self.scale.nbytes
                + (0 if self.zero is None else self.zero.nbytes))

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        if self.bits == 8:
            return (self.data.astype(jnp.float32)
                    * self.scale).astype(dtype)
        lo = (self.data & 0xF).astype(jnp.float32)
        hi = (self.data >> 4).astype(jnp.float32)
        v = jnp.stack([lo, hi], axis=-1)            # (..., hd/2, 2)
        *lead, half, _ = v.shape
        v = v.reshape(*lead, half * 2)
        return (v * self.scale + self.zero).astype(dtype)


def quantize_kv_pages(x: jax.Array, bits: int) -> QuantizedKVPages:
    """Per-(…, token) quantization over the LAST axis (head_dim) —
    shape-agnostic, so pool leaves, projection chunks and exported block
    runs all go through this one owner.  int8 is symmetric on the
    weight rails' absmax/127 grid; int4's 15-level grid needs the
    asymmetric [min, max] span (a symmetric 7-level grid wastes half
    the codes whenever a token's channels share a sign)."""
    xf = x.astype(jnp.float32)
    if bits == 8:
        absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
        return QuantizedKVPages(data=q, scale=scale, zero=None, bits=8)
    if bits != 4:
        raise ValueError(f"kv quantization is int8 or int4, got {bits}")
    mn = jnp.min(xf, axis=-1, keepdims=True)
    mx = jnp.max(xf, axis=-1, keepdims=True)
    scale = jnp.maximum(mx - mn, 1e-8) / 15.0
    q = jnp.clip(jnp.round((xf - mn) / scale), 0, 15).astype(jnp.uint8)
    *lead, d = q.shape
    if d % 2:
        raise ValueError(f"int4 packing needs an even head_dim, got {d}")
    pairs = q.reshape(*lead, d // 2, 2)
    packed = (pairs[..., 0] | (pairs[..., 1] << 4)).astype(jnp.uint8)
    return QuantizedKVPages(data=packed, scale=scale, zero=mn, bits=4)


def quantize_kv_like(ref, x: jax.Array):
    """Payload matching the pool tensor ``ref``: a dtype cast for a
    plain pool, quantized leaves for a quantized one — so every page
    scatter site quantizes through one line."""
    if isinstance(ref, QuantizedKVPages):
        return quantize_kv_pages(x, ref.bits)
    return x.astype(ref.dtype)


def dequantize_kv(x, dtype=jnp.float32) -> jax.Array:
    """Full-width view of ``x`` (plain array or QuantizedKVPages)."""
    if isinstance(x, QuantizedKVPages):
        return x.dequantize(dtype)
    return x.astype(dtype)


def alloc_kv_pages(shape, kv_dtype: Optional[str], base_dtype):
    """One zeroed pool tensor for a ``(..., head_dim)`` page-pool shape:
    a plain ``base_dtype`` array for bf16, :class:`QuantizedKVPages`
    leaves for int8/int4.  Callers build the V pool with
    ``jax.tree.map(jnp.zeros_like, pk)`` — works for both."""
    kv_dtype = resolve_kv_dtype(kv_dtype)
    *lead, d = shape
    if kv_dtype == "bf16":
        return jnp.zeros(shape, base_dtype)
    if kv_dtype == "int8":
        return QuantizedKVPages(
            data=jnp.zeros((*lead, d), jnp.int8),
            scale=jnp.zeros((*lead, 1), jnp.float32),
            zero=None, bits=8)
    return QuantizedKVPages(
        data=jnp.zeros((*lead, d // 2), jnp.uint8),
        scale=jnp.zeros((*lead, 1), jnp.float32),
        zero=jnp.zeros((*lead, 1), jnp.float32), bits=4)


def alloc_kv_pool(shape, kv_dtype: Optional[str], base_dtype,
                  pool_sharding=None, streams: int = 2):
    """``(k_pool, v_pool)``, both zeroed :func:`alloc_kv_pages` tensors.
    ``streams=1`` (a latent-attention model: one row a token,
    ``ModelConfig.kv_streams``): the first is the pool and the second
    holds no element (``shape`` with a last dimension of 0), so the seam
    of two operands stays and nothing is stored twice.

    ``pool_sharding`` (the paged seam's ``KVCache`` of NamedShardings,
    or None off-mesh): the pools are BORN on their kv-head shards — a
    jit with ``out_shardings`` writes each device's slice in place.
    Allocating whole and ``device_put``-ing afterwards would first
    materialize the full pool on device 0, which a pool sized for N
    chips does not fit.  The one sharding per pool broadcasts over the
    quantized layouts' data/scale/zero leaves (all keep the
    ``[L, N, H(tp), bt, ·]`` axis order), so scales shard WITH their
    pages."""
    second = tuple(shape) if streams == 2 else tuple(shape[:-1]) + (0,)

    def alloc():
        return (alloc_kv_pages(shape, kv_dtype, base_dtype),
                alloc_kv_pages(second, kv_dtype, base_dtype))

    if pool_sharding is None:
        return alloc()
    return jax.jit(alloc, out_shardings=(pool_sharding.keys,
                                         pool_sharding.values))()


def kv_token_head_bytes(head_dim: int, kv_dtype: Optional[str],
                        base_dtype) -> int:
    """Bytes one (token, kv-head) of ONE tensor (K or V) occupies in the
    page pool, scale/zero sidecar INCLUDED — the single owner of the
    page-width arithmetic shared by the byte-budget admission
    (``make_kv_backend``) and the manager's accounting, so the two can
    never disagree about what a block costs."""
    kv_dtype = resolve_kv_dtype(kv_dtype)
    if kv_dtype == "bf16":
        return head_dim * np.dtype(base_dtype).itemsize
    if kv_dtype == "int8":
        return head_dim + 4                  # int8 lanes + f32 scale
    return head_dim // 2 + 8                 # packed nibbles + scale + zero


def kv_scale_token_head_bytes(kv_dtype: Optional[str]) -> int:
    """The sidecar-only share of :func:`kv_token_head_bytes` — what the
    ``dwt_kvcache_quant_scale_bytes`` gauge reports."""
    kv_dtype = resolve_kv_dtype(kv_dtype)
    return {"bf16": 0, "int8": 4, "int4": 8}[kv_dtype]


def dense(x: jax.Array,
          w: Union[jax.Array, QuantizedArray, QuantizedArray4],
          eq: str) -> jax.Array:
    """einsum that transparently handles quantized weights.

    Dequantizes to the activation dtype right at the contraction so XLA
    fuses the int8/int4 unpack + convert + scale into the matmul's
    operand feed — HBM sees only the quantized bytes.
    """
    if isinstance(w, AnyQuantized):
        w = w.dequantize(x.dtype)
    return jnp.einsum(eq, x, w)
