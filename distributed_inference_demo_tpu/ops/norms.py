"""Normalization layers as pure functions.

RMSNorm (llama family) and LayerNorm (bloom family, with bias — the bloom
blocks in the reference's exported ONNX modules use torch LayerNorm).
Accumulation in float32 regardless of activation dtype: on TPU the VPU does
fp32 math anyway and this avoids bf16 variance underflow.
"""

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5,
             unit_offset: bool = False) -> jnp.ndarray:
    """``unit_offset``: the gain is ``1 + weight`` (evabyte's
    ``norm_add_unit_offset``), added in float32."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jnp.reciprocal(jnp.sqrt(var + eps))
    gain = weight.astype(jnp.float32)
    return (x * (1.0 + gain if unit_offset else gain)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)
