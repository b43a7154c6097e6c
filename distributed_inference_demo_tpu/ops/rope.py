"""Rotary position embeddings (llama family).

Computed on the fly from integer positions — no host-side tables to ship —
so the same jitted stage function serves prefill (``positions = [0..L)``)
and decode (``positions = [cache_len]``) with static shapes.
"""

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    """Per-channel inverse frequencies, shape [head_dim // 2]."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0
               ) -> jnp.ndarray:
    """Rotate q or k. x: [batch, seq, heads, head_dim]; positions: [batch, seq]."""
    dtype = x.dtype
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [b, s, 1, hd/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dtype)


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           theta: float = 10000.0) -> jnp.ndarray:
    """Rotary embedding over INTERLEAVED pairs: channels ``(2i, 2i+1)``
    turn by ``positions * theta ** (-2i / d)`` (the pairing of the
    RoFormer paper and of deepseek_v3's ``rope_interleave``), where
    :func:`apply_rope` pairs ``(i, i + d/2)``.  Same shapes."""
    dtype = x.dtype
    d = x.shape[-1]
    angles = positions[..., None].astype(jnp.float32) * rope_frequencies(
        d, theta)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(dtype)
