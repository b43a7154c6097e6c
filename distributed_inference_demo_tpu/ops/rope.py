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
                           theta: float = 10000.0, yarn: tuple = ()
                           ) -> jnp.ndarray:
    """Rotary embedding over INTERLEAVED pairs: channels ``(2i, 2i+1)``
    turn by ``positions * theta ** (-2i / d)`` (the pairing of the
    RoFormer paper and of deepseek_v3's ``rope_interleave``), where
    :func:`apply_rope` pairs ``(i, i + d/2)``.  Same shapes.  ``yarn`` =
    ``(factor, original, beta_fast, beta_slow, attention_factor)``: the
    frequencies are :func:`yarn_frequencies` and cos and sin are scaled
    by ``attention_factor`` (``apply_rope_kind`` says the same of the
    rotate-half pairing)."""
    dtype = x.dtype
    d = x.shape[-1]
    if yarn:
        factor, original, beta_fast, beta_slow, attention_factor = yarn
        angles = positions[..., None].astype(jnp.float32) * yarn_frequencies(
            d, theta, factor, original, beta_fast, beta_slow)
        cos = (jnp.cos(angles) * attention_factor)[:, :, None, :]
        sin = (jnp.sin(angles) * attention_factor)[:, :, None, :]
    else:
        angles = positions[..., None].astype(jnp.float32) * rope_frequencies(
            d, theta)
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(dtype)


def yarn_frequencies(rotary_dim: int, theta: float, factor: float,
                     original: float, beta_fast: float,
                     beta_slow: float) -> jnp.ndarray:
    """YaRN's per-channel inverse frequencies, shape [rotary_dim // 2]
    (Peng et al. 2023, arXiv:2309.00071; HF ``_compute_yarn_parameters``):
    channel ``i`` keeps its own frequency (``extrap``) where it turns at
    least ``beta_fast`` times over the ``original`` positions, takes it
    divided by ``factor`` (``interp``) where it turns ``beta_slow`` times
    or fewer, and a linear ramp between the two channels where that
    happens (floor / ceil, kept inside the dim)."""
    import math
    extrap = rope_frequencies(rotary_dim, theta)
    interp = extrap / factor

    def turns_at(rotations):
        return (rotary_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp)


def apply_rope_kind(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                    rotary_share: float = 1.0, yarn: tuple = ()
                    ) -> jnp.ndarray:
    """Rotate-half rotary embedding as a block kind states it
    (``models.base.BlockKind``): the first ``rotary_share`` of a head's
    channels turn (pairs ``(i, i + rotary_dim / 2)`` within them) and the
    rest pass; with ``yarn`` = ``(factor, original, beta_fast, beta_slow,
    attention_factor)`` the frequencies are :func:`yarn_frequencies` and
    cos and sin are scaled by ``attention_factor``.  Tables in float32.
    x: [batch, seq, heads, head_dim]; positions: [batch, seq]."""
    dtype = x.dtype
    rd = int(x.shape[-1] * rotary_share)
    if yarn:
        factor, original, beta_fast, beta_slow, attention_factor = yarn
        freqs = yarn_frequencies(rd, theta, factor, original, beta_fast,
                                 beta_slow)
    else:
        attention_factor = 1.0
        freqs = rope_frequencies(rd, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = (jnp.cos(angles) * attention_factor)[:, :, None, :]
    sin = (jnp.sin(angles) * attention_factor)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :rd // 2], xf[..., rd // 2:rd]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                           xf[..., rd:]], axis=-1)
    return out.astype(dtype)
