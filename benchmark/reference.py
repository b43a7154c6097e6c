"""A plain reference forward pass, independent of the code under test.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
over the whole sequence at once, with no KV cache, no kernel, no batching
and no line of ``models/decoder.py`` or ``ops/``.  It reads the program's
parameter tree only as data (the names of its leaves); an int8 leaf is its
stored integers times its scales.

What is specific to one kind of block (the embedding step, one layer, the
final norm, as its paper and its HF ``modeling_*`` file give them) is the
family's own module, ``families/<family>.py``, found by the name in the
configuration's ``model_config.family``.  Here is what every family
shares: the helpers their equations are written in, the layer loop and
the head, as two halves (``halves``: the rows after the last layer for a
list of ids; chosen rows scored against chosen targets).
``emitted_logprobs`` composes them for a model that generates one token a
pass, left to right; a family that generates otherwise brings the
composition itself, its ``replay``.

One layer runs at a time (one jitted function, the layer picked by index),
and the head runs in blocks of vocabulary rows with a running
log-sum-exp, so the float32 copies stay a few hundred MB.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import families

F32 = jnp.float32
HEAD_BLOCK = 16384          # vocabulary rows a head block covers


def _f32(leaf):
    """A parameter leaf as float32: an int8 leaf (``.q`` integers and
    ``.scale`` per output channel) is dequantized exactly."""
    if hasattr(leaf, "q"):
        if leaf.q.dtype != jnp.int8:
            raise ValueError(f"reference handles int8 leaves, not "
                             f"{leaf.q.dtype}")
        return leaf.q.astype(F32) * leaf.scale.astype(F32)
    return leaf.astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, theta):
    """Rotate-half rotary embedding.  x: [T, heads, d], positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def alibi_slopes(n_heads: int) -> list:
    """ALiBi slopes (Press et al. 2022), one a head."""
    def pow2(n):
        start = 2.0 ** (-8.0 / n)
        return [start ** (i + 1) for i in range(n)]
    if math.log2(n_heads).is_integer():
        return pow2(n_heads)
    closest = 2 ** math.floor(math.log2(n_heads))
    return pow2(closest) + pow2(2 * closest)[0::2][: n_heads - closest]


def _attention(q, k, v, slopes):
    """Causal softmax attention.  q: [T, nh, d]; k, v: [T, nkv, d]."""
    t, nh, d = q.shape
    g = nh // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    if slopes is not None:
        s = s + slopes[:, None, None] * jnp.arange(t, dtype=F32)[None, None, :]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _make_layer_fn(layer_eq):
    """A family's ``layer_eq(p, x)`` as one jitted function of the stacked
    leaves and a layer's index."""

    @jax.jit
    def layer(x, layers, i):
        p = {k: _f32(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            v)) for k, v in layers.items()}
        return layer_eq(p, x)

    return layer


def halves(params, cfg: dict):
    """The reference of one model in its two halves, ``(rows, score)``.
    ``emitted_logprobs`` composes them left to right; a family that
    generates otherwise composes them in its ``replay``
    (``families/__init__.py``), once a pass.  The layer function is jitted
    once for both: passes of one length share its compiled program.

    ``cfg`` is the configuration file's ``model_config`` group."""
    embed, layer_eq, final_norm = families.load(cfg["family"]).equations(cfg)
    layer = _make_layer_fn(layer_eq)
    vocab = cfg["vocab_size"]

    def rows(ids):
        """The rows ``[T, H]`` after the last layer for the ids ``[T]``,
        positions ``0..T-1``: every one, before the final norm."""
        with jax.default_matmul_precision("highest"):
            x = embed(params, jnp.asarray(ids, jnp.int32))
            for i in range(cfg["num_layers"]):
                x = layer(x, params.layers, jnp.int32(i))
        return x

    def score(x, target):
        """Rows ``[G, H]`` of ``rows`` against the ids ``[G]`` each is to
        be scored on: the final norm, the head (tied or not) in blocks of
        ``HEAD_BLOCK`` with a running log-sum-exp.  One entry a row: the
        log-probability of its target, the best id and its
        log-probability."""
        target = jnp.asarray(target, jnp.int32)
        with jax.default_matmul_precision("highest"):
            x = final_norm(params, x)
            run_max = jnp.full((x.shape[0],), -jnp.inf, F32)
            run_sum = jnp.zeros((x.shape[0],), F32)
            best = jnp.full((x.shape[0],), -jnp.inf, F32)
            best_id = jnp.zeros((x.shape[0],), jnp.int32)
            picked = jnp.zeros((x.shape[0],), F32)
            for lo in range(0, vocab, HEAD_BLOCK):
                hi = min(vocab, lo + HEAD_BLOCK)
                if cfg.get("tie_embeddings"):
                    w = params.embed["tokens"][lo:hi].astype(F32).T
                else:
                    w = _f32(params.lm_head["w"])[:, lo:hi]
                logits = x @ w                              # [G, hi - lo]
                m = jnp.maximum(run_max, logits.max(-1))
                run_sum = (run_sum * jnp.exp(run_max - m)
                           + jnp.exp(logits - m[:, None]).sum(-1))
                run_max = m
                blk_best = logits.max(-1)
                blk_id = logits.argmax(-1).astype(jnp.int32) + lo
                best_id = jnp.where(blk_best > best, blk_id, best_id)
                best = jnp.maximum(best, blk_best)
                inside = (target >= lo) & (target < hi)
                col = jnp.clip(target - lo, 0, hi - lo - 1)
                picked = jnp.where(
                    inside,
                    jnp.take_along_axis(logits, col[:, None], 1)[:, 0],
                    picked)
            lse = run_max + jnp.log(run_sum)
        return {"logprobs": [float(v) for v in (picked - lse)],
                "best_ids": [int(v) for v in best_id],
                "best_logprobs": [float(v) for v in (best - lse)]}

    return rows, score


def emitted_logprobs(params, cfg: dict, ids: list, n_prompt: int,
                     generation=None) -> dict:
    """For each token the server emitted (``ids`` is the prompt, then
    those tokens): the reference's log-probability of it, and the
    reference's own best token and its log-probability.

    A family with a ``replay`` scores the request itself, the way it
    generated it, from ``generation``: what the server's reply said about
    the request, ``None`` where it said nothing.  Every other family
    generates one token a pass, left to right, and needs no record:
    teacher-forced over ``ids`` in one forward, row ``t - 1`` scores
    token ``t``."""
    family = families.load(cfg["family"])
    if hasattr(family, "replay"):
        return family.replay(cfg)(params, ids, n_prompt, generation)
    rows, score = halves(params, cfg)
    return score(rows(ids)[n_prompt - 1: len(ids) - 1],     # rows that predict
                 ids[n_prompt:])
