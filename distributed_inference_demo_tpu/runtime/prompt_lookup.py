"""Prompt-lookup (n-gram) speculative decoding: draft-FREE speculation.

Speculative decoding needs a proposer that is much cheaper than the target
model.  A small draft model (runtime/speculative.py) is one choice; this
module uses an even cheaper one: **the text itself**.  Generated text
constantly re-uses spans of its own context — quoted input, repeated
entities, code identifiers, summarized passages — so "find where the
current n-gram last occurred and propose the tokens that followed it"
(prompt lookup / PLD) gets high acceptance on exactly the workloads where
decode throughput matters, at zero extra weights and zero extra HBM
traffic for the proposer.

TPU-first shape of the idea:

- The token history (prompt + emitted) lives on device as a fixed
  ``[b, cap]`` buffer riding the round scan's carry; matching is a masked
  vectorized compare + argmax over positions — pure VPU work, fused into
  the same compiled program as the verify forward.  No host round-trip
  per round.
- Proposal scoring prefers a bigram match over a unigram match, and the
  latest occurrence within each class (score = 2*bigram + unigram,
  tie-broken by position, one argmax).
- Verification / lockstep advance / cache rollback are exactly the
  draft-model machinery: ONE prefill-shaped target forward over the K
  proposals, the standard rejection rule with the proposer treated as a
  one-hot distribution (accept d with prob p(d); on rejection resample
  from p with d masked out — the max(p - q, 0) rule specialized to
  q = one-hot), bonus token after K accepts.  Greedy mode is bit-exact
  vs target-only decode (pinned by tests).

The reference has no analog (one token per ring trip); this composes with
the same engine surface as everything else (``generate`` /
``generate_stream``, ``serve --prompt-lookup``).
"""

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import (KVCache, ModelConfig, StageParams,
                           StageSpec, pad_cache_capacity)
from ..models.decoder import stage_forward
from ..ops.sampling import SamplingParams, sample_logits
from .engine import GenerationResult, check_capacity
from .speculative import (SpecStats, drain_round_blocks, emit_stream_block,
                          init_done, mask_after_eos, pad_to_width,
                          verify_emit)


def ngram_propose(history: jnp.ndarray, hist_len: jnp.ndarray,
                  num_draft: int) -> jnp.ndarray:
    """[b, K] proposals from the latest bigram/unigram match over a
    [b, cap] token-history buffer with per-row valid lengths.

    For each row: score position j by 2*(bigram match ending at j) +
    (history[j] == last token), require j < hist_len - 1 (the match must
    have a following token inside the valid region), take the
    highest-scoring latest j, and propose the K tokens after it.  Score 0
    everywhere degenerates to j = cap-1, whose clamped gather proposes
    the last token repeated — verification makes any bad proposal merely
    useless, never wrong.  Shared by PromptLookupEngine's round scan and
    the continuous-batching slot loop (prompt_lookup=True)."""
    cap, K = history.shape[1], num_draft
    pos = jnp.arange(cap)[None, :]                    # [1, cap]
    last = jnp.take_along_axis(
        history, (hist_len - 1)[:, None], axis=1)     # [b, 1]
    prev = jnp.take_along_axis(
        history, jnp.maximum(hist_len - 2, 0)[:, None], axis=1)
    uni = history == last                             # [b, cap]
    prev_hist = jnp.roll(history, 1, axis=1)
    bi = uni & (prev_hist == prev) & (pos > 0)
    valid = pos < (hist_len - 1)[:, None]
    score = (2 * bi + uni) * valid
    # lexicographic (score, position) argmax via score*cap + pos
    j = jnp.argmax(score * cap + pos, axis=1)         # [b]
    idx = j[:, None] + 1 + jnp.arange(K)[None, :]     # [b, K]
    idx = jnp.minimum(idx, hist_len[:, None] - 1)
    return jnp.take_along_axis(history, idx, axis=1).astype(jnp.int32)


class PromptLookupEngine:
    """Draft-free speculative generation over a single-stage model."""

    def __init__(self, cfg: ModelConfig, params: StageParams,
                 max_seq: Optional[int] = None,
                 sampling: SamplingParams = SamplingParams(),
                 num_draft: int = 4,
                 attn_backend: str = "auto",
                 mesh=None,
                 eos_id: Optional[int] = None,
                 kv_cache_dtype=None,
                 prefill_chunk: Optional[int] = None,
                 kv_cache_blocks: Optional[int] = None,
                 kv_block_tokens: Optional[int] = None,
                 kv_dtype: Optional[str] = None):
        """``mesh``: tp mesh — the target forward runs sharded (see
        InferenceEngine); proposal matching stays replicated VPU work.
        ``kv_cache_dtype``: reduced-precision cache storage, same
        contract as InferenceEngine (insert rounds, attention upcasts,
        jnp path forced).  ``prefill_chunk``: C-token chunked prefill
        (engine.run_chunked_prefill semantics; the proposer's history
        buffer is host-seeded from the ids and unaffected).

        ``kv_cache_blocks`` / ``kv_block_tokens``: the
        block-level KV prefix pool behind the backend seam
        (docs/DESIGN.md §14), batch 1: a prompt sharing whole leading
        blocks with an earlier prefill seeds its cache and prefills only
        the suffix — exactness is a prefill-side property, so it
        composes with the n-gram proposer untouched (the history buffer
        still seeds from the full ids).  Default off (0 blocks); the
        pool is device-resident."""
        if num_draft < 1:
            raise ValueError("num_draft must be >= 1")
        self.cfg, self.params = cfg, params
        self.max_seq = max_seq or cfg.max_seq_len
        self.sampling = sampling
        self.num_draft = num_draft
        self.eos_id = eos_id
        self.spec = StageSpec(0, 1, 0, cfg.num_layers)
        self.mesh = mesh
        from .engine import validate_prefill_chunk
        self.prefill_chunk = validate_prefill_chunk(prefill_chunk,
                                                    self.max_seq)

        from .engine import resolve_attn_impl
        self.kv_cache_dtype, _, attn_impl = resolve_attn_impl(
            kv_cache_dtype, attn_backend)

        cfg_, spec_, samp_, K = cfg, self.spec, sampling, num_draft
        # history/cache slack per round, sublane-aligned for flash
        cap = pad_cache_capacity(self.max_seq + num_draft + 2)

        from ..parallel.tensor import make_forward_seam
        fwd, self._cache_sharding = make_forward_seam(
            cfg, self.spec, mesh, params, attn_impl=attn_impl)

        @jax.jit
        def prefill(params, ids, cache):
            b, s = ids.shape
            pos = jnp.broadcast_to(jnp.arange(s), (b, s))
            logits, cache = fwd(params, ids, cache, pos, s - 1)
            return logits[:, -1], cache

        def one_round(params, last_tok, cache, history, hist_len, rng):
            b = last_tok.shape[0]
            n = cache.length

            drafts = ngram_propose(history, hist_len, K)   # [b, K]

            verify_in = jnp.concatenate([last_tok[:, None], drafts], axis=1)
            pos = n + jnp.broadcast_to(jnp.arange(K + 1), (b, K + 1))
            t_logits, cache = fwd(params, verify_in, cache, pos,
                                  None)                       # [b, K+1, V]

            # shared rejection rule; q_logits=None = one-hot proposer
            rng, sub_u, sub_x = jax.random.split(rng, 3)
            emitted, m, new_last = verify_emit(t_logits, drafts, None,
                                               samp_, sub_u, sub_x)
            cache = KVCache(cache.keys, cache.values, n + m)
            # history gains the emitted block at positions n+1..; entries
            # past m are garbage that next round's write overlaps, and
            # `propose` masks reads beyond hist_len
            history = jax.lax.dynamic_update_slice(
                history, emitted, (jnp.int32(0), n + 1))
            hist_len = hist_len + m
            return emitted, m, new_last, cache, history, hist_len, rng

        @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(6,))
        def rounds(params, last_tok, cache, history, hist_len, rng,
                   num_rounds):
            def body(carry, _):
                last_tok, cache, history, hist_len, rng = carry
                emitted, m, last_tok, cache, history, hist_len, rng = \
                    one_round(params, last_tok, cache, history, hist_len,
                              rng)
                return (last_tok, cache, history, hist_len, rng), \
                    (emitted, m)

            (last_tok, cache, history, hist_len, rng), (em, ms) = \
                jax.lax.scan(body, (last_tok, cache, history, hist_len,
                                    rng), None, length=num_rounds)
            return em, ms, last_tok, cache, history, hist_len, rng

        self._prefill, self._rounds, self._cap = prefill, rounds, cap
        from .engine import make_chunk_programs
        self._chunk_mid, self._chunk_last = make_chunk_programs(fwd)

        from .kvcache import make_kv_backend
        self.kv_cache = make_kv_backend(
            cfg, kv_cache_blocks, kv_block_tokens,
            dtype=self.kv_cache_dtype, kv_dtype=kv_dtype,
            default_blocks=0)

    # ------------------------------------------------------------------

    def _init_state(self, ids: jnp.ndarray, rng):
        """Prefill + first target-sampled token + seeded history buffer —
        the state both generate paths start every run from.  A KV-cache
        hit (backend seam) seeds the cache's leading columns and
        prefills only the suffix; the full prompt is stored back before
        the rounds program donates the cache."""
        b, plen = ids.shape
        cache = KVCache.create(self.cfg, self.cfg.num_layers, b, self._cap,
                               dtype=self.kv_cache_dtype)
        if self._cache_sharding is not None:
            cache = jax.device_put(cache, self._cache_sharding)
        start = 0
        if self.kv_cache is not None:
            start, cache = self.kv_cache.seed(ids, cache)
        from .engine import run_seeded_prefill
        last_logits, cache = run_seeded_prefill(
            self.params, ids, cache, self.prefill_chunk, self.max_seq,
            self._prefill, self._chunk_mid,
            self._chunk_last, start=start)
        if self.kv_cache is not None:
            self.kv_cache.store(ids, cache)
        rng, sub = jax.random.split(rng)
        last_tok = sample_logits(last_logits, sub, self.sampling)
        history = jnp.zeros((b, self._cap), jnp.int32)
        history = jax.lax.dynamic_update_slice(history, ids, (0, 0))
        history = jax.lax.dynamic_update_slice(
            history, last_tok[:, None], (jnp.int32(0), jnp.int32(plen)))
        hist_len = jnp.full((b,), plen + 1, jnp.int32)
        return last_tok, cache, history, hist_len, rng

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 seed: int = 0,
                 rounds_per_dispatch: Optional[int] = None
                 ) -> "tuple[GenerationResult, SpecStats]":
        ids = jnp.asarray(prompt_ids, jnp.int32)
        b, plen = ids.shape
        check_capacity(self.max_seq, plen, max_new_tokens)
        R = rounds_per_dispatch or min(8, max(1, max_new_tokens))
        rng = jax.random.PRNGKey(seed)

        t0 = time.perf_counter()
        last_tok, cache, history, hist_len, rng = self._init_state(ids, rng)

        stats = SpecStats()
        first = np.asarray(last_tok)
        out = [first[:, None]]
        done = init_done(first, self.eos_id)
        total = 1
        while total < max_new_tokens and not done.all():
            em, ms, last_tok, cache, history, hist_len, rng = self._rounds(
                self.params, last_tok, cache, history, hist_len, rng, R)
            total = drain_round_blocks(np.asarray(em), np.asarray(ms), out,
                                       stats, self.num_draft, total,
                                       max_new_tokens, self.eos_id, done)

        toks = np.concatenate(out, axis=1)[:, :max_new_tokens]
        toks = mask_after_eos(pad_to_width(toks, max_new_tokens,
                                           self.eos_id), self.eos_id)
        dt = time.perf_counter() - t0
        # actual emitted count, not the eos-padded width (keeps
        # tokens_per_round honest and matches the stream path)
        stats.emitted = min(total, max_new_tokens)
        return (GenerationResult(tokens=toks.astype(np.int32),
                                 prompt_len=plen,
                                 num_new=toks.shape[1], seconds=dt),
                stats)

    def generate_stream(self, prompt_ids: np.ndarray, max_new_tokens: int,
                        seed: int = 0,
                        stats_out: Optional[SpecStats] = None):
        """Yield [batch] token arrays per emitted token; tokens arrive in
        per-round bursts (the speculation win showing through the
        stream).  ``stats_out``, if given, is updated in place."""
        if max_new_tokens <= 0:
            return
        ids = jnp.asarray(prompt_ids, jnp.int32)
        b, plen = ids.shape
        check_capacity(self.max_seq, plen, max_new_tokens)
        rng = jax.random.PRNGKey(seed)
        stats = stats_out if stats_out is not None else SpecStats()
        last_tok, cache, history, hist_len, rng = self._init_state(ids, rng)

        first = np.asarray(last_tok)
        yield first
        done = init_done(first, self.eos_id)
        total = stats.emitted = 1
        while total < max_new_tokens and not done.all():
            em, ms, last_tok, cache, history, hist_len, rng = self._rounds(
                self.params, last_tok, cache, history, hist_len, rng, 1)
            m = int(np.asarray(ms)[0])
            block = np.asarray(em)[0]
            stats.rounds += 1
            stats.drafted += self.num_draft
            stats.accepted += m - 1
            for tok, all_done in emit_stream_block(
                    block, m, done, total, max_new_tokens, self.eos_id,
                    stats):
                yield tok
                if all_done:
                    return
            total += m
            stats.emitted = min(total, max_new_tokens)
