"""Speculative decoding: a draft model proposes, the target verifies.

Decode is HBM-bandwidth-bound — every step streams all target weights for
one token's worth of MXU work (PERF.md §5, ``step_weight_stream_pct``).
Speculative decoding converts that stream into several tokens: a small DRAFT model
autoregressively proposes ``num_draft`` tokens (cheap — its weights are a
fraction of the target's), then the TARGET verifies all of them in ONE
prefill-shaped forward ([batch, K+1] positions — the MXU-friendly shape),
and the standard rejection rule keeps a prefix that is distributed exactly
as target-only sampling (Leviathan et al., 2023; PAPERS.md).

Everything per round is ONE compiled program (`_rounds`): draft scan →
target verify → accept/resample → cache rollback, with ``R`` rounds fused
in a ``lax.scan`` so one dispatch yields up to ``R*(K+1)`` tokens — each
dispatch is a host round trip, so fusing rounds matters beside the
algorithm (how much, on a directly attached chip: not measured).

TPU-first design points (vs the CUDA/torch implementations of this idea):

- **Static shapes throughout**: every round emits a fixed ``[b, K+1]``
  token block plus a count; the host trims.  No dynamic-length tensors,
  no recompiles.
- **Cache rollback is a length reset.**  ``KVCache.length`` is a traced
  scalar; rejected tokens' KV simply stays as stale slots ABOVE the valid
  length.  The causal mask (kv_pos <= q_pos) guarantees a stale slot is
  never attended before the next round overwrites it — no scatter, no
  copy.
- **Batch rows advance in lockstep** by ``m = min_b(accepted_b + 1)``
  (the per-round emit count must be one scalar for static shapes).  Each
  row's kept prefix is its own exactly-distributed sample; rows that
  accepted more than ``m`` tokens just re-propose them next round, so
  batch skew costs throughput, never correctness.  The reference has no
  analog (one token per ring trip, ``Communication.java:682-928``); this
  is a pure capability add on top of engine.py's fused decode.

The draft and target must share a vocabulary (checked).  Greedy mode
(``SamplingParams(greedy=True)``) verifies by argmax equality and is
bit-exact vs target-only greedy decode — the property the tests pin.
"""

import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import (KVCache, ModelConfig, StageParams, StageSpec,
                           require_kv_pair, require_one_kind,
                           require_token_rows,
                           require_single_pass)
from ..models.decoder import stage_forward
from ..ops.sampling import SamplingParams, filtered_logits, sample_logits
from .engine import GenerationResult, check_capacity


@dataclass
class SpecStats:
    """Acceptance diagnostics for one generate() call."""
    rounds: int = 0
    drafted: int = 0            # num_draft * rounds
    accepted: int = 0           # draft tokens accepted (excl. bonus/resample)
    emitted: int = 0            # tokens actually kept (after min + trim)

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else float("nan")

    @property
    def tokens_per_round(self) -> float:
        return self.emitted / self.rounds if self.rounds else float("nan")


def accept_and_extra(t_logits, drafts, q_logits, samp: SamplingParams,
                     sub_u, sub_x, k_cap=None):
    """The speculative accept/resample rule, shared by every proposer
    (draft model, prompt lookup) and every advance policy (lockstep,
    per-row).

    t_logits: [b, K+1, V] target logits over [last_tok, d_1..d_K];
    drafts:   [b, K] proposals;
    q_logits: [b, K, V] proposer's filtered logits, or None for a
              DETERMINISTIC proposer (one-hot q: accept d with prob p(d),
              resample from p with d masked out).
    k_cap:    [b] int32 per-row draft-length cap in [1, K], or None for
              the full width.  Proposals at positions >= k_cap[i] are
              never accepted — the adaptive-draft-length seam
              (docs/DESIGN.md §22): a capped row behaves exactly as if
              only its first k_cap proposals existed.  A TRUNCATION at
              k_cap (< K, with the capped proposal otherwise live) is
              not a rejection: the follow-up token samples from the
              target's own distribution at that position, not the
              residual.  Rng spend is identical either way, so capped
              and uncapped schedules stay split-for-split comparable.

    Returns (a [b] accepted-draft counts in [0, K], extra [b]: the
    rejection-point resample, or the bonus token after K accepts).
    """
    b, K = drafts.shape
    if samp.greedy:
        t_arg = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
        accept = drafts == t_arg[:, :K]                # [b, K] bool
        if k_cap is not None:
            accept = accept & (jnp.arange(K)[None, :] < k_cap[:, None])
        a = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)   # [b] in [0, K]
        # rejected at a -> the target's own argmax; all accepted -> bonus
        # argmax after d_K.  Both are t_arg[:, a].  A k_cap truncation is
        # also t_arg[:, a] — the token greedy decode would emit there.
        extra = jnp.take_along_axis(t_arg, a[:, None], axis=1)[:, 0]
    else:
        p_logits = filtered_logits(t_logits, samp)     # [b, K+1, V]
        p = jax.nn.softmax(p_logits[:, :K], axis=-1)
        p_d = jnp.take_along_axis(
            p, drafts[..., None], axis=-1)[..., 0]     # [b, K]
        u = jax.random.uniform(sub_u, p_d.shape)
        if q_logits is None:
            accept = u < p_d
        else:
            q = jax.nn.softmax(q_logits, axis=-1)
            q_d = jnp.take_along_axis(
                q, drafts[..., None], axis=-1)[..., 0]
            accept = u * jnp.maximum(q_d, 1e-20) < p_d
        if k_cap is not None:
            accept = accept & (jnp.arange(K)[None, :] < k_cap[:, None])
        a = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
        # resample dist at the rejection point: norm(max(p - q, 0)); for a
        # one-hot q that is p with the draft token masked out
        a_idx = jnp.minimum(a, K - 1)[:, None, None]
        p_a = jnp.take_along_axis(p, a_idx, axis=1)[:, 0]  # [b, V]
        if q_logits is None:
            d_a = jnp.take_along_axis(
                drafts, jnp.minimum(a, K - 1)[:, None], axis=1)
            resid_a = p_a.at[jnp.arange(b)[:, None], d_a].set(0.0)
        else:
            resid = jnp.maximum(p - jax.nn.softmax(q_logits, -1), 0.0)
            resid_a = jnp.take_along_axis(resid, a_idx, axis=1)[:, 0]
        # all-zero residual (p == q exactly / point mass on d): fall back
        # to p_a — accept/resample then reduces to plain sampling from p
        resid_sum = jnp.sum(resid_a, axis=-1, keepdims=True)
        resid_a = jnp.where(resid_sum > 0, resid_a, p_a)
        if k_cap is not None:
            # truncated at k_cap < K with every eligible proposal
            # accepted: the position-a proposal was never offered, so
            # the correct follow-up is a plain sample from p there
            trunc = (a == k_cap) & (k_cap < K)
            resid_a = jnp.where(trunc[:, None], p_a, resid_a)
        bonus = jax.nn.softmax(p_logits[:, K], axis=-1)
        extra_probs = jnp.where((a == K)[:, None], bonus, resid_a)
        extra = jax.random.categorical(
            sub_x, jnp.log(extra_probs + 1e-30), axis=-1).astype(jnp.int32)
    return a, extra


def assemble_emitted(drafts, a, extra):
    """[b, K+1] emitted block from per-row accept counts: row i is
    [d_1..d_{a_i}, extra_i, 0...] — each row its own exactly-distributed
    sample."""
    K = drafts.shape[1]
    idx = jnp.arange(K + 1)[None, :]
    drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
    return jnp.where(idx < a[:, None], drafts_pad,
                     jnp.where(idx == a[:, None], extra[:, None], 0))


def verify_emit(t_logits, drafts, q_logits, samp: SamplingParams,
                sub_u, sub_x):
    """Accept/resample + emitted-block assembly with LOCKSTEP advance:
    all rows move by ``m = min_b(a_b) + 1`` (one scalar keeps the
    single-cache engines' shapes static; rows that accepted more
    re-propose next round).

    Returns (emitted [b, K+1], m scalar in [1, K+1], new_last [b]).
    """
    b = drafts.shape[0]
    a, extra = accept_and_extra(t_logits, drafts, q_logits, samp,
                                sub_u, sub_x)
    emitted = assemble_emitted(drafts, a, extra)
    m = jnp.min(a) + 1                                 # scalar, [1, K+1]
    new_last = jnp.take_along_axis(
        emitted, (m - 1)[None, None].astype(jnp.int32).repeat(b, axis=0),
        axis=1)[:, 0]
    return emitted, m, new_last


def verify_emit_per_row(t_logits, drafts, q_logits, samp: SamplingParams,
                        sub_u, sub_x, k_cap=None):
    """Accept/resample + assembly with PER-ROW advance: row i moves by
    ``n_i = a_i + 1`` — no lockstep minimum, no wasted acceptances.  The
    policy for engines whose cache positions are already per-row (the
    continuous-batching slot cache); the follow-up token is always the
    row's ``extra``.  ``k_cap`` ([b] or None): per-row draft-length cap
    (see :func:`accept_and_extra`).

    Returns (emitted [b, K+1], n [b] in [1, K+1], new_last [b]).
    """
    a, extra = accept_and_extra(t_logits, drafts, q_logits, samp,
                                sub_u, sub_x, k_cap=k_cap)
    return assemble_emitted(drafts, a, extra), a + 1, extra


def mask_after_eos(toks: np.ndarray, eos_id: Optional[int]) -> np.ndarray:
    """Rows keep emitting ``eos_id`` after their first eos — the fused
    decode scan's row-padding semantics (engine.py ``_mask_eos``), applied
    host-side to a speculative run's assembled [b, T] block."""
    if eos_id is None:
        return toks
    hit = toks == eos_id
    after = (np.cumsum(hit, axis=1) - hit) > 0
    toks = toks.copy()
    toks[after] = eos_id
    return toks


def init_done(first: np.ndarray, eos_id: Optional[int]) -> np.ndarray:
    """[b] done mask seeded from the prefill-sampled first token — the
    one definition shared by every speculative generate/stream path."""
    return (first == eos_id if eos_id is not None
            else np.zeros(first.shape, bool))


def pad_to_width(toks: np.ndarray, max_new: int,
                 eos_id: Optional[int]) -> np.ndarray:
    """Pad an early-eos-stopped [b, T] block out to the fused scan's
    full [b, max_new] shape.  Only reachable when every row already
    emitted eos (the generate loops can't stop early otherwise), so the
    pad is all-eos."""
    b, t = toks.shape
    if t < max_new:
        toks = np.concatenate(
            [toks, np.full((b, max_new - t), eos_id, toks.dtype)], axis=1)
    return toks


def emit_stream_block(block, m, done, total, max_new, eos_id,
                      stats: SpecStats):
    """Mask and hand out one verify round's [b, m] token block for the
    streaming surface: finished rows keep emitting eos (the streamed twin
    of the fused scan's _mask_eos padding, like
    InferenceEngine.generate_stream), ``done`` ([b] bool) updates in
    place, ``stats.emitted`` advances per token.  Yields
    ``(tok, all_done)`` pairs; the caller yields ``tok`` outward and
    returns on ``all_done``.  Shared by every speculative engine."""
    for j in range(min(m, max_new - total)):
        tok = block[:, j].copy()
        if eos_id is not None:
            tok[done] = eos_id
        stats.emitted = total + j + 1
        all_done = False
        if eos_id is not None:
            np.logical_or(done, tok == eos_id, out=done)
            all_done = bool(done.all())
        yield tok, all_done


def drain_round_blocks(em, ms, out, stats: SpecStats, num_draft: int,
                       total: int, max_new: int, eos_id: Optional[int] = None,
                       done: Optional[np.ndarray] = None) -> int:
    """Host-side collection of a fused dispatch's round blocks into
    ``out``/``stats``; returns the updated emitted-token total.  With
    ``eos_id``/``done`` given, ORs each block's eos hits into ``done``
    row-wise (the generate loops' incremental early-stop mask).  Shared
    by every speculative engine's generate loop."""
    for r in range(em.shape[0]):
        m = int(ms[r])
        block = em[r][:, :m]
        out.append(block)
        if eos_id is not None and done is not None:
            np.logical_or(done, (block == eos_id).any(axis=1), out=done)
        stats.rounds += 1
        stats.drafted += num_draft
        stats.accepted += m - 1   # lockstep: min_b(accepted) used
        total += m
        if total >= max_new:
            break
    return total


class SpeculativeEngine:
    """Draft/verify generation over two full single-stage models."""

    def __init__(self, cfg: ModelConfig, params: StageParams,
                 draft_cfg: ModelConfig, draft_params: StageParams,
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
        """``kv_cache_dtype``: reduced-precision storage for BOTH the
        target and draft caches (same contract as InferenceEngine /
        ContinuousBatchingEngine: insert rounds via update_kv_cache's
        cast, attention upcasts to f32, the jnp attention path is
        forced) — greedy output matches a plain engine with the same
        cache dtype bit-exactly.

        ``prefill_chunk``: bound prefill activation memory on long
        prompts by running BOTH models' prefill in fixed C-token chunks
        (engine.run_chunked_prefill, once per model; the draft's final
        chunk needs no logits).  Same semantics as InferenceEngine's.

        ``kv_cache_blocks`` / ``kv_block_tokens``: block-level KV prefix
        cache (``runtime/kvcache``) on the TARGET side, batch 1: a hit
        seeds the target cache from stored blocks and prefills only the
        suffix; the draft always prefills its full prompt (it is cheap
        by construction, and only the target's logits gate emission, so
        reuse exactness is a target-side property).  Default off; env
        ``DWT_KVCACHE_*`` knobs apply as in InferenceEngine.  The pool
        is device-resident behind the backend seam (docs/DESIGN.md
        §14), so two speculative requests sharing a prompt prefix
        reference the SAME pages in HBM (the accepted prefix is never
        duplicated; pinned by the ownership tests) and hits move zero
        bytes through the host."""
        if cfg.vocab_size != draft_cfg.vocab_size:
            raise ValueError(
                f"draft vocab ({draft_cfg.vocab_size}) != target vocab "
                f"({cfg.vocab_size}); speculative decoding needs a shared "
                "token space")
        if num_draft < 1:
            raise ValueError("num_draft must be >= 1")
        self.cfg, self.params = cfg, params
        self.draft_cfg, self.draft_params = draft_cfg, draft_params
        self.max_seq = max_seq or cfg.max_seq_len
        self.sampling = sampling
        self.num_draft = num_draft
        self.eos_id = eos_id
        from .engine import validate_prefill_chunk
        self.prefill_chunk = validate_prefill_chunk(prefill_chunk,
                                                    self.max_seq)
        require_single_pass(draft_cfg, "the draft side of speculation")
        require_kv_pair(draft_cfg, "the draft side of speculation")
        require_token_rows(draft_cfg, "the draft side of speculation")
        require_one_kind(draft_cfg, "the draft side of speculation")
        self.spec = StageSpec(0, 1, 0, cfg.num_layers)
        self.draft_spec = StageSpec(0, 1, 0, draft_cfg.num_layers)
        self.mesh = mesh

        from .engine import resolve_attn_impl
        self.kv_cache_dtype, _, attn_impl = resolve_attn_impl(
            kv_cache_dtype, attn_backend)

        cfg_, spec_ = cfg, self.spec
        dcfg_, dspec_ = draft_cfg, self.draft_spec
        samp_, K = sampling, num_draft

        # BOTH models build on the shared seam over the same tp axis
        # (the draft must also satisfy the kv-head divisibility check)
        from ..parallel.tensor import make_forward_seam
        fwd_t, self._cache_sharding = make_forward_seam(
            cfg, self.spec, mesh, params, attn_impl=attn_impl)
        fwd_d, _ = make_forward_seam(
            draft_cfg, self.draft_spec, mesh, draft_params,
            attn_impl=attn_impl)

        @jax.jit
        def prefill_both(tparams, dparams, ids, tcache, dcache):
            b, s = ids.shape
            pos = jnp.broadcast_to(jnp.arange(s), (b, s))
            t_logits, tcache = fwd_t(tparams, ids, tcache, pos, s - 1)
            _, dcache = fwd_d(dparams, ids, dcache, pos, s - 1)
            return t_logits[:, -1], tcache, dcache

        # chunked-prefill programs (engine.run_chunked_prefill drives
        # them; one mid+last pair for the target, mid-only for the
        # draft — its final chunk needs no logits).  Shared factory with
        # InferenceEngine so the programs cannot drift.
        from .engine import make_chunk_programs
        self._t_chunk_mid, self._t_chunk_last = make_chunk_programs(fwd_t)
        self._d_chunk_mid, _ = make_chunk_programs(fwd_d)

        from .kvcache import make_kv_backend
        self.kv_cache = make_kv_backend(
            cfg, kv_cache_blocks, kv_block_tokens,
            dtype=self.kv_cache_dtype, kv_dtype=kv_dtype,
            default_blocks=0)

        def one_round(tparams, dparams, last_tok, tcache, dcache, rng):
            """Draft K, verify K+1 in one target forward, accept/resample.

            Returns (emitted [b, K+1], m scalar, accepted [b], new state).
            ``last_tok`` sits at position tcache.length and is not yet in
            either cache.
            """
            b = last_tok.shape[0]
            n = tcache.length

            # --- draft phase: K+1 autoregressive steps --------------------
            # K proposals, plus ONE extra step whose proposal is discarded:
            # the extra step exists to insert d_K's KV into the draft cache
            # (the scan inserts each step's INPUT token), so that after an
            # all-accept round (m = K+1) the rolled-forward draft cache is
            # fully populated — without it, position n+K would be a stale
            # zero slot that silently derails the next round's first draft.
            def dstep(carry, _):
                tok, dc, rng = carry
                pos = jnp.broadcast_to(dc.length, (b, 1))
                logits, dc = fwd_d(dparams, tok[:, None], dc, pos, 0)
                logits = logits[:, 0]
                rng, sub = jax.random.split(rng)
                if samp_.greedy:
                    d = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    q = logits  # unused in greedy verify
                else:
                    q = filtered_logits(logits, samp_)
                    d = jax.random.categorical(sub, q, axis=-1)
                    d = d.astype(jnp.int32)
                return (d, dc, rng), (d, q)

            (_, dcache, rng), (drafts, q_logits) = jax.lax.scan(
                dstep, (last_tok, dcache, rng), None, length=K + 1)
            drafts = drafts[:K].T                  # [b, K]
            q_logits = jnp.swapaxes(q_logits[:K], 0, 1)  # [b, K, V]

            # --- target verify: ONE forward over K+1 tokens ---------------
            verify_in = jnp.concatenate([last_tok[:, None], drafts], axis=1)
            pos = n + jnp.broadcast_to(jnp.arange(K + 1), (b, K + 1))
            t_logits, tcache = fwd_t(tparams, verify_in, tcache, pos,
                                     None)         # [b, K+1, V]

            # --- accept / resample / lockstep advance (shared rule) -------
            rng, sub_u, sub_x = jax.random.split(rng, 3)
            emitted, m, new_last = verify_emit(
                t_logits, drafts, None if samp_.greedy else q_logits,
                samp_, sub_u, sub_x)

            # --- cache rollback -------------------------------------------
            tcache = KVCache(tcache.keys, tcache.values, n + m)
            dcache = KVCache(dcache.keys, dcache.values, n + m)
            return emitted, m, new_last, tcache, dcache, rng

        @partial(jax.jit, donate_argnums=(3, 4), static_argnums=(6,))
        def rounds(tparams, dparams, last_tok, tcache, dcache, rng,
                   num_rounds):
            def body(carry, _):
                last_tok, tc, dc, rng = carry
                emitted, m, last_tok, tc, dc, rng = one_round(
                    tparams, dparams, last_tok, tc, dc, rng)
                return (last_tok, tc, dc, rng), (emitted, m)

            (last_tok, tcache, dcache, rng), (em, ms) = jax.lax.scan(
                body, (last_tok, tcache, dcache, rng), None,
                length=num_rounds)
            return em, ms, last_tok, tcache, dcache, rng

        self._prefill_both = prefill_both
        self._rounds = rounds

    # ------------------------------------------------------------------

    def new_caches(self, batch: int):
        # +num_draft+1 slack: a round may write K+1 positions past the
        # valid length before the rollback trims it (KVCache.create pads
        # the buffer to the sublane granule on top)
        cap = self.max_seq + self.num_draft + 1
        tc = KVCache.create(self.cfg, self.cfg.num_layers, batch, cap,
                            dtype=self.kv_cache_dtype)
        dc = KVCache.create(self.draft_cfg, self.draft_cfg.num_layers,
                            batch, cap, dtype=self.kv_cache_dtype)
        if self._cache_sharding is not None:
            tc = jax.device_put(tc, self._cache_sharding)
            dc = jax.device_put(dc, self._cache_sharding)
        return tc, dc

    def _run_prefill_both(self, ids, tcache, dcache):
        """(last_target_logits, tcache, dcache) — whole-prompt in one
        fused program, or chunked per model (engine.run_chunked_prefill
        semantics: zero-pad, aligned last window, length rewind)."""
        C = self.prefill_chunk
        if C is None:
            return self._prefill_both(self.params, self.draft_params,
                                      ids, tcache, dcache)
        from .engine import run_chunked_prefill
        last, tcache = run_chunked_prefill(
            self.params, ids, tcache, C, self.max_seq,
            self._t_chunk_mid, self._t_chunk_last)
        _, dcache = run_chunked_prefill(
            self.draft_params, ids, dcache, C, self.max_seq,
            self._d_chunk_mid)
        return last, tcache, dcache

    def _run_prefills(self, ids, tcache, dcache):
        """The KV-cache-aware prefill front end: on a target-side block
        hit (batch 1), seed the target cache and prefill only its
        suffix while the draft prefills the full prompt; otherwise the
        fused/chunked both-model path.  Stores the target's full blocks
        afterwards — before the rounds program donates the cache."""
        from .engine import run_chunked_prefill
        b, plen = ids.shape
        start = 0
        if self.kv_cache is not None:
            start, tcache = self.kv_cache.seed(ids, tcache)
        if start:
            C = self.prefill_chunk
            suffix = ids[:, start:]
            if C is not None:
                last, tcache = run_chunked_prefill(
                    self.params, suffix, tcache, C, self.max_seq,
                    self._t_chunk_mid, self._t_chunk_last, start=start)
            else:
                last, tcache = self._t_chunk_last(
                    self.params, suffix, tcache, jnp.int32(start),
                    jnp.int32(suffix.shape[1] - 1))
                tcache = KVCache(tcache.keys, tcache.values,
                                 jnp.int32(plen))
            # draft side: always the full prompt (one logits-free
            # dispatch, or its own chunked drive)
            _, dcache = run_chunked_prefill(
                self.draft_params, ids, dcache, C if C else plen,
                self.max_seq, self._d_chunk_mid)
        else:
            last, tcache, dcache = self._run_prefill_both(ids, tcache,
                                                          dcache)
        if self.kv_cache is not None:
            self.kv_cache.store(ids, tcache)
        return last, tcache, dcache

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 seed: int = 0,
                 rounds_per_dispatch: Optional[int] = None
                 ) -> "tuple[GenerationResult, SpecStats]":
        """Generate with draft/verify rounds; returns (result, stats).

        ``rounds_per_dispatch``: how many rounds to fuse per device call
        (default 8, capped by the rounds max_new_tokens could possibly
        need — overshoot is trimmed; each extra round costs one wasted
        draft block, each missing round costs a full dispatch).
        """
        ids = jnp.asarray(prompt_ids, jnp.int32)
        b, plen = ids.shape
        check_capacity(self.max_seq, plen, max_new_tokens)
        R = rounds_per_dispatch or min(8, max(1, max_new_tokens))
        rng = jax.random.PRNGKey(seed)

        t0 = time.perf_counter()
        tcache, dcache = self.new_caches(b)
        last_logits, tcache, dcache = self._run_prefills(
            ids, tcache, dcache)
        # first token comes from the target's prefill logits (the draft
        # never gets to choose a token unchecked)
        rng, sub = jax.random.split(rng)
        last_tok = sample_logits(last_logits, sub, self.sampling)

        stats = SpecStats()
        first = np.asarray(last_tok)
        out = [first[:, None]]
        done = init_done(first, self.eos_id)
        total = 1
        while total < max_new_tokens and not done.all():
            em, ms, last_tok, tcache, dcache, rng = self._rounds(
                self.params, self.draft_params, last_tok, tcache, dcache,
                rng, R)
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
        """Yield [batch] token arrays per emitted token (UI streaming
        surface).  Tokens arrive in bursts — one verify round emits up to
        num_draft+1 at once — which is exactly speculative decoding's
        latency win showing through the stream.  ``stats_out``, if given,
        is updated in place per round (a generator can't return stats)."""
        if max_new_tokens <= 0:
            return
        ids = jnp.asarray(prompt_ids, jnp.int32)
        b, plen = ids.shape
        check_capacity(self.max_seq, plen, max_new_tokens)
        rng = jax.random.PRNGKey(seed)
        stats = stats_out if stats_out is not None else SpecStats()

        tcache, dcache = self.new_caches(b)
        last_logits, tcache, dcache = self._run_prefills(
            ids, tcache, dcache)
        rng, sub = jax.random.split(rng)
        last_tok = sample_logits(last_logits, sub, self.sampling)
        first = np.asarray(last_tok)
        yield first
        done = init_done(first, self.eos_id)
        total = stats.emitted = 1
        while total < max_new_tokens and not done.all():
            em, ms, last_tok, tcache, dcache, rng = self._rounds(
                self.params, self.draft_params, last_tok, tcache, dcache,
                rng, 1)
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


def stats_json(stats: Optional[SpecStats], num_draft: int) -> Optional[dict]:
    """SpecStats → JSON-safe dict (0 rounds yields NaN rates; JSON has no
    NaN).  The one shaping shared by the CLI and the HTTP backend."""
    if stats is None:
        return None

    def finite(x, nd):
        return round(x, nd) if x == x else None

    return {"num_draft": num_draft,
            "rounds": stats.rounds,
            "acceptance_rate": finite(stats.acceptance_rate, 4),
            "tokens_per_round": finite(stats.tokens_per_round, 3)}


class SpeculativeBackend:
    """Adapts SpeculativeEngine to the HTTP backend surface (engine-style
    ``generate`` returning a result object, plus acceptance stats on
    ``/stats``).  Follows HeaderBackend's streaming discipline
    (http_server.py): the device runs on a worker thread that holds the
    lock only at device pace, tokens cross to the client-paced generator
    over a queue — a stalled client can't wedge the server."""

    def __init__(self, engine: SpeculativeEngine):
        self.engine = engine
        self.max_seq = engine.max_seq
        self.last_stats: Optional[SpecStats] = None
        self._lock = threading.Lock()   # one generation at a time

    def generate(self, prompt_ids, max_new_tokens: int, seed: int = 0):
        with self._lock:
            res, stats = self.engine.generate(prompt_ids, max_new_tokens,
                                              seed=seed)
            self.last_stats = stats
        return res

    def generate_stream(self, prompt_ids, max_new_tokens: int,
                        seed: int = 0):
        import queue as queue_mod

        q: "queue_mod.Queue" = queue_mod.Queue()
        SENTINEL = object()
        stats = SpecStats()

        def run():
            try:
                with self._lock:
                    for toks in self.engine.generate_stream(
                            prompt_ids, max_new_tokens, seed=seed,
                            stats_out=stats):
                        q.put(toks)
                    self.last_stats = stats
            except BaseException as e:     # surface in the consumer
                q.put(e)
            finally:
                q.put(SENTINEL)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is SENTINEL:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
        t.join(timeout=10)

    def stats(self) -> dict:
        with self._lock:
            return {"speculative": stats_json(self.last_stats,
                                              self.engine.num_draft)}
