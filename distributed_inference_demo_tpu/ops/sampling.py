"""Token sampling: temperature / top-k / top-p / greedy, jit-friendly.

TPU-native replacement for the reference's ``StaticDecoding`` C++ sampler
(``cpp/decoding.cpp:24-66``: top-k over the last position via partial_sort,
renormalize, discrete_distribution) and its mislabeled ``GreedyDecoding``
(actually top-k=6 sampling, ``cpp/inference.cpp:107-143``).  All variants are
static-shape jnp programs so they fuse into the tail stage's jitted step —
no host round-trip per token.  The reference's temperature support exists but
is commented out (``decoding.cpp:51-52``); here it works.
"""

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.tree_util.register_dataclass,
         data_fields=[], meta_fields=["temperature", "top_k", "top_p",
                                      "min_p", "greedy"])
@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.7   # reference default: BackgroundService.java:113
    top_k: int = 7             # reference default k=7
    top_p: float = 1.0
    min_p: float = 0.0         # keep tokens with prob >= min_p * max_prob
    greedy: bool = False

    def __post_init__(self):
        # min_p > 1 would mask even the max-probability token (the fused
        # and full-vocab paths then disagree on a meaningless output);
        # reject at construction, where the CLI renders it as a one-line
        # config error
        if not 0.0 <= self.min_p <= 1.0:
            raise ValueError(f"min_p must be in [0, 1], got {self.min_p}")


def pad_stop_ids(stop_token_ids) -> jnp.ndarray:
    """Stop-token ids as the traced ``[S]`` int32 vector the device
    decode loops consume (``-1`` = empty slot, matching the eos
    sentinel convention).  ``None``/empty becomes a single ``-1`` slot
    so every engine compiles ONE loop shape whether or not stops are
    configured."""
    ids = sorted(set(int(t) for t in (stop_token_ids or ())))
    if any(t < 0 for t in ids):
        raise ValueError(f"stop_token_ids must be >= 0, got {ids}")
    return jnp.asarray(ids or [-1], jnp.int32)


def match_stop_ids(tok: jnp.ndarray, stop_ids: jnp.ndarray) -> jnp.ndarray:
    """[b] sampled tokens vs the padded ``[S]`` stop-id vector -> [b]
    bool (True where the token IS a stop id).  Pure compare-and-any, so
    it fuses into the decode loops' step body; ``-1`` slots can never
    match (token ids are non-negative)."""
    return jnp.any((tok[:, None] == stop_ids[None, :])
                   & (stop_ids[None, :] >= 0), axis=-1)


def kth_largest(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest value of [..., vocab] logits (counting
    duplicates, exactly ``lax.top_k(x, k)[0][..., -1]``), as k
    argmax-and-mask passes instead of a sort.

    Decode's top-k filter needs only this one VALUE per row, but
    ``lax.top_k`` pays a full-vocab sort per step — per-row VPU work that
    grows with batch (not measured on the chip: ROADMAP Speed 3 reads
    it from a traced run's ``breakdown.device_ops``).  k-1 (argmax,
    mask-one-element) rounds plus a final max are O(k*V)
    elementwise/reduce work with no sort; each round masks only
    the FIRST occurrence of the current max (argmax's tie rule), so
    duplicate logit values count toward k exactly as in top_k.  For
    large k the unrolled rounds lose to the sort — callers gate on k."""
    x = logits
    iota = jnp.arange(x.shape[-1])
    for _ in range(k - 1):
        idx = jnp.argmax(x, axis=-1, keepdims=True)
        x = jnp.where(iota == idx, -jnp.inf, x)
    return jnp.max(x, axis=-1, keepdims=True)


def topk_vals_idx(logits: jnp.ndarray, k: int, with_mask: bool = False):
    """Exact top-k (values, indices) of [..., vocab] logits via k
    argmax-and-mask passes — no full-vocab sort.  Ties resolve to the
    first occurrence per round, i.e. the same index set as
    ``lax.top_k``.  Same O(k*V) elementwise shape as :func:`kth_largest`
    (which keeps only the k-th VALUE); this variant also carries the
    indices so the sampler can draw over k candidates instead of the
    whole vocab.  ``with_mask`` additionally returns the boolean
    membership mask over the vocab axis (accumulated for free during the
    passes — it is exactly the set of removed maxima)."""
    x = logits
    iota = jnp.arange(x.shape[-1])
    vals, idxs = [], []
    member = jnp.zeros(x.shape, bool)
    for _ in range(k):
        i = jnp.argmax(x, axis=-1)
        vals.append(jnp.take_along_axis(x, i[..., None], axis=-1)[..., 0])
        idxs.append(i)
        hit = iota == i[..., None]
        member = member | hit
        x = jnp.where(hit, -jnp.inf, x)
    out = (jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1))
    return out + (member,) if with_mask else out


def topk_mask(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Boolean membership mask of the exactly-k first-occurrence top-k
    over [..., vocab] — the ONE tie semantic shared by
    :func:`filtered_logits` and :func:`sample_logits`'s fused draw (a
    value-threshold mask would keep MORE than k tokens when logits tie
    at the k-th boundary, silently diverging from the fused draw's
    distribution).  Small k: iterative passes; large k: ``lax.top_k``
    (same first-occurrence tie rule) + scatter.  Crossover at 16: the
    unrolled argmax/mask/take rounds triple per-round ops vs a sort at
    k=32 (compile time and program size grow linearly with k), while the
    serving defaults (k<=8) stay comfortably on the sort-free path."""
    if k <= 16:
        return topk_vals_idx(logits, k, with_mask=True)[2]
    _, idx = jax.lax.top_k(logits, k)
    flat = idx.reshape(-1, k)
    m = jnp.zeros((flat.shape[0], logits.shape[-1]), bool)
    m = m.at[jnp.arange(flat.shape[0])[:, None], flat].set(True)
    return m.reshape(logits.shape)


def _temperature_scaled(logits: jnp.ndarray,
                        params: SamplingParams) -> jnp.ndarray:
    """f32 + temperature preamble shared by filtered_logits and the fused
    draw — one owner, so the two distribution-identical paths cannot
    drift."""
    logits = logits.astype(jnp.float32)
    if params.temperature != 1.0:
        logits = logits / jnp.maximum(params.temperature, 1e-6)
    return logits


def filtered_logits(logits: jnp.ndarray,
                    params: SamplingParams) -> jnp.ndarray:
    """Apply temperature / top-k / top-p to [..., vocab] logits.

    ``softmax(filtered_logits(l, p))`` IS the sampling distribution of
    ``sample_logits(l, rng, p)`` — speculative decoding's accept/resample
    rule (runtime/speculative.py) needs that distribution explicitly for
    both the draft and the target, so the filter lives here, next to the
    sampler it must stay consistent with.  Not meaningful for greedy
    (argmax needs no distribution).
    """
    # top-k membership is computed on the NATIVE-dtype logits, BEFORE
    # temperature scaling — the same selection rule as sample_logits'
    # fused draw, so the two paths keep identical candidate sets by
    # construction (scaling first could collapse 1-ulp-apart f32 values
    # into a boundary tie and flip the kept set).  Exactly-k
    # first-occurrence membership (topk_mask), NOT a value threshold,
    # which would keep extra boundary-tied tokens.
    keep = (topk_mask(logits, params.top_k)
            if 0 < params.top_k < logits.shape[-1] else None)
    logits = _temperature_scaled(logits, params)
    if keep is not None:
        logits = jnp.where(keep, logits, -jnp.inf)

    if params.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until cumulative prob exceeds top_p (always keep top-1)
        cutoff_mask = cum - probs > params.top_p
        cutoff = jnp.where(cutoff_mask, -jnp.inf, sorted_logits)
        threshold = jnp.min(jnp.where(jnp.isfinite(cutoff), cutoff, jnp.inf),
                            axis=-1, keepdims=True)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)

    if params.min_p > 0.0:
        # min-p: keep tokens whose probability is >= min_p * max_prob on
        # the temperature-scaled distribution.  prob_i / prob_max =
        # exp(logit_i - logit_max), so the filter is a pure max + compare
        # — no sort, no cumsum (why min-p scales where top-p doesn't).
        # The max logit survives every earlier mask, so the threshold is
        # order-independent w.r.t. top-k/top-p.
        thr = (jnp.max(logits, axis=-1, keepdims=True)
               + jnp.log(params.min_p))
        logits = jnp.where(logits < thr, -jnp.inf, logits)
    return logits


def sample_logits(logits: jnp.ndarray, rng: jax.Array,
                  params: SamplingParams) -> jnp.ndarray:
    """Sample next-token ids from [batch, vocab] logits -> [batch] int32.

    Small-k top-k sampling (the serving default, k=7) draws the
    categorical over the [batch, k] candidate VALUES and gathers the
    chosen index, instead of masking the vocab and drawing over
    [batch, vocab] — saves the full-vocab gumbel+softmax passes that
    grow with batch.  The sampling
    DISTRIBUTION is identical to ``softmax(filtered_logits(...))`` (the
    contract speculative decoding's accept/resample rule depends on);
    only the RNG consumption pattern differs, so a fixed seed yields a
    different — equally distributed — sequence than the full-vocab
    draw would."""
    if params.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = params.top_k
    if 0 < k <= 16 and k < logits.shape[-1] and params.top_p >= 1.0:
        # select on the NATIVE dtype — the same rule filtered_logits
        # applies (its top-k mask is also computed pre-scaling), so the
        # candidate SET is identical by construction — then scale only
        # the [batch, k] values: no full-vocab f32 cast or divide pass
        vals, idx = topk_vals_idx(logits, k)
        vals = _temperature_scaled(vals, params)
        if params.min_p > 0.0:
            # vals are descending, so vals[..., :1] IS the global max
            # logit — the same threshold filtered_logits computes over
            # the full vocab (tokens min-p would mask outside the top-k
            # are already excluded), keeping the two paths
            # distribution-identical
            vals = jnp.where(
                vals < vals[..., :1] + jnp.log(params.min_p),
                -jnp.inf, vals)
        choice = jax.random.categorical(rng, vals, axis=-1)
        return jnp.take_along_axis(
            idx, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)
    return jax.random.categorical(
        rng, filtered_logits(logits, params), axis=-1).astype(jnp.int32)
