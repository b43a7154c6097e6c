"""EVA attention as EvaByte specialises it (Zheng et al., "Efficient
Attention via Control Variates", arXiv:2302.04542; docs/DESIGN.md
section 26): exact softmax attention inside the query's own window of
``W`` tokens, every EARLIER window seen as ``W / C`` summaries (one
learned-pooled key and value a chunk of ``C`` tokens), one joint softmax
over both.

    k~_c = sum_j softmax_j(k_j . mu_h) k_j      v~_c = sum_j softmax_j(k_j . phi_h) v_j
    query i, window w = i // W:   exact keys {j <= i, j // W = w},
                                  summaries {c : c // (W / C) < w}

Keys are roped at their true positions BEFORE they are pooled; ``mu_h``,
``phi_h`` are learned, one pair a kv head a layer.  A chunk's summary is
visible to no query of its own window, so a partial chunk is never read.

Two forms, one arithmetic:

- :func:`eva_dense_attn`: over a dense cache that keeps every token; the
  summaries are pooled from the cache at every call (``stage_forward``
  without a page pool: scoring, the plain engine, the tests).
- :func:`paged_eva_attention`: over the page pool.  Both roles of row are
  ``[kv heads, head_dim]`` keys and values, so both live in the one pool
  at the one page shape: a page of ``bt = W / C`` tokens holds one closed
  window's summaries or ``bt`` exact rows.  A request leases one summary
  page a window (``S_0, S_1, ...``) and ``W / bt`` window pages
  (``P_0 ...``), written from the first again after every close.  At
  position ``t`` with ``w = t // W`` closed windows the ATTENDED table is
  ``[S_0 .. S_{w-1}, P_0 .. P_{W/bt-1}]``, token ``t`` sits at row ``bt w
  + t % W`` of it and the row attends ``bt w + t % W + 1`` rows: pure
  functions of ``t`` and the two leases (:func:`eva_tables`,
  :func:`eva_positions`), built on the device at every call, so a window
  may close at any step of a fused decode block.  The paged kernels walk
  that table to that length unchanged (causal by row index: every
  summary row lies before every exact one).  A chunk that a call
  completes is pooled into row ``(t % W) // C`` of the PENDING summary
  page ``S_w``, which no table shows until ``t`` reaches the next
  multiple of ``W``: closing a window is no device work at all.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import update_kv_cache
from .paged_attention import (POOL_PLANE, WRITE_SCATTER, _write_group,
                              route_pool, write_paged_kv)
from .quant import QuantizedKVPages
from .stacked import LayerOf

_HI = jax.lax.Precision.HIGHEST


def eva_pool(k, v, mu, phi):
    """The summaries of whole chunks: ``k`` / ``v`` ``[..., n, C, nkv,
    hd]`` (the roped keys and the values of ``n`` chunks of ``C`` tokens)
    to ``(k~, v~)`` ``[..., n, nkv, hd]`` in float32.  Both sets of
    weights come from the KEYS: ``softmax_j(k_j . mu_h)`` pools the keys,
    ``softmax_j(k_j . phi_h)`` the values, with no further factor."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    weights = lambda w: jax.nn.softmax(jnp.einsum(
        "...cnd,nd->...cn", kf, w.astype(jnp.float32), precision=_HI),
        axis=-2)
    pooled = lambda w, x: jnp.einsum("...cn,...cnd->...nd", w, x,
                                     precision=_HI)
    return pooled(weights(mu), kf), pooled(weights(phi), vf)


def eva_attention(q, k_cache, v_cache, q_positions, window: int, chunk: int,
                  mu, phi):
    """EVA attention of a chunk of queries ``[b, s, nh, hd]`` at
    ``q_positions`` ``[b, s]`` over a dense head-major cache ``[b, nkv,
    S, hd]`` that holds every token at its position: the exact keys of
    each query's window, the summaries (pooled here, from the cache) of
    every chunk of every window before it, one softmax."""
    b, s, nh, hd = q.shape
    nkv, S = k_cache.shape[1], k_cache.shape[2]
    g = nh // nkv
    n_c = S // chunk
    qf = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, s, nkv, g, hd)
    kf, vf = k_cache.astype(jnp.float32), v_cache.astype(jnp.float32)
    chunks = lambda a: a[:, :, :n_c * chunk].reshape(
        b, nkv, n_c, chunk, hd).transpose(0, 2, 3, 1, 4)
    ks, vs = eva_pool(chunks(kf), chunks(vf), mu, phi)  # [b, n_c, nkv, hd]
    exact = jnp.einsum("bqkgh,bksh->bkgqs", qf, kf, precision=_HI)
    summ = jnp.einsum("bqkgh,bckh->bkgqc", qf, ks, precision=_HI)
    qpos = q_positions[:, :, None]
    kv_pos = jnp.arange(S)[None, None, :]
    see_exact = (kv_pos <= qpos) & (kv_pos // window == qpos // window)
    see_summ = (jnp.arange(n_c)[None, None, :] // (window // chunk)
                < qpos // window)
    scores = jnp.concatenate([
        jnp.where(see_exact[:, None, None], exact, -1e30),
        jnp.where(see_summ[:, None, None], summ, -1e30)], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    out = (jnp.einsum("bkgqs,bksh->bqkgh", probs[..., :S], vf,
                      precision=_HI)
           + jnp.einsum("bkgqc,bckh->bqkgh", probs[..., S:], vs,
                        precision=_HI))
    return out.reshape(b, s, nh, hd).astype(q.dtype)


def eva_dense_attn(window: int, chunk: int, mu, phi):
    """The attention hook over a dense cache (``models.decoder.
    _default_attn``'s signature): insert the chunk at its true
    positions, attend under the EVA mask."""

    def attn(q, k, v, k_cache, v_cache, positions, cache_start, slopes):
        k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v,
                                           cache_start)
        out = eva_attention(q, k_cache, v_cache, positions, window, chunk,
                            mu, phi)
        return out, k_cache, v_cache

    return attn


# ---------------------------------------------------------------------------
# the page pool: tables and positions as functions of t


def eva_positions(positions, window: int, bt: int):
    """Row of the attended table that the token at ``positions`` sits at:
    ``bt`` summary rows a closed window, then its offset in the open
    one.  Plain arithmetic: on traced arrays in the hook, on Python
    integers in the scheduler's records."""
    return positions // window * bt + positions % window


def eva_tables(raw, closed, window_pages: int, sentinel: int):
    """The attended tables ``[b, Wt]`` of rows with ``closed`` ``[b]``
    closed windows, from their leases ``raw`` ``[b, Wt]`` = ``[S_0 ..
    S_{Ws-1} | P_0 .. P_{window_pages-1}]``: ``[S_0 .. S_{closed-1}, P_0
    .. P_{window_pages-1}, sentinel ...]``.  The pending summary page
    ``S_closed`` is in no table."""
    Wt = raw.shape[1]
    Ws = Wt - window_pages
    j = jnp.arange(Wt, dtype=jnp.int32)[None, :]
    c = closed.astype(jnp.int32)[:, None]
    summary = (j < c) & (j < Ws)
    in_window = (j >= c) & (j - c < window_pages)
    src = jnp.where(summary, j, Ws + j - c)
    entry = jnp.take_along_axis(raw, jnp.clip(src, 0, Wt - 1), axis=1)
    return jnp.where(summary | in_window, entry, sentinel).astype(jnp.int32)


# ---------------------------------------------------------------------------
# pooling a chunk that a decode step completed, from the cached rows


def _summarise_kernel(page_ref, row_ref, live_ref, layer_ref, mu_ref,
                      phi_ref, k_hbm, v_hbm, ko_ref, vo_ref, k_buf, v_buf,
                      sems, *, chunk: int):
    """Grid (b,): row ``b``'s chunk is rows ``[row, row + chunk)`` of page
    ``page_ref[b]`` of layer ``layer_ref[0]``, all kv heads (``[nkv,
    chunk, hd]``, one copy a pool).  A row that completed no chunk
    (``live_ref[b] == 0``) moves nothing and answers zeros."""
    b = pl.program_id(0)

    @pl.when(live_ref[b] > 0)
    def _pool():
        row = pl.multiple_of(row_ref[b], chunk)
        copies = [
            pltpu.make_async_copy(
                hbm.at[layer_ref[0], page_ref[b], :, pl.ds(row, chunk), :],
                buf, sems.at[i])
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()
        k = k_buf[...].astype(jnp.float32)              # [nkv, chunk, hd]
        v = v_buf[...].astype(jnp.float32)

        def pooled(w, x):                               # w [nkv, 1, hd]
            logit = jnp.sum(k * w, axis=-1, keepdims=True)
            p = jnp.exp(logit - jnp.max(logit, axis=1, keepdims=True))
            p = p / jnp.sum(p, axis=1, keepdims=True)
            return jnp.sum(p * x, axis=1, keepdims=True)   # [nkv, 1, hd]

        ko_ref[0] = pooled(mu_ref[...], k)
        vo_ref[0] = pooled(phi_ref[...], v)

    @pl.when(live_ref[b] == 0)
    def _idle():
        ko_ref[0] = jnp.zeros(ko_ref.shape[1:], ko_ref.dtype)
        vo_ref[0] = jnp.zeros(vo_ref.shape[1:], vo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _eva_summarise(page, row, live, layer, mu, phi, k_pages, v_pages, *,
                   chunk, interpret):
    """The Pallas call, named as the trace readers know it
    (``_eva_summarise.<n>``): the stacked pools stay in HBM and only the
    completed chunks' rows move."""
    b = page.shape[0]
    nkv, hd = k_pages.shape[2], k_pages.shape[4]
    vec_spec = pl.BlockSpec((nkv, 1, hd), lambda i, *_: (0, 0, 0))
    out_spec = pl.BlockSpec((1, nkv, 1, hd), lambda i, *_: (i, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((nkv, chunk, hd), k_pages.dtype)
    out = jax.ShapeDtypeStruct((b, nkv, 1, hd), jnp.float32)
    return pl.pallas_call(
        functools.partial(_summarise_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[vec_spec, vec_spec, pool_spec, pool_spec],
            out_specs=[out_spec, out_spec],
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[out, out],
        interpret=interpret,
    )(page, row, live, layer, mu, phi, k_pages, v_pages)


def summarise_cached(k_pages: LayerOf, v_pages: LayerOf, page, row, live,
                     mu, phi, chunk: int, *, kernel: bool,
                     interpret: bool = False):
    """``(k~, v~)`` ``[b, 1, nkv, hd]`` float32 of the chunk each row
    completed: rows ``[row[b], row[b] + chunk)`` of page ``page[b]`` of
    the layer's pool (zeros, or garbage the caller drops, where ``live``
    is false).  ``kernel``: the Pallas call (plain pages whose head fills
    the lanes and whose chunk is whole tile groups), else an XLA gather
    of the same rows."""
    K, V, li = k_pages.stack, v_pages.stack, k_pages.layer
    num_pages = K.shape[1]
    page = jnp.minimum(page, num_pages - 1).astype(jnp.int32)
    if kernel:
        vec = lambda w: w.astype(jnp.float32)[:, None, :]
        ks, vs = _eva_summarise(
            page, row.astype(jnp.int32), live.astype(jnp.int32),
            jnp.asarray(li, jnp.int32).reshape(1), vec(mu), vec(phi), K, V,
            chunk=chunk, interpret=interpret)
        return ks.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1, 3)
    rows = row[:, None] + jnp.arange(chunk)[None, :]
    gather = lambda P: P[li, page[:, None], :, rows]    # [b, C, nkv, hd]
    ks, vs = eva_pool(gather(K)[:, None], gather(V)[:, None], mu, phi)
    return ks, vs


def _summarise_covers(backend: str, platform: str, pool, chunk: int) -> bool:
    """Whether the Pallas pooling call covers this pool: where the Pallas
    page write does (``route_pool``), at a chunk of whole tile groups."""
    if backend == "xla" or (backend == "auto" and platform != "tpu"):
        return False
    return (not isinstance(pool, QuantizedKVPages)
            and pool.shape[-1] % 128 == 0
            and chunk % _write_group(pool.dtype) == 0)


def _write_rows(k_pages, v_pages, k_new, v_new, tables, positions, backend,
                interpret):
    """``write_paged_kv`` into the stacked pool by the route its shape
    takes (``route_pool``: in place, or through the layer's plane)."""
    pool = route_pool(backend, jax.default_backend(), k_pages,
                      k_new.shape[1])
    if pool != POOL_PLANE:
        return write_paged_kv(k_pages, v_pages, k_new, v_new, tables,
                              positions, form=pool, interpret=interpret)
    kp, vp = write_paged_kv(k_pages.sliced(), v_pages.sliced(), k_new,
                            v_new, tables, positions, form=WRITE_SCATTER)
    return (LayerOf(k_pages.updated(kp), k_pages.layer),
            LayerOf(v_pages.updated(vp), v_pages.layer))


def paged_eva_attention(attend, q, k, v, k_pages: LayerOf, v_pages: LayerOf,
                        positions, raw_tables, program: str, window: int,
                        chunk: int, mu, phi, *, backend: str = "auto",
                        interpret: bool = False):
    """One layer call of EVA attention over the page pool: ``(out,
    k_pages', v_pages')``.

    ``positions`` ``[b, s]`` are the tokens' TRUE positions (contiguous a
    row, the whole chunk inside one window: ``s == 1``, or ``s`` divides
    the window and every chunk starts on a multiple of ``s``);
    ``raw_tables`` ``[b, Wt]`` a row's leases, summary pages then window
    pages.  ``attend`` is ``make_paged_attn_impl``'s write-then-attend
    over a table (the two paged kernels or the gather, by its routing):
    it gets the attended table and the rows' places in it.  Then the
    chunks this call completed are pooled into the pending summary page:
    all ``s / C`` of a prefill chunk, from the chunk's own keys and
    values as the pool holds them (cast to the page dtype); in decode the
    rows whose ``t % C == C - 1``, from the ``C`` cached rows."""
    pool = k_pages.stack
    if isinstance(pool, QuantizedKVPages):
        raise ValueError("a summarised cache keeps plain (bf16) pages: a "
                         "summary is pooled from the rows the pool holds")
    num_pages, bt = pool.shape[1], pool.shape[3]
    s = q.shape[1]
    if window % bt or window // chunk != bt:
        raise ValueError(
            f"a summary page is one closed window: window / chunk "
            f"({window} / {chunk}) must equal the page's tokens ({bt}) "
            f"(--kv-block-tokens {window // chunk})")
    if s > 1 and (window % s or s % chunk):
        raise ValueError(
            f"a chunk of {s} tokens must divide the window ({window}) and "
            f"hold whole pooling chunks of {chunk} (--prefill-chunk): it "
            f"then lies in one window and completes every chunk it holds")
    window_pages = window // bt
    n_summary = raw_tables.shape[1] - window_pages
    closed = positions[:, 0] // window
    cpos = eva_positions(positions, window, bt)
    tables = eva_tables(raw_tables, closed, window_pages, num_pages)
    out, k_pages, v_pages = attend(q, k, v, k_pages, v_pages, cpos, None,
                                   tables, program, eva=True)
    summary_tables = raw_tables[:, :n_summary]
    with jax.named_scope("eva_summarise"):
        if s == 1:
            t = positions[:, 0]
            first = cpos[:, 0] - (chunk - 1)
            page = jnp.take_along_axis(
                tables, jnp.clip(first // bt, 0, tables.shape[1] - 1)[:, None],
                axis=1)[:, 0]
            live = (t % chunk == chunk - 1) & (page < num_pages)
            ks, vs = summarise_cached(
                k_pages, v_pages, page, jnp.maximum(first, 0) % bt, live,
                mu, phi, chunk,
                kernel=_summarise_covers(backend, jax.default_backend(),
                                         pool, chunk),
                interpret=interpret)
            spos = (closed * bt + (t % window) // chunk)[:, None]
            # a row that completed no chunk writes nowhere
            summary_tables = jnp.where(live[:, None], summary_tables,
                                       num_pages)
        else:
            n = s // chunk
            held = lambda a: a.astype(pool.dtype).reshape(
                (a.shape[0], n, chunk) + a.shape[2:])
            ks, vs = eva_pool(held(k), held(v), mu, phi)
            spos = (closed * bt + (positions[:, 0] % window) // chunk
                    )[:, None] + jnp.arange(n)[None, :]
        k_pages, v_pages = _write_rows(k_pages, v_pages, ks, vs,
                                       summary_tables, spos, backend,
                                       interpret)
    return out, k_pages, v_pages
