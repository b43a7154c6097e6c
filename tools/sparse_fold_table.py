#!/usr/bin/env python
"""A sparse kind's prefill fold beside the accepted dense one, on the chip:
ms a call of each over the same pages, for a slab's ``--segments`` segments
of 256 queries that all start at ``--starts`` (PERF.md section 5, PR 69):

    python tools/sparse_fold_table.py
    python tools/sparse_fold_table.py --starts 32768 --reps 20

The shapes are a configuration's (``--model minicpm-sala-9b-bf16``: GQA
32 / 2, heads of 128, pages of 128 tokens, blocks of 64, top-64).  Keys,
values and queries are seeded normals in bfloat16, the index rows are the
keys' pooled means as ``write_index`` leaves them.  The columns:

* ``dense_ms``: ``ops.paged_attention.paged_prefill_attention``
  (``_paged_prefill_call``, the chunk cut into the kernel's tiles as the
  seam cuts it), every live page of every segment: what the same layer
  would cost without the selection.
* ``select_ms``: ``select_blocks(kernel=True)``: the index rows gathered,
  ``_sparse_scores``, the bisection.
* ``fold_ms``: ``sparse_fold`` under that selection (``tile_entries``'
  lists and words, then ``_paged_prefill_call_sparse``), and
  ``union_share``, the blocks a tile of 32 queries folds over the blocks of
  its context: over seeded q and k the queries of a tile keep nearly
  unrelated blocks, so a tile folds about everything.
* ``fold_shared_ms``: the same fold where a tile's queries all keep the
  blocks its FIRST query chose beside their own forced ones (a union of
  ~98 blocks): what the call costs when neighbouring queries agree, as a
  checkpoint's do.  A bound from the other side, not a claim about any
  checkpoint.

A start under ``dense_len`` shows the dense rule through the sparse call.
A time is the least of three runs of ``--reps`` calls, host clock around
``block_until_ready``.  One JSON line a row, also to
``chiprun_out/sparse_fold_table.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tools"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_inference_demo_tpu.ops import (  # noqa: E402
    sparse_attention as sa)
from distributed_inference_demo_tpu.ops.paged_attention import (  # noqa: E402
    paged_prefill_attention, sub_chunk)
from distributed_inference_demo_tpu.ops.stacked import LayerOf  # noqa: E402


def pooled_rows(K, stride: int, kernel: int):
    """The index rows of a pool whose pages lie in their requests' order,
    ``[pages x bt / stride, nkv x hd]``: row ``j`` the mean of the
    ``kernel`` keys from token ``stride j`` on (the last of a pool run
    into nothing, and no query reads them)."""
    N, nkv, bt, hd = K.shape
    lin = K.transpose(0, 2, 1, 3).reshape(N * bt, nkv * hd).astype(jnp.float32)
    lin = jnp.pad(lin, ((0, kernel), (0, 0)))
    rows = sum(lin[d:d + N * bt:stride] for d in range(kernel)) / kernel
    return rows.astype(K.dtype)


def timed(fn, *args, reps: int) -> float:
    """ms a call: the least of three runs of ``reps`` calls."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return 1e3 * best


def main(argv=None) -> int:
    from bench_config import model_config_for

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="minicpm-sala-9b-bf16")
    ap.add_argument("--segments", type=int, default=5)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--page", type=int, default=128)
    ap.add_argument("--starts", type=int, nargs="+",
                    default=[4096, 8192, 16384, 24576, 32768, 40704])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path,
                    default=ROOT / "chiprun_out" / "sparse_fold_table.jsonl")
    args = ap.parse_args(argv)

    cfg = model_config_for(args.model)
    kind = cfg.sparse_kind
    sizes = kind.sparse_sizes
    kernel, stride, block = sizes[:3]
    nkv, nh, hd = cfg.num_kv_heads, kind.num_heads, cfg.head_dim
    r, C, bt = args.segments, args.chunk, args.page
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    W = -(-(max(args.starts) + C) // bt)
    N = r * W
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    K = jax.random.normal(ks[0], (1, N, nkv, bt, hd), jnp.bfloat16)
    V = jax.random.normal(ks[1], (1, N, nkv, bt, hd), jnp.bfloat16)
    index = LayerOf(pooled_rows(K[0], stride, kernel)[None], jnp.int32(0))
    kp, vp = LayerOf(K, jnp.int32(0)), LayerOf(V, jnp.int32(0))
    tables = jnp.arange(N, dtype=jnp.int32).reshape(r, W)
    q = jax.random.normal(ks[2], (r, C, nh, hd), jnp.bfloat16)
    tq = sa.tile_queries(C, nh // nkv)

    # as the seam hands a chunk to the dense kernel: tiles of the queries
    # whose rows it holds, each a chunk that starts later
    sub = sub_chunk(C, nh // nkv)
    cut = lambda a: a.reshape((r * (C // sub), sub) + a.shape[2:])  # noqa: E731
    dense = jax.jit(lambda q, pos: paged_prefill_attention(
        cut(q), kp, vp, jnp.repeat(tables, C // sub, axis=0), cut(pos),
        interpret=interpret).reshape(q.shape))
    select = jax.jit(lambda q, pos: sa.select_blocks(
        q, index, tables, pos, sizes, nkv, bt, kernel=True,
        interpret=interpret))
    fold = jax.jit(lambda q, pos, keep: sa.sparse_fold(
        q, kp, vp, tables, pos, keep, block=block, cap=keep.shape[-1],
        interpret=interpret))

    args.out.parent.mkdir(exist_ok=True)
    with args.out.open("a") as f:
        for start in args.starts:
            pos = jnp.broadcast_to(start + jnp.arange(C, dtype=jnp.int32),
                                   (r, C))
            keep = select(q, pos)
            NB = keep.shape[-1]
            blk = jnp.arange(NB)
            last = (pos // block)[:, :, None, None]
            own = (blk <= last) & ((blk < sizes[4])
                                   | (blk > last - sizes[5] // block))
            first = jnp.repeat(keep[:, ::tq], tq, axis=1)
            shared = (own | first) & (blk <= last)
            if start + C <= sizes[6]:       # the dense rule keeps all
                shared = keep
            tiles = keep.reshape(r, C // tq, tq, nkv, NB).any(axis=2)
            live = (start + C - 1) // block + 1
            row = {
                "model": args.model, "device_kind": dev.device_kind,
                "segments": r, "chunk": C, "start": start,
                "tile_queries": tq, "blocks_live": live,
                "kept_a_query": float(keep[:, -1].sum(-1).mean()),
                "union_share": float(tiles.sum(-1).mean()) / live,
                "shared_union": float(shared.reshape(
                    r, C // tq, tq, nkv, NB).any(axis=2).sum(-1).mean()),
                "dense_ms": timed(dense, q, pos, reps=args.reps),
                "select_ms": timed(select, q, pos, reps=args.reps),
                "fold_ms": timed(fold, q, pos, keep, reps=args.reps),
                "fold_shared_ms": timed(fold, q, pos, shared,
                                        reps=args.reps)}
            line = json.dumps({k: (round(v, 4) if isinstance(v, float)
                                   else v) for k, v in row.items()})
            print("SPARSE_FOLD " + line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
