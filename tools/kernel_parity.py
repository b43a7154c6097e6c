#!/usr/bin/env python3
"""Compile every Pallas kernel specialisation on this device (the attention
kernels and the experts' grouped matmul) and compare it with the XLA path
it replaces, at the shapes the server runs.

    python tools/kernel_parity.py                 # the chip (chip_smoke's
                                                  # third phase)
    python tools/kernel_parity.py --engines       # + each specialisation
                                                  # through an engine
    JAX_PLATFORMS=cpu python tools/kernel_parity.py --interpret
                                                  # debug the tool itself

Interpret-mode parity (tests/) pins the kernels' arithmetic; it says
nothing about Mosaic's lowering.  Each static specialisation is its own
Mosaic program: bf16 pages, int8 pages (``quantized``), ALiBi on/off, the
decode and the prefill kernel, and the dense ``flash_attention`` kernel.
The pages are filled through ``write_paged_kv`` (the served write path,
which also quantizes), the reference is ``paged_gather_attention`` /
``ops.attention.attention`` under ``jax.default_matmul_precision
("highest")`` on the SAME device — on a TPU a float32 einsum is otherwise
a one-pass bf16 product, and a reference must not share the error it is
there to bound.

The prefill kernel's page loop runs ragged calls at 128-token pages and the
cells' shapes (``PREFILL_LOOP_SHAPES``: laguna's full and window kind over
a table of 200 at contexts of 0 pages, 1, 53 and 200, bloom with ALiBi,
olmoe): against the gather like every case, and against the GRID kernel on
the same tiles, which must be EQUAL; both are then timed alone
(``--prefill-only`` runs these cases and nothing else).

The grouped matmul (``ops.grouped_matmul``) runs at the expert layer's two
shapes in ``olmoe-1b-7b`` (256 token-expert rows: a decode step at 32
slots; 4,096: the 512-token slab), both projections' shapes, int8 and
bf16 stacks, with uniform, Zipf and one-expert-takes-all groups (the last
two leave experts empty), against ``ragged_dot`` at the highest precision
on the same device, and then timed alone (``gmm_times``).

One ``KERNEL {json}`` line per case, ``KERNEL_PARITY_DONE`` at the end,
exit code 1 if any case was refused or disagreed.  A refusal carries
Mosaic's message.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_inference_demo_tpu.models.registry import (  # noqa: E402
    get_model_config)
from distributed_inference_demo_tpu.ops.attention import (  # noqa: E402
    alibi_slopes, attention)
from distributed_inference_demo_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention)
from distributed_inference_demo_tpu.ops import (  # noqa: E402
    paged_attention as pa)
from distributed_inference_demo_tpu.ops.paged_attention import (  # noqa: E402
    paged_flash_attention, paged_gather_attention, paged_prefill_attention,
    write_paged_kv)
from distributed_inference_demo_tpu.ops.grouped_matmul import (  # noqa: E402
    grouped_matmul)
from distributed_inference_demo_tpu.ops.quant import (  # noqa: E402
    alloc_kv_pages, quantize_array)

# the head geometries served: GQA at two head widths, MHA + ALiBi at two
MODELS = ("qwen2.5-7b", "tinyllama-1.1b", "bloom560m", "bloom7b1")


def heads(model: str):
    """``(num_heads, num_kv_heads, head_dim, alibi)`` from the registry."""
    cfg = get_model_config(model)
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.use_alibi

# What the bound has to allow.  The kernels feed the MXU float32 operands
# at the TPU's default matmul precision: one bf16 pass with float32
# accumulation — the same arithmetic XLA's own path uses at default
# precision, which is what the server would otherwise run.  Rounding the
# scaled queries and the softmax weights to bf16 (relative 2**-9) moves a
# score by ~2**-8 |s| and an output, an average of |v| <= ~4 values, by
# ~1e-2; the bf16 output adds half a step, <= 2**-7 below |o| = 4.
# Measured on the v5e (CHANGES.md, PR 21): max |err| 0.0020-0.0156 over
# every specialisation, and XLA at default precision sits at the same
# distance from this reference (reported beside each case).  2**-5 holds
# all of that and nothing more: a wrong mask, scale, slope or page is off
# by 0.1-1, bf16 accumulation over 1024 keys by ~0.06 |o|.  Arithmetic
# beyond rounding is pinned on the CPU at 2e-5 (tests/).
TOL = 2.0 ** -5


def emit(row: dict) -> None:
    print("KERNEL " + json.dumps(row), flush=True)


def run_case(name: str, kernel_fn, ref_fn, args) -> bool:
    """Compile + run ``kernel_fn(*args)`` and compare with ``ref_fn``."""
    row = {"name": name, "tol": TOL}
    try:
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.jit(kernel_fn)(*args))
        row["compile_and_run_s"] = round(time.perf_counter() - t0, 2)
        with jax.default_matmul_precision("highest"):
            ref = jax.block_until_ready(jax.jit(ref_fn)(*args))
        # what the server would compute had it fallen to the XLA path
        # at the default precision — context for the kernel's number
        ref_default = jax.block_until_ready(jax.jit(ref_fn)(*args))
        o, r, d = (np.asarray(x, np.float32)
                   for x in (out, ref, ref_default))
        row["max_abs_err"] = float(np.max(np.abs(o - r)))
        row["xla_default_precision_max_abs_err"] = float(
            np.max(np.abs(d - r)))
        row["finite"] = bool(np.isfinite(o).all())
        row["ok"] = bool(row["finite"] and row["max_abs_err"] <= TOL)
    except Exception as e:  # a refusal is a result: record Mosaic's words
        row["ok"] = False
        row["error"] = f"{type(e).__name__}: {str(e)[:600]}"
    emit(row)
    return row["ok"]


def group_sizes(kind: str, rows: int, experts: int, seed: int):
    """Rows a group, summing to ``rows``: "uniform"; "zipf" (expert e
    drawn with weight 1 / (e + 1)^1.2: a few experts take most rows and
    many stay empty at 256 rows); "one" (a single expert takes all)."""
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        sizes = np.full(experts, rows // experts)
        sizes[: rows - sizes.sum()] += 1
    elif kind == "one":
        sizes = np.zeros(experts, np.int64)
        sizes[int(rng.integers(experts))] = rows
    else:
        w = 1.0 / (np.arange(experts) + 1.0) ** 1.2
        sizes = np.bincount(rng.choice(experts, size=rows, p=w / w.sum()),
                            minlength=experts)
    return sizes.astype(np.int32)


def gmm_cases(interpret: bool) -> bool:
    """The grouped matmul at the cell's shapes (module docstring)."""
    cfg = get_model_config("olmoe-1b-7b")
    E, H, I = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    if interpret:
        E, H, I = 8, 256, 128
    ok = True
    rng = np.random.default_rng(0)
    stacks = {}
    for k, n in ((H, I), (I, H)):
        w = jnp.asarray(rng.standard_normal((E, k, n)) * k ** -0.5,
                        jnp.bfloat16)
        for quant in ("int8", "bf16"):
            rhs = stacks[quant, k] = (quantize_array(w) if quant == "int8"
                                      else w)
            for rows in ((64,) if interpret else (256, 4096)):
                x = jnp.asarray(rng.standard_normal((rows, k)),
                                jnp.bfloat16)
                for kind in ("uniform", "zipf", "one"):
                    gs = jnp.asarray(group_sizes(kind, rows, E, rows + k))
                    backend = "pallas" if interpret else "auto"
                    ok &= run_case(
                        f"gmm/{quant}/rows={rows}/k={k}/n={n}/{kind}",
                        lambda x_, r_, g_: grouped_matmul(
                            x_, r_, g_, backend=backend,
                            interpret=interpret),
                        lambda x_, r_, g_: grouped_matmul(
                            x_.astype(jnp.float32),
                            jax.tree.map(lambda a: a if a.dtype == jnp.int8
                                         else a.astype(jnp.float32), r_),
                            g_, backend="xla"),
                        (x, rhs, gs))
    if not interpret:
        gmm_times(stacks, E, H, I)
    return ok


def gmm_times(stacks, E, H, I, pairs: int = 8) -> None:
    """The call's time alone.  One host dispatch costs several calls'
    worth, so a jitted loop chains ``pairs`` x (an H -> I projection, then
    the I -> H one) and the row reports the loop's time over its 2 x
    ``pairs`` calls (group metadata and all), and that against the chip's
    peaks: ``hbm_pct`` over the touched experts' matrices and scales plus
    the rows in and out, ``mxu_pct`` over 2 x rows x k x n."""
    from distributed_inference_demo_tpu.telemetry.profiling import (
        device_peaks)
    peaks = device_peaks(jax.devices()[0].device_kind)
    rng = np.random.default_rng(1)
    for quant in ("int8", "bf16"):
        up, down = stacks[quant, H], stacks[quant, I]

        @jax.jit
        def chain(x, gs):
            return jax.lax.fori_loop(
                0, pairs, lambda _, y: grouped_matmul(
                    grouped_matmul(y, up, gs), down, gs), x)

        for rows in (256, 4096):
            x = jnp.asarray(rng.standard_normal((rows, H)), jnp.bfloat16)
            for kind in ("uniform", "zipf", "one"):
                sizes = group_sizes(kind, rows, E, rows + H)
                gs = jnp.asarray(sizes)
                jax.block_until_ready(chain(x, gs))
                ts = []
                for _ in range(10):
                    t0 = time.perf_counter()
                    jax.block_until_ready(chain(x, gs))
                    ts.append(time.perf_counter() - t0)
                t = float(np.median(ts)) / (2 * pairs)
                touched = int((sizes > 0).sum())
                wb = 1 if quant == "int8" else 2
                nbytes = (touched * (H * I * wb + (2 * (H + I) if wb == 1
                                                   else 0))
                          + rows * (H + I) * 2)
                emit({"name": f"gmm/{quant}/rows={rows}/{kind}/time",
                      "ms_a_call": round(t * 1e3, 4), "touched": touched,
                      "hbm_pct": round(100 * nbytes / t
                                       / (peaks.hbm_gbs * 1e9), 1),
                      "mxu_pct": round(100 * 2 * rows * H * I / t
                                       / (peaks.bf16_tflops * 1e12), 1)})


# the prefill page loop at the shapes the cells serve it (PERF.md §4;
# 128-token bf16 pages): (query heads, kv heads, ALiBi, the tile's
# tokens, the table's width, the window, [the two segments' starts])
PREFILL_LOOP_SHAPES = {
    # laguna's full kind: a 256-token chunk as 4 sub-chunks of 64, two
    # segments a call; contexts of 0 and of 1 page, of 53 and of 200
    "laguna-full": (48, 8, False, 64, 200, 0,
                    [(0, 128), (6528, 25344)]),
    "laguna-window": (72, 8, False, 32, 200, 512,
                      [(0, 384), (6528, 25344)]),
    "bloom7b1": (32, 32, True, 256, 16, 0, [(0, 1536)]),
    "olmoe-1b-7b": (16, 16, False, 256, 32, 0, [(256, 3840)]),
}


def prefill_loop_cases(interpret: bool) -> bool:
    """Ragged prefill calls through the page loop: against the gather at
    the highest precision (``run_case``), and the loop's output against
    the grid kernel's on the same operands, which must be EQUAL (the two
    fold the same live pages in the same order with the same float32
    arithmetic); then both timed alone (``prefill_times``)."""
    ok = True
    hd, bt = 128, 128
    for name, (nh, nkv, alibi, tile, W, window, pairs) in (
            PREFILL_LOOP_SHAPES.items()):
        if interpret:
            W, pairs = 6, [(0, 128 * 6 - 256)]
        rng = np.random.default_rng(len(name))
        N = 2 * W
        pk, pv = (jnp.asarray(rng.standard_normal((N, nkv, bt, hd)),
                              jnp.bfloat16) for _ in range(2))
        seg_tables = rng.permutation(N).reshape(2, W)
        n = 256 // tile                        # tiles a segment
        tables = jnp.asarray(np.repeat(seg_tables, n, axis=0), jnp.int32)
        slopes = alibi_slopes(nh) if alibi else None
        kw = {"window": window} if window else {}
        for s0, s1 in pairs:
            starts = jnp.asarray(
                [s + tile * t for s in (s0, s1) for t in range(n)],
                jnp.int32)
            pos = starts[:, None] + jnp.arange(tile, dtype=jnp.int32)[None]
            q = jnp.asarray(rng.standard_normal((2 * n, tile, nh, hd)),
                            jnp.bfloat16)
            tag = f"{name} starts={s0},{s1} W={W}"
            ok &= run_case(
                f"paged_prefill loop {tag}",
                lambda q, pk, pv, t, p: paged_prefill_attention(
                    q, pk, pv, t, p, slopes, interpret=interpret, **kw),
                lambda q, pk, pv, t, p: paged_gather_attention(
                    q, pk, pv, t, p, slopes, **kw),
                (q, pk, pv, tables, pos))
            q_g, slopes_g = pa._query_tiles(q, nkv, slopes)
            K, V, li = pa._stacked(pk, pv)
            args = (q_g, K, V, li.reshape(1), tables, starts, slopes_g)
            static = dict(block_tokens=bt, chunk=tile, groups=nh // nkv,
                          use_alibi=alibi, interpret=interpret, **kw)
            calls = {how: jax.jit(functools.partial(fn, **static))
                     for how, fn in (("loop", pa._paged_prefill_loop_call),
                                     ("grid", pa._paged_prefill_grid_call))}
            outs = {how: np.asarray(jax.block_until_ready(fn(*args)),
                                    np.float32)
                    for how, fn in calls.items()}
            row = {"name": f"paged_prefill loop == grid {tag}", "tol": 0,
                   "max_abs_err": float(np.max(np.abs(
                       outs["loop"] - outs["grid"])))}
            row["ok"] = bool(np.array_equal(outs["loop"], outs["grid"]))
            if not interpret:
                row.update(prefill_times(calls, args))
            emit(row)
            ok &= row["ok"]
    return ok


def prefill_times(calls, args, chain: int = 8) -> dict:
    """``{"loop_ms": .., "grid_ms": ..}``: one call's time, each kernel
    chained ``chain`` times inside one program (a call's output is the
    next one's queries) so that the host's dispatch is not in it."""
    out = {}
    for how, fn in calls.items():
        many = jax.jit(lambda q, *rest: jax.lax.fori_loop(
            0, chain, lambda _, y: fn(y, *rest), q))
        jax.block_until_ready(many(*args))
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(many(*args))
            ts.append(time.perf_counter() - t0)
        out[f"{how}_ms"] = round(float(np.median(ts)) / chain * 1e3, 4)
    return out


def paged_pool(rng, b, nkv, hd, bt, W, kv_dtype):
    """A pool whose every page of every row's table holds seeded K/V,
    written through the served write path."""
    N = b * W
    pk = alloc_kv_pages((N, nkv, bt, hd), kv_dtype, jnp.bfloat16)
    pv = alloc_kv_pages((N, nkv, bt, hd), kv_dtype, jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(N).reshape(b, W), jnp.int32)
    span = W * bt
    k = jnp.asarray(rng.standard_normal((b, span, nkv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, span, nkv, hd)), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(span, dtype=jnp.int32), (b, span))
    pk, pv = jax.jit(write_paged_kv)(pk, pv, k, v, tables, pos)
    return pk, pv, tables


def paged_cases(model: str, kv_dtype: str, bt: int, W: int, b: int,
                chunk: int, interpret: bool, prefill: bool = True) -> bool:
    nh, nkv, hd, alibi = heads(model)
    rng = np.random.default_rng(0)
    pk, pv, tables = paged_pool(rng, b, nkv, hd, bt, W, kv_dtype)
    slopes = alibi_slopes(nh) if alibi else None
    tag = (f"{model} {kv_dtype} pages bt={bt} W={W}"
           + (" alibi" if alibi else ""))
    span = W * bt
    ok = True

    lens = jnp.asarray(rng.integers(1, span + 1, size=b), jnp.int32)
    lens = lens.at[0].set(span).at[-1].set(1)      # both ends of the range
    q = jnp.asarray(rng.standard_normal((b, 1, nh, hd)), jnp.bfloat16)
    ok &= run_case(
        f"paged_decode {tag}",
        lambda q, pk, pv, t, n: paged_flash_attention(
            q, pk, pv, t, n, slopes, interpret=interpret),
        lambda q, pk, pv, t, n: paged_gather_attention(
            q, pk, pv, t, (n - 1)[:, None], slopes),
        (q, pk, pv, tables, lens))

    if prefill:
        pb = min(b, 2)               # the mixed dispatch's slab rows
        starts = jnp.asarray(
            rng.integers(0, span - chunk + 1, size=pb), jnp.int32)
        starts = starts.at[0].set(0)
        pos = starts[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None]
        qp = jnp.asarray(rng.standard_normal((pb, chunk, nh, hd)),
                         jnp.bfloat16)
        ok &= run_case(
            f"paged_prefill chunk={chunk} {tag}",
            lambda q, pk, pv, t, p: paged_prefill_attention(
                q, pk, pv, t, p, slopes, interpret=interpret),
            lambda q, pk, pv, t, p: paged_gather_attention(
                q, pk, pv, t, p, slopes),
            (qp, pk, pv, tables[:pb], pos))
    return ok


def flash_cases(model: str, max_seq: int, chunks, interpret: bool) -> bool:
    nh, nkv, hd, alibi = heads(model)
    rng = np.random.default_rng(1)
    slopes = alibi_slopes(nh) if alibi else None
    b, ok = 2, True
    kc = jnp.asarray(rng.standard_normal((b, nkv, max_seq, hd)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((b, nkv, max_seq, hd)), jnp.bfloat16)
    for chunk in chunks:
        start = jnp.int32(max_seq - chunk - 8)
        kv_len = start + chunk
        q = jnp.asarray(rng.standard_normal((b, chunk, nh, hd)), jnp.bfloat16)
        ok &= run_case(
            f"flash chunk={chunk} max_seq={max_seq} {model}"
            + (" alibi" if alibi else ""),
            lambda q, k, v, s, n: flash_attention(
                q, k, v, s, n, slopes, interpret=interpret),
            lambda q, k, v, s, n: attention(
                q, k, v,
                s + jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2]),
                n, slopes),
            (q, kc, vc, start, kv_len))
    return ok


def int8_pool_layout_case() -> bool:
    """How much HBM an int8 page pool really takes: the ``[.., bt, 1]``
    float32 scale sidecar has a minor dimension of 1, which a tiled
    device layout may pad to a full lane row.  Informational (always
    ok): the number goes to the perf queue, not to a gate."""
    dev = jax.devices()[0]
    row = {"name": "int8 pool HBM footprint (512 pages x 4 heads x 32 x 128)",
           "tol": None, "ok": True}
    stats = dev.memory_stats()
    if stats:
        before = stats["bytes_in_use"]
        pool = jax.block_until_ready(
            alloc_kv_pages((512, 4, 32, 128), "int8", jnp.bfloat16))
        row["bytes_in_use"] = dev.memory_stats()["bytes_in_use"] - before
        row["logical_bytes"] = int(pool.data.nbytes + pool.scale.nbytes)
        row["scale_logical_bytes"] = int(pool.scale.nbytes)
    emit(row)
    return True


def engine_case(model: str, kv_dtype: str, bt: int) -> bool:
    """One specialisation THROUGH an engine: the kernels inside the layer
    scan, the fused decode ``while_loop`` and the donated pool of the
    mixed dispatch.  Passes when every request finishes with in-vocab
    tokens and ``attention_paths`` names the kernels."""
    from distributed_inference_demo_tpu.models.loader import load_or_init
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    row = {"name": f"engine mixed_step {model} {kv_dtype} pages bt={bt}",
           "tol": None}
    try:
        cfg = get_model_config(model)
        params = load_or_init(model, cfg, seed=0)
        t0 = time.perf_counter()
        with ContinuousBatchingEngine(
                cfg, params, max_seq=512, max_batch=4,
                sampling=SamplingParams(greedy=True), decode_block=4,
                prefill_chunk=32, mixed_token_budget=96,
                kv_dtype=kv_dtype, kv_block_tokens=bt) as eng:
            rng = np.random.default_rng(2)
            reqs = [eng.submit(rng.integers(1, cfg.vocab_size, size=n), 8)
                    for n in (75, 9, 40)]
            toks = [r.wait(timeout=900) for r in reqs]
            paths = eng.stats()["attention_paths"]["mixed_step"]
        row["seconds"] = round(time.perf_counter() - t0, 1)
        row["attention_paths"] = paths
        row["ok"] = bool(
            all(len(t) == 8 and ((0 <= t) & (t < cfg.vocab_size)).all()
                for t in toks)
            and (jax.default_backend() != "tpu"
                 or sorted(paths.values()) == ["pallas_decode",
                                               "pallas_prefill"]))
    except Exception as e:
        row["ok"] = False
        row["error"] = f"{type(e).__name__}: {str(e)[:600]}"
    emit(row)
    return row["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpreter at small tables: debugs this "
                         "tool on a CPU, proves nothing about Mosaic")
    ap.add_argument("--gmm-only", action="store_true",
                    help="the grouped-matmul cases and timings alone")
    ap.add_argument("--prefill-only", action="store_true",
                    help="the prefill page loop's cases and timings alone")
    ap.add_argument("--engines", action="store_true",
                    help="also run bloom560m (ALiBi) and qwen2.5-0.5b "
                         "(GQA) engines with bf16 and int8 pages")
    args = ap.parse_args(argv)
    # the package's one compile-cache rule, before any backend exists
    from distributed_inference_demo_tpu.cli import configure_compile_cache
    configure_compile_cache()
    dev = jax.devices()[0]
    print(f"kernel_parity: platform={dev.platform} "
          f"device_kind={dev.device_kind!r} interpret={args.interpret}",
          flush=True)
    if dev.platform != "tpu" and not args.interpret:
        print("kernel_parity: no TPU; the compiled kernels exist only "
              "there (use --interpret to debug the tool)", file=sys.stderr)
        return 1
    W, b = (4, 2) if args.interpret else (64, 8)   # 64 pages = max_seq 1024
    ok = True
    if args.gmm_only or args.prefill_only:
        ok = (gmm_cases if args.gmm_only
              else prefill_loop_cases)(args.interpret)
        print("KERNEL_PARITY_DONE", flush=True)
        return 0 if ok else 1
    for model in MODELS:
        nh, nkv, _, _ = heads(model)
        group = nh // nkv
        chunk = 16 if args.interpret else min(64, 512 // group)
        ok &= paged_cases(model, "bf16", 16, W, b, chunk, args.interpret)
        ok &= paged_cases(model, "int8", 32, W // 2, b, chunk,
                          args.interpret)
    # the benchmark's page size (128 tokens) at its cells' slots and table
    # widths, ragged lengths from 1 token to the whole table: the decode
    # kernel's page loop runs from 1 to W times a row (int8 pages take
    # the loop only at this page size)
    for model, kv_dtype, cw, cb in (("qwen2.5-7b", "bf16", 32, 32),
                                    ("bloom7b1", "bf16", 16, 8),
                                    ("qwen2.5-7b", "int8", 32, 32)):
        ok &= paged_cases(model, kv_dtype, 128,
                          *((W, b) if args.interpret else (cw, cb)),
                          64, args.interpret, prefill=False)
    if not args.interpret:
        # the block table at max_seq 32768 with 16-token pages: 8 x 2048
        # int32 = 64 KB of scalar memory
        ok &= paged_cases("qwen2.5-7b", "bf16", 16, 2048, 8, 64,
                          args.interpret, prefill=False)
        # int8 pages at the DEFAULT page size: serving gates this shape
        # off the kernel (block_tokens % 32, route_paged_attention) on
        # the int8 tile's 32 sublanes; whether Mosaic needs the gate is
        # what this case answers
        ok &= paged_cases("qwen2.5-7b", "int8", 16, W, b, 64,
                          args.interpret)
        ok &= int8_pool_layout_case()
    ok &= prefill_loop_cases(args.interpret)
    for model in ("qwen2.5-7b", "bloom560m"):
        ok &= flash_cases(model, 64 if args.interpret else 1024,
                          (16,) if args.interpret else (64, 256),
                          args.interpret)
    ok &= gmm_cases(args.interpret)
    if args.engines:
        for model, kv_dtype, bt in (("bloom560m", "bf16", 16),
                                    ("bloom560m", "int8", 32),
                                    ("qwen2.5-0.5b", "int8", 32)):
            ok &= engine_case(model, kv_dtype, bt)
    print("KERNEL_PARITY_DONE", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
