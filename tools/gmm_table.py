#!/usr/bin/env python
"""The experts' grouped matmul alone, on the chip: us a call at each
benchmark configuration's slab and decode shapes, for the tiles the rule
gave before PR 63 and for a contraction tile that spans ``k`` at four row
tiles.  It is the table ``ops/grouped_matmul.tiling`` was chosen from
(PERF.md section 6, PR 63); run it again when a configuration brings a
new shape or the rule changes:

    python tools/gmm_table.py                      # every expert configuration
    python tools/gmm_table.py --config granite-4.0-h-small-bf16-ep2 --even

Shapes come from ``benchmark/configs/*.json``: ``hidden_size`` x
``intermediate_size`` (gate and up; down is the transpose), the experts
held here and routed over, and the rows of the two calls a dispatch makes
from the serve flags: a slab's ``(segments x chunk + slots) x top-k``
token-expert rows and a decode step's ``slots x top-k``.  Group sizes come
from a seeded router that spreads evenly in expectation (top-k of uniform
scores a token); ``--even`` gives every group the same rows.  The stack
holds three layers and a call takes the next one, as the scan over layers
does.

A time is the least of three runs of ``--reps`` calls in one jitted scan,
host clock around ``block_until_ready``.  ``hbm`` / ``mxu`` are the
touched experts' matrices over 819 GB/s and the groups' rows x 2 k n over
197 TFLOP/s: the call's bound is the larger.  ``err`` is the rule's call
against ``ragged_dot`` on the same rows, max |difference| over max
|value|.  One JSON line a row goes to ``chiprun_out/gmm_table.jsonl``.

``--combine`` times what follows the down projection instead (PERF.md
section 6, PR 65): a token's ``k`` rows back from expert order, weighted
and summed, at the same slab and decode shapes.  ``before`` is the
combine as it stood before PR 65 (widen and mask ``[T k, H]``, gather in
float32, reshape to ``[T, k, H]``, einsum); ``bf16`` is
``decoder._combine`` on the rows as the kernel writes them, ``f32`` the
same on rows widened first, so that the gather moves float32.  ``bytes``
are what a form's passes move through HBM by its shapes (a float32
``[T, k, H]`` pads ``k`` to a multiple of 8), ``floor`` the bf16 rows
read once and ``[T, H]`` float32 written, over 819 GB/s.  ``err`` is
``bf16`` against ``before`` on the same rows.

``--two-matrix`` times the experts of TWO matrices instead (nemotron_h:
``down(relu(up h) ** 2)``, an expert width of 1,856 = 14.5 x 128; PERF.md
section 6, PR 66), the up and the down call at the same slab and decode
rows in the two forms a width off the lanes can be stored in:
``transposed`` (the up projection ``[1856, 2688]`` a group, one tile that
spans the 1,856, contracted on its last dimension; the down projection
``[1856, 2688]`` with the whole contraction one tile) and ``padded``
(columns zero-padded to 1,920 = 15 x 128: ``[2688, 1920]`` and ``[1920,
2688]``, the kernel's ordinary path; ``relu ** 2`` leaves the padding
exactly zero).  ``hbm`` is the touched experts' matrices at the PUBLISHED
width in both forms.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_inference_demo_tpu.models.decoder import _combine  # noqa: E402
from distributed_inference_demo_tpu.ops import grouped_matmul as gmm  # noqa: E402
from distributed_inference_demo_tpu.telemetry.profiling import (  # noqa: E402
    DEVICE_PEAKS)

LAYERS = 3
ROW_TILES = (32, 64, 128, 256)
# the floors are the v5e's whatever runs the script (a rehearsal has none)
PEAKS = DEVICE_PEAKS["TPU v5 lite"]


def tiles_before(m: int, k: int, n: int, itemsize: int) -> tuple:
    """The rule as it stood before PR 63: a row tile by ``m`` alone and a
    right-hand tile of at most 2 MiB."""
    tm = 64 if m >= 2048 else 32 if m >= 32 else 16
    tn = gmm._divisor_tile(n, 1024)
    return tm, gmm._divisor_tile(k, max(128, (2 << 20) // (tn * itemsize))), tn


def expert_configs(names, two_matrix: bool = False):
    """The configurations with gated experts (three calls a block: the
    table's), or with ``two_matrix`` those whose experts are of two
    matrices (``--two-matrix``'s rows)."""
    for path in sorted((ROOT / "benchmark" / "configs").glob("*.json")):
        conf = json.loads(path.read_text())
        mc = conf["model_config"]
        if not mc.get("num_experts") or (names and conf["name"] not in names):
            continue
        if (mc.get("mlp_act") == "relu2") != two_matrix:
            continue
        flags = conf["serve_flags"]
        flag = lambda name: int(flags[flags.index(name) + 1])  # noqa: E731
        slots, chunk = flag("--batch-slots"), flag("--prefill-chunk")
        segments = (flag("--mixed-token-budget")
                    - slots * flag("--decode-block")) // chunk
        held, first = mc.get("experts_held") or (mc["num_experts"], 0)
        yield dict(
            name=conf["name"], hidden=mc["hidden_size"],
            inter=mc["intermediate_size"], routed=mc["num_experts"],
            held=held, first=first, top_k=mc["experts_per_token"],
            int8=conf["name"].endswith("int8"),
            tokens={"slab": segments * chunk + slots, "decode": slots})


def group_sizes(c: dict, tokens: int, seed: int, even: bool) -> np.ndarray:
    if even:
        rows = tokens * c["top_k"] * c["held"] // c["routed"]
        return np.full(c["held"], rows // c["held"], np.int32)
    scores = np.random.RandomState(seed).rand(tokens, c["routed"])
    picked = np.argsort(-scores, axis=1)[:, :c["top_k"]]
    rows = np.bincount(picked.ravel(), minlength=c["routed"])
    return rows[c["first"]:c["first"] + c["held"]].astype(np.int32)


def stack(key, c: dict, k: int, n: int):
    shape = (LAYERS, c["held"], k, n)
    if c["int8"]:
        q = jax.random.randint(key, shape, -127, 128, jnp.int8)
        return q, jnp.full(shape[:2] + (1, n), k ** -0.5 / 127, jnp.float32)
    w = jax.random.normal(key, shape, jnp.bfloat16) * k ** -0.5
    return w.astype(jnp.bfloat16), None


def us_a_call(lhs, rhs, scale, sizes, tiles, reps: int,
              interpret: bool, transposed: bool = False) -> float:
    @jax.jit
    def calls(lhs, rhs, scale, sizes):
        def one(carry, i):
            out = gmm._moe_gmm_call(lhs, rhs, scale, sizes,
                                    (i % LAYERS)[None], tiles=tiles,
                                    interpret=interpret,
                                    transposed=transposed)
            return carry + out[0, 0].astype(jnp.float32), None
        return jax.lax.scan(one, jnp.float32(0),
                            jnp.arange(reps, dtype=jnp.int32))[0]

    calls(lhs, rhs, scale, sizes).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        calls(lhs, rhs, scale, sizes).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / reps


def rows_of(c: dict, args):
    key = jax.random.PRNGKey(args.seed)
    for proj, (k, n) in (("gate_up", (c["hidden"], c["inter"])),
                         ("down", (c["inter"], c["hidden"]))):
        rhs, scale = stack(key, c, k, n)
        itemsize = rhs.dtype.itemsize
        for call, tokens in c["tokens"].items():
            m = tokens * c["top_k"]
            sizes_np = group_sizes(c, tokens, args.seed, args.even)
            sizes = jnp.asarray(sizes_np)
            lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
            touched, in_groups = int((sizes_np > 0).sum()), int(sizes_np.sum())
            rule = gmm.tiling(m, k, n, itemsize, c["routed"])
            row = dict(
                config=c["name"], call=call, proj=proj, m=m, k=k, n=n,
                rows_in_groups=in_groups, touched=touched,
                rows_a_group=round(in_groups / max(touched, 1), 1),
                hbm_us=round(1e-3 * touched * k * n * itemsize / PEAKS.hbm_gbs, 1),
                mxu_us=round(1e-6 * 2 * in_groups * k * n / PEAKS.bf16_tflops, 1),
                rule=list(rule), before=list(tiles_before(m, k, n, itemsize)))
            timed = lambda tiles: round(us_a_call(  # noqa: E731
                lhs, rhs, scale, sizes, tiles, args.reps, args.rehearse), 1)
            row["before_us"] = timed(tuple(row["before"]))
            for tm in ROW_TILES:
                row[f"k_tm{tm}_us"] = timed((tm, k, rule[2]))
            got = gmm._moe_gmm_call(lhs, rhs, scale, sizes,
                                    jnp.zeros((1,), jnp.int32), tiles=rule,
                                    interpret=args.rehearse)
            want = gmm._ragged(lhs, rhs[0], None if scale is None
                               else scale[0], sizes)
            diff = jnp.abs(got[:in_groups].astype(jnp.float32)
                           - want[:in_groups].astype(jnp.float32))
            row["err"] = float(diff.max() / jnp.abs(want[:in_groups]).max())
            yield row


def _lanes_up(n: int) -> int:
    return -(-n // 128) * 128


def two_matrix_rows_of(c: dict, args):
    """An expert of two matrices, ``[H, I]`` then ``[I, H]`` at an ``I``
    that is not whole lanes, in the two stored forms: one row a (call,
    form, projection)."""
    H, I = c["hidden"], c["inter"]
    Ip = _lanes_up(I)
    key = jax.random.PRNGKey(args.seed)
    up = jax.random.normal(key, (LAYERS, c["held"], H, I),
                           jnp.bfloat16) * H ** -0.5
    down = jax.random.normal(jax.random.fold_in(key, 1),
                             (LAYERS, c["held"], I, H),
                             jnp.bfloat16) * I ** -0.5
    pad = lambda a, axis: jnp.pad(  # noqa: E731
        a, [(0, Ip - I) if i == axis else (0, 0) for i in range(4)])
    # one form's stacks at a time beside the plain pair (1.9 GB a stack)
    forms = {"transposed": lambda: (jnp.swapaxes(up, 2, 3), down),
             "padded": lambda: (pad(up, 3), pad(down, 2))}
    for form, stacks in forms.items():
        w_up, w_down = stacks()
        transposed = form == "transposed"
        width = I if transposed else Ip
        for call, tokens in c["tokens"].items():
            m = tokens * c["top_k"]
            sizes_np = group_sizes(c, tokens, args.seed, args.even)
            sizes = jnp.asarray(sizes_np)
            touched, in_groups = (int((sizes_np > 0).sum()),
                                  int(sizes_np.sum()))
            x = jax.random.normal(key, (m, H), jnp.bfloat16)
            hidden = jnp.pad(jax.random.normal(key, (m, I), jnp.bfloat16),
                             ((0, 0), (0, width - I)))
            for proj, lhs, rhs, k, n, t, plain in (
                    ("up", x, w_up, H, width, transposed, up),
                    ("down", hidden, w_down, width, H, False, down)):
                tiles = gmm.tiling(m, k, n, 2, c["routed"])
                us = us_a_call(lhs, rhs, None, sizes, tiles, args.reps,
                               args.rehearse, transposed=t)
                got = gmm._moe_gmm_call(
                    lhs, rhs, None, sizes, jnp.zeros((1,), jnp.int32),
                    tiles=tiles, interpret=args.rehearse, transposed=t)
                want = gmm._ragged(lhs[:, :plain.shape[2]], plain[0], None,
                                   sizes)
                cols = want.shape[1]
                diff = jnp.abs(got[:in_groups, :cols].astype(jnp.float32)
                               - want[:in_groups].astype(jnp.float32))
                yield dict(
                    config=c["name"], call=call, two_matrix=True, form=form,
                    proj=proj, m=m, k=k, n=n, rows_in_groups=in_groups,
                    touched=touched, tiles=list(tiles),
                    hbm_us=round(1e-3 * touched * H * I * 2 / PEAKS.hbm_gbs,
                                 1),
                    mxu_us=round(1e-6 * 2 * in_groups * H * I
                                 / PEAKS.bf16_tflops, 1),
                    us=round(us, 1),
                    err=float(diff.max() / jnp.abs(want[:in_groups]).max()))
        del w_up, w_down


def combine_before(out, order, weights, written):
    """The combine as it stood before PR 65, on rows laid token-major
    (row ``t k + j`` is token ``t``'s ``j``-th expert) before the sort."""
    T, k = weights.shape
    out = out.astype(jnp.float32)
    out = jnp.where((jnp.arange(T * k) < written)[:, None], out, 0.0)
    out = out[jnp.argsort(order)].reshape(T, k, -1)
    return jnp.einsum("tkh,tk->th", out, weights)


def combine_f32(out, order, weights, written):
    return _combine(out.astype(jnp.float32), order, weights, written)


COMBINE_FORMS = {"before": combine_before, "bf16": _combine,
                 "f32": combine_f32}


def combine_bytes(T: int, k: int, H: int) -> dict:
    """Bytes a form's passes move through HBM, and the arithmetic's own."""
    rows, padded, y = T * k * H, T * -(-k // 8) * 8 * H, 4 * T * H
    return {"before": (2 + 4) * rows + (4 + 4) * rows + 4 * (rows + padded)
            + 4 * padded + y,
            "bf16": (2 + 2) * rows + 2 * rows + y,
            "f32": (2 + 4) * rows + (4 + 4) * rows + 4 * rows + y,
            "floor": 2 * rows + y}


def us_a_combine(form, out, order, weights, written, reps: int) -> float:
    # three permutations in turn, and each call's sum written into the
    # next call's rows in place: no pass is the same twice, so the
    # compiler hoists none out of the loop, and the rows are not copied
    orders = jnp.stack([jnp.roll(order, i) for i in range(LAYERS)])

    @jax.jit
    def calls(out):
        def one(out, i):
            y = form(out, orders[i % LAYERS], weights, written)
            return jax.lax.dynamic_update_slice(
                out, y[:1].astype(out.dtype), (0, 0)), None
        return jax.lax.scan(one, out, jnp.arange(reps, dtype=jnp.int32))[0]

    calls(out).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        calls(out).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / reps


def combine_rows_of(c: dict, args):
    H, k = c["hidden"], c["top_k"]
    rs = np.random.RandomState(args.seed)
    for call, T in c["tokens"].items():
        picked = np.argsort(-rs.rand(T, c["routed"]), axis=1)[:, :k]
        held = (picked >= c["first"]) & (picked < c["first"] + c["held"])
        flat = np.where(held, picked - c["first"], c["held"])     # [T, k]
        written = jnp.int32(held.sum())
        weights = jnp.asarray(rs.rand(T, k), jnp.float32)
        per_row = jax.random.normal(jax.random.PRNGKey(args.seed),
                                    (T, k, H), jnp.bfloat16)
        # the same rows in the two orders: token-major and k-major
        order_t = np.argsort(flat.reshape(-1), kind="stable")
        order_k = np.argsort(flat.T.reshape(-1), kind="stable")
        out_t = per_row.reshape(T * k, H)[order_t]
        out_k = per_row.transpose(1, 0, 2).reshape(k * T, H)[order_k]
        nbytes = combine_bytes(T, k, H)
        row = dict(config=c["name"], call=call, combine=True, tokens=T,
                   top_k=k, hidden=H, rows_written=int(written),
                   floor_us=round(1e-3 * nbytes["floor"] / PEAKS.hbm_gbs, 1))
        for name, form in COMBINE_FORMS.items():
            out, order = ((out_t, order_t) if name == "before"
                          else (out_k, order_k))
            row[f"{name}_mb"] = round(nbytes[name] / 1e6, 1)
            row[f"{name}_us"] = round(us_a_combine(
                form, out, jnp.asarray(order, jnp.int32), weights, written,
                args.reps), 1)
        want = combine_before(out_t, jnp.asarray(order_t), weights, written)
        got = _combine(out_k, jnp.asarray(order_k), weights, written)
        row["err"] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        yield row


HEAD = ["config", "call", "proj", "m", "rows/group", "hbm", "mxu", "before",
        "us", *(f"k,tm={tm}" for tm in ROW_TILES), "rule", "err"]
TWO_MATRIX_HEAD = ["config", "call", "form", "proj", "m", "k", "n", "touched",
                   "hbm", "mxu", "tiles", "us", "err"]
COMBINE_HEAD = ["config", "call", "tokens", "k", "H", "floor",
                *(f"{name}: MB, us" for name in COMBINE_FORMS), "err"]


def cells(row: dict) -> list:
    return [row["config"], row["call"], row["proj"], row["m"],
            row["rows_a_group"], row["hbm_us"], row["mxu_us"],
            "x".join(map(str, row["before"])), row["before_us"],
            *(row[f"k_tm{tm}_us"] for tm in ROW_TILES),
            "x".join(map(str, row["rule"])), f"{row['err']:.1e}"]


def two_matrix_cells(row: dict) -> list:
    return [row["config"], row["call"], row["form"], row["proj"], row["m"],
            row["k"], row["n"], row["touched"], row["hbm_us"], row["mxu_us"],
            "x".join(map(str, row["tiles"])), row["us"], f"{row['err']:.1e}"]


def combine_cells(row: dict) -> list:
    return [row["config"], row["call"], row["tokens"], row["top_k"],
            row["hidden"], row["floor_us"],
            *(f"{row[f'{name}_mb']}, {row[f'{name}_us']}"
              for name in COMBINE_FORMS), f"{row['err']:.1e}"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", action="append", default=[],
                    help="a benchmark configuration's name (default: every "
                         "one with experts)")
    ap.add_argument("--even", action="store_true",
                    help="every group the same rows, not a seeded router's")
    ap.add_argument("--reps", type=int, default=32)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: the kernel interpreted at 48 and 8 "
                         "tokens a call, to see the script run; its times "
                         "mean nothing")
    ap.add_argument("--combine", action="store_true",
                    help="the combine after the down projection alone, "
                         "not the grouped matmul")
    ap.add_argument("--two-matrix", action="store_true",
                    help="the experts of two matrices (an expert width "
                         "off the lanes) in their two stored forms, not "
                         "the gated experts' table")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "gmm_table.jsonl"))
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        ap.error(f"no chip here ({dev.platform}): a time comes from the "
                 "chip (--rehearse runs the script without one)")
    print(f"# device {dev.platform} {dev.device_kind}; groups "
          f"{'even' if args.even else f'seeded router, seed {args.seed}'}; "
          f"us a call, least of 3 x {args.reps}")
    rows_of_config, head, cells_of = (
        (combine_rows_of, COMBINE_HEAD, combine_cells) if args.combine
        else (two_matrix_rows_of, TWO_MATRIX_HEAD, two_matrix_cells)
        if args.two_matrix else (rows_of, HEAD, cells))
    print("| " + " | ".join(head) + " |")
    print("|" + "---|" * len(head))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as out:
        for c in expert_configs(args.config, args.two_matrix):
            if args.rehearse:
                c["tokens"] = {"slab": 48, "decode": 8}
            for row in rows_of_config(c, args):
                row.update(device=dev.device_kind, even=args.even,
                           seed=args.seed, rehearsal=args.rehearse)
                out.write(json.dumps(row) + "\n")
                out.flush()
                print("| " + " | ".join(map(str, cells_of(row))) + " |",
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
