#!/usr/bin/env python
"""The KDA chunk form's ``Tm = (I + N)^-1 diag(beta)`` alone, on the chip:
us a call of each way to form it at the cell's ``[2, 64, 128, 128]`` (one
segment of 256 tokens, 64 heads) and at ``[6, 64, 128, 128]`` (a slab's
three segments in one batch), beside each way's error on two inputs.  It is
the table ``ops/kda.unit_lower_inverse`` and its base were chosen from
(PERF.md section 6, PR 68; docs/DESIGN.md section 27); run it again when
the chunk, the heads or the form change:

    python tools/kda_tinv_table.py
    python tools/kda_tinv_table.py --shapes 2x64 --reps 50 --tried

The ways:

* ``solve``: ``jax.scipy.linalg.solve_triangular(I + N, diag(beta))``,
  what ``chunk_matrices`` did before PR 68 (XLA's triangular-solve
  custom-call on the chip).
* ``blocks16``, ``blocks32``: ``ops.kda.unit_lower_inverse`` at that base:
  substitution inside diagonal blocks of 16 / 32 rows, then the merges
  ``[[A, 0], [-D N21 A, D]]`` as ``HIGHEST`` products up to 128.
  ``blocks32`` is what is served.
* ``substitution``: the same function with the whole chunk as its one
  block (127 steps, no merge): what a CPU's solve does.
* ``neumann``: ``(I - N)(I + N^2)(I + N^4) .. (I + N^64)``, exact in exact
  arithmetic because ``N^128 = 0``; the form PERF.md and ROADMAP.md named
  before PR 68.  Error only: it is the control, not a candidate.
* ``--tried`` adds two forms PR 68 wrote first and did not keep, both
  ``blocks32``'s mathematics: ``rows32``, the substitution with the
  blocks as they lie (``[.., 32, 32]``, a quarter of the lanes) in a
  Python loop of 31 steps, which the compiler fuses into megabytes of
  code; ``wide32``, the served substitution with each merge as two
  whole-matrix products ``X - X N_l X`` (``N_l`` the level's ``N21``
  blocks), lane-dense and four times the arithmetic.

The inputs (``N`` made in float64 from seeded vectors, rounded to float32;
both are what ``tests/test_solar_open2.py`` holds the inverse to):

* ``cell``: keys of unit length in random directions, the configuration's
  decay (``-A softplus``: ``A`` in [1, 16] a head, a step in [1e-3, 0.1] a
  channel, as the published initialiser draws them) and ``beta = 2
  sigmoid(normal)``: what the benchmark's random ids give, ``|N|`` under
  0.5.
* ``repeated``: ONE direction plus 5 % noise, the decay times 0.01 and
  ``beta`` 1.9, a prompt that repeats a token: ``|N|`` up to 1.9, where
  the powers of ``N`` overflow float32.

A time is the least of three runs of ``--reps`` calls in one jitted
``lax.map`` over ``reps`` copies of the input (nothing to hoist, each
call's ``Tm`` written to memory as the program writes it), host clock
around ``block_until_ready``.  ``err`` is max ``|X - inv|`` over max
``|inv|``, ``inv`` numpy's float64 inverse of the same float32 ``I + N``,
at ``[2, 4, 128, 128]``.  One JSON line a row goes to
``chiprun_out/kda_tinv_table.jsonl``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_inference_demo_tpu.ops import kda  # noqa: E402

CHUNK, HEAD = kda.CHUNK, 128
_dot = functools.partial(jnp.matmul, precision=kda.HIGHEST)


# --------------------------------------------------------------- the inputs

def _strictly_lower_products(k, g, beta):
    """``N = diag(beta) tril(A, -1)``, ``A[t, s] = sum_d k_t k_s exp(G_t -
    G_s)``, in float64 from ``k, g`` ``[n, H, c, d]`` and ``beta`` ``[n, H,
    c]``; float32 ``[n, H, c, c]``."""
    G = np.cumsum(g, axis=2)
    N = np.empty(k.shape[:3] + (k.shape[2],), np.float32)
    for i in np.ndindex(*k.shape[:2]):
        E = np.exp(np.minimum(G[i][:, None, :] - G[i][None, :, :], 0.0))
        A = np.einsum("td,sd,tsd->ts", k[i], k[i], E)
        N[i] = beta[i][:, None] * np.tril(A, -1)
    return N


def _decay(rng, n, H, c, d):
    """``log alpha`` as the configuration's initialiser gives it."""
    A = np.exp(rng.uniform(0.0, np.log(16.0), (1, H, 1, 1)))
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (n, H, c, d)))
    return -A * step


def cell_input(seed: int, n: int, H: int, c: int = CHUNK, d: int = HEAD):
    """``(N, beta)`` float32 like the cell's: random unit keys."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(n, H, c, d))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(n, H, c))))
    return (_strictly_lower_products(k, _decay(rng, n, H, c, d), beta),
            beta.astype(np.float32))


def repeated_input(seed: int, n: int, H: int, c: int = CHUNK, d: int = HEAD):
    """``(N, beta)`` float32 of one key repeated: a direction a head plus
    5 % noise a token, hardly any decay, ``beta`` 1.9."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    k = unit(unit(rng.normal(size=(n, H, 1, d)))
             + 0.05 * unit(rng.normal(size=(n, H, c, d))))
    beta = np.full((n, H, c), 1.9)
    return (_strictly_lower_products(k, 0.01 * _decay(rng, n, H, c, d), beta),
            beta.astype(np.float32))


INPUTS = {"cell": cell_input, "repeated": repeated_input}


# ----------------------------------------------------------------- the ways

def solve(N, beta):
    eye = jnp.eye(N.shape[-1], dtype=N.dtype)
    return jax.scipy.linalg.solve_triangular(
        eye + N, beta[..., None] * eye, lower=True, unit_diagonal=True)


def blocks(base: int):
    return lambda N, beta: (kda.unit_lower_inverse(N, base)
                            * beta[..., None, :])


def neumann(N, beta):
    """The whole chunk's product of ``(I + N^(2^j))``: the control."""
    eye = jnp.eye(N.shape[-1], dtype=N.dtype)
    X, P = eye - N, N
    for _ in range(int(np.log2(N.shape[-1])) - 1):
        P = _dot(P, P)
        X = _dot(X, eye + P)
    return X * beta[..., None, :]


def rows32(N, beta, b: int = 32):
    """Tried first: the blocks as they lie, the steps unrolled."""
    D = kda._blocks(N, b, [(i, i) for i in range(N.shape[-1] // b)])
    X = jnp.broadcast_to(jnp.eye(b, dtype=N.dtype), D.shape)
    for j in range(b - 1):
        X = X - D[..., :, j:j + 1] * X[..., j:j + 1, :]
    return kda._merge_inverted_blocks(X, N) * beta[..., None, :]


def wide32(N, beta, b: int = 32):
    """Tried second: the merges as whole-matrix products."""
    c = N.shape[-1]
    blocks = kda.unit_lower_inverse(
        kda._blocks(N, b, [(i, i) for i in range(c // b)]))
    X = jnp.zeros_like(N)
    for i in range(c // b):
        X = X.at[..., i * b:(i + 1) * b, i * b:(i + 1) * b].set(
            blocks[..., i, :, :])
    at = jnp.arange(c)
    while b < c:
        blk = at // b
        level = (blk[:, None] == blk[None, :] + 1) & (blk[:, None] % 2 == 1)
        X = X - _dot(_dot(X, jnp.where(level, N, 0.0)), X)
        b *= 2
    return X * beta[..., None, :]


WAYS = {"solve": solve, "blocks16": blocks(16), "blocks32": blocks(32),
        "substitution": blocks(CHUNK), "neumann": neumann}
TIMED = ("solve", "blocks16", "blocks32")
TRIED = {"rows32": rows32, "wide32": wide32}


def error(way, N) -> float:
    """max ``|X - inv|`` over max ``|inv|`` of the inverse alone (``beta``
    1), ``inv`` numpy's float64 inverse of the float32 ``I + N``."""
    want = np.linalg.inv(np.eye(N.shape[-1]) + N.astype(np.float64))
    got = np.asarray(jax.jit(way)(jnp.asarray(N), jnp.ones(N.shape[:-1])),
                     np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def seconds_a_call(way, N, beta, reps: int) -> float:
    run = jax.jit(lambda Ns, bs: jax.lax.map(lambda x: way(*x), (Ns, bs)))
    Ns = jnp.broadcast_to(jnp.asarray(N), (reps,) + N.shape) + 0.0
    bs = jnp.broadcast_to(jnp.asarray(beta), (reps,) + beta.shape) + 0.0
    jax.block_until_ready(run(Ns, bs))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(Ns, bs))
        best = min(best, time.perf_counter() - t0)
    return best / reps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=["2x64", "6x64"],
                    help="chunks x heads of a call")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=68)
    ap.add_argument("--tried", action="store_true",
                    help="time the two forms PR 68 did not keep as well")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "kda_tinv_table.jsonl"))
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    rows = []
    for name, make in INPUTS.items():
        N, _ = make(args.seed, 2, 4)
        print(f"[input] {name}: max |N| {np.abs(N).max():.3f}", flush=True)
        for way, f in (WAYS | (TRIED if args.tried else {})).items():
            rows.append(dict(way=way, input=name, err=error(f, N)))
    for shape in args.shapes:
        n, H = (int(x) for x in shape.split("x"))
        N, beta = cell_input(args.seed, 1, 1)
        N = np.broadcast_to(N, (n, H) + N.shape[2:])
        beta = np.broadcast_to(beta, (n, H) + beta.shape[2:])
        timed = {w: WAYS[w] for w in TIMED} | (TRIED if args.tried else {})
        for way, f in timed.items():
            rows.append(dict(way=way, shape=shape, us=1e6 * seconds_a_call(
                f, N, beta, args.reps)))
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("w") as f:
        for row in rows:
            row["device"] = device
            f.write(json.dumps(row) + "\n")
    err = {(r["way"], r["input"]): r["err"] for r in rows if "err" in r}
    us = {(r["way"], r["shape"]): r["us"] for r in rows if "us" in r}
    print("| way | " + " | ".join(f"us at {s}" for s in args.shapes)
          + " | err, cell | err, repeated |")
    print("|---|" + "---|" * (len(args.shapes) + 2))
    for way in list(WAYS) + [w for w in TRIED if args.tried]:
        print(f"| `{way}` | " + " | ".join(
            f"{us[way, s]:.1f}" if (way, s) in us else "not timed"
            for s in args.shapes)
            + f" | {err[way, 'cell']:.2e} | {err[way, 'repeated']:.2e} |")
    print(json.dumps({"device": device, "out": str(out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
