#!/usr/bin/env python
"""The state-space step alone, on the chip: us a call of ``_ssd_step`` at
each benchmark configuration's state and decode rows, beside the time its
bytes take, for the loop as it stood before PR 67 and for each form tried
since.  It is the table ``ops/ssd._ssd_step_kernel`` was chosen from
(PERF.md section 6, PR 67; docs/DESIGN.md section 29); run it again when a
configuration brings a new state or the kernel changes:

    python tools/ssd_step_table.py
    python tools/ssd_step_table.py --config granite-4.0-h-small-bf16-ep2

Shapes come from ``benchmark/configs/*.json``: the ssd kind's heads, head
width, state size and groups, a pool of as many planes as the file's
period has ssd blocks and ``--batch-slots`` + 2 rows, and the rows that
decode (``LIVE``: 20 of granite's 32, whose other rows are in their
prompts; all of nemotron's 64), the dead ones drawn by ``--seed``.  A time
is the least of three runs of ``--reps`` calls in one jitted scan whose
carry is the pool (in place, as served), a call a plane in turn, host
clock around ``block_until_ready``; only the Pallas call is in the scan
(what XLA lays out for it before is made once).  ``bytes`` is a live
row's block once in and once out over 819 GB/s
(``benchmark/families/granite_moe_hybrid.ssd_decode_kernel_bytes``);
``err`` a form's output and state against ``ops.ssd._step_math`` on the
same rows, max |difference| over max |value|.  One JSON line a row goes to
``chiprun_out/ssd_step_table.jsonl``.

``--dma`` times what bounds every form instead: blocks of the
configuration's size moved HBM -> VMEM -> HBM in place by a kernel that
computes nothing, under four schedules of its own copies (``read``: in
alone, two in flight; ``write``: out alone; ``both``: row ``r + 1`` comes
in while row ``r`` goes out, as a BlockSpec pipeline has them; ``phased``:
two rows in, then the two out, never a read beside a write), GB/s on the
device's clock.

The forms:

* ``before``: a head at a time on ``[P, N]`` tiles; the decay and ``dt x``
  come as lane ``h`` of ``[P, H]`` tiles and are broadcast along the lanes,
  the output is a lane reduce a register and a one-lane store (PR 62).
* ``served``: what ``ops.ssd`` serves now, eight tiles of its loop
  unrolled together (``unroll<n>``: ``n`` tiles; 64 is the whole loop);
  the live rows FIRST, where every other form takes the rows as they come.
* ``columns``: ``served``'s read-out, but ``dt x`` still taken as lane
  slices of a ``[P, H]`` tile and broadcast, a head at a time.
* ``nt``: the POOL laid ``[H P / 128, N, 128]`` (a head's state
  transposed, as many heads side by side as fill the lanes: the issue's
  second way).  The decay and ``dt x`` are rows, B and C columns broadcast
  along the lanes once a group, the read-out a sum down the sublanes: no
  cross-lane work a register.  The kernel alone: nothing serves this pool.
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
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from distributed_inference_demo_tpu.ops import ssd  # noqa: E402
from distributed_inference_demo_tpu.telemetry.profiling import (  # noqa: E402
    DEVICE_PEAKS)

F32 = jnp.float32
# the floors are the v5e's whatever runs the script (a rehearsal has none)
PEAKS = DEVICE_PEAKS["TPU v5 lite"]
# rows that decode of a configuration's slots, as its cell's dispatches have
# them (PERF.md section 5); a configuration not named here: every slot
LIVE = {"granite-4.0-h-small-bf16-ep2": 20, "minicpm-sala-9b-bf16": 12}


def ssd_configs(names):
    """The configurations with an ssd kind of block, or with a linear-
    attention kind, which rides the same call with B and C a head's own
    (groups = heads, P = N = the head's width)."""
    for path in sorted((ROOT / "benchmark" / "configs").glob("*.json")):
        conf = json.loads(path.read_text())
        mc = conf["model_config"]
        kinds = [k for k in mc.get("period") or []
                 if k.get("attn") in ("ssd", "lightning")]
        if not kinds or (names and conf["name"] not in names):
            continue
        flags = conf["serve_flags"]
        slots = int(flags[flags.index("--batch-slots") + 1])
        k = kinds[0]
        shape = (dict(heads=k["num_heads"], p=mc["head_dim_override"],
                      n=mc["head_dim_override"], groups=k["num_heads"])
                 if k["attn"] == "lightning" else
                 dict(heads=k["state_heads"], p=k["state_head_dim"],
                      n=k["state_size"], groups=k.get("groups", 1)))
        yield dict(name=conf["name"], planes=len(kinds), slots=slots,
                   live=LIVE.get(conf["name"], slots), **shape)


# ---------------------------------------------------------------- the forms
# A form is (lay, scalars, call, read): ``lay(x, B, C, dt, A)`` what XLA
# lays out for the call, ``scalars(rows, live, trash, plane)`` the call's
# scalar operands, ``call(*scalars, *laid, state)`` the Pallas call ``(y as
# the kernel writes it, state')``, ``read(y)`` ``[b, H, P]``.

def _scalars_before(rows, live, trash: int, plane):
    """``ops.ssd._blocks_of`` as PR 66 left it: the rows in the order they
    came, a dead row on the block of the last live row before it (mode 0;
    nobody's row, mode 2, where there is none), a live row mode 1."""
    b = rows.shape[0]
    at = jnp.arange(b, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, at, -1))
    rows = jnp.where(last >= 0, rows[jnp.maximum(last, 0)], trash)
    opens = jnp.concatenate([jnp.ones((1,), bool), rows[1:] != rows[:-1]])
    return rows.astype(jnp.int32), plane, jnp.where(
        live, 1, jnp.where(opens, 2, 0)).astype(jnp.int32)


def _scalars_served(rows, live, trash: int, plane):
    rows, at, n = ssd._blocks_of(rows, live, trash)
    return rows, at, plane, n


def _before_kernel(rows_ref, plane_ref, mode_ref, ax_ref, bc_ref, s_ref,
                   y_ref, out_ref, *, heads: int, groups: int):
    """``ops.ssd._ssd_step_kernel`` as PR 66 left it."""
    del rows_ref, plane_ref
    mode = mode_ref[pl.program_id(0)]

    @pl.when(mode == 1)
    def _live():
        for h in range(heads):
            g = h // (heads // groups)
            B = bc_ref[0, 0, g:g + 1, :]
            C = bc_ref[0, 1, g:g + 1, :]
            a = ax_ref[0, 0, :, h:h + 1]
            dx = ax_ref[0, 1, :, h:h + 1]
            S = s_ref[0, 0, h] * a + dx * B
            out_ref[0, 0, h] = S
            y_ref[0, :, h:h + 1] = jnp.sum(S * C, axis=1, keepdims=True)

    @pl.when(mode == 2)
    def _through():
        out_ref[...] = s_ref[...]


def _before_lay(x, B, C, dt, A):
    b, H, P = x.shape
    a = jnp.broadcast_to(jnp.exp(dt * A)[:, None, :], (b, P, H))
    return (jnp.stack([a, jnp.swapaxes(dt[..., None] * x, 1, 2)], axis=1),
            jnp.stack([B, C], axis=1))


def _rows_call(kernel, scalars, operands, y_shape, state, interpret):
    """The call of a form that takes the rows as they come: grid (rows,),
    the three scalars of ``_scalars_before``, every operand a row a step
    (one of one dimension whole, in scalar memory), the pool's block by
    plane and row and aliased to the second output."""
    b = y_shape[0]
    a_row = lambda t: pl.BlockSpec(  # noqa: E731
        (1, *t[1:]), lambda i, *_: (i,) + (0,) * (len(t) - 1))
    s_spec = pl.BlockSpec((1, 1, *state.shape[2:]),
                          lambda i, rows, plane, mode: (plane[0], rows[i],
                                                        0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM) if t.ndim == 1
                      else a_row(t.shape) for t in operands] + [s_spec],
            out_specs=[a_row(y_shape), s_spec]),
        out_shape=[jax.ShapeDtypeStruct(y_shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={3 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=ssd._VMEM),
        interpret=interpret, name="_ssd_step",
    )(*scalars, *operands, state)


def _before_call(rows, plane, mode, ax, bc, state, *, interpret=False):
    b, _, P, H = ax.shape
    return _rows_call(
        functools.partial(_before_kernel, heads=H, groups=bc.shape[2]),
        (rows, plane, mode), (ax, bc), (b, P, H), state, interpret)


def _columns_kernel(rows_ref, plane_ref, mode_ref, a_ref, dxT_ref, bc_ref,
                    s_ref, y_ref, out_ref, *, heads: int, groups: int):
    """The read-out of ``served`` (a tile of 128 ``(h, p)`` rows transposed
    once, summed down the sublanes), ``dt x`` as ``before`` took it."""
    del rows_ref, plane_ref
    i = pl.program_id(0)
    mode = mode_ref[i]
    P = s_ref.shape[3]
    hp = 128 // P

    @pl.when(mode == 1)
    def _live():
        for t in range(heads // hp):
            T = []
            for h in range(t * hp, (t + 1) * hp):
                g = h // (heads // groups)
                B = bc_ref[0, 0, g:g + 1, :]
                C = bc_ref[0, 1, g:g + 1, :]
                S = (s_ref[0, 0, h] * a_ref[i * heads + h]
                     + dxT_ref[0, :, h:h + 1] * B)
                out_ref[0, 0, h] = S
                T.append(S * C)
            y_ref[0, t:t + 1, :] = jnp.sum(
                jnp.concatenate(T, axis=0).T, axis=0, keepdims=True)

    @pl.when(mode == 2)
    def _through():
        out_ref[...] = s_ref[...]


def _columns_lay(x, B, C, dt, A):
    return (jnp.exp(dt * A).reshape(-1),
            jnp.swapaxes(dt[..., None] * x, 1, 2), jnp.stack([B, C], axis=1))


def _columns_call(rows, plane, mode, a, dxT, bc, state, *, interpret=False):
    b, P, H = dxT.shape
    return _rows_call(
        functools.partial(_columns_kernel, heads=H, groups=bc.shape[2]),
        (rows, plane, mode), (a, dxT, bc), (b, H * P // 128, 128), state,
        interpret)


def _nt_kernel(rows_ref, plane_ref, mode_ref, ax_ref, bcT_ref, s_ref, y_ref,
               out_ref, *, tiles: int, groups: int):
    """The pool laid ``[tiles, N, 128]``: ``ax_ref`` ``[1, 2, tiles, 128]``
    the decay and ``dt x`` as rows, ``bcT_ref`` ``[1, 2, N, G]`` B and C as
    columns, a group a lane."""
    del rows_ref, plane_ref
    mode = mode_ref[pl.program_id(0)]
    N = s_ref.shape[3]

    @pl.when(mode == 1)
    def _live():
        per = tiles // groups
        for g in range(groups):
            B = jnp.broadcast_to(bcT_ref[0, 0, :, g:g + 1], (N, 128))
            C = jnp.broadcast_to(bcT_ref[0, 1, :, g:g + 1], (N, 128))
            for t in range(g * per, (g + 1) * per):
                S = (s_ref[0, 0, t] * ax_ref[0, 0, t:t + 1, :]
                     + ax_ref[0, 1, t:t + 1, :] * B)
                out_ref[0, 0, t] = S
                y_ref[0, t:t + 1, :] = jnp.sum(S * C, axis=0, keepdims=True)

    @pl.when(mode == 2)
    def _through():
        out_ref[...] = s_ref[...]


def _nt_lay(x, B, C, dt, A):
    b, H, P = x.shape
    a = jnp.broadcast_to(jnp.exp(dt * A)[..., None], (b, H, P))
    rows = lambda t: t.reshape(b, H * P // 128, 128)  # noqa: E731
    return (jnp.stack([rows(a), rows(dt[..., None] * x)], axis=1),
            jnp.swapaxes(jnp.stack([B, C], axis=1), 2, 3))


def _nt_call(rows, plane, mode, ax, bcT, state, *, interpret=False):
    b, _, tiles, _ = ax.shape
    return _rows_call(
        functools.partial(_nt_kernel, tiles=tiles, groups=bcT.shape[3]),
        (rows, plane, mode), (ax, bcT), (b, tiles, 128), state, interpret)


def _nt_pool(state):
    """``[.., H, P, N]`` -> ``[.., H P / 128, N, 128]``."""
    *lead, H, P, N = state.shape
    hp = 128 // P
    return jnp.swapaxes(
        state.reshape(*lead, H // hp, hp * P, N), -1, -2)


def _nt_unpool(state, P: int):
    *lead, tiles, N, _ = state.shape
    return jnp.swapaxes(state, -1, -2).reshape(
        *lead, tiles * 128 // P, P, N)


FORMS = {
    "before": (_before_lay, _scalars_before, _before_call,
               lambda y: jnp.swapaxes(y, 1, 2)),
    "served": (ssd._step_operands, _scalars_served, ssd._ssd_step_call, None),
    **{f"unroll{u}": (ssd._step_operands, _scalars_served,
                      functools.partial(ssd._ssd_step_call, unroll=u), None)
       for u in (1, 2, 4, 64)},
    "columns": (_columns_lay, _scalars_before, _columns_call, None),
    "nt": (_nt_lay, _scalars_before, _nt_call, None),
}


def rows_of(c: dict, args):
    H, P, N, G = c["heads"], c["p"], c["n"], c["groups"]
    b, R = c["slots"], c["slots"] + 2
    rs = np.random.RandomState(args.seed)
    live_np = np.zeros(b, bool)
    live_np[(np.arange(b) if args.dead == "last"
             else rs.permutation(b))[:c["live"]]] = True
    live = jnp.asarray(live_np)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    x = jax.random.normal(ks[0], (b, H, P))
    B = 0.3 * jax.random.normal(ks[1], (b, G, N))
    C = 0.3 * jax.random.normal(ks[2], (b, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.7))
    pool = jax.random.normal(ks[5], (c["planes"], R, H, P, N))
    rows = jnp.asarray(rs.permutation(R - 1)[:b], jnp.int32)
    want_y, want_S = jax.jit(ssd.ssd_step)(pool, jnp.int32(0), rows, x, B, C,
                                           dt, A, live)
    rel = lambda a, w: float(jnp.abs(a - w).max() / jnp.abs(w).max())  # noqa: E731
    # a dead row's and nobody's (the last): to be left bit for bit
    rest = jnp.where(live, R - 1, rows)
    rows = jnp.where(live, rows, R - 1)
    nbytes = c["live"] * 2 * H * P * N * 4
    row = dict(config=c["name"], state=[H, P, N], groups=G, rows=b,
               live=c["live"], planes=c["planes"],
               bytes_us=round(1e-3 * nbytes / PEAKS.hbm_gbs, 1))
    for name in args.form:
        lay, scalars, call, read = FORMS[name]
        laid = lay(x, B, C, dt, A)
        state = _nt_pool(pool) if name == "nt" else pool
        y, got = call(*scalars(rows, live, R - 1, jnp.zeros((1,), jnp.int32)),
                      *laid, state, interpret=args.rehearse)
        y = (read(y) if read else y.reshape(b, H, P))
        got = _nt_unpool(got, P) if name == "nt" else got
        err = max(rel(jnp.where(live[:, None, None], y, 0.0), want_y),
                  rel(got[0], want_S[0]))
        untouched = bool(jnp.array_equal(got[0, rest], pool[0, rest]))
        del y, got

        @jax.jit
        def calls(state, laid):
            def one(state, i):
                plane = jnp.reshape(i % c["planes"], (1,))
                return call(*scalars(rows, live, R - 1, plane), *laid, state,
                            interpret=args.rehearse)[1], None
            return jax.lax.scan(one, state,
                                jnp.arange(args.reps, dtype=jnp.int32))[0]

        state = calls(state, laid).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            state = calls(state, laid).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        us = 1e6 * best / args.reps
        row[name] = dict(us=round(us, 1),
                         pct=round(100 * row["bytes_us"] / us, 1),
                         err=err, dead_rows_untouched=untouched)
        if args.trace:
            row[name]["device_us"] = round(device_us(
                lambda: calls(state, laid).block_until_ready())
                / args.reps, 1)
        del state
    yield row


# ------------------------------------------------------ what the DMA gives

DMA_SCHEDULES = ("read", "write", "both", "phased")


def _dma_kernel(s_hbm, o_hbm, buf, sem_in, sem_out, *, n: int,
                schedule: str):
    def copy(into: bool, r, slot):
        hbm, sem = (s_hbm, sem_in) if into else (o_hbm, sem_out)
        pair = (hbm.at[r], buf.at[slot]) if into else (buf.at[slot],
                                                       hbm.at[r])
        return pltpu.make_async_copy(*pair, sem.at[slot])

    if schedule in ("read", "write"):
        into = schedule == "read"
        copy(into, 0, 0).start()

        def row(r, _):
            @pl.when(r + 1 < n)
            def _next():
                copy(into, r + 1, (r + 1) % 2).start()
            copy(into, r, r % 2).wait()
    elif schedule == "both":
        copy(True, 0, 0).start()
        copy(True, 0, 0).wait()

        def row(r, _):
            @pl.when(r + 1 < n)
            def _next():
                copy(True, r + 1, (r + 1) % 2).start()
            copy(False, r, r % 2).start()

            @pl.when(r + 1 < n)
            def _came():
                copy(True, r + 1, (r + 1) % 2).wait()
            copy(False, r, r % 2).wait()
    else:
        def row(q, _):
            for into in (True, False):
                for slot in (0, 1):
                    copy(into, 2 * q + slot, slot).start()
                for slot in (0, 1):
                    copy(into, 2 * q + slot, slot).wait()
        n = n // 2
    jax.lax.fori_loop(0, n, row, None)


def dma_rows_of(c: dict, args):
    """GB/s a schedule: ``slots`` blocks of a row's state a call."""
    n, shape = c["slots"], (c["heads"] * c["p"], c["n"])
    pool = jnp.ones((n, *shape), F32)
    row = dict(config=c["name"], dma=True, block_bytes=4 * shape[0] * shape[1],
               rows=n)
    for schedule in DMA_SCHEDULES:
        call = pl.pallas_call(
            functools.partial(_dma_kernel, n=n, schedule=schedule),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(pool.shape, F32),
            scratch_shapes=[pltpu.VMEM((2, *shape), F32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))],
            input_output_aliases={0: 0},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=ssd._VMEM),
            interpret=args.rehearse, name="ssd_dma_probe")
        calls = jax.jit(lambda pool, call=call: jax.lax.fori_loop(
            0, args.reps, lambda _, pool: call(pool), pool))
        pool = calls(pool).block_until_ready()
        us = device_us(lambda: calls(pool).block_until_ready(),
                       "ssd_dma_probe") / args.reps
        moved = row["block_bytes"] * n * (1 if schedule in ("read", "write")
                                          else 2)
        row[schedule] = dict(us=round(us, 1),
                             gbs=round(moved / us / 1e3, 1) if us else None)
    yield row


def device_us(run, name: str = "_ssd_step") -> float:
    """The device's own time in the calls named ``name`` while ``run``
    runs under the profiler: what the benchmark's roofline metrics read
    (``benchmark/trace_reduce.py``: the ``XLA Ops`` line of the chip's
    plane), without the scan's steps between the calls."""
    import glob
    import tempfile

    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        run()
        jax.profiler.stop_trace()
        path, = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        total = 0
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    total += sum(e.duration_ns for e in line.events
                                 if name in e.name.split(" = ")[0])
    return total / 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", action="append", default=[],
                    help="a benchmark configuration's name (default: every "
                         "one with an ssd kind)")
    ap.add_argument("--form", action="append", default=[],
                    choices=sorted(FORMS), help="default: every form")
    ap.add_argument("--reps", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: the kernels interpreted at 16 heads "
                         "and 4 rows, to see the script run; its times mean "
                         "nothing")
    ap.add_argument("--dma", action="store_true",
                    help="what the chip's DMA gives blocks of this size "
                         "with no arithmetic, not the forms' table")
    ap.add_argument("--trace", action="store_true",
                    help="also the device's own time in the calls, from a "
                         "profiler trace of one more run (device_us)")
    ap.add_argument("--live", type=int, default=None,
                    help="rows that decode (default: the cell's, LIVE)")
    ap.add_argument("--dead", choices=("seeded", "last"), default="seeded",
                    help="where the rows that do not decode lie: drawn by "
                         "--seed (default), or behind the live ones")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "ssd_step_table.jsonl"))
    args = ap.parse_args()
    args.form = args.form or list(FORMS)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        ap.error(f"no chip here ({dev.platform}): a time comes from the "
                 "chip (--rehearse runs the script without one)")
    print(f"# device {dev.platform} {dev.device_kind}; seed {args.seed}; us "
          f"a call (% of the bytes' time), least of 3 x {args.reps}")
    head = (["config", "block", "rows", *DMA_SCHEDULES] if args.dma else
            ["config", "state", "groups", "live / rows", "bytes",
             *args.form, "err"])
    print("| " + " | ".join(head) + " |")
    print("|" + "---|" * len(head))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as out:
        for c in ssd_configs(args.config):
            if args.dma:
                if args.rehearse:
                    c.update(heads=16, slots=4)
                for row in dma_rows_of(c, args):
                    row.update(device=dev.device_kind, reps=args.reps,
                               rehearsal=args.rehearse)
                    out.write(json.dumps(row) + "\n")
                    print("| " + " | ".join(map(str, [
                        row["config"], row["block_bytes"], row["rows"],
                        *(f"{row[k]['us']} us, {row[k]['gbs']} GB/s"
                          for k in DMA_SCHEDULES)])) + " |", flush=True)
                continue
            if args.live is not None:
                c["live"] = min(args.live, c["slots"])
            if args.rehearse:
                c.update(heads=16, slots=4, live=min(c["live"], 3),
                         planes=2, groups=min(c["groups"], 2))
            for row in rows_of(c, args):
                row.update(device=dev.device_kind, seed=args.seed,
                           dead=args.dead,
                           reps=args.reps, rehearsal=args.rehearse)
                out.write(json.dumps(row) + "\n")
                out.flush()
                print("| " + " | ".join(map(str, [
                    row["config"], "x".join(map(str, row["state"])),
                    row["groups"], f"{row['live']} / {row['rows']}",
                    row["bytes_us"],
                    *(f"{row[f]['us']} ({row[f]['pct']})"
                      + (f" dev {row[f]['device_us']}" if args.trace else "")
                      for f in args.form),
                    f"{max(row[f]['err'] for f in args.form):.1e}"]))
                    + " |",
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
