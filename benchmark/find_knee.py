#!/usr/bin/env python3
"""Find a cell's knee once, on the chip, when the cell is defined.

    python3 benchmark/find_knee.py --workload <cell> --rates 3,5,7,9 [--seconds 20]

Starts the cell's replica and gateway once and offers the cell's traffic
at each rate in turn (a closed-loop cell takes client counts).  A step is a
ramp, a window and a drain like a run's, each with a seed of its own so
that no step finds the last one's prompts in the cache.  For each step it
prints the share of requests that met both limits of the mix (TTFT and
TPOT; a failed request misses), the tails, tokens per second and the
scheduler's backlog at the window's end.  The knee is the highest rate at
which at least 90 % met both and the backlog did not grow; the cell then
runs at 0.8 x that, written into ``cells/<cell>.json``.  Not part of a run:
it prints no result line.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from arith import late_ms, median, percentile, tokens_in_window, tpot_ms, ttft_ms  # noqa: E402
from client import run_plan                        # noqa: E402
from stack import BenchFailure, Stack              # noqa: E402
import run as bench_run                            # noqa: E402


def step(stack, generator, mix, load, seed, seconds, vocab) -> dict:
    ramp_s, drain_s = float(mix.get("ramp_s", 5)), float(mix.get("drain_s", 10))
    params = dict(mix, **load, horizon_s=ramp_s + seconds)
    plan = generator.make(params, seed, vocab, 1.0)
    t0 = time.monotonic()
    _, records = run_plan(stack.gw_port, plan, ramp_s + seconds,
                          ramp_s + seconds + drain_s, t0=t0)
    stats = stack.stats()
    t_open, t_close = t0 + ramp_s, t0 + ramp_s + seconds
    sample = [r for r in records if t_open <= r.due < t_close]
    ok = [r for r in sample if not r.problem(vocab)]
    lim = mix.get("limits", {})
    met = [r for r in ok
           if ttft_ms(r) <= lim.get("ttft_ms", float("inf"))
           and (tpot_ms(r) or 0.0) <= lim.get("tpot_ms", float("inf"))]
    ttfts = [ttft_ms(r) for r in ok]
    tpots = [x for x in map(tpot_ms, ok) if x is not None]
    half = (t_open + t_close) / 2
    first = [ttft_ms(r) for r in ok if r.due < half]
    second = [ttft_ms(r) for r in ok if r.due >= half]
    return {
        "load": load, "seed": seed, "attempted": len(sample),
        "failed": len(sample) - len(ok),
        "met_both_pct": 100.0 * len(met) / max(1, len(sample)),
        "ttft_p50_ms": median(ttfts) if ttfts else None,
        "ttft_p90_ms": percentile(ttfts, 90, min_beyond=3),
        "ttft_p50_first_half_ms": median(first) if first else None,
        "ttft_p50_second_half_ms": median(second) if second else None,
        "tpot_p50_ms": median(tpots) if tpots else None,
        "tpot_p90_ms": percentile(tpots, 90, min_beyond=3),
        "out_tokens_per_s": tokens_in_window(
            [r for r in records if not r.problem(vocab)], t_open, t_close)
        / seconds,
        "late_max_ms": max((late_ms(r) for r in sample if r.sent), default=0),
        "unfinished_at_drain_end": sum(
            1 for r in records if "drain" in r.error),
        "queue_depth_after": stats.get("queue_depth"),
        "active_slots_after": stats.get("active_slots"),
        "kv_blocks_used": stats.get("kvcache", {}).get("blocks_used"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma list: rates per second, or client counts "
                         "for a closed-loop mix")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--flag", action="append", default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    _, cell, entry, conf, mix = bench_run.load_cell(args.workload)
    key = "clients" if mix["loop"] == "closed" else "rate_per_s"
    vocab = conf["model_config"]["vocab_size"]
    generator = importlib.import_module(f"generators.{mix['generator']}")
    out_dir = Path(args.out) if args.out else BENCH / "out" / "knee" / cell["name"]
    rows = []
    try:
        with Stack(ROOT / entry["file"], args.seed, "tpu", cell["chips"],
                   False, out_dir, args.flag) as stack:
            health = stack.health()
            print(f"[knee] {cell['name']} on {health['platform']} "
                  f"{health['device_kind']!r} x{health['device_count']}",
                  flush=True)
            chunk = bench_run.flag_value(conf["serve_flags"],
                                         "--prefill-chunk")
            warm = bench_run.FixedPlan([bench_run.Request(
                0.0, bench_run.seeded_prompt(1, chunk + 8, vocab), 8)])
            run_plan(stack.gw_port, warm, 1.0, 1100.0)
            for i, x in enumerate(args.rates.split(",")):
                load = {key: int(x) if key == "clients" else float(x)}
                row = step(stack, generator, mix, load, args.seed + i,
                           args.seconds, vocab)
                rows.append(row)
                print("[knee] " + json.dumps(row), flush=True)
                stack.check_alive()
    except BenchFailure as e:
        print(f"find_knee: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    (out_dir / "knee.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
