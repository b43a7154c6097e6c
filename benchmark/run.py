#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are all looked
up by name: ``BENCHMARK.json`` (cells, metric lists), ``configs/<name>.json``,
``traffic/<mix>.json``, ``cells/<cell>.json`` (the cell's rate or client
count), ``generators/<generator>.py``, ``end_to_end/<metric>.py``,
``layer_metrics/<metric>.py`` and, for the configuration's kind of block,
``families/<family>.py``.  This file holds none of those names.

This parent is load generator and meter and never imports JAX.  It starts
the replica (``replica_main.py`` around the program's ``serve``) and the
program's ``gateway`` as children under ``JAX_PLATFORMS=tpu``: no TPU means
a non-zero exit and no result line, never a CPU run.  ``--rehearse-cpu``
walks the same path on the CPU with the configuration's toy model; it
prints no result line and exits 3, so a CPU number can never be taken for
a device's.

Phases: children up -> warm-up -> reference check and canaries -> ramp
(the cell's traffic, unmeasured) -> window of ``--seconds`` -> drain ->
canaries again -> ``/stats``, ``/health`` -> stop children -> (traced run)
reduce the trace -> the result line.  The wall seconds of each are printed
in the ``[time]`` line (``Stages``).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import threading
import time
import traceback
from pathlib import Path

T_PROCESS_START = time.monotonic()

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import families                                    # noqa: E402
from client import Request, ask, run_plan          # noqa: E402
from stack import BenchFailure, Stack              # noqa: E402

CANARIES, CANARY_PROMPT, CANARY_NEW = 4, 96, 16
TRACE_S = 8.0                   # the traced part of the window
REHEARSAL_EXIT = 3


def say(msg: str) -> None:
    print(msg, flush=True)


class Stages:
    """Where a run's wall seconds went: ``lap(name)`` books the seconds
    since the last lap (or up to the instant ``at``, if that has come)
    under ``name``, in the order of the run, from the process's start."""

    def __init__(self, start: float):
        self.start = self.last = start
        self.seconds = {}

    def lap(self, name: str, at: float | None = None) -> None:
        now = time.monotonic()
        at = now if at is None else min(at, now)
        self.seconds[name] = self.seconds.get(name, 0.0) + at - self.last
        self.last = at

    def report(self, marks: dict, reducer: dict) -> dict:
        """The stages, the profiler's own two calls (they overlap the
        window), what the reducer says of itself, and the total."""
        out = dict(self.seconds)
        for key, mark, a, b in (
                ("trace_start_s", "trace_started", "start", "running"),
                ("trace_collect_s", "trace_stopped", "stop", "collected"),
                ("trace_stop_s", "trace_stopped", "stop", "written")):
            stamp = marks.get(mark) or {}
            if a in stamp and b in stamp:
                out[key] = stamp[b]["monotonic"] - stamp[a]["monotonic"]
        if reducer:
            out["reducer"] = reducer
        out["total_s"] = self.last - self.start
        return out


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except OSError as e:
        raise BenchFailure(f"cannot read {path}: {e}") from None


def seeded_prompt(seed: int, n: int, vocab: int) -> list:
    """A seeded prompt of ``n`` in-vocabulary ids (chip_smoke's LCG)."""
    x = (seed * 2654435761 + 12345) & 0xFFFFFFFF
    out = []
    for _ in range(n):
        x = (x * 1664525 + 1013904223) & 0xFFFFFFFF
        out.append(1 + (x >> 8) % (vocab - 1))
    return out


class FixedPlan:
    """A plan of requests that set off nothing (warm-up)."""

    def __init__(self, requests):
        self.requests = requests

    def initial(self):
        return list(self.requests)

    def on_done(self, request, tokens, now_s):
        return []


def flag_value(flags: list, name: str) -> int:
    """The integer after ``name`` in a list of serve flags."""
    return int(flags[flags.index(name) + 1])


def load_cell(workload: str) -> tuple:
    """``(manifest, cell, configuration's manifest entry, configuration
    file, mix)`` of the cell named ``workload``.  A configuration whose
    family (its own or its rehearsal model's) has no module under
    ``families/`` is refused here, before any child starts."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no cell {workload!r} in BENCHMARK.json; "
                           f"cells: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    conf = load_json(ROOT / entry["file"])
    for served in (conf, conf["rehearsal"]):
        try:
            families.require(served["model_config"]["family"])
        except families.UnknownFamily as e:
            raise BenchFailure(f"{entry['file']}: {e}") from None
    return (manifest, cell, entry, conf,
            load_json(BENCH / "traffic" / f"{cell['traffic']}.json"))


def metric_entries(manifest: dict, group: str, cell: str) -> list:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(package: str, entries: list, ctx: dict) -> dict:
    """``{name: {"value", "unit"}}`` from each metric's own reader; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in entries:
        mod = importlib.import_module(f"{package}.{m['name']}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def canaries(port: int, vocab: int, seed: int, scale: float) -> list:
    """Four seeded greedy requests, one at a time; the first also asks
    for log-probabilities (the reference check reads them)."""
    n = max(4, int(CANARY_PROMPT * scale))
    new = max(4, int(CANARY_NEW * scale))
    out = []
    for i in range(CANARIES):
        prompt = seeded_prompt(seed * 1000 + 17 + i, n, vocab)
        res = ask(port, prompt, new, logprobs=(i == 0))
        if res["status"] != 200 or res["error"] or len(res["tokens"]) != new:
            raise BenchFailure(f"canary {i}: {res}")
        out.append({"prompt": prompt, **res})
    return out


def reference_check(stack: Stack, canary: dict, tolerance: float) -> dict:
    """The served log-probabilities of one canary's tokens against the
    plain float32 reference, run in the replica on the same parameters.
    What the reply said of how it generated them (``generation``) goes to
    the reference as it came and stays in the record."""
    said = ({} if canary.get("generation") is None
            else {"generation": canary["generation"]})
    ids = canary["prompt"] + canary["tokens"]
    reply = json.loads(stack.control(
        "REFERENCE " + json.dumps({"ids": ids,
                                   "n_prompt": len(canary["prompt"]),
                                   **said}),
        "REFERENCE_RESULT", 600))
    if "error" in reply:
        raise BenchFailure(f"reference check failed to run: {reply}")
    lengths = (len(canary["logprobs"]), len(reply["logprobs"]),
               len(canary["tokens"]))
    if len(set(lengths)) != 1:
        raise BenchFailure(
            "reference check: {} served log-probabilities, {} of the "
            "reference's and {} tokens: one a token is compared".format(
                *lengths))
    errs = [abs(a - b) for a, b in zip(canary["logprobs"],
                                       reply["logprobs"])]
    return {"max_abs_err": max(errs), "errs": errs,
            "tolerance": tolerance, "ok": max(errs) <= tolerance,
            "served": canary["logprobs"], "reference": reply["logprobs"],
            "reference_best_ids": reply["best_ids"],
            "reference_best_logprobs": reply["best_logprobs"],
            "tokens": canary["tokens"], "seconds": reply["seconds"], **said}


class Marks(threading.Thread):
    """Reads ``/stats`` at the window's edges and, in a traced run,
    switches the profiler and polls ``/stats`` once a second."""

    def __init__(self, stack: Stack, t_open: float, seconds: float,
                 trace_dir: Path | None):
        super().__init__(daemon=True)
        self.stack, self.t_open, self.seconds = stack, t_open, seconds
        self.trace_dir = trace_dir
        self.out = {"polls": []}
        self.error = None

    def _sleep_until(self, t: float) -> None:
        left = t - time.monotonic()
        if left > 0:
            time.sleep(left)

    def run(self) -> None:
        try:
            st, out = self.stack, self.out
            self._sleep_until(self.t_open)
            out["stats_open"] = st.stats()
            if self.trace_dir is not None:
                out["trace_started"] = json.loads(st.control(
                    f"TRACE_START {self.trace_dir}", "TRACE_STARTED", 60))
                out["stats_trace_start"] = st.stats()
                out["stats_trace_start_at"] = time.monotonic()
                end = time.monotonic() + min(TRACE_S, self.seconds)
                while time.monotonic() < end:
                    time.sleep(min(1.0, max(0.0, end - time.monotonic())))
                    out["polls"].append(st.stats())
                out["stats_trace_stop"] = out["polls"][-1]
                out["stats_trace_stop_at"] = time.monotonic()
                out["trace_stopped"] = json.loads(st.control(
                    "TRACE_STOP", "TRACE_STOPPED", 300))
            self._sleep_until(self.t_open + self.seconds)
            out["stats_close"] = st.stats()
        except Exception as e:      # handed to the main thread
            self.error = e


def reduce_trace(trace_dir: Path, out_path: Path) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir`` in a child
    process (it imports jax.profiler; the chip is free by now)."""
    pbs = sorted(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not pbs:
        return {}
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
    done = subprocess.run(
        [sys.executable, str(BENCH / "trace_reduce.py"), str(pbs[-1]),
         str(out_path)],
        env=env, capture_output=True, text=True, timeout=600)
    for pb in pbs:                  # the reduced JSON is what is kept
        pb.unlink()
    if done.returncode != 0:
        raise BenchFailure(f"trace reduction failed:\n{done.stderr[-2000:]}")
    return json.loads(out_path.read_text())


def run(args) -> int:
    manifest, cell, conf_entry, conf, mix = load_cell(args.workload)
    load = load_json(BENCH / "cells" / f"{cell['name']}.json")
    for k in ("rate_per_s", "clients"):
        if getattr(args, k) is not None:
            load[k] = getattr(args, k)
    rehearse = args.rehearse_cpu
    served = conf["rehearsal"] if rehearse else conf
    vocab = served["model_config"]["vocab_size"]
    max_seq = flag_value(served["serve_flags"], "--max-seq")
    scale = (max_seq / flag_value(conf["serve_flags"], "--max-seq")
             if rehearse else 1.0)
    chunk = flag_value(served["serve_flags"], "--prefill-chunk")
    out_dir = Path(args.out) if args.out else BENCH / "out" / cell["name"]
    out_dir.mkdir(parents=True, exist_ok=True)
    trace_dir = out_dir / "trace" if args.trace else None
    ramp_s = float(mix.get("ramp_s", 5))
    drain_s = float(mix.get("drain_s", 10))
    params = dict(mix, **load, horizon_s=ramp_s + args.seconds)
    generator = importlib.import_module(f"generators.{mix['generator']}")
    stages = Stages(T_PROCESS_START)

    with Stack(ROOT / conf_entry["file"], args.seed,
               "cpu" if rehearse else "tpu", cell["chips"], rehearse,
               out_dir, args.flag) as stack:
        stages.lap("children_up_s")
        health = stack.health()
        if not rehearse and (health.get("platform") != "tpu"
                             or health.get("device_count") != cell["chips"]):
            raise BenchFailure(
                f"the cell needs {cell['chips']} TPU chip(s); the replica "
                f"runs on {health.get('device_count')} x "
                f"{health.get('platform')}")
        say(f"[setup] children up at {time.monotonic() - T_PROCESS_START:.1f}"
            f" s: {health['platform']} {health['device_kind']!r} x"
            f"{health['device_count']} model={health['model']}")

        # warm-up: one prompt longer than the chunk (a chunk-only dispatch,
        # a dispatch with a final, decode-only dispatches) and a few short
        # requests at once, through the gateway.  Every variant of
        # mixed_step is compiled and launched by the engine before it is
        # ready, whatever these requests pack
        warm = [Request(0.0, seeded_prompt(args.seed + 1, min(
            chunk + 8, max_seq - 12), vocab), 8)]
        warm += [Request(0.0, seeded_prompt(args.seed + 2 + i, 12, vocab), 6)
                 for i in range(3)]
        _, recs = run_plan(stack.gw_port, FixedPlan(warm), 1.0, 1100.0)
        bad = [r.problem(vocab) for r in recs if r.problem(vocab)]
        if bad:
            raise BenchFailure(f"warm-up failed: {bad}")
        stages.lap("warm_up_s")
        say(f"[setup] warm-up done at "
            f"{time.monotonic() - T_PROCESS_START:.1f} s; compile ledger "
            f"{json.dumps(stack.stats().get('compile', {}))}")

        first = canaries(stack.gw_port, vocab, args.seed, scale)
        ref = reference_check(stack, first[0],
                              float(load_json(BENCH / "tolerance.json")
                                    ["max_abs_logprob_err"]))
        say(f"[correct] reference: max |err| {ref['max_abs_err']:.5f} "
            f"(tolerance {ref['tolerance']}) in {ref['seconds']:.1f} s; "
            f"errs {[round(e, 4) for e in ref['errs']]}")

        plan = generator.make(params, args.seed, vocab, scale)
        stages.lap("reference_and_canaries_s")
        t0 = time.monotonic()
        marks = Marks(stack, t0 + ramp_s, args.seconds, trace_dir)
        marks.start()
        _, records = run_plan(stack.gw_port, plan, ramp_s + args.seconds,
                              ramp_s + args.seconds + drain_s, t0=t0)
        stages.lap("ramp_s", t0 + ramp_s)
        stages.lap("window_s", t0 + ramp_s + args.seconds)
        stages.lap("drain_s")
        marks.join(timeout=600)
        if marks.error is not None or marks.is_alive():
            raise BenchFailure(f"reading /stats or tracing failed: "
                               f"{marks.error!r}")
        stack.check_alive()
        stages.lap("marks_joined_s")
        second = canaries(stack.gw_port, vocab, args.seed, scale)
        stats_end, health_end = stack.stats(), stack.health()
        stages.lap("canaries_again_s")

    stages.lap("children_stopped_s")
    reduced, reducer = {}, {}
    if trace_dir is not None:
        reduced = reduce_trace(trace_dir, out_dir / "trace_reduced.json")
        reducer = reduced.pop("reducer", {})    # its own cost: no metric's
        stages.lap("reduce_trace_s")

    # children are stopped; everything below is arithmetic
    t_open, t_close = t0 + ramp_s, t0 + ramp_s + args.seconds
    sample = [r for r in records if t_open <= r.due < t_close]
    problems = [(r, r.problem(vocab)) for r in records]
    well_formed = [r for r, p in problems if not p]
    failed = [(r, p) for r, p in problems
              if p and t_open <= r.due < t_close]
    ok = [r for r in sample if not r.problem(vocab)]
    ctx = {
        "cell": cell, "config": conf, "mix": mix, "load": load,
        "served": served, "rehearse": rehearse, "seconds": args.seconds,
        "seconds_before_window": t_open - T_PROCESS_START, "window": (t_open, t_close),
        "records": records, "sample": sample, "ok": ok,
        "well_formed": well_formed, "health": health_end,
        "stats_open": marks.out["stats_open"],
        "stats_close": marks.out["stats_close"], "stats_end": stats_end,
        "marks": marks.out, "trace": reduced,
    }
    compiled_in_window = {
        prog: c["compiles"] - ctx["stats_open"].get("compile", {}).get(
            prog, {}).get("compiles", 0)
        for prog, c in ctx["stats_close"].get("compile", {}).items()}
    paths = stats_end.get("attention_paths", {})
    checks = {
        "requests_well_formed": not failed,
        "canaries_identical": [c["tokens"] for c in first]
        == [c["tokens"] for c in second],
        "reference_within_tolerance": ref["ok"],
        "no_compile_in_window": not any(compiled_in_window.values()),
        "attention_paths_as_configured":
            rehearse or paths == conf["attention_paths"],
    }
    correct = all(checks.values())

    # earlier lines: everything that is not the contract's result
    say(f"[checks] {json.dumps(checks)}")
    if not checks["attention_paths_as_configured"]:
        say(f"[checks] attention paths {json.dumps(paths)} != configured "
            f"{json.dumps(conf['attention_paths'])}")
    if not checks["canaries_identical"]:
        say(f"[checks] canaries before {[c['tokens'] for c in first]} "
            f"after {[c['tokens'] for c in second]}")
    for r, p in failed[:10]:
        say(f"[failed] due +{r.due - t0:.2f}s prompt {r.prompt_len} "
            f"max_new {r.max_new}: {p}")
    say(f"[load] {json.dumps(load)} sent {len(records)} in window "
        f"{len(sample)} ok {len(ok)}; prompt tokens in window "
        f"{sum(r.prompt_len for r in sample)}, asked output tokens "
        f"{sum(r.max_new for r in sample)}")
    say(f"[paths] {json.dumps(paths)}")
    say(f"[compile] {json.dumps(stats_end.get('compile', {}))} "
        f"in window {json.dumps(compiled_in_window)}")
    stages.lap("report_s")
    spent = stages.report(marks.out, reducer)
    say(f"[time] {json.dumps(spent)}")
    (out_dir / f"records_seed{args.seed}_trace{args.trace}.json").write_text(
        json.dumps({"t0": t0, "window": [t_open, t_close], "time": spent,
                    "checks": checks, "reference": ref, "load": load,
                    "stats_open": ctx["stats_open"],
                    "stats_close": ctx["stats_close"],
                    "health": health_end,
                    "records": [[r.due - t0, r.sent - t0, r.prompt_len,
                                 r.max_new, len(r.tokens),
                                 (r.token_times[0] - t0) if r.token_times
                                 else None, r.end - t0, r.problem(vocab)]
                                for r in records]}))

    # a per-layer metric is reported only where the metric it moves is
    end_to_end = read_metrics(
        "end_to_end", metric_entries(manifest, "end_to_end", cell["name"]),
        ctx)
    if args.trace:
        metrics = read_metrics(
            "layer_metrics",
            [m for m in metric_entries(manifest, "per_layer", cell["name"])
             if m["moves"] in end_to_end], ctx)
    else:
        metrics = end_to_end
    devices = health_end.get("devices", [])
    device = {"platform": health_end["platform"],
              "kind": health_end["device_kind"],
              "count": health_end["device_count"],
              "memory_peak_bytes": max(
                  (d.get("peak_bytes_in_use", 0) for d in devices),
                  default=0)}
    result = {"correct": correct, "attempted": len(sample),
              "failed": len(failed), "metrics": metrics, "device": device}
    if args.trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        import breakdown
        result["breakdown"] = breakdown.build(ctx)
    if rehearse:
        say("[rehearsal] the result line a chip run would print (NOT a "
            "result: CPU, toy model): " + json.dumps(result)[:3000])
        say("[rehearsal] passed; no result line is printed and the exit "
            f"code is {REHEARSAL_EXIT}")
        return REHEARSAL_EXIT
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the run on the CPU with the configuration's "
                         "toy model; prints no result line, exits 3")
    ap.add_argument("--rate-per-s", dest="rate_per_s", type=float,
                    default=None, help="(sweeps) override the cell's rate")
    ap.add_argument("--clients", type=int, default=None,
                    help="(sweeps) override the cell's client count")
    ap.add_argument("--flag", action="append", default=[],
                    help="(experiments) extra serve flag word, repeatable")
    ap.add_argument("--out", default="",
                    help="directory for logs and records "
                         "(default benchmark/out/<cell>)")
    args = ap.parse_args(argv)
    if not (ROOT / "distributed_inference_demo_tpu" / "cli.py").is_file():
        print("benchmark/run.py: the program is not beside the benchmark; "
              "this is not a checkout of the repo", file=sys.stderr)
        return 2
    try:
        return run(args)
    except BenchFailure as e:
        print(f"benchmark/run.py: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
