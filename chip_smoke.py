#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python3 chip_smoke.py            # one TPU chip, qwen2.5-7b-int8, full width
    python3 chip_smoke.py --tp 4     # one four-chip host, qwen2.5-7b bf16

Drives the main path once through the entry points a user calls:
``python -m distributed_inference_demo_tpu gateway`` in front of
``... serve --batch-slots 8 --prefill-chunk 64 --decode-block 4
--mixed-token-budget 160`` as two child processes, a handful of
``POST /generate`` requests through the gateway, then the replica's
``/stats``.  The server phase runs twice (cold, then warm against the
compile cache the first run filled); a third child compares every Pallas
kernel specialisation with the XLA gather path on the chip
(``tools/kernel_parity.py``).

This parent never imports JAX — a process that has touched JAX holds the
chip, and the replica needs it.  The children get ``JAX_PLATFORMS=tpu``,
so a missing chip is an initialisation error and never a CPU run.  Exit
code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only
when every phase passed; any failure exits 1 and prints no result (2
when the package is not beside this file).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PKG = "distributed_inference_demo_tpu"
DEADLINE_S = 1150            # the contract allows 1200, compilation included

MODEL = "qwen2.5-7b-int8"    # published width, 28 of 28 layers (depth not cut)
MODEL_TP = "qwen2.5-7b"      # bf16: ~15.2 GB of weights, one kv head per chip
VOCAB = 152064
LAYERS = 28

SLOTS, CHUNK, DECODE_BLOCK, BUDGET, MAX_SEQ = 8, 64, 4, 160, 1024
BLOCK_TOKENS = 16            # serve's default page size (gateway matches it)


class SmokeFailure(Exception):
    """A phase did not do what it must; the message says which and why."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# children

_CHILDREN: list = []


class Child:
    """One CLI child in its own process group, output teed to a log file."""

    def __init__(self, name: str, argv: list, env: dict, log_dir: Path):
        self.name = name
        self.log_path = log_dir / f"{name}.log"
        self._log = open(self.log_path, "w")
        self.lines: list = []
        self._cv = threading.Condition()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, start_new_session=True)
        _CHILDREN.append(self)
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._log.write(line)
            self._log.flush()
            with self._cv:
                self.lines.append(line.rstrip("\n"))
                self._cv.notify_all()
        with self._cv:
            self._cv.notify_all()

    def wait_for(self, marker: str, timeout: float) -> str:
        """The first output line holding ``marker``; a child that exits
        first, or the timeout, is a failure carrying the log's tail."""
        end = time.monotonic() + timeout
        seen = 0
        with self._cv:
            while True:
                for line in self.lines[seen:]:
                    if marker in line:
                        return line
                seen = len(self.lines)
                if self.proc.poll() is not None and not self._pump.is_alive():
                    no_tpu = any("Unable to initialize backend 'tpu'" in ln
                                 for ln in self.lines)
                    raise SmokeFailure(
                        ("JAX found no TPU on this machine: " if no_tpu
                         else "")
                        + f"{self.name} exited with code "
                        f"{self.proc.returncode} before {marker!r}:\n"
                        f"{self.tail()}")
                left = end - time.monotonic()
                if left <= 0:
                    raise SmokeFailure(
                        f"{self.name}: no {marker!r} within {timeout:.0f}s:\n"
                        f"{self.tail()}")
                self._cv.wait(min(left, 1.0))

    def tail(self, n: int = 30) -> str:
        return "\n".join(f"    | {line}" for line in self.lines[-n:])

    def stop(self, grace: float = 30.0) -> None:
        """SIGINT the group (the CLI's clean-shutdown path), then SIGKILL:
        the chip is free again only once the process is gone."""
        if self.proc.poll() is None:
            for sig, wait in ((signal.SIGINT, grace), (signal.SIGKILL, 10.0)):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=wait)
                    break
                except subprocess.TimeoutExpired:
                    continue
        self._pump.join(timeout=5)
        self._log.close()
        if self in _CHILDREN:
            _CHILDREN.remove(self)


def stop_all_children() -> None:
    for child in list(_CHILDREN):
        child.stop(grace=5.0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# HTTP

def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 600.0):
    """``(status, parsed JSON)`` of one request to 127.0.0.1:port."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None if body is None else json.dumps(body).encode()
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw or b"{}")
        except ValueError:
            return resp.status, {"raw": raw.decode("utf-8", "replace")}
    finally:
        conn.close()


def generate(port: int, prompt: list, max_new: int, stream: bool = False,
             timeout: float = 900.0) -> dict:
    """One ``POST /generate`` of a single-row prompt.  Returns
    ``{"status", "tokens", "errors", "seconds"}``; a streamed request's
    tokens are the concatenation of its JSONL lines."""
    body = {"prompt_ids": [prompt], "max_new_tokens": max_new}
    t0 = time.monotonic()
    if not stream:
        status, out = http_json(port, "POST", "/generate", body, timeout)
        rows = out.get("tokens") or [[]]
        errors = [out["error"]] if "error" in out else []
        return {"status": status, "tokens": list(rows[0]), "errors": errors,
                "seconds": time.monotonic() - t0}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/generate",
                     body=json.dumps(dict(body, stream=True)).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        tokens, errors = [], []
        for raw in resp:                       # chunked JSONL, line by line
            raw = raw.strip()
            if not raw:
                continue
            item = json.loads(raw)
            if "error" in item:
                errors.append(item["error"])
            elif "tokens" in item and not item.get("done"):
                tokens.append(int(item["tokens"][0]))
        return {"status": resp.status, "tokens": tokens, "errors": errors,
                "seconds": time.monotonic() - t0}
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# the request script

def _prompt(seed: int, n: int, vocab: int) -> list:
    """A seeded prompt of ``n`` in-vocabulary ids (no JAX, no numpy)."""
    x = (seed * 2654435761 + 12345) & 0xFFFFFFFF
    out = []
    for _ in range(n):
        x = (x * 1664525 + 1013904223) & 0xFFFFFFFF
        out.append(1 + (x >> 8) % (vocab - 1))
    return out


def request_script(gw_port: int, vocab: int) -> dict:
    """The handful of requests, all through the gateway.  Returns
    ``{name: result}``; raises :class:`SmokeFailure` on the first thing
    that is wrong."""
    shared = _prompt(1, 3 * BLOCK_TOKENS, vocab)      # three whole KV pages
    plan = {
        # longer than --prefill-chunk: chunked admission inside the mixed
        # dispatch (two whole chunks and a final)
        "long": (_prompt(2, 2 * CHUNK + 22, vocab), 8, False),
        # two prompts sharing a 48-token prefix: the second finds the
        # first's pages in the radix tree
        "prefix_a": (shared + _prompt(3, 9, vocab), 8, False),
        "prefix_b": (shared + _prompt(4, 11, vocab), 8, False),
        "stream": (_prompt(5, 20, vocab), 12, True),
        "short_1": (_prompt(6, 17, vocab), 12, False),
        "short_2": (_prompt(7, 33, vocab), 12, False),
        "short_3": (_prompt(8, 5, vocab), 12, False),
    }
    results: dict = {}

    def run(name: str, alias: str = "") -> None:
        prompt, max_new, stream = plan[name]
        try:
            res = generate(gw_port, prompt, max_new, stream)
        except (OSError, ValueError, http.client.HTTPException) as e:
            # a request thread must hand its failure to the checks below
            res = {"status": 0, "tokens": [], "seconds": 0.0,
                   "errors": [f"{type(e).__name__}: {e}"]}
        results[alias or name] = res

    def together(*names: str) -> None:
        threads = [threading.Thread(target=run, args=(n,)) for n in names]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        if any(t.is_alive() for t in threads):
            raise SmokeFailure(f"requests {names} did not finish in 900 s")

    run("long")                       # first request: pays the compiles
    # several in flight at once: K-step fused decode with >1 live slot
    together("prefix_a", "stream", "short_1", "short_2", "short_3")
    run("prefix_b")                   # radix hit on prefix_a's pages
    run("long", "long_again")         # the same greedy request, twice
    run("prefix_b", "prefix_b_again")

    for name, res in results.items():
        want = plan[name.removesuffix("_again")][1]
        if res["status"] != 200:
            raise SmokeFailure(f"request {name}: HTTP {res['status']} "
                               f"{res['errors']}")
        if res["errors"]:
            raise SmokeFailure(f"request {name}: error line {res['errors']}")
        toks = res["tokens"]
        if len(toks) != want:
            raise SmokeFailure(f"request {name}: {len(toks)} tokens, "
                               f"asked for {want}")
        if not all(isinstance(t, int) and 0 <= t < vocab for t in toks):
            raise SmokeFailure(f"request {name}: token outside the "
                               f"vocabulary [0, {vocab}): {toks}")
    for cold, hit in (("long", "long_again"), ("prefix_b", "prefix_b_again")):
        if results[cold]["tokens"] != results[hit]["tokens"]:
            raise SmokeFailure(
                f"greedy request {cold!r} answered differently the second "
                f"time (served from cached pages): "
                f"{results[cold]['tokens']} vs {results[hit]['tokens']}")
    return results


def expected_paths(platform: str) -> dict:
    """What ``/stats["attention_paths"]["mixed_step"]`` must say: on the
    chip both kernels (decode rows and the 64-token prefill slab, 448
    query rows at group 7), elsewhere the gather with its reason."""
    if platform == "tpu":
        return {"chunk=1": "pallas_decode", f"chunk={CHUNK}": "pallas_prefill"}
    why = f"gather: backend=auto on platform={platform}"
    return {"chunk=1": why, f"chunk={CHUNK}": why}


def check_stats(stats: dict, platform: str) -> None:
    kv = stats.get("kvcache", {})
    loop = stats.get("device_loop", {})
    mixed = stats.get("mixed", {})
    chunked = stats.get("chunked_prefill", {})
    if kv.get("hits", 0) < 1 or kv.get("partial_hit_tokens", 0) < 1:
        raise SmokeFailure(f"no prefix hit counted: {kv}")
    if (loop.get("host_dispatches", 0) < 1
            or loop.get("device_loop_steps", 0) <= loop["host_dispatches"]):
        raise SmokeFailure(f"decode was not fused (steps per dispatch "
                           f"<= 1): {loop}")
    if mixed.get("dispatches", 0) < 1 or mixed.get("prefill_tokens", 0) < 1:
        raise SmokeFailure(f"no mixed dispatch carried prefill: {mixed}")
    if chunked.get("chunks", 0) < 2:
        raise SmokeFailure(f"the long prompt was not chunked: {chunked}")
    got = stats.get("attention_paths", {}).get("mixed_step")
    if got != expected_paths(platform):
        raise SmokeFailure(
            f"attention paths of mixed_step are {got}, expected "
            f"{expected_paths(platform)}: a program that should have taken "
            "a kernel gathered (or the reverse)")


# ---------------------------------------------------------------------------
# one server phase

def cache_dir() -> Path:
    """Where the children keep their compile cache — the package's own
    rule (cli.configure_compile_cache), restated without importing it."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or ROOT / ".jax_cache")


def cache_entries() -> int:
    d = cache_dir()
    return sum(1 for p in d.iterdir() if p.is_file()) if d.is_dir() else 0


def serving_phase(label: str, model: str, vocab: int, platform: str,
                  log_dir: Path, tp: int = 1,
                  ready_timeout: float = 600.0) -> dict:
    """Gateway first, then the replica, both through the CLI; the
    request script through the gateway; the replica's ``/stats``; both
    children stopped.  Returns what the phase observed."""
    env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONUNBUFFERED="1")
    gw_port, rep_port = free_port(), free_port()
    py = [sys.executable, "-m", PKG]
    entries0 = cache_entries()
    # the gateway starts FIRST and shares the replica's environment: had
    # it initialised a JAX backend it would hold the chip and the replica
    # could not come up
    gateway = Child(f"{label}-gateway", py + [
        "gateway", "--replicas", f"127.0.0.1:{rep_port}",
        "--http-port", str(gw_port), "--health-interval", "0.5",
        "--readmit-cooldown", "1", "--route-block-tokens",
        str(BLOCK_TOKENS)], env, log_dir)
    replica = None
    try:
        gateway.wait_for("GATEWAY_READY", 120)
        t0 = time.monotonic()
        replica = Child(f"{label}-replica", py + [
            "serve", "--model", model, "--batch-slots", str(SLOTS),
            "--prefill-chunk", str(CHUNK), "--decode-block",
            str(DECODE_BLOCK), "--mixed-token-budget", str(BUDGET),
            "--max-seq", str(MAX_SEQ), "--greedy", "--http-port",
            str(rep_port)] + (["--tp", str(tp)] if tp > 1 else []),
            env, log_dir)
        replica.wait_for("HTTP_READY", ready_timeout)
        startup_s = time.monotonic() - t0

        status, health = http_json(rep_port, "GET", "/health", timeout=60)
        if status != 200 or health.get("status") != "ok":
            raise SmokeFailure(f"replica /health: {status} {health}")
        if health.get("platform") != platform:
            raise SmokeFailure(
                f"replica runs on platform {health.get('platform')!r}, "
                f"not {platform!r}: {health}")
        if health.get("device_count", 0) < tp:
            raise SmokeFailure(f"--tp {tp} on {health.get('device_count')} "
                               "devices")
        end = time.monotonic() + 60
        while True:
            _, gw_health = http_json(gw_port, "GET", "/health", timeout=10)
            if gw_health.get("replicas_routable", 0) >= 1:
                break
            if time.monotonic() > end:
                raise SmokeFailure(f"gateway never saw the replica up: "
                                   f"{gw_health}")
            time.sleep(0.5)

        results = request_script(gw_port, vocab)
        status, stats = http_json(rep_port, "GET", "/stats", timeout=60)
        if status != 200:
            raise SmokeFailure(f"replica /stats: {status} {stats}")
        check_stats(stats, platform)
        _, health_after = http_json(rep_port, "GET", "/health", timeout=60)
        if health_after.get("status") != "ok":
            raise SmokeFailure(f"replica /health after the requests: "
                               f"{health_after}")
        for child in (gateway, replica):
            if child.proc.poll() is not None:
                raise SmokeFailure(f"{child.name} died during the phase "
                                   f"(code {child.proc.returncode}):\n"
                                   f"{child.tail()}")
    finally:
        if replica is not None:
            replica.stop()
        gateway.stop()
    compiles = stats.get("compile", {})
    return {
        "label": label, "startup_s": round(startup_s, 1),
        "first_request_s": round(results["long"]["seconds"], 1),
        "compile_s": round(sum(c.get("compile_seconds", 0.0)
                               for c in compiles.values()), 1),
        "cache_entries": (entries0, cache_entries()),
        "health": health, "stats": stats,
        "tokens": {k: v["tokens"] for k, v in results.items()},
    }


def report_phase(ph: dict) -> None:
    h, st = ph["health"], ph["stats"]
    say(f"[{ph['label']}] platform={h['platform']} "
        f"device_kind={h['device_kind']!r} device_count={h['device_count']} "
        f"model={h['model']} backend={h['backend']}")
    say(f"[{ph['label']}] start-up to HTTP_READY {ph['startup_s']} s; first "
        f"request (compiles included) {ph['first_request_s']} s; compile "
        f"ledger {ph['compile_s']} s; compile-cache files "
        f"{ph['cache_entries'][0]} -> {ph['cache_entries'][1]} "
        f"in {cache_dir()}")
    say(f"[{ph['label']}] requests answered through the gateway: "
        f"{len(ph['tokens'])}; prefix hits {st['kvcache']['hits']} "
        f"({st['kvcache']['partial_hit_tokens']} tokens); mixed dispatches "
        f"{st['mixed']['dispatches']}; chunks "
        f"{st['chunked_prefill']['chunks']}; decode steps/dispatches "
        f"{st['device_loop']['device_loop_steps']}/"
        f"{st['device_loop']['host_dispatches']}")
    for prog, chunks in sorted(st["attention_paths"].items()):
        for chunk, path in sorted(chunks.items()):
            say(f"[{ph['label']}] attention path  {prog:<18} {chunk:<10} "
                f"{path}")
    for d in h["devices"]:
        mem = (f": {d['bytes_in_use'] / 2**30:.2f} GiB in use, peak "
               f"{d['peak_bytes_in_use'] / 2**30:.2f} of "
               f"{d['bytes_limit'] / 2**30:.2f} GiB"
               if "bytes_limit" in d else "")
        say(f"[{ph['label']}] {d['device']}{mem}")


# ---------------------------------------------------------------------------
# the kernel phase

def kernel_phase(platform: str, log_dir: Path) -> list:
    """``tools/kernel_parity.py`` as a child (the chip is free again):
    every kernel specialisation compiled and compared with the gather
    path at the served shapes.  Returns its JSON rows."""
    env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONUNBUFFERED="1")
    child = Child("kernels", [sys.executable,
                              str(ROOT / "tools" / "kernel_parity.py")],
                  env, log_dir)
    try:
        child.wait_for("KERNEL_PARITY_DONE", 600)
    finally:
        child.stop()
    rows = [json.loads(line[len("KERNEL "):]) for line in child.lines
            if line.startswith("KERNEL ")]
    bad = [r for r in rows if not r["ok"]]
    for r in rows:
        if "max_abs_err" in r:
            detail = (f"max|err| {r['max_abs_err']:.6f} (bound "
                      f"{r['tol']}; XLA at default precision "
                      f"{r['xla_default_precision_max_abs_err']:.6f})")
        else:
            detail = " ".join(f"{k}={v}" for k, v in r.items()
                              if k not in ("name", "ok", "tol"))
        say(f"[kernels] {'ok  ' if r['ok'] else 'FAIL'} {r['name']:<62} "
            f"{detail}")
    if bad or not rows:
        raise SmokeFailure(f"{len(bad)} of {len(rows)} kernel "
                           "specialisations refused or disagreed")
    return rows


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="serve tensor-parallel over N chips of one host "
                         f"({MODEL_TP}, bf16) instead of {MODEL} on one")
    args = ap.parse_args(argv)
    if not (ROOT / PKG / "cli.py").is_file():
        print(f"chip_smoke: {PKG}/ is not beside {Path(__file__).name}; "
              "this is not a checkout of the repo", file=sys.stderr)
        return 2

    def on_deadline(signum, frame):
        raise SmokeFailure(f"not done within {DEADLINE_S} s")
    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)

    log_dir = ROOT / "chiprun_out" / "chip_smoke"
    log_dir.mkdir(parents=True, exist_ok=True)
    model = MODEL_TP if args.tp > 1 else MODEL
    say(f"chip_smoke: {model} at published width, {LAYERS} of {LAYERS} "
        f"layers (depth not cut), seeded weights; serve --batch-slots "
        f"{SLOTS} --prefill-chunk {CHUNK} --decode-block {DECODE_BLOCK} "
        f"--mixed-token-budget {BUDGET}"
        + (f" --tp {args.tp}" if args.tp > 1 else "")
        + " behind gateway; children run with JAX_PLATFORMS=tpu")
    t_all = time.monotonic()
    try:
        phases = []
        for label in ("cold", "warm"):
            ph = serving_phase(label, model, VOCAB, "tpu", log_dir,
                               tp=args.tp)
            report_phase(ph)
            phases.append(ph)
        cold, warm = phases
        if cold["tokens"] != warm["tokens"]:
            raise SmokeFailure(
                "the second server start answered the same greedy requests "
                f"differently: {cold['tokens']} vs {warm['tokens']}")
        say(f"[cache] start-up {cold['startup_s']} s cold, "
            f"{warm['startup_s']} s warm; first request "
            f"{cold['first_request_s']} s cold, {warm['first_request_s']} s "
            f"warm; compile ledger {cold['compile_s']} s cold, "
            f"{warm['compile_s']} s warm")
        if args.tp == 1:
            # one chip's business; on a four-chip host it would only
            # hold the other three idle
            kernel_phase("tpu", log_dir)
    except Exception as e:
        # the one boundary: whatever went wrong, the children are stopped,
        # the reason is printed and no result line follows
        signal.alarm(0)
        stop_all_children()
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"chip_smoke: FAILED after {time.monotonic() - t_all:.0f} s: "
              f"{e}", flush=True)
        return 1
    finally:
        signal.alarm(0)
        stop_all_children()
    h = phases[0]["health"]
    say(f"chip_smoke: all phases passed in {time.monotonic() - t_all:.0f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": h["platform"], "kind": h["device_kind"],
        "count": h["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
