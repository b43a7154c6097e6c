"""The processes of one run and plain HTTP to them.

Copied from ``chip_smoke.py`` (``Child``, ``free_port``, ``http_json``) so
that a later change to the program cannot change the yardstick.  The parent
that uses this module never imports JAX: a process that has touched JAX
holds the chip, and the replica needs it.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PKG = "distributed_inference_demo_tpu"


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


class Child:
    """One child in its own process group; output teed to a log file and
    kept as lines, stdin open for the replica's control lines."""

    def __init__(self, name: str, argv: list, env: dict, log_dir: Path):
        self.name = name
        self.log_path = log_dir / f"{name}.log"
        self._log = open(self.log_path, "w")
        self.lines: list = []
        self._cv = threading.Condition()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True)
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

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def wait_for(self, marker: str, timeout: float, start: int = 0) -> str:
        """The first output line from index ``start`` that begins with
        ``marker``; a child that exits first, or the timeout, fails."""
        end = time.monotonic() + timeout
        seen = start
        with self._cv:
            while True:
                for line in self.lines[seen:]:
                    if line.startswith(marker):
                        return line
                seen = len(self.lines)
                if self.proc.poll() is not None and not self._pump.is_alive():
                    no_tpu = any("Unable to initialize backend 'tpu'" in ln
                                 for ln in self.lines)
                    raise BenchFailure(
                        ("JAX found no TPU on this machine: " if no_tpu
                         else "")
                        + f"{self.name} exited with code "
                        f"{self.proc.returncode} before {marker!r}:\n"
                        f"{self.tail()}")
                left = end - time.monotonic()
                if left <= 0:
                    raise BenchFailure(
                        f"{self.name}: no {marker!r} within {timeout:.0f}s:\n"
                        f"{self.tail()}")
                self._cv.wait(min(left, 1.0))

    def tail(self, n: int = 30) -> str:
        return "\n".join(f"    | {line}" for line in self.lines[-n:])

    def stop(self, grace: float = 20.0) -> None:
        """SIGINT the group (the CLI's clean way out), then SIGKILL, and
        wait: the chip is free only once the process is gone."""
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
        try:                      # whatever else the group still holds
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self._pump.join(timeout=5)
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        self._log.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 120.0):
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


class Stack:
    """Replica (``replica_main.py`` around ``cli serve``) and gateway
    (``python -m <package> gateway``), as a user deploys them.  Use as a
    context manager: both children are stopped and waited for on exit."""

    def __init__(self, config_path: Path, seed: int, platform: str,
                 chips: int, rehearse: bool, log_dir: Path,
                 flag_overrides: list | None = None):
        self.config_path, self.seed = config_path, seed
        self.platform, self.chips, self.rehearse = platform, chips, rehearse
        self.log_dir = log_dir
        self.flag_overrides = flag_overrides or []
        self.replica = self.gateway = None
        self.rep_port = self.gw_port = 0

    def __enter__(self) -> "Stack":
        env = dict(os.environ, JAX_PLATFORMS=self.platform,
                   PYTHONUNBUFFERED="1", TPU_LOG_DIR="disabled")
        if self.platform == "cpu" and self.chips > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count="
                                f"{self.chips}").strip()
        self.rep_port, self.gw_port = free_port(), free_port()
        self.log_dir.mkdir(parents=True, exist_ok=True)
        try:
            self.replica = Child("replica", [
                sys.executable, str(BENCH / "replica_main.py"),
                "--config", str(self.config_path), "--port",
                str(self.rep_port), "--seed", str(self.seed)]
                + (["--rehearse"] if self.rehearse else [])
                + [f"--flag={f}" for f in self.flag_overrides],
                env, self.log_dir)
            self.gateway = Child("gateway", [
                sys.executable, "-m", PKG, "gateway", "--replicas",
                f"127.0.0.1:{self.rep_port}", "--http-port",
                str(self.gw_port)], env, self.log_dir)
            self.gateway.wait_for("GATEWAY_READY", 120)
            self.replica.wait_for("HTTP_READY", 1000)
            end = time.monotonic() + 60
            while True:
                _, h = http_json(self.gw_port, "GET", "/health", timeout=10)
                if h.get("replicas_routable", 0) >= 1:
                    break
                if time.monotonic() > end:
                    raise BenchFailure(f"gateway never saw the replica: {h}")
                time.sleep(0.2)
        except BaseException:
            self.__exit__(None, None, None)
            raise
        return self

    def __exit__(self, *exc) -> None:
        for child in (self.gateway, self.replica):
            if child is not None:
                child.stop()

    def check_alive(self) -> None:
        for child in (self.gateway, self.replica):
            if child.proc.poll() is not None:
                raise BenchFailure(
                    f"{child.name} died (code {child.proc.returncode}):\n"
                    f"{child.tail()}")

    def stats(self) -> dict:
        status, out = http_json(self.rep_port, "GET", "/stats")
        if status != 200:
            raise BenchFailure(f"replica /stats: {status} {out}")
        return out

    def health(self) -> dict:
        status, out = http_json(self.rep_port, "GET", "/health")
        if status != 200 or out.get("status") != "ok":
            raise BenchFailure(f"replica /health: {status} {out}")
        return out

    def control(self, line: str, reply: str, timeout: float) -> str:
        """Send one control line to the replica wrapper and return the
        JSON text after its ``reply`` marker."""
        start = len(self.replica.lines)
        self.replica.send(line)
        got = self.replica.wait_for(reply, timeout, start)
        return got[len(reply):].strip()
