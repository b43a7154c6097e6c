#!/usr/bin/env python3
"""The replica child of a benchmark run: the program's own ``serve``.

The main thread calls the program's entry point
``cli.main(["serve", "--model", ..., "--batch-slots", ...])`` with the
flags of the configuration file.  What this wrapper adds lies outside the
program's files:

1. the configuration's ``model_config`` goes into the model registry under
   the file's ``model`` name before ``serve`` resolves it (a name the
   registry already holds must hold the same sizes);
2. a control thread reads lines from stdin: ``TRACE_START <dir>`` /
   ``TRACE_STOP`` switch ``jax.profiler`` and stamp ``time.time_ns()`` and
   ``time.monotonic()`` at both ends, so the client's timeline and the
   device trace share a clock (``TRACE_STOP`` writes the ``.xplane.pb``
   and not the viewer's ``trace.json.gz``: ``_stop_trace``);
   ``REFERENCE <json>`` runs ``reference.emitted_logprobs`` on the served
   parameters (``ids``, ``n_prompt`` and, where the server's reply gave
   one, its ``generation`` record);
3. the one loader call ``models.loader.load_or_init`` is wrapped to keep a
   read-only handle on those parameters.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import socket
import sys
import threading
import time
import traceback
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH))

_HELD = {}                      # "params": the served parameter tree


def _register(model: str, fields: dict) -> None:
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.registry import MODEL_REGISTRY
    want = ModelConfig(**fields)
    have = MODEL_REGISTRY.get(model)
    if have is None:
        MODEL_REGISTRY[model] = want
    elif have != want:
        diff = {k: (getattr(have, k), v)
                for k, v in dataclasses.asdict(want).items()
                if getattr(have, k) != v}
        raise SystemExit(f"replica_main: the registry's {model!r} differs "
                         f"from the configuration file: {diff}")


def _hold_params() -> None:
    from distributed_inference_demo_tpu.models import loader
    inner = loader.load_or_init

    def load_or_init(*args, **kwargs):
        params = inner(*args, **kwargs)
        _HELD.setdefault("params", params)
        return params

    loader.load_or_init = load_or_init


def _say(marker: str, payload: dict) -> None:
    print(f"{marker} {json.dumps(payload)}", flush=True)


def _stamp() -> dict:
    return {"time_ns": time.time_ns(), "monotonic": time.monotonic()}


def _stop_trace(log_dir: str) -> dict:
    """End the profiler's session and write its ``.xplane.pb`` under
    ``log_dir``, and nothing else; returns the stamp between the two.

    ``jax.profiler.stop_trace()`` also converts the whole trace to a
    ``trace.json.gz`` for a viewer.  Nothing here reads that file, and
    writing it took 35 of the call's 44 s on one chip (PR 30; PERF.md
    section 6), with the replica serving the window's remaining
    requests beside it.  The session's own ``stop()`` hands back the same
    ``XSpace``, serialised.  It is reached through a private name; a JAX
    that does not show it gets the public call and both files."""
    import jax
    try:
        from jax._src import profiler as impl
        state = impl._profile_state
        lock, session, reset = state.lock, state.profile_session, state.reset
        stop = session.stop
    except AttributeError:
        jax.profiler.stop_trace()
        return _stamp()
    with lock:
        xspace = stop()
        reset()
    collected = _stamp()
    out = (Path(log_dir) / "plugins" / "profile"
           / time.strftime("%Y_%m_%d_%H_%M_%S"))
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{socket.gethostname()}.xplane.pb").write_bytes(xspace)
    return collected


def _control(model_fields: dict) -> None:
    """Serve control lines until stdin closes.  A failure answers with an
    ``error`` field: the parent decides what it means for the run."""
    import jax
    trace_dir = ""
    for line in sys.stdin:
        cmd, _, rest = line.strip().partition(" ")
        try:
            if cmd == "TRACE_START":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                trace_dir = rest
                before = _stamp()
                jax.profiler.start_trace(rest, profiler_options=opts)
                _say("TRACE_STARTED", {"start": before, "running": _stamp()})
            elif cmd == "TRACE_STOP":
                before = _stamp()
                collected = _stop_trace(trace_dir)
                _say("TRACE_STOPPED", {"stop": before, "collected": collected,
                                       "written": _stamp()})
            elif cmd == "REFERENCE":
                import reference
                req = json.loads(rest)
                t0 = time.monotonic()
                out = reference.emitted_logprobs(
                    _HELD["params"], model_fields, req["ids"],
                    req["n_prompt"], req.get("generation"))
                out["seconds"] = time.monotonic() - t0
                _say("REFERENCE_RESULT", out)
        except Exception as e:          # the thread must answer, not die
            traceback.print_exc()
            _say({"TRACE_START": "TRACE_STARTED", "TRACE_STOP":
                  "TRACE_STOPPED"}.get(cmd, "REFERENCE_RESULT"),
                 {"error": f"{type(e).__name__}: {e}"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="serve the file's toy rehearsal model instead")
    ap.add_argument("--flag", action="append", default=[],
                    help="extra serve flag (one word each), after the file's")
    args = ap.parse_args(argv)
    conf = json.loads(Path(args.config).read_text())
    if args.rehearse:
        conf = conf["rehearsal"]
    _register(conf["model"], conf["model_config"])
    _hold_params()
    threading.Thread(target=_control, args=(conf["model_config"],),
                     daemon=True).start()
    from distributed_inference_demo_tpu import cli
    return cli.main(["serve", "--model", conf["serve_model"],
                     *conf["serve_flags"], *args.flag,
                     "--weights-seed", str(args.seed),
                     "--http-port", str(args.port)])


if __name__ == "__main__":
    sys.exit(main())
