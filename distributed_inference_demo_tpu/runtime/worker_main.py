"""Standalone worker process entry point: pipeline stage, or a
disaggregated prefill/decode role.

``--role stage`` (the default) launches one pipeline stage over the
socket transport — the role of the reference's on-device worker runtime
(``BackgroundService`` driving ``Communication.running``, SURVEY.md
§3.2/§3.3) as a plain CLI process.  Used by the multi-process
integration tests and the ``worker`` CLI.

``--role prefill`` / ``--role decode`` launch the disaggregated serving
roles (docs/DESIGN.md §15, runtime/disagg.py): a prefill worker runs
chunked prefill and migrates KV pages over the transport; a decode
worker adopts migrated pages into its continuous-batching engine and
streams tokens back.  Peers (the decode worker / prefill workers / the
coordinator) are dialed with repeatable ``--peer id@host:port`` flags.

Weights come either from a seed (every process derives the same full
parameter set deterministically, then slices its own stage — the test
path, replacing the reference's ONNX-zip shipping) or, in the full
deployment path, from the control plane's artifact channel (cli.py).
"""

from __future__ import annotations

import argparse
import sys


def build_worker(args):
    import jax

    from ..comm.transport import ZmqTransport
    from ..models.base import StageSpec, slice_stage
    from ..models.decoder import init_full_params
    from ..models.registry import get_model_config
    from ..ops.sampling import SamplingParams
    from .distributed import PipelineWorker, StageRuntime

    cfg = get_model_config(args.model)
    if args.dtype:
        cfg = cfg.replace(dtype_name=args.dtype)
    spec = StageSpec(args.stage_id, args.num_stages,
                     args.layer_start, args.layer_end)
    full = init_full_params(jax.random.PRNGKey(args.weights_seed), cfg)
    params = slice_stage(full, cfg, spec)
    sampling = SamplingParams(greedy=True) if args.greedy else \
        SamplingParams(temperature=args.temperature, top_k=args.top_k,
                       min_p=args.min_p)
    # pipeline x tensor parallelism: this stage runs tp-sharded over its
    # host's first N local devices; the wire stays [b, s, H]
    from ..parallel.mesh import local_tp_mesh
    runtime = StageRuntime(cfg, spec, params, max_seq=args.max_seq,
                           sampling=sampling, seed=args.seed,
                           mesh=local_tp_mesh(getattr(args, "tp", 1)),
                           kv_cache_dtype=getattr(args, "kv_cache_dtype",
                                                  "") or None)

    from ..comm.faults import load_fault_plan, maybe_wrap
    transport = maybe_wrap(
        ZmqTransport(args.device_id, bind_host=args.bind_host,
                     port=args.port),
        load_fault_plan(getattr(args, "fault_plan", ""),
                        getattr(args, "chaos", False)))
    next_id = None
    if args.next:
        next_id, next_addr = args.next.split("@", 1)
        transport.connect(next_id, next_addr)
    header_id, header_addr = args.header.split("@", 1)
    transport.connect(header_id, header_addr)
    worker = PipelineWorker(runtime, transport, next_id=next_id,
                            header_id=header_id,
                            step_timeout=args.step_timeout)
    return worker, transport


def build_role_worker(args):
    """Build a disaggregated-role worker (``--role prefill|decode``) on
    a ZMQ transport with its ``--peer`` connections dialed."""
    import jax

    from ..comm.faults import load_fault_plan, maybe_wrap
    from ..comm.transport import ZmqTransport
    from ..models.decoder import init_full_params
    from ..models.registry import get_model_config
    from ..ops.sampling import SamplingParams
    from .disagg import DecodeWorker, PrefillWorker

    cfg = get_model_config(args.model)
    if args.dtype:
        cfg = cfg.replace(dtype_name=args.dtype)
    params = init_full_params(jax.random.PRNGKey(args.weights_seed), cfg)
    transport = maybe_wrap(
        ZmqTransport(args.device_id, bind_host=args.bind_host,
                     port=args.port),
        load_fault_plan(getattr(args, "fault_plan", ""),
                        getattr(args, "chaos", False)))
    for peer in args.peer or ():
        pid, addr = peer.split("@", 1)
        transport.connect(pid, addr)
    if args.role == "prefill":
        worker = PrefillWorker(
            cfg, params, transport, max_seq=args.max_seq,
            prefill_chunk=args.prefill_chunk or 32,
            kv_cache_blocks=args.kv_cache_blocks,
            kv_block_tokens=args.kv_block_tokens,
            ack_timeout=args.migration_ack_timeout,
            migration_retries=args.migration_retries)
        return worker, transport, None
    from .batching import ContinuousBatchingEngine
    sampling = SamplingParams(greedy=True) if args.greedy else \
        SamplingParams(temperature=args.temperature, top_k=args.top_k,
                       min_p=args.min_p)
    engine = ContinuousBatchingEngine(
        cfg, params, max_seq=args.max_seq, max_batch=args.batch_slots,
        sampling=sampling, seed=args.seed, eos_id=args.eos_id,
        decode_block=args.decode_block,
        kv_cache_blocks=args.kv_cache_blocks,
        kv_block_tokens=args.kv_block_tokens,
        kv_cache_dtype=getattr(args, "kv_cache_dtype", "") or None)
    worker = DecodeWorker(engine, transport)
    if getattr(args, "live_migration", False):
        # co-serve the §18 live decode-to-decode migration protocol on
        # the same transport; both protocols share ONE PageStager so
        # their pg:/pgx: frames resolve to the same staging records
        from .migration import CoServingWorker, MigrationWorker
        mig = MigrationWorker(engine, transport,
                              ack_timeout=args.migration_ack_timeout,
                              retries=args.migration_retries,
                              stager=worker.stager)
        worker = CoServingWorker(worker, mig)
    return worker, transport, engine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="pipeline stage / "
                                 "disaggregated-role worker")
    ap.add_argument("--model", required=True)
    ap.add_argument("--role", default="stage",
                    choices=["stage", "prefill", "decode"],
                    help="stage = one pipeline stage (default); "
                         "prefill/decode = the disaggregated serving "
                         "roles (docs/DESIGN.md §15): prefill runs "
                         "chunked prefill and migrates KV pages to its "
                         "decode peer; decode adopts migrated pages "
                         "into a continuous-batching engine")
    ap.add_argument("--stage-id", type=int, default=None)
    ap.add_argument("--num-stages", type=int, default=None)
    ap.add_argument("--layer-start", type=int, default=None)
    ap.add_argument("--layer-end", type=int, default=None)
    ap.add_argument("--device-id", required=True)
    ap.add_argument("--bind-host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--next", default="",
                    help="next stage as id@host:port (empty on the tail)")
    ap.add_argument("--header", default="",
                    help="header as id@host:port (token return edge; "
                         "required for --role stage)")
    ap.add_argument("--peer", action="append", default=[],
                    help="disagg roles: connect a peer as id@host:port "
                         "(repeatable) — the prefill role dials its "
                         "decode worker + coordinator; the decode role "
                         "dials its prefill workers + coordinator")
    ap.add_argument("--batch-slots", type=int, default=8,
                    help="--role decode: continuous-batching slots")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="--role decode: fuse N decode steps per "
                         "dispatch when no admission could land")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="--role prefill: chunk size for the chunked "
                         "prefill whose chunk boundaries the page "
                         "migration streams on")
    ap.add_argument("--migration-ack-timeout", type=float, default=None,
                    help="--role prefill (or decode --live-migration): "
                         "seconds to wait for a migration ack before "
                         "retransmitting (default "
                         "DWT_DISAGG_ACK_TIMEOUT_S, else 2.0)")
    ap.add_argument("--migration-retries", type=int, default=None,
                    help="--role prefill (or decode --live-migration): "
                         "bounded end/retransmit rounds before the "
                         "handoff is reported failed (default "
                         "DWT_DISAGG_MIGRATION_RETRIES, else 5)")
    ap.add_argument("--live-migration", action="store_true",
                    help="--role decode: co-serve the live decode-to-"
                         "decode migration protocol (docs/DESIGN.md "
                         "§18) on this worker's transport, so the "
                         "replica can export and import mid-flight "
                         "requests for rebalance/drain/defragment")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--dtype", default="",
                    help="override model dtype (e.g. float32 for CPU runs)")
    ap.add_argument("--weights-seed", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--top-k", type=int, default=7)
    ap.add_argument("--min-p", type=float, default=0.0)
    ap.add_argument("--step-timeout", type=float, default=120.0)
    ap.add_argument("--kv-cache-dtype", default="",
                    help="reduced-precision KV cache storage for this "
                         "stage, e.g. float8_e4m3fn")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor parallelism over this host's first N "
                         "local devices (pipeline x tp)")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve Prometheus GET /metrics on this port "
                         "(0 = ephemeral, -1 = disabled); the header's "
                         "main HTTP server has its own /metrics")
    ap.add_argument("--kv-cache-blocks", type=int, default=None,
                    help="block-level KV prefix cache (runtime/kvcache): "
                         "pool size for the prefill/decode roles; "
                         "REJECTED on pipeline stage workers — a stage "
                         "sees upstream activations, not token ids, so "
                         "there is no key to match cached blocks by; "
                         "the flag exists for CLI parity with serve and "
                         "errors loudly instead of silently ignoring")
    ap.add_argument("--kv-block-tokens", type=int, default=None,
                    help="tokens per KV cache block (see "
                         "--kv-cache-blocks; rejected on stage workers)")
    ap.add_argument("--fault-plan", default="",
                    help="CHAOS TESTING ONLY: JSON fault-plan spec (path "
                         "or inline) injected into this stage's "
                         "transport; requires --chaos (docs/DESIGN.md "
                         "§12; env DWT_FAULT_PLAN)")
    ap.add_argument("--chaos", action="store_true",
                    help="explicitly acknowledge fault injection; "
                         "--fault-plan/DWT_FAULT_PLAN are rejected "
                         "without it")
    args = ap.parse_args(argv)
    from ..comm.faults import FaultConfigError, load_fault_plan
    try:
        load_fault_plan(args.fault_plan, args.chaos)  # validate EARLY:
    except FaultConfigError as e:   # a leaked env plan must not reach
        print(str(e), file=sys.stderr)     # the serve loop
        return 1
    if args.live_migration and args.role != "decode":
        print("--live-migration requires --role decode (live handoffs "
              "move mid-flight requests between decode replicas)",
              file=sys.stderr)
        return 1
    if args.role == "stage":
        if args.kv_cache_blocks or args.kv_block_tokens:
            print("--kv-cache-blocks/--kv-block-tokens are not supported "
                  "on pipeline stage workers (stages see activations, "
                  "not tokens; block KV reuse lives in the engine-backed "
                  "serve modes — serve --batch-slots, the plain engine, "
                  "or the disagg --role prefill/decode workers)",
                  file=sys.stderr)
            return 1
        missing = [f for f, v in (("--stage-id", args.stage_id),
                                  ("--num-stages", args.num_stages),
                                  ("--layer-start", args.layer_start),
                                  ("--layer-end", args.layer_end),
                                  ("--header", args.header))
                   if v in (None, "")]
        if missing:
            print(f"--role stage requires {'/'.join(missing)}",
                  file=sys.stderr)
            return 1

    # black-box capture: the flight ring is labeled with this worker's
    # identity, and an unhandled crash dumps a postmortem bundle (when
    # DWT_POSTMORTEM_DIR is configured) before the process dies
    from ..telemetry import flightrecorder, postmortem
    flightrecorder.get_flight_recorder().proc = args.device_id
    postmortem.install_crash_handler(config=vars(args))

    engine = None
    if args.role == "stage":
        worker, transport = build_worker(args)
    else:
        worker, transport, engine = build_role_worker(args)
    metrics_srv = None
    if args.metrics_port >= 0:
        from ..telemetry import MetricsHTTPServer
        from ..telemetry import catalog as _catalog

        def _debugz() -> dict:
            out = {
                "device_id": args.device_id,
                "flight": flightrecorder.debug_state(),
                "postmortem": postmortem.debug_state(),
            }
            if args.role == "stage":
                out["stats"] = worker.stats.snapshot()
            else:
                # the disagg /debugz satellite: a wedged handoff is
                # observable from a scrape on EITHER role — in-flight
                # handoffs/staged migrations, adopted pages, last
                # migration latency
                out["disagg"] = worker.debug_state()
            return out

        if args.role == "stage":
            def _render():
                return _catalog.render_worker(worker.stats,
                                              args.device_id)
        else:
            def _render():
                return _catalog.scrape(engine if engine is not None
                                       else worker)
        metrics_srv = MetricsHTTPServer(
            _render, host=args.bind_host, port=args.metrics_port,
            debug_provider=_debugz)
        metrics_srv.start()
        print(f"METRICS_READY http://{metrics_srv.host}:"
              f"{metrics_srv.port}/metrics", flush=True)
    print(f"WORKER_READY {args.device_id} {transport.address}", flush=True)
    # no explicit except-and-trigger here: a serve-loop crash propagates
    # to the sys.excepthook installed above, which writes the ONE crash
    # bundle (an extra trigger in an except clause would double-capture
    # the same exception and halve the pruned bundle history)
    try:
        worker.serve_forever()
    finally:
        if metrics_srv is not None:
            metrics_srv.shutdown()
        if engine is not None:
            engine.close()
        transport.close()
    return 0


if __name__ == "__main__":
    # run as a module (the socket-pipeline launchers do): pass the one
    # compile-cache rule cli.main() would have passed
    from ..cli import configure_compile_cache
    configure_compile_cache()
    sys.exit(main())
